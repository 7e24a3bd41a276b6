"""Property tests for the relaxed solvers (Eq. 3/4/5) and the rounding
algorithms (Algorithm 2 / Algorithm 3) — the paper's §4 machinery."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import relax, rewards as R, rounding

instances = st.integers(0, 10_000)


def make_instance(seed, k_min=3, k_max=9):
    rng = np.random.default_rng(seed)
    k = int(rng.integers(k_min, k_max))
    n = int(rng.integers(1, k))
    mu = rng.uniform(0.05, 0.99, k)
    c = rng.uniform(0.01, 0.6, k)
    # keep the instance feasible: budget >= cheapest n-subset
    rho = float(np.sort(c)[:n].sum() * rng.uniform(1.05, 2.5))
    return mu, c, n, rho


# ===================================================================== relax
@given(instances)
@settings(max_examples=40, deadline=None)
def test_lp_feasible_and_beats_integral(seed):
    """The relaxed optimum is feasible and >= the best integral action."""
    mu, c, n, rho = make_instance(seed)
    for kind in ("suc", "aic"):
        z = np.array(relax.solve_relaxed(
            kind, jnp.array(mu, jnp.float32), jnp.array(c, jnp.float32),
            n=n, rho=rho))
        assert np.all(z >= -1e-6) and np.all(z <= 1 + 1e-6)
        assert float(np.dot(c, z)) <= rho * 1.002 + 1e-5
        assert abs(z.sum() - n) < 1e-3         # base matroid: Σz == N
        _, best = relax.solve_direct(kind, mu, c, n, rho)
        val = float(R.relaxed_reward(kind, jnp.array(z), jnp.array(mu)))
        assert val >= best - 1e-3, (kind, val, best)


@given(instances)
@settings(max_examples=25, deadline=None)
def test_awc_frank_wolfe_alpha_guarantee(seed):
    """AWC continuous greedy attains ≥ (1−1/e)·OPT (Lemma 3)."""
    mu, c, n, rho = make_instance(seed)
    z = np.array(relax.solve_relaxed(
        "awc", jnp.array(mu, jnp.float32), jnp.array(c, jnp.float32),
        n=n, rho=rho))
    assert float(np.dot(c, z)) <= rho * 1.01 + 1e-4
    assert z.sum() <= n + 1e-3
    _, opt = relax.solve_direct("awc", mu, c, n, rho)
    val = float(R.relaxed_reward("awc", jnp.array(z), jnp.array(mu)))
    assert val >= (1 - 1 / np.e) * opt - 5e-3


def test_direct_enumeration_small():
    mu = np.array([0.9, 0.1, 0.5])
    c = np.array([0.9, 0.1, 0.2])
    s, v = relax.solve_direct("suc", mu, c, n=2, rho=0.35)
    assert set(np.flatnonzero(s)) == {1, 2}
    assert v == pytest.approx(0.6)


# ============================================= grid engine vs bisect reference
@given(instances)
@settings(max_examples=40, deadline=None)
def test_grid_engine_matches_bisect_reference(seed):
    """The grid engine is decision-equivalent to the retained bisection
    reference: LP objective within 1e-5, budget feasibility preserved, and
    ≤2 fractional coordinates (the LP-optimum shape) for the base-matroid
    kinds — on randomized instances across all three reward models."""
    mu, c, n, rho = make_instance(seed)
    mu_j = jnp.array(mu, jnp.float32)
    c_j = jnp.array(c, jnp.float32)
    for kind in ("suc", "aic", "awc"):
        zg = np.array(relax.solve_relaxed(kind, mu_j, c_j, n, rho,
                                          engine="grid"))
        zb = np.array(relax.solve_relaxed(kind, mu_j, c_j, n, rho,
                                          engine="bisect"))
        vg = float(R.relaxed_reward(kind, jnp.array(zg), mu_j))
        vb = float(R.relaxed_reward(kind, jnp.array(zb), mu_j))
        assert vg >= vb - 1e-5, (kind, vg, vb)
        assert float(c @ zg) <= rho * 1.002 + 1e-5, (kind, float(c @ zg))
        assert np.all(zg >= -1e-6) and np.all(zg <= 1 + 1e-6)
        if kind != "awc":
            assert abs(zg.sum() - n) < 1e-3
            assert int(((zg > 1e-5) & (zg < 1 - 1e-5)).sum()) <= 2


@pytest.mark.parametrize("seed", [2766, 3520, 5100, 9269])
def test_warm_awc_frank_wolfe_matches_bisect(seed):
    """Instances where a warm-started FW step escaped its carried λ bracket
    and, bisecting only a few rows, mixed vertices two swaps apart: the
    objective fell 2.5e-3 to 1.3e-2 below the bisect reference."""
    mu, c, n, rho = make_instance(seed)
    mu_j = jnp.array(mu, jnp.float32)
    c_j = jnp.array(c, jnp.float32)
    zg = relax.solve_relaxed("awc", mu_j, c_j, n, rho, engine="grid",
                             fw_warm=True)
    zb = relax.solve_relaxed("awc", mu_j, c_j, n, rho, engine="bisect")
    vg = float(R.relaxed_reward("awc", zg, mu_j))
    vb = float(R.relaxed_reward("awc", zb, mu_j))
    assert vg >= vb - 1e-5, (vg, vb)


@given(instances)
@settings(max_examples=15, deadline=None)
def test_grid_static_and_dyn_paths_agree(seed):
    """`lp_topn` (static n) and `lp_topn_dyn` (traced n) route through the
    same grid engine and must pick identical selections."""
    mu, c, n, rho = make_instance(seed)
    w = jnp.array(mu, jnp.float32)
    cj = jnp.array(c, jnp.float32)
    for equality in (True, False):
        z_s = np.array(relax.lp_topn(w, cj, n, rho, equality, engine="grid"))
        z_d = np.array(relax.lp_topn_dyn(w, cj, jnp.int32(n),
                                         jnp.float32(rho), equality,
                                         engine="grid"))
        assert np.array_equal(z_s, z_d), (z_s, z_d)


def test_grid_wide_lowering_matches_reference(tpu_lowering):
    """The accelerator (G-way + Pallas interpret) lowering of the grid
    engine agrees with the bisect reference too."""
    for seed in range(4):
        mu, c, n, rho = make_instance(seed)
        mu_j = jnp.array(mu, jnp.float32)
        c_j = jnp.array(c, jnp.float32)
        for kind in ("suc", "awc"):
            zg = np.array(relax.solve_relaxed(kind, mu_j, c_j, n, rho,
                                              engine="grid"))
            zb = np.array(relax.solve_relaxed(kind, mu_j, c_j, n, rho,
                                              engine="bisect"))
            vg = float(R.relaxed_reward(kind, jnp.array(zg), mu_j))
            vb = float(R.relaxed_reward(kind, jnp.array(zb), mu_j))
            assert vg >= vb - 1e-5, (kind, seed, vg, vb)
            assert float(c @ zg) <= rho * 1.002 + 1e-5


def test_grid_wide_lowering_straddles_adjacent_vertices(tpu_lowering):
    """The wide lowering brackets with score-form probes (the Pallas
    kernels) but materializes by crossing thresholds; at this instance the
    two disagree at the crossing, and without guard probes the final batch
    mixed vertices two swaps apart (4 fractional coordinates, objective
    below the bisect reference)."""
    mu = jnp.array([0.3966922163963318, 0.5911787748336792,
                    0.5796077251434326, 0.9303721189498901,
                    0.4144137501716614, 0.20489569008350372,
                    0.874316930770874, 0.8910447955131531,
                    0.09536965936422348], jnp.float32)
    c = jnp.array([0.15250921249389648, 0.5945771336555481,
                   0.4853133261203766, 0.030987830832600594,
                   0.26923760771751404, 0.29725217819213867,
                   0.19643373787403107, 0.5919968485832214,
                   0.39945176243782043], jnp.float32)
    n, rho = 5, 1.2675365209579468
    zg = np.array(relax.solve_relaxed("aic", mu, c, n, rho, engine="grid"))
    zb = np.array(relax.solve_relaxed("aic", mu, c, n, rho, engine="bisect"))
    vg = float(R.relaxed_reward("aic", jnp.array(zg), mu))
    vb = float(R.relaxed_reward("aic", jnp.array(zb), mu))
    assert vg >= vb - 1e-5, (vg, vb)
    assert float(np.asarray(c) @ zg) <= rho * 1.002 + 1e-5
    assert int(((zg > 1e-5) & (zg < 1 - 1e-5)).sum()) <= 2, zg


def test_unknown_engine_rejected():
    with pytest.raises(ValueError):
        relax.lp_topn(jnp.ones(4), jnp.ones(4), 2, 1.0, True,
                      engine="simplex")


# ================================================= warm-started Frank-Wolfe
def test_awc_warm_fw_matches_cold_fw_decisions():
    """Warm-started FW (λ bracket carried across iterations) must be
    decision-equivalent to cold-start FW: equal objective within numerical
    tolerance, budget feasibility preserved, and bit-identical z̃ on the
    overwhelming majority of instances (the carried bracket isolates the
    same straddling vertex pair whenever λ* drifts slowly — near-tie
    instances may mix an adjacent, objective-equal pair). Deterministic
    corpus: engine tolerances, not sampler luck, decide the outcome."""
    neq = 0
    for seed in range(120):
        mu, c, n, rho = make_instance(seed)
        mu_j = jnp.array(mu, jnp.float32)
        c_j = jnp.array(c, jnp.float32)
        zw = np.array(relax.solve_relaxed("awc", mu_j, c_j, n, rho,
                                          engine="grid", fw_warm=True))
        zc = np.array(relax.solve_relaxed("awc", mu_j, c_j, n, rho,
                                          engine="grid", fw_warm=False))
        vw = float(R.relaxed_reward("awc", jnp.array(zw), mu_j))
        vc = float(R.relaxed_reward("awc", jnp.array(zc), mu_j))
        assert vw >= vc - 2e-4, (seed, vw, vc)
        assert float(c @ zw) <= rho * 1.01 + 1e-4, seed
        assert np.all(zw >= -1e-6) and np.all(zw <= 1 + 1e-6)
        neq += int(not np.array_equal(zw, zc))
    assert neq <= 12, f"warm z̃ diverged from cold on {neq}/120 instances"


def test_awc_fw_step_count_sweep_objective():
    """The FW step-count knob: fewer continuous-greedy steps trade LP
    solves for objective. The 12-step knob must stay within 1e-3 of the
    original 16 on the paper-style corpus; the 8-step fleet default
    within its documented 5e-3."""
    worst = {8: 0.0, 12: 0.0}
    for seed in range(30):
        mu, c, n, rho = make_instance(seed)
        mu_j = jnp.array(mu, jnp.float32)
        c_j = jnp.array(c, jnp.float32)
        v16 = float(R.relaxed_reward("awc", jnp.array(
            np.array(relax.solve_relaxed("awc", mu_j, c_j, n, rho,
                                         fw_steps=16))), mu_j))
        for steps in worst:
            z = np.array(relax.solve_relaxed("awc", mu_j, c_j, n, rho,
                                             fw_steps=steps))
            v = float(R.relaxed_reward("awc", jnp.array(z), mu_j))
            worst[steps] = max(worst[steps], v16 - v)
    assert worst[12] <= 1e-3, worst
    assert worst[8] <= 5e-3, worst


# ================================================== infeasible-budget edges
def test_rho_below_cheapest_subset_returns_min_cost_vertex():
    """ρ below the cheapest n-subset: both engines degrade to the λ-cap
    vertex — the n cheapest arms — and the budget is (necessarily)
    violated, as documented in `lp_topn`."""
    rng = np.random.default_rng(5)
    k, n = 7, 3
    mu = jnp.asarray(rng.uniform(0.2, 0.9, k), jnp.float32)
    c = rng.uniform(0.1, 0.6, k)
    rho = float(np.sort(c)[:n].sum()) * 0.5          # unattainable
    cheapest = np.zeros(k)
    cheapest[np.argsort(c)[:n]] = 1.0
    for engine in ("grid", "bisect"):
        z = np.array(relax.lp_topn(mu, jnp.asarray(c, jnp.float32), n, rho,
                                   True, engine=engine))
        assert np.array_equal(z, cheapest), (engine, z)
        assert float(c @ z) > rho                    # documented violation


def test_lambda_cap_insufficient_returns_cap_vertex():
    """Score scales so large that even λ = 2^24 cannot flip the ranking to
    the cheap arms: both engines return the λ-cap vertex (here the top-n
    by score), violating ρ — the documented degradation."""
    k, n = 5, 2
    w = jnp.asarray([9e8, 8e8, 7e8, 6e8, 5e8], jnp.float32)   # huge scores
    c = np.array([0.5, 0.6, 0.4, 0.01, 0.02])
    rho = 0.05            # only arms {3, 4} are affordable
    by_w = np.zeros(k)
    by_w[:n] = 1.0        # cap vertex: ranking still by w
    for engine in ("grid", "bisect"):
        z = np.array(relax.lp_topn(w, jnp.asarray(c, jnp.float32), n, rho,
                                   True, engine=engine))
        assert np.array_equal(z, by_w), (engine, z)
        assert float(c @ z) > rho


# ===================================================================== rounding
@given(instances)
@settings(max_examples=20, deadline=None)
def test_pairwise_round_marginal_preservation(seed):
    """Algorithm 3 preserves marginals: E[1_S] == z̃ (App. C.2)."""
    rng = np.random.default_rng(seed)
    k = 6
    z = rng.uniform(0, 1, k)
    trials = 3000
    acc = np.zeros(k)
    for i in range(trials):
        acc += rounding.pairwise_round_np(z, np.random.default_rng(i))
    est = acc / trials
    assert np.allclose(est, z, atol=0.05), (est, z)


def test_pairwise_round_jax_matches_numpy_distribution():
    z = np.array([0.3, 0.7, 0.5, 0.5])
    trials = 2000
    keys = jax.random.split(jax.random.PRNGKey(0), trials)
    masks = jax.vmap(lambda k: rounding.pairwise_round(jnp.array(z), k))(keys)
    est = np.asarray(masks).mean(0)
    assert np.allclose(est, z, atol=0.06)
    # cardinality is preserved when Σz is integral
    assert np.all(np.asarray(masks).sum(1) == 2)


@given(instances)
@settings(max_examples=15, deadline=None)
def test_swap_round_valid_base(seed):
    """Algorithm 2 returns a set of size ≤ N with E[1_S] ≈ z̃."""
    rng = np.random.default_rng(seed)
    k, n = 6, 3
    z = rng.uniform(0, 1, k)
    z = z / z.sum() * (n - 0.5)          # Σz < n: inclusive matroid case
    z = np.minimum(z, 1.0)               # stay in the polytope: z̃ ∈ [0,1]^K
    trials = 1500
    acc = np.zeros(k)
    for i in range(trials):
        m = rounding.swap_round_np(z, n, np.random.default_rng(i))
        assert m.sum() <= n + 1e-9
        acc += m
    assert np.allclose(acc / trials, z, atol=0.07)


@given(instances)
@settings(max_examples=15, deadline=None)
def test_pairwise_round_np_jax_agree_support_cardinality(seed):
    """Both Algorithm-3 flavours stay on z̃'s support, keep z̃==1 arms, and
    land on cardinality ⌈Σz̃⌉/⌊Σz̃⌋ (exact when Σz̃ is integral)."""
    rng = np.random.default_rng(seed)
    k = 7
    z = rng.uniform(0, 1, k)
    z[rng.integers(k)] = 1.0              # a saturated arm must survive
    for i in range(25):
        m_np = rounding.pairwise_round_np(z, np.random.default_rng(i))
        m_jx = np.asarray(rounding.pairwise_round(
            jnp.array(z, jnp.float32), jax.random.PRNGKey(i)))
        for m in (m_np, m_jx):
            assert set(np.unique(m)) <= {0.0, 1.0}
            assert np.all(m[z >= 1 - rounding.EPS] == 1.0)   # keep saturated
            assert np.all(m[z <= rounding.EPS] == 0.0)       # stay on support
            assert m.sum() in (np.floor(z.sum()), np.ceil(z.sum()))


def test_batched_rounding_matches_per_row():
    """pairwise_round_batch row i == pairwise_round(z[i], keys[i]) exactly,
    and the dynamic pad agrees with the padded per-row result."""
    rng = np.random.default_rng(3)
    m, k, n = 8, 6, 3
    z = jnp.asarray(rng.uniform(0, 1, (m, k)), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(42), m)
    batched = np.asarray(rounding.pairwise_round_batch(z, keys))
    for i in range(m):
        row = np.asarray(rounding.pairwise_round(z[i], keys[i]))
        assert np.array_equal(batched[i], row), i
    padded = np.asarray(jax.vmap(rounding.pad_to_n_dyn, in_axes=(0, 0, None,
                                                                 None))(
        jnp.asarray(batched), z, jnp.int32(n), True))
    assert np.all(padded.sum(-1) >= n)
    assert np.all(padded >= batched)      # padding only adds arms


def _pairwise_round_argsort_ref(z, key):
    """The PR-2 `pairwise_round` body (stable argsort pair selection) —
    regression oracle for the cheaper two-smallest-index selection."""
    z = jnp.clip(z.astype(jnp.float32), 0.0, 1.0)

    def frac_mask(z):
        return (z > rounding.EPS) & (z < 1.0 - rounding.EPS)

    def cond(carry):
        z, _ = carry
        return frac_mask(z).sum() >= 2

    def body(carry):
        z, key = carry
        f = frac_mask(z)
        idx = jnp.argsort(~f)          # fractional entries first (stable)
        i, j = idx[0], idx[1]
        zi, zj = z[i], z[j]
        p = jnp.minimum(1.0 - zi, zj)
        q = jnp.minimum(zi, 1.0 - zj)
        key, k1 = jax.random.split(key)
        u = jax.random.uniform(k1)
        first = u < q / jnp.maximum(p + q, 1e-12)
        zi_new = jnp.where(first, zi + p, zi - q)
        zj_new = jnp.where(first, zj - p, zj + q)
        z = z.at[i].set(zi_new).at[j].set(zj_new)
        return z, key

    z, key = jax.lax.while_loop(cond, body, (z, key))
    f = frac_mask(z)
    key, k1 = jax.random.split(key)
    u = jax.random.uniform(k1)
    return jnp.where(f, (u < z).astype(jnp.float32), jnp.round(z))


@given(instances)
@settings(max_examples=20, deadline=None)
def test_pairwise_round_two_smallest_bit_identical_to_argsort(seed):
    """The argmin-based pair selection keeps the RNG stream and the result
    bit-identical to the original stable-argsort implementation."""
    rng = np.random.default_rng(seed)
    k = int(rng.integers(3, 12))
    z = jnp.asarray(rng.uniform(0, 1, k), jnp.float32)
    key = jax.random.PRNGKey(seed)
    new = np.asarray(rounding.pairwise_round(z, key))
    old = np.asarray(_pairwise_round_argsort_ref(z, key))
    assert np.array_equal(new, old), (new, old)


@given(instances)
@settings(max_examples=30, deadline=None)
def test_pairwise_round_fixed_trips_bit_identical_to_while(seed):
    """The fixed (K−1)-trip scan driver consumes the identical RNG stream
    (a finished row's key only advances on active trips) and returns the
    identical mask as the data-dependent while_loop reference — across
    fractional counts from 0 to K, including near-integral entries inside
    the EPS finalization band."""
    rng = np.random.default_rng(seed)
    k = int(rng.integers(2, 12))
    z = rng.uniform(0, 1, k)
    # sprinkle saturated / near-integral / integral coordinates
    pick = rng.integers(0, 4, k)
    z = np.where(pick == 0, np.round(z), z)
    z = np.where(pick == 1, np.clip(z, 1 - 5e-6, 1.0), z)
    z = np.where(pick == 2, np.clip(z, 0.0, 5e-6), z)
    zj = jnp.asarray(z, jnp.float32)
    key = jax.random.PRNGKey(seed)
    fixed = np.asarray(rounding.pairwise_round(zj, key))          # K−1 scan
    while_ = np.asarray(rounding.pairwise_round(zj, key, trips=None))
    assert np.array_equal(fixed, while_), (z, fixed, while_)
    batched = np.asarray(rounding.pairwise_round_batch(
        zj[None], key[None]))[0]
    assert np.array_equal(fixed, batched)


def test_pairwise_round_near_integral_marginal_preservation():
    """Residual-fraction finalization audit: values left in (0, EPS] ∪
    [1−EPS, 1) are snapped deterministically by the final jnp.round — a
    per-arm marginal bias of at most EPS. Near-integral inputs must round
    to their integral neighbour with probability 1 and exact marginals
    must hold for the remaining arms."""
    eps = rounding.EPS
    z = np.array([1 - 1e-6, 1e-6, 0.5, 1.0, 0.0, 1 - eps, eps * 0.99])
    trials = 400
    acc = np.zeros(len(z))
    for i in range(trials):
        m = np.asarray(rounding.pairwise_round(
            jnp.asarray(z, jnp.float32), jax.random.PRNGKey(i)))
        assert m[0] == 1.0 and m[3] == 1.0, "snapped up inside the band"
        assert m[1] == 0.0 and m[4] == 0.0 and m[6] == 0.0, \
            "snapped down inside the band"
        acc += m
    est = acc / trials
    # the genuinely fractional arm keeps its marginal; snapped arms sit
    # within EPS of it by construction
    assert abs(est[2] - 0.5) < 0.08
    assert np.all(np.abs(est - z) <= np.maximum(0.08, eps))


def test_shared_ranks_util_consistency():
    """`core.ranks` is the single selection core: stable ranks match a
    stable argsort, and the crossing-form λ-batch mask matches ranking the
    subtracted scores directly (tie-free instances)."""
    from repro.core import ranks
    rng = np.random.default_rng(9)
    s = jnp.asarray(rng.normal(size=(5, 8)), jnp.float32)
    want = np.argsort(np.argsort(-np.asarray(s), axis=-1, kind="stable"),
                      axis=-1, kind="stable")
    assert np.array_equal(np.asarray(ranks.stable_desc_ranks(s)), want)

    w = jnp.asarray(rng.uniform(0.1, 1.0, 8), jnp.float32)
    c = jnp.asarray(rng.uniform(0.05, 0.6, 8), jnp.float32)
    lams = jnp.asarray([0.0, 0.3, 1.7, 10.0], jnp.float32)
    for equality in (True, False):
        got = np.asarray(ranks.lagrangian_topn_mask(w, c, lams, 3, equality))
        want = np.stack([
            np.asarray(ranks.topn_mask(w - lam * c, 3, equality))
            for lam in np.asarray(lams)])
        assert np.array_equal(got, want)
        cost = np.asarray(ranks.lagrangian_topn_cost(w, c, lams, 3,
                                                     equality))
        assert np.allclose(cost, (want * np.asarray(c)).sum(-1), atol=1e-6)


def test_rounding_expected_reward_dominates_relaxed():
    """E[r(S)] ≥ r̃(z̃) — the convexity step the regret proof rests on."""
    mu = np.array([0.8, 0.6, 0.4, 0.3])
    z = np.array([0.5, 0.5, 0.7, 0.3])
    vals = []
    for i in range(4000):
        m = rounding.pairwise_round_np(z, np.random.default_rng(i))
        vals.append(float(R.set_reward("awc", jnp.array(m), jnp.array(mu))))
    relaxed = float(R.relaxed_reward("awc", jnp.array(z), jnp.array(mu)))
    assert np.mean(vals) >= relaxed - 0.02
