"""Relaxed constrained solvers (paper §4.1, Eq. 3/4/5) — pure JAX.

The shared polytope is  P = { z̃∈[0,1]^K : Σz̃ (=|≤) N,  Σ c̲_k z̃_k ≤ ρ }.

`lp_topn` solves  max ⟨w, z̃⟩ over P with a *parametric Lagrangian* method:
for multiplier λ the optimizer of the Lagrangian is the top-N arms by score
w−λc; cost(λ) is non-increasing, so locating the breakpoint λ* and mixing
the two straddling vertices hits the budget exactly. For this 2-constraint
box LP the optimum has ≤2 fractional coordinates, so the mixed point is the
true LP optimum (validated against brute-force vertex enumeration in tests).
This replaces the paper's Gurobi call with a jit-able routine that vmaps
across tenants/seeds.

Two engines locate λ*:

  grid   (default) — exact-ladder parametric search with two lowerings.
         On accelerators (Pallas `topn_lp` kernel active): one batched
         octave round over λ = 2^0..2^24 (the whole doubling ladder as a
         single (G, K) batch) followed by GRID_ROUNDS G-way mantissa rounds
         — each probe is only the *scalar* vertex cost Σc·z(λ), reduced by
         the tiled Pallas kernel, so the search is a handful of wide fused
         batches instead of ~72 dependent vertex evaluations. On CPU
         (dispatch/throughput-bound; wide batches buy nothing): the same
         ladder walked probe-count-optimally — integer-exponent bisection
         then mantissa bisection against *precomputed pairwise crossing
         thresholds* t[i,j] = (w_j−w_i)/(c_j−c_i), making each probe one
         compare+xor per arm pair (~29 cheap rows vs the reference's 72).
         Every probe λ is exactly representable (2^e · dyadic m), so all
         recomputation is bitwise reproducible under any XLA fusion.
  bisect — the original sequential double-then-bisect chain (DOUBLE_ITERS +
         BISECT_ITERS depth, full score-vertex evaluation per step),
         retained as the reference implementation for equivalence tests
         and benchmark baselines (the PR-2 solver).

Both engines pair the straddling vertices with the costs that were actually
probed for them when mixing (recomputing z from λ through a
differently-rounded score expression can flip a near-tie and return a
vertex whose cost was never the one tested — see `core.ranks` on why
w − λ·c is never ranked directly).

  SUC: lp_topn(μ̄)                    (Eq. 4, α = 1)
  AIC: lp_topn(ln μ̄)                 (Eq. 5 log-transform, α = 1)
  AWC: continuous greedy — Frank-Wolfe on the multilinear extension with
       lp_topn as the linear-maximization oracle (Eq. 3, α = 1 − 1/e).

AWC fast path (the fleet's hardest reward model): consecutive FW gradients
barely move the Lagrangian breakpoint λ*, so on the grid engine the λ
bracket found for step t seeds step t+1 — a 2-row revalidation probe
({λ_lo, λ_hi}) plus two escape rows plus FW_WARM_ITERS bisection rows
replaces the full ~25-probe-row cold search (`_grid_tail_warm`; the
escape schedule guarantees whole-ladder recovery, and a lane that
escaped keeps bisecting until its bracket is as narrow as the cold
search's — a data-dependent trip count bounded by FW_WARM_MAX_ITERS).
`fw_steps` (default `FW_STEPS`, env ``REPRO_FW_STEPS``) and `fw_warm`
(env ``REPRO_FW_WARM``) are trace-time static knobs threaded through every
solver entry point; warm-started and cold-started FW are decision-
equivalent (property-tested: equal objective, overwhelmingly bit-equal
z̃). On accelerators the per-step gradient + octave-ladder probe fuse into
the Pallas `awc_fw` kernel (`kernels/awc_fw.py`) so gradient rows are
never materialized between host-level ops.

Two entry points: `solve_relaxed` (static kind/n, the single-instance path)
and `solve_batch` = vmap(`solve_relaxed_ix`) — traced per-tenant kind index,
N, and ρ, dispatched via lax.switch, for the multi-tenant fleet driver.
All solver entry points take ``engine=None`` which resolves to
`DEFAULT_ENGINE` (env ``REPRO_LP_ENGINE``, default "grid"); the argument is
trace-time static, so jitted callers must thread it as a static argument.
"""
from __future__ import annotations

import itertools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rewards as R
from repro.core.ranks import (lagrangian_topn_cost, lagrangian_topn_mask,
                              stable_desc_ranks, topn_mask)
from repro.kernels import ops as kops

__all__ = [
    "lp_topn", "lp_topn_dyn", "solve_relaxed", "solve_relaxed_ix",
    "solve_batch", "solve_direct", "enumerate_actions", "stable_desc_ranks",
    "budget_cost", "ENGINES", "DEFAULT_ENGINE",
]

BISECT_ITERS = 48     # bisect engine: sequential bisection depth
DOUBLE_ITERS = 24     # bisect engine: λ-doubling depth (cap λ at 2^24)
# Continuous-greedy step count. The warm-started search makes each step
# ~8 probe rows instead of ~25, so the AWC round is dominated by step
# count again — the default drops to 8, which stays within 5e-3 of the
# original 16 on the paper-pool corpus (property-tested sweep; 12 stays
# within 1e-3) while halving the LP-oracle chain, the dominant term of an
# AWC fleet round. ``REPRO_FW_STEPS=16`` restores the PR-2/3 setting;
# callers may also thread ``fw_steps``. The (1−1/e) offline guarantee
# holds at every tested count (fixed-step continuous greedy attains
# 1−(1−1/T)^T ≥ 1−1/e for any T, and the α-guarantee test runs at the
# default).
FW_STEPS = int(os.environ.get("REPRO_FW_STEPS", "8"))
FW_WARM = os.environ.get("REPRO_FW_WARM", "1") not in ("0", "false", "False")
FW_WARM_ITERS = 3      # warm FW: bisection probe rows per step (on top of
#                        the 2-row revalidation and 2 escape rows; escapes
#                        double as bisections when the carried bracket is
#                        still valid, and refinement compounds across FW
#                        steps — near-bit-equal to cold FW on the test
#                        corpus, objective gap ≤ 2e-6). A lane whose
#                        bracket is still wider than the cold search's
#                        resolution after them keeps bisecting, up to
#                        FW_WARM_MAX_ITERS rows in all.

LAM_MAX_EXP = 24       # both engines cap λ at 2^LAM_MAX_EXP
GRID_ROUNDS = 4        # wide lowering: mantissa rounds (incl. the final one)
GRID_POINTS = 64       # wide lowering: λ probes per round (power of 2)
GRID_EXP_ITERS = 5     # CPU lowering: integer-exponent bisection depth
GRID_TAIL_ITERS = 18   # CPU lowering: mantissa bisection depth
# enough rows to narrow [4·λ, 2^LAM_MAX_EXP] (an `up` escape's bracket) to
# the cold search's resolution
FW_WARM_MAX_ITERS = FW_WARM_ITERS + LAM_MAX_EXP + GRID_TAIL_ITERS + 1

ENGINES = ("grid", "bisect")
DEFAULT_ENGINE = os.environ.get("REPRO_LP_ENGINE", "grid")


def _resolve_engine(engine: Optional[str]) -> str:
    engine = DEFAULT_ENGINE if engine is None else engine
    if engine not in ENGINES:
        raise ValueError(f"unknown LP engine {engine!r}, want one of "
                         f"{ENGINES}")
    return engine


def _resolve_fw(fw_steps: Optional[int], fw_warm: Optional[bool]):
    return (FW_STEPS if fw_steps is None else int(fw_steps),
            FW_WARM if fw_warm is None else bool(fw_warm))


def _topn_given_lambda(w, c, n: int, lam, equality: bool):
    """Vertex z(λ): indicator of the top-n arms by score w - λ·c."""
    score = w - lam * c
    k = w.shape[-1]
    _, idx = jax.lax.top_k(score, n)
    z = jnp.zeros((k,), jnp.float32).at[idx].set(1.0)
    if not equality:
        z = z * (score > 0)  # inclusive matroid: drop negative-score arms
    return z


def _topn_given_lambda_dyn(w, c, n, lam, equality: bool):
    """`_topn_given_lambda` with a *traced* cardinality n.

    Rank-threshold formulation so n can vary per tenant under vmap."""
    return topn_mask(w - lam * c, n, equality)


def _mix_straddle(rho, z_lo, c_lo, z_hi, c_hi):
    """Mix the straddling vertices to meet the budget exactly.

    z_lo is the infeasible-side vertex (cost > ρ when one exists), z_hi the
    feasible-side one; c_lo/c_hi are the costs *as probed for those
    vertices* (the consistency every engine path relies on). When even
    z_hi violates ρ (unattainable budget, see `lp_topn`) θ clips to 0 and
    z_hi is returned as-is."""
    theta = jnp.where(c_lo > c_hi, (rho - c_hi) / jnp.maximum(c_lo - c_hi,
                                                              1e-12), 0.0)
    theta = jnp.clip(theta, 0.0, 1.0)
    return theta * z_lo + (1 - theta) * z_hi


# ============================================================== grid engine
def _lagrangian_costs(w, c, n, lams, equality: bool):
    """cost(λ) = Σ c·z(λ) for a whole λ batch: lams (G,) -> (G,) float32.

    Only the scalar reduction is computed; no (G, K) vertex is ever
    materialized during the search. On TPU the reduction is the Pallas
    `topn_lp` kernel over (G, K) score rows; elsewhere it is the
    FMA-proof crossing form (`ranks.lagrangian_topn_cost`)."""
    if kops.use_pallas():
        scores = w[None, :] - lams[:, None] * c[None, :]
        return kops.topn_lp(scores, jnp.broadcast_to(c, scores.shape),
                            jnp.broadcast_to(jnp.asarray(n, jnp.int32),
                                             lams.shape), equality=equality)
    return lagrangian_topn_cost(w, c, lams, n, equality)


def _octave_ladder():
    """The exact power-of-two λ ladder 2^0..2^LAM_MAX_EXP shared by the
    wide lowering's octave round and the fused `awc_fw` kernel probe."""
    return jnp.asarray(2.0 ** np.arange(LAM_MAX_EXP + 1), jnp.float32)


def _grid_wide(w, c, n, rho, equality: bool):
    """Accelerator lowering: G-way batched mantissa rounds.

    The λ ladder is kept *exactly representable* throughout: an octave
    scale 2^e gathered from a constant ladder times a mantissa m carrying
    log2(GRID_POINTS) bits per round. Every probe λ = 2^e·m is then an
    exact product, so recomputing anything from λ is bitwise reproducible
    no matter how XLA fuses or duplicates the expression — the property
    the engine's probe/materialize consistency rests on (see `core.ranks`
    module docstring for the failure mode this avoids)."""
    # octave round: the whole doubling ladder as one batch
    feas = _lagrangian_costs(w, c, n, _octave_ladder(), equality) <= rho
    return _grid_wide_from_octave(w, c, n, rho, equality, feas)


def _grid_wide_from_octave(w, c, n, rho, equality: bool, feas):
    """Mantissa rounds of the wide lowering given the octave round's
    feasibility row (`feas` = cost(2^e) <= ρ over the whole ladder) — split
    out so the fused AWC kernel (`kernels/awc_fw.py`), which emits the
    octave costs together with the multilinear gradient, can feed the same
    refinement."""
    bits = GRID_POINTS.bit_length() - 1
    assert GRID_POINTS == 1 << bits, "GRID_POINTS must be a power of two"

    geom = _octave_ladder()
    i = jnp.argmax(feas)                     # first feasible octave
    any_f = feas.any()
    # bracket = scale·[m_lo, m_hi]: below the first octave the "octave" is
    # [0, 1] (m in [0, 1], scale 1); with no feasible octave at all the
    # ladder walks up from the λ-cap (ρ unattainable, see `lp_topn`).
    scale = jnp.where(any_f & (i > 0), geom[jnp.maximum(i - 1, 0)],
                      jnp.where(any_f, 1.0, geom[geom.shape[0] - 1]))
    m_lo = jnp.where(any_f & (i == 0), 0.0, 1.0)
    m_hi = jnp.where(any_f & (i == 0), 1.0, jnp.where(any_f, 2.0, 1.0))

    # mantissa rounds: GRID_POINTS probes refine `bits` more bits each.
    # ks·step and scale·m are exact, m_lo + ks·step rounds an exact sum —
    # all uniquely-rounded ops. Straddle updates are positional (first
    # feasible probe), so the bracket stays ordered even where boundary
    # rounding makes the measured feasibility locally non-monotone.
    # λ probes are clamped to the cap so the degenerate no-feasible-octave
    # bracket (m walking above 1 at scale 2^24) cannot discover λ's beyond
    # the documented 2^LAM_MAX_EXP contract of `lp_topn`.
    lam_cap = jnp.float32(2.0 ** LAM_MAX_EXP)
    ks = jnp.arange(GRID_POINTS, dtype=jnp.float32)
    for r in range(1, GRID_ROUNDS):
        step = jnp.float32(2.0 ** (-bits * r))
        ms = m_lo + ks * step
        lams = jnp.minimum(scale * ms, lam_cap)
        feas = _lagrangian_costs(w, c, n, lams, equality) <= rho
        i = jnp.argmax(feas)
        any_f = feas.any()
        m_hi = jnp.where(any_f, ms[i], m_hi)
        m_lo = jnp.where(any_f & (i > 0), ms[jnp.maximum(i - 1, 0)],
                         jnp.where(any_f, m_lo, ms[GRID_POINTS - 1]))

    # final round: λ=0 and the feasible-side endpoint ride along with the
    # finest ladder so every possible straddle lies inside ONE batch; the
    # (G, K) vertex rows, their costs, the feasibility test, and the mixing
    # weight θ all derive from that batch. Selection is value-based (the
    # cheapest feasible λ and the costliest infeasible one), which needs no
    # ordering assumption and pairs the true straddling vertices even if a
    # boundary probe flipped during bracketing.
    # The bracketing probes rank scores w - λ·c (the Pallas kernels); this
    # batch ranks by crossing thresholds (`lagrangian_topn_mask`). The two
    # can disagree within a few ulps of a crossing, which may leave every
    # fine probe on one side of it; a guard one bracketing step outside
    # each end keeps a vertex of each side in the batch, so the straddle
    # pairs adjacent vertices instead of falling back to λ = 0.
    step = jnp.float32(2.0 ** (-bits * GRID_ROUNDS))
    guard = jnp.float32(2.0 ** (-bits * (GRID_ROUNDS - 1)))
    lams = jnp.concatenate([jnp.zeros((1,), jnp.float32),
                            jnp.maximum(scale * (m_lo - guard), 0.0)[None],
                            jnp.minimum(scale * (m_lo + ks * step), lam_cap),
                            jnp.minimum(scale * m_hi, lam_cap)[None],
                            jnp.minimum(scale * (m_hi + guard), lam_cap)[None]])
    masks = lagrangian_topn_mask(w, c, lams, n, equality)      # (G+4, K)
    costs = (masks * c).sum(-1)
    feas = costs <= rho
    i_hi = jnp.where(feas.any(), jnp.argmin(jnp.where(feas, lams, jnp.inf)),
                     jnp.argmax(lams))
    i_lo = jnp.where((~feas).any(),
                     jnp.argmax(jnp.where(feas, -jnp.inf, lams)), i_hi)
    return _mix_straddle(rho, masks[i_lo], costs[i_lo],
                         masks[i_hi], costs[i_hi])


def _probe_factory(c, n, equality):
    """Two-stage crossing-threshold probe builder: everything derivable
    from the cost side alone is computed once per *solve* (the AWC
    Frank-Wolfe loop re-makes the probe for a fresh gradient every step,
    but c never changes), and `make(w)` adds the score-dependent pieces.

    ``equality`` is a python bool on the single-kind paths — the
    inclusive-matroid positivity filter is then compiled in or out — or a
    traced per-row bool on the mixed-fleet unified path, where the filter
    is applied behind a select so one probe chain serves every reward
    model in the batch.

    All pairwise crossings are precomputed as thresholds
    t[i,j] = (w_j−w_i)/(c_j−c_i), and a probe is then one compare+xor per
    pair,

        beats[i,j] = (λ < t[i,j]) XOR (c_j < c_i),

    with t[j,i] == t[i,j] bitwise (negation-exact division) and the xor
    bit flipped — exactly one of each pair beats, so the induced ranks are
    always a permutation, under any fusion (`core.ranks` docstring)."""
    k = c.shape[-1]
    idx = jnp.arange(k)
    lower = idx[None, :] < idx[:, None]
    dc = c[None, :] - c[:, None]
    dc0 = dc == 0
    d = dc < 0                               # direction bit
    eq_static = isinstance(equality, bool)
    need_pos = (not equality) if eq_static else True
    if need_pos:
        pd = c < 0
        c0 = c == 0
    nn = jnp.asarray(n)

    def make(w):
        dw = w[None, :] - w[:, None]         # [i, j] = w_j − w_i
        # λ-free pairs (c_i == c_j): order by dw, index breaks exact ties
        tie = (dw > 0) | ((dw == 0) & lower)
        t = jnp.where(dc0, jnp.where(tie, jnp.inf, -jnp.inf),
                      dw / dc)               # crossing λ of each pair
        if need_pos:
            # positivity crossing (inclusive): s_i > 0 <=> λ < w_i/c_i
            p = jnp.where(c0, jnp.where(w > 0, jnp.inf, -jnp.inf), w / c)

        def probe(lam):                      # vertex + cost at λ (or batch)
            beats = (lam[..., None, None] < t) ^ d
            mask = (beats.sum(-1) < nn[..., None]).astype(jnp.float32)
            if need_pos:
                pos = ((lam[..., None] < p) ^ pd).astype(jnp.float32)
                if eq_static:
                    mask = mask * pos
                else:
                    mask = mask * jnp.where(equality, 1.0, pos)
            return mask, (mask * c).sum(-1)

        return probe

    return make


def _make_probe(w, c, n, equality):
    """One-shot probe closure (the cold search path)."""
    return _probe_factory(c, n, equality)(w)


def _exp2i(e):                               # exact 2^e for int32 e >= -126
    return jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)


def _grid_tail(w, c, n, rho, equality: bool):
    """CPU lowering: crossing-threshold bisection, probe-count optimal.

    On a dispatch/throughput-bound host, wall time tracks the number of
    probe rows evaluated, batched or not — so this lowering spends the
    probe budget like a binary search: 2 init rows (λ=0 and the λ-cap),
    GRID_EXP_ITERS integer-exponent rows locating λ*'s octave (replacing
    the reference's 24 sequential doublings), and GRID_TAIL_ITERS mantissa
    rows — ~29 rows against the reference's 72, each made cheap by the
    precomputed crossing thresholds of `_make_probe`.
    Probe λ's stay exactly representable (2^e, then 2^e·m with dyadic m),
    and vertices ride the loop carry with their costs like the bisect
    reference, so the returned mix uses exactly the probed quantities."""
    z, _, _ = _grid_tail_bracket(w, c, n, rho, equality)
    return z


def _grid_tail_bracket(w, c, n, rho, equality: bool):
    """`_grid_tail` that also returns the final (λ_lo, λ_hi) bracket — the
    warm-start seed the AWC Frank-Wolfe loop carries across iterations."""
    probe = _make_probe(w, c, n, equality)
    exp2i = _exp2i

    # both anchors in one probe batch: λ=0 and the λ-cap. Carries stay in
    # this packed [infeasible-side, feasible-side] pair layout so each
    # bisection step updates them with one shared select: a feasible mid
    # replaces slot 1, an infeasible one slot 0.
    Z, C = probe(jnp.asarray([0.0, 2.0 ** LAM_MAX_EXP], jnp.float32))
    z0, cost0 = Z[0], C[0]
    slot = jnp.asarray([False, True])        # which slot a feasible λ takes

    # phase 1: integer bisection over the exponent e ∈ {0..LAM_MAX_EXP},
    # with e_lo = -1 standing for λ=0 and e_hi = LAM_MAX_EXP+1 for the cap.
    def ebis(_, carry):
        e, Z, C = carry
        mid = (e[0] + e[1]) // 2
        z_m, c_m = probe(exp2i(mid))
        sel = (c_m <= rho) == slot
        return (jnp.where(sel, mid, e), jnp.where(sel[:, None], z_m, Z),
                jnp.where(sel, c_m, C))

    e, Z, C = jax.lax.fori_loop(
        0, GRID_EXP_ITERS, ebis,
        (jnp.asarray([-1, LAM_MAX_EXP + 1], jnp.int32), Z, C))

    # phase 2: mantissa bisection inside the octave. λ = scale·m is an
    # exact product (scale a power of two, m dyadic), probed in λ-space
    # against the same thresholds. e_lo = -1 means λ* ∈ (0, 1]: scale 1,
    # m ∈ [0, 1]. With ρ unattainable the carries never update and the
    # λ-cap vertex flows through (θ clips to 0; see `lp_topn`).
    e_lo = e[0]
    scale = jnp.where(e_lo < 0, jnp.float32(1.0),
                      exp2i(jnp.maximum(e_lo, 0)))
    # e_lo == LAM_MAX_EXP means even the cap is infeasible: a degenerate
    # [1, 1] bracket keeps every probe AT the cap rather than walking m
    # above it (λ beyond 2^LAM_MAX_EXP would break the `lp_topn` contract)
    m0 = jnp.where(e_lo < 0, jnp.asarray([0.0, 1.0]),
                   jnp.where(e_lo >= LAM_MAX_EXP, jnp.asarray([1.0, 1.0]),
                             jnp.asarray([1.0, 2.0])))

    def mbis(_, carry):
        m, Z, C = carry
        mid = 0.5 * (m[0] + m[1])
        z_m, c_m = probe(scale * mid)
        sel = (c_m <= rho) == slot
        return (jnp.where(sel, mid, m), jnp.where(sel[:, None], z_m, Z),
                jnp.where(sel, c_m, C))

    m, Z, C = jax.lax.fori_loop(0, GRID_TAIL_ITERS, mbis, (m0, Z, C))
    z_mix = _mix_straddle(rho, Z[0], C[0], Z[1], C[1])
    return (jnp.where(cost0 <= rho, z0, z_mix), scale * m[0], scale * m[1])


def _grid_tail_warm(probe, rho, lam_lo, lam_hi, Zi, Ci):
    """Warm-started `_grid_tail`: revalidate + refine a carried λ bracket.

    The caller supplies the probe closure and the 2-row revalidation probe
    at {λ_lo, λ_hi} (`Zi`/`Ci`). Classification, then two escape probes,
    then pure bisection — every trip count fixed (vmap/switch friendly):

      refine    — the carried bracket still straddles the breakpoint:
                  all remaining probes are plain packed-slot bisections
                  (the cold search's phase-2 machinery).
      down      — both carried ends went feasible (λ* fell below λ_lo):
                  escape probe A re-anchors at λ=0, which doubles as the
                  cold search's feasible-at-0 early-exit probe — cost(0)
                  bounds every cost(λ), so the early exit is *provably
                  unreachable* in refine/up lanes and the λ=0 row is paid
                  only where it can matter. Bisection of [0, λ_lo]
                  refines.
      up        — both ends infeasible (λ* rose above λ_hi): escape probe
                  A tries λ_hi·4; if still infeasible, escape probe B
                  jumps straight to the λ-cap — either feasible (valid,
                  if coarse, bracket [λ_hi·4, cap] that bisection then
                  tightens) or infeasible (ρ unattainable: the cap vertex
                  flows to both slots, θ clips to 0 — the cold search's
                  documented degradation).

    Every lane therefore holds a valid (or terminal-cap) straddle after
    the two escape probes no matter how far λ* drifted, and the common
    no-drift case spends its whole budget bisecting — a step whose carried
    bracket still isolates the breakpoint returns the cold answer
    bit-for-bit. FW_WARM_ITERS counts the bisection rows; with the 2-row
    revalidation and 2 escape rows the warm step costs ~8 probe rows
    against the cold search's ~25. A lane that escaped holds a coarse
    bracket that may span several breakpoints, whose end vertices differ
    by more than one swap and mix to less than the LP optimum: bisection
    continues until every lane's bracket is as narrow as the cold
    search's (relative 2^-(GRID_TAIL_ITERS+1), absolute below λ = 1)."""
    lam_cap = jnp.float32(2.0 ** LAM_MAX_EXP)
    slot = jnp.asarray([False, True])

    lo_feas = Ci[0] <= rho        # λ* < λ_lo: both carried ends feasible
    hi_infeas = Ci[1] > rho       # λ* > λ_hi: both carried ends infeasible
    # modes: refine, down (re-anchor at 0), up (expand toward the cap)
    lam = jnp.stack([jnp.where(lo_feas, 0.0, jnp.where(hi_infeas, lam_hi,
                                                       lam_lo)),
                     jnp.where(lo_feas, lam_lo, jnp.where(hi_infeas, lam_cap,
                                                          lam_hi))])
    # slot 0 = infeasible side, slot 1 = feasible side. Stale slots (0 in
    # mode down until probe A lands, 1 in mode up until probe B) are
    # overwritten before the bisection phase in every lane.
    Z = jnp.stack([jnp.where(hi_infeas[..., None], Zi[1], Zi[0]),
                   jnp.where(lo_feas[..., None], Zi[0], Zi[1])])
    C = jnp.stack([jnp.where(hi_infeas, Ci[1], Ci[0]),
                   jnp.where(lo_feas, Ci[0], Ci[1])])

    # escape probe A: λ=0 (down), ×4 clamped to the cap (up), bisect
    # (refine). Down lanes commit A to slot 0 unconditionally — it is the
    # 0-anchor — and a feasible cost(0) raises the early-exit flag.
    mid = jnp.where(lo_feas, 0.0,
                    jnp.where(hi_infeas,
                              jnp.minimum(4.0 * lam[0], lam_cap),
                              0.5 * (lam[0] + lam[1])))
    z_m, c_m = probe(mid)
    feas = c_m <= rho
    done = lo_feas & feas         # cost(0) <= ρ: z(0) is the optimum
    z_done = z_m
    sel = jnp.where(lo_feas, ~slot, feas == slot)
    lam = jnp.where(sel, mid, lam)
    Z = jnp.where(sel[:, None], z_m, Z)
    C = jnp.where(sel, c_m, C)
    up = hi_infeas & ~feas        # still infeasible at min(4·λ_hi, cap)

    # escape probe B: unresolved-up jumps to the cap; everything else
    # bisects its bracket.
    mid = jnp.where(up, lam_cap, 0.5 * (lam[0] + lam[1]))
    z_m, c_m = probe(mid)
    feas = c_m <= rho
    at_cap = up & ~feas           # ρ unattainable: cap vertex, both slots
    sel = (feas == slot) | at_cap
    lam = jnp.where(sel, mid, lam)
    Z = jnp.where(sel[:, None], z_m, Z)
    C = jnp.where(sel, c_m, C)

    # pure bisection on a now-valid bracket — the cold phase-2 machinery —
    # for FW_WARM_ITERS rows, then while the bracket is coarser than the
    # cold search's
    tol = jnp.float32(2.0 ** -(GRID_TAIL_ITERS + 1))

    def more(carry):
        i, lam, _, _ = carry
        coarse = lam[1] - lam[0] > tol * jnp.maximum(lam[1], 1.0)
        return (i < FW_WARM_ITERS) | (coarse & ~done
                                      & (i < FW_WARM_MAX_ITERS))

    def bis(carry):
        i, lam, Z, C = carry
        mid = 0.5 * (lam[0] + lam[1])
        z_m, c_m = probe(mid)
        sel = (c_m <= rho) == slot
        return (i + 1, jnp.where(sel, mid, lam),
                jnp.where(sel[:, None], z_m, Z), jnp.where(sel, c_m, C))

    _, lam, Z, C = jax.lax.while_loop(more, bis, (0, lam, Z, C))
    z_mix = _mix_straddle(rho, Z[0], C[0], Z[1], C[1])
    return jnp.where(done, z_done, z_mix), lam[0], lam[1]


def _lp_topn_grid(w, c, n, rho, equality: bool):
    """Shared grid engine: static and traced n both route here (vertices
    are rank-thresholded, so n may vary per tenant under vmap). Dispatches
    to the wide G-way lowering when the Pallas `topn_lp` kernel is active
    (TPU) and to the probe-optimal crossing-threshold lowering elsewhere;
    both handle the feasible-at-λ=0 early exit and the unattainable-ρ cap
    internally."""
    w = w.astype(jnp.float32)
    c = c.astype(jnp.float32)
    rho = jnp.float32(rho)
    body = _grid_wide if kops.use_pallas() else _grid_tail
    return body(w, c, n, rho, equality)


# ========================================================= AWC Frank-Wolfe
def _awc_fw(dyn: bool, mu_bar, c_low, n, rho, engine: Optional[str],
            fw_steps: Optional[int], fw_warm: Optional[bool]):
    """Continuous greedy (Eq. 3): `fw_steps` Frank-Wolfe steps on the AWC
    multilinear extension, each solving the relaxed LP for the current
    gradient.

    On the grid engine with ``fw_warm`` (the default) the λ bracket of each
    step seeds the next (`_grid_tail_warm`): ~11 probe rows per warm step
    against the cold search's ~25 — the dominant cost of an AWC tenant
    round on a dispatch-bound host. The wide (accelerator) lowering keeps
    per-step G-way rounds — batching is free there — and fuses the gradient
    with the octave-ladder probe in the Pallas `awc_fw` kernel, so no
    gradient row is materialized between host-level ops.
    ``engine="bisect"`` retains the PR-2 cold reference; ``fw_warm=False``
    on the grid engine is the cold-start reference for the warm==cold
    equivalence tests."""
    fw_steps, fw_warm = _resolve_fw(fw_steps, fw_warm)
    zeros = jnp.zeros_like(mu_bar, jnp.float32)
    if _resolve_engine(engine) == "bisect":
        vertex = _topn_given_lambda_dyn if dyn else _topn_given_lambda

        def fw(i, z):
            g = R.awc_multilinear_grad(z, mu_bar)
            v = _lp_topn_bisect(vertex, g, c_low, n, rho, False)
            return z + v / fw_steps
        return jax.lax.fori_loop(0, fw_steps, fw, zeros)

    c32 = c_low.astype(jnp.float32)
    rho32 = jnp.asarray(rho, jnp.float32)
    if kops.use_pallas():
        # wide lowering: G-way rounds are already one fused batch per
        # round, so warm-starting buys no rows; the fused kernel folds the
        # gradient into the octave probe instead.
        def fw(i, z):
            g, oct_costs = kops.awc_fw(z[None], mu_bar[None], c32[None],
                                       _octave_ladder()[None],
                                       jnp.asarray(n, jnp.int32)[None])
            v = _grid_wide_from_octave(g[0], c32, n, rho32, False,
                                       oct_costs[0] <= rho32)
            return z + v / fw_steps
        return jax.lax.fori_loop(0, fw_steps, fw, zeros)

    g0 = R.awc_multilinear_grad(zeros, mu_bar).astype(jnp.float32)
    v0, lo, hi = _grid_tail_bracket(g0, c32, n, rho32, False)
    return _awc_fw_cont(mu_bar, c32, n, rho32, fw_steps, fw_warm,
                        v0, lo, hi)


def _awc_fw_cont(mu_bar, c32, n, rho32, fw_steps: int, fw_warm: bool,
                 v0, lo, hi):
    """Frank-Wolfe continuation from an already-solved first step: FW
    iterations 1..fw_steps−1, warm-seeded by step 0's λ bracket. Shared by
    the single-kind AWC solve (step 0 = its own cold search) and the
    mixed-fleet unified path (step 0 = the fleet-wide batched search)."""
    if not fw_warm:
        def fw(i, carry):
            z, lo, hi = carry
            g = R.awc_multilinear_grad(z, mu_bar).astype(jnp.float32)
            v, lo, hi = _grid_tail_bracket(g, c32, n, rho32, False)
            return z + v / fw_steps, lo, hi
    else:
        make = _probe_factory(c32, n, False)   # c-side tables: once/solve

        def fw(i, carry):
            z, lo, hi = carry
            g = R.awc_multilinear_grad(z, mu_bar).astype(jnp.float32)
            probe = make(g)
            Zi, Ci = probe(jnp.stack([lo, hi]))
            v, lo, hi = _grid_tail_warm(probe, rho32, lo, hi, Zi, Ci)
            return z + v / fw_steps, lo, hi

    z, _, _ = jax.lax.fori_loop(1, fw_steps, fw, (v0 / fw_steps, lo, hi))
    return z


# ============================================================ bisect engine
def budget_cost(c, z):
    """Budget-side cost ⟨c, z⟩ (z (K,) or a stack of rows (..., K)) in
    exact float32: the TPU's default matmul precision would round c to
    bfloat16 and flip near-budget checks."""
    return jnp.dot(z, c, precision=jax.lax.Precision.HIGHEST)


def _lp_topn_bisect(vertex, w, c, n, rho, equality: bool):
    """Reference engine: sequential λ-doubling then bisection (PR-2 path)."""
    w = w.astype(jnp.float32)
    c = c.astype(jnp.float32)
    z0 = vertex(w, c, n, 0.0, equality)
    cost0 = budget_cost(c, z0)

    def cost_at(lam):
        return budget_cost(c, vertex(w, c, n, lam, equality))

    # double λ until feasible
    def dbl(_, lam):
        return jnp.where(cost_at(lam) > rho, lam * 2.0, lam)
    lam_hi0 = jax.lax.fori_loop(0, DOUBLE_ITERS, dbl, jnp.float32(1.0))

    # Bisection carrying the *vertices* on each side of the breakpoint.
    z_hi0 = vertex(w, c, n, lam_hi0, equality)

    def bis(_, carry):
        lo, hi, z_l, z_h = carry
        mid = 0.5 * (lo + hi)
        z_m = vertex(w, c, n, mid, equality)
        feas = budget_cost(c, z_m) <= rho
        lo_n = jnp.where(feas, lo, mid)
        hi_n = jnp.where(feas, mid, hi)
        z_l = jnp.where(feas, z_l, z_m)
        z_h = jnp.where(feas, z_m, z_h)
        return lo_n, hi_n, z_l, z_h

    _, _, z_lo, z_hi = jax.lax.fori_loop(
        0, BISECT_ITERS, bis, (jnp.float32(0.0), lam_hi0, z0, z_hi0))
    z_mix = _mix_straddle(rho, z_lo, budget_cost(c, z_lo), z_hi,
                          budget_cost(c, z_hi))
    return jnp.where(cost0 <= rho, z0, z_mix)


def _lp_topn_impl(vertex, w, c, n, rho, equality: bool,
                  engine: Optional[str] = None):
    if _resolve_engine(engine) == "grid":
        return _lp_topn_grid(w, c, n, rho, equality)
    return _lp_topn_bisect(vertex, w, c, n, rho, equality)


def lp_topn(w, c, n: int, rho: float, equality: bool,
            engine: Optional[str] = None):
    """max ⟨w,z⟩ s.t. Σz (=|≤) n, ⟨c,z⟩ ≤ rho, z∈[0,1]^K.

    Unattainable budgets degrade gracefully rather than erroring (the UCB
    loop may produce them transiently): λ is capped at 2^24, so when no
    vertex on the λ-ladder meets ρ — e.g. ρ below the cheapest n-subset
    cost, or score scales so large that even λ=2^24 cannot flip the ranking
    to the cheap arms — both engines return the λ-cap vertex (the
    minimum-cost top-n selection reachable under the cap), which then
    *violates* the budget. Callers needing hard feasibility must check
    ⟨c, z⟩ themselves.
    """
    return _lp_topn_impl(_topn_given_lambda, w, c, n, rho, equality, engine)


def lp_topn_dyn(w, c, n, rho, equality: bool, engine: Optional[str] = None):
    """`lp_topn` with traced (n, rho) — the per-tenant fleet/vmap path."""
    return _lp_topn_impl(_topn_given_lambda_dyn, w, c, n, rho, equality,
                         engine)


def solve_relaxed(kind: str, mu_bar, c_low, n: int, rho: float,
                  engine: Optional[str] = None,
                  fw_steps: Optional[int] = None,
                  fw_warm: Optional[bool] = None):
    """Fractional z̃ solving the relaxed problem for the given reward model.

    ``fw_steps``/``fw_warm`` (AWC only, trace-time static) select the
    Frank-Wolfe step count and the warm-started λ search — see `_awc_fw`;
    ``None`` resolves to `FW_STEPS` / `FW_WARM`."""
    if kind == "suc":
        return lp_topn(mu_bar, c_low, n, rho, equality=True, engine=engine)
    if kind == "aic":
        w = jnp.log(jnp.clip(mu_bar, R.EPS, 1.0))
        return lp_topn(w, c_low, n, rho, equality=True, engine=engine)
    if kind == "awc":
        return _awc_fw(False, mu_bar, c_low, n, rho, engine, fw_steps,
                       fw_warm)
    raise ValueError(kind)


def solve_relaxed_ix(kind_ix, mu_bar, c_low, n, rho,
                     kinds_present: Tuple[int, ...] = (0, 1, 2),
                     engine: Optional[str] = None,
                     fw_steps: Optional[int] = None,
                     fw_warm: Optional[bool] = None):
    """`solve_relaxed` with a *traced* reward-model index (R.KIND_INDEX
    order: awc=0, suc=1, aic=2) and traced (n, rho) — lax.switch dispatch so
    a mixed-kind fleet solves every tenant inside one jitted program.

    ``kinds_present`` (static) prunes the dispatch to the kinds a fleet
    actually contains: under vmap the switch evaluates *every* branch for
    the whole batch, and the AWC Frank-Wolfe branch alone is ~8 LP solves —
    a uniform SUC/AIC fleet must not pay for it.

    On the grid engine's CPU lowering a mixed batch does NOT pay one probe
    chain per kind: the first LP solve of every kind is the same
    parametric search on a per-row weight vector (μ̄ for SUC, ln μ̄ for
    AIC, the z̃=0 gradient — clipped μ̄ — for AWC) with a per-row matroid
    flag, so it runs as ONE unified `_grid_tail_bracket` chain for the
    whole batch (sequential probe rows are the scarce resource on a
    dispatch-bound host — branch chains under vmapped switch serialize,
    they don't overlap). Only the AWC Frank-Wolfe *continuation* stays
    behind the switch; SUC/AIC rows return the unified solve as-is.

    CONTRACT: every runtime kind_ix value must appear in kinds_present — an
    absent kind silently dispatches to another kind's branch (the index is
    traced, so it cannot be validated here). Derive it host-side from the
    actual batch, as `router.fleet._kinds_present` does."""

    def awc():
        return _awc_fw(True, mu_bar, c_low, n, rho, engine, fw_steps,
                       fw_warm)

    def suc():
        return lp_topn_dyn(mu_bar, c_low, n, rho, equality=True,
                           engine=engine)

    def aic():
        w = jnp.log(jnp.clip(mu_bar, R.EPS, 1.0))
        return lp_topn_dyn(w, c_low, n, rho, equality=True, engine=engine)

    branches = (awc, suc, aic)
    present = tuple(sorted(set(kinds_present)))
    if len(present) == 1:
        return branches[present[0]]()
    if _resolve_engine(engine) == "grid" and not kops.use_pallas():
        return _solve_ix_unified(kind_ix, mu_bar, c_low, n, rho, present,
                                 fw_steps, fw_warm)
    lut = np.zeros(len(branches), np.int32)      # kind index -> branch slot
    for slot, kind in enumerate(present):
        lut[kind] = slot
    slot = jnp.asarray(lut)[kind_ix]
    return jax.lax.switch(slot, [branches[kind] for kind in present])


AWC_IX = R.KIND_INDEX["awc"]


def _solve_ix_unified(kind_ix, mu_bar, c_low, n, rho,
                      present: Tuple[int, ...],
                      fw_steps: Optional[int], fw_warm: Optional[bool]):
    """Mixed-batch grid solve as one probe chain (see `solve_relaxed_ix`).

    The per-row weight vector selects the kind's score transform; the
    matroid flag (equality for SUC/AIC, inclusive for AWC) rides the probe
    behind a select. Row results are bitwise identical to the single-kind
    paths: the AWC z̃=0 gradient is exactly clip(μ̄, 0, 1−1e−6) (log1p(0)
    and exp(0) are exact), and the traced-equality probe computes the
    equality-side mask with the same ops as the static one."""
    fw_steps, fw_warm = _resolve_fw(fw_steps, fw_warm)
    c32 = c_low.astype(jnp.float32)
    rho32 = jnp.asarray(rho, jnp.float32)
    mu32 = mu_bar.astype(jnp.float32)
    w = mu32 if 1 in present else None
    if 2 in present:
        w_aic = jnp.log(jnp.clip(mu32, R.EPS, 1.0))
        w = w_aic if w is None else jnp.where(kind_ix == 2, w_aic, w)
    if AWC_IX in present:
        g0 = R.awc_multilinear_grad(jnp.zeros_like(mu32), mu_bar)
        w = g0 if w is None else jnp.where(kind_ix == AWC_IX, g0, w)
    # static equality when no AWC row exists: the positivity filter (and
    # its per-probe select) compiles out entirely
    equality = True if AWC_IX not in present else kind_ix != AWC_IX
    z1, lo, hi = _grid_tail_bracket(w.astype(jnp.float32), c32, n, rho32,
                                    equality)
    if AWC_IX not in present:
        return z1
    return jax.lax.cond(
        kind_ix == AWC_IX,
        lambda: _awc_fw_cont(mu_bar, c32, n, rho32, fw_steps, fw_warm,
                             z1, lo, hi),
        lambda: z1)


def solve_batch(kind_ix, mu_bar, c_low, n, rho,
                kinds_present: Tuple[int, ...] = (0, 1, 2),
                engine: Optional[str] = None,
                fw_steps: Optional[int] = None,
                fw_warm: Optional[bool] = None):
    """Batched relax solve: one row per tenant, per-tenant task kind.

    kind_ix (M,) int32, mu_bar/c_low (M, K), n (M,) int32, rho (M,) — vmap
    of `solve_relaxed_ix`; under vmap the lax.switch evaluates each present
    branch once for the whole batch and selects per row."""
    return jax.vmap(
        lambda ki, mb, cl, nn, rr: solve_relaxed_ix(ki, mb, cl, nn, rr,
                                                    kinds_present, engine,
                                                    fw_steps, fw_warm)
    )(kind_ix, mu_bar, c_low, n, rho)


# ===================================================================== direct
def enumerate_actions(k: int, n: int, equality: bool) -> np.ndarray:
    """All feasible index sets as a boolean matrix (M, K)."""
    sizes = [n] if equality else range(1, n + 1)
    rows = []
    for sz in sizes:
        for comb in itertools.combinations(range(k), sz):
            row = np.zeros(k, bool)
            row[list(comb)] = True
            rows.append(row)
    return np.asarray(rows)


def solve_direct(kind: str, mu, c, n: int, rho: float,
                 actions: Optional[np.ndarray] = None
                 ) -> Tuple[np.ndarray, float]:
    """C2MAB-V-Direct (paper Eq. 48 / App. E.3): exact enumeration of the
    discrete constrained problem. Exponential in K — the Table-4 baseline."""
    mu = np.asarray(mu, np.float64)
    c = np.asarray(c, np.float64)
    k = mu.shape[0]
    if actions is None:
        actions = enumerate_actions(k, n, R.equality_constrained(kind))
    cost = actions @ c
    feas = cost <= rho + 1e-12
    if kind == "awc":
        vals = 1.0 - np.prod(1.0 - mu[None, :] * actions, axis=1)
    elif kind == "suc":
        vals = actions @ mu
    else:
        vals = np.exp(actions @ np.log(np.maximum(mu, 1e-12)))
    vals = np.where(feas, vals, -np.inf)
    best = int(np.argmax(vals))
    if not np.isfinite(vals[best]):   # infeasible instance: cheapest action
        best = int(np.argmin(cost))
    return actions[best], float(vals[best])
