"""Continuous-batching serving core: the slot-indexed engine + scheduler
must be *equivalent* to the retained sequential reference, not just close.

The load-bearing property: on row-deterministic model families (dense
attention), a request routed through the continuous path — bucketed
prefill into slots, shared decode batches with unrelated co-resident
requests, evict/reuse — produces BIT-IDENTICAL tokens, out_lens and
logprobs to `Engine.generate` on that request alone. That is what lets
`router.service` treat dispatch mode as a pure scheduling choice (and the
serve benchmark call its speedup a scheduling win).

Also covered here: the prefill half of the split vs the full forward, EOS
forcing/freezing semantics, per-row decode-attention positions (partial
slot fills) vs the jnp oracle, the jitted M=1 `cloud.select` pad path vs
the numpy reference, and sequential≡continuous at the service level for
both SUC and the AWC cascade.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, list_archs
from repro.core import rounding
from repro.core.policies import PolicyConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.kernels import ops, ref
from repro.models import model as M
from repro.router.cloud import Replica, SchedulingCloud, _pad_to_n_np
from repro.router.service import MultiLLMService
from repro.serving.engine import Engine
from repro.serving.scheduler import (ContinuousScheduler, ReplicaRunner,
                                     Request)

VOCAB = 64


@pytest.fixture(scope="module")
def dense_cfg():
    # a dense (row-deterministic) family: bitwise-equal decode across batch
    # compositions, which the equivalence tests below rely on
    return dataclasses.replace(get_config("h2o-danube-3-4b").reduced(),
                               vocab=VOCAB)


@pytest.fixture(scope="module")
def dense_engine(dense_cfg):
    params = M.init_params(dense_cfg, jax.random.PRNGKey(0))
    return Engine(dense_cfg, params, max_len=32, eos_id=0, temperature=0.7)


@pytest.fixture(scope="module")
def pool(dense_cfg):
    return [Replica(f"m{i}",
                    Engine(dense_cfg,
                           M.init_params(dense_cfg, jax.random.PRNGKey(i)),
                           max_len=32, eos_id=0, temperature=0.7),
                    0.001 * (1 + i))
            for i in range(3)]


def drain_all(engine, requests, *, n_slots, chunk):
    runner = ReplicaRunner(engine, n_slots=n_slots, chunk=chunk)
    got = {}
    sched = ContinuousScheduler(
        [runner], on_complete=lambda c: got.__setitem__(c.request.rid,
                                                        c.result))
    for r in requests:
        sched.submit(r)
    sched.drain()
    return runner, got


# ===================================================== engine equivalence
def test_continuous_equals_sequential_bitwise(dense_engine):
    """5 requests through a 4-slot runner (forcing bucketing, queueing and
    slot evict/reuse) == `Engine.generate` per request, bit for bit."""
    rng = np.random.default_rng(1)
    reqs = [Request(tenant=0, arm=0,
                    prompts=rng.integers(1, VOCAB, (2, 6)),
                    max_new=8, seed=i) for i in range(5)]
    runner, got = drain_all(dense_engine, reqs, n_slots=4, chunk=3)
    assert set(got) == {r.rid for r in reqs}
    for r in reqs:
        want = dense_engine.generate(r.prompts, r.max_new, seed=r.seed)
        res = got[r.rid]
        np.testing.assert_array_equal(res.tokens, want.tokens)
        np.testing.assert_array_equal(res.out_lens, want.out_lens)
        np.testing.assert_array_equal(res.logprobs, want.logprobs)
    # every slot released and reusable after the drain
    assert sorted(runner._free) == list(range(4))
    assert not runner.resident and not runner.pending
    assert not np.asarray(runner.state.active).any()


def test_mixed_request_shapes_and_budgets(dense_engine):
    """Requests with different batch sizes and per-request max_new share
    slots; same-length prompts bucket into one prefill.

    Tokens and lengths stay exact. Logprobs are only allclose here: a
    bucket stacking differently-sized requests (1+3 rows -> a (4, S)
    prefill) changes XLA's CPU matmul tiling, so logits drift ~2e-7 vs
    the request-alone reference. Uniform-size buckets (the fleet case,
    above) are bit-equal end to end."""
    rng = np.random.default_rng(2)
    reqs = [Request(tenant=0, arm=0, prompts=rng.integers(1, VOCAB, (b, 6)),
                    max_new=mn, seed=7 + i)
            for i, (b, mn) in enumerate([(1, 4), (3, 10), (2, 7), (1, 12)])]
    _, got = drain_all(dense_engine, reqs, n_slots=5, chunk=4)
    for r in reqs:
        want = dense_engine.generate(r.prompts, r.max_new, seed=r.seed)
        res = got[r.rid]
        np.testing.assert_array_equal(res.tokens, want.tokens)
        np.testing.assert_array_equal(res.out_lens, want.out_lens)
        np.testing.assert_allclose(res.logprobs, want.logprobs, atol=1e-5)


# ========================================================== EOS semantics
@pytest.fixture(scope="module")
def eos_engine(dense_cfg):
    # tiny vocab + hot temperature => rows hit EOS well before the budget
    cfg = dataclasses.replace(dense_cfg, vocab=8)
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    return Engine(cfg, params, max_len=32, eos_id=0, temperature=2.0)


def test_eos_forcing_and_freeze(eos_engine):
    """After a row emits EOS it is forced to EOS for the rest of the budget
    with frozen stats — identically in both paths, even while the finished
    row keeps riding along in shared decode batches."""
    rng = np.random.default_rng(4)
    prompts = rng.integers(1, 8, (4, 6))
    max_new = 16
    want = eos_engine.generate(prompts, max_new, seed=11)
    # the fixture/seed choice must actually exercise early finish
    assert (want.out_lens < max_new).any(), want.out_lens
    for i in range(4):
        n = int(want.out_lens[i])
        if n < max_new:
            assert want.tokens[i, n - 1] == eos_engine.eos_id
            assert (want.tokens[i, n:] == eos_engine.eos_id).all()
    # continuous: co-resident with a second request so finished rows decode
    # alongside live ones before harvest (different prompt length => own
    # prefill bucket => the first request's prefill is untouched)
    reqs = [Request(tenant=0, arm=0, prompts=prompts, max_new=max_new,
                    seed=11),
            Request(tenant=1, arm=0, prompts=rng.integers(1, 8, (2, 7)),
                    max_new=max_new, seed=12)]
    _, got = drain_all(eos_engine, reqs, n_slots=8, chunk=5)
    res = got[reqs[0].rid]
    np.testing.assert_array_equal(res.tokens, want.tokens)
    np.testing.assert_array_equal(res.out_lens, want.out_lens)
    # logprobs only allclose: this vocab-8 unembed is skinny enough that
    # XLA tiles its matmul differently at decode batch 6 vs 4 (~1 ULP).
    # The vocab-64 configs above are pinned bit-equal.
    np.testing.assert_allclose(res.logprobs, want.logprobs, atol=1e-5)


def test_early_finish_frees_slots_for_queue(eos_engine):
    """A finished request is harvested mid-stream and its slots readmit
    queued work — the runner never deadlocks on a full cache."""
    rng = np.random.default_rng(5)
    reqs = [Request(tenant=0, arm=0, prompts=rng.integers(1, 8, (2, 6)),
                    max_new=12, seed=s) for s in range(6)]
    runner, got = drain_all(eos_engine, reqs, n_slots=2, chunk=2)
    assert len(got) == 6
    for r in reqs:
        want = eos_engine.generate(r.prompts, r.max_new, seed=r.seed)
        np.testing.assert_array_equal(got[r.rid].tokens, want.tokens)
    assert sorted(runner._free) == [0, 1]


# ==================================================== prefill vs forward
@pytest.mark.parametrize("arch", list_archs())
def test_prefill_matches_forward(arch):
    """`model.prefill` (the serving prompt phase) reproduces the training
    forward's next-token logits for every family."""
    cfg = get_config(arch).reduced()
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=64.0)  # no-drop MoE
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(7), (b, s), 0, cfg.vocab,
                              jnp.int32)
    inputs = {"tokens": toks}
    if cfg.family == "audio":
        inputs["frames"] = jnp.zeros((b, 64, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        inputs["vision_embeds"] = jnp.zeros(
            (b, max(s // M.VLM_VISION_FRACTION, 1), cfg.d_model),
            jnp.float32)
    logits_full, _ = M.forward(cfg, params, inputs)
    last, cache = M.prefill(cfg, params, inputs, 32)
    np.testing.assert_allclose(np.asarray(last),
                               np.asarray(logits_full[:, -1]),
                               atol=2e-5, rtol=2e-5)
    # the cache is the real decode cache: one more step stays consistent
    # with forward on the extended sequence
    nxt = jnp.argmax(last, -1).astype(jnp.int32)[:, None]
    pos0 = M.prefill_len(cfg, s)
    lg2, _ = M.decode_step(cfg, params, nxt, cache, jnp.int32(pos0))
    ext = {**inputs, "tokens": jnp.concatenate([toks, nxt], axis=1)}
    full2, _ = M.forward(cfg, params, ext)
    np.testing.assert_allclose(np.asarray(lg2[:, 0]),
                               np.asarray(full2[:, -1]),
                               atol=2e-3, rtol=2e-3)


# ============================================== decode-attention (kernel)
def test_decode_attention_per_row_pos():
    """Partially-filled slots: each row attends only to its own pos+1 cache
    entries. Kernel (interpret mode on CPU) vs the jnp oracle, and each row
    vs a scalar-pos single-row call."""
    b, h, kv, t, hd = 4, 4, 2, 128, 64
    k0 = jax.random.PRNGKey(9)
    q = jax.random.normal(k0, (b, 1, h, hd))
    k = jax.random.normal(jax.random.fold_in(k0, 1), (b, t, kv, hd))
    v = jax.random.normal(jax.random.fold_in(k0, 2), (b, t, kv, hd))
    pos = jnp.asarray([0, 5, 63, 127], jnp.int32)
    out = ops.decode_attention(q, k, v, pos, interpret=True)
    want = ref.decode_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    for i in range(b):
        row = ops.decode_attention(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                   jnp.int32(int(pos[i])), interpret=True)
        np.testing.assert_array_equal(np.asarray(out[i:i + 1]),
                                      np.asarray(row))


def test_decode_attention_scalar_pos_unchanged():
    """Scalar pos (the training-era calling convention) still broadcasts."""
    b, h, kv, t, hd = 2, 4, 2, 64, 64
    k0 = jax.random.PRNGKey(10)
    q = jax.random.normal(k0, (b, 1, h, hd))
    k = jax.random.normal(jax.random.fold_in(k0, 1), (b, t, kv, hd))
    v = jax.random.normal(jax.random.fold_in(k0, 2), (b, t, kv, hd))
    out = ops.decode_attention(q, k, v, jnp.int32(17), interpret=True)
    want = ref.decode_attention(q, k, v, jnp.full((b,), 17, jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ====================================================== select jit path
def test_select_pad_matches_numpy_reference(rng):
    """`rounding.pad_to_n_dyn` (inside the jitted M=1 `cloud.select` path)
    == the retained numpy pad reference, bit for bit, over random masks."""
    for _ in range(200):
        k = int(rng.integers(2, 10))
        n = int(rng.integers(1, k + 1))
        z = rng.random(k).astype(np.float32)
        mask = rng.random(k) < 0.5
        got = rounding.pad_to_n_dyn(jnp.asarray(mask, jnp.float32),
                                    jnp.asarray(z), n, True)
        want = _pad_to_n_np(mask, z, n)
        np.testing.assert_array_equal(np.asarray(got) > 0.5, want)
        # AWC's inclusive matroid: equality=False is the identity
        ident = rounding.pad_to_n_dyn(jnp.asarray(mask, jnp.float32),
                                      jnp.asarray(z), n, False)
        np.testing.assert_array_equal(np.asarray(ident) > 0.5, mask)


# ==================================================== service-level modes
@pytest.mark.parametrize("kind", ["suc", "awc"])
def test_service_modes_equivalent(kind, pool):
    """sequential vs continuous dispatch: identical RoundLogs (action,
    observed, rewards, cost) and identical bandit state after 4 rounds —
    including the AWC cascade re-submissions."""
    pcfg = PolicyConfig(kind=kind, k=3, n=2, rho=1e9, delta=0.1)
    cloud = SchedulingCloud(pcfg, pool)
    data = SyntheticLM(DataConfig(vocab=VOCAB, seq_len=8, global_batch=2,
                                  seed=0))
    seq = MultiLLMService(pcfg, cloud, data, prompt_len=8, max_new=8,
                          seed=7, dispatch="sequential")
    con = MultiLLMService(pcfg, cloud, data, prompt_len=8, max_new=8,
                          seed=7, dispatch="continuous")
    for a, b in zip(seq.run(4), con.run(4)):
        np.testing.assert_array_equal(a.action, b.action)
        np.testing.assert_array_equal(a.observed, b.observed)
        np.testing.assert_array_equal(a.rewards, b.rewards)
        assert a.cost == b.cost
    np.testing.assert_array_equal(np.asarray(seq.local.mu_hat),
                                  np.asarray(con.local.mu_hat))
    np.testing.assert_array_equal(np.asarray(seq.local.c_hat),
                                  np.asarray(con.local.c_hat))
    if kind == "awc":
        # the cascade actually cascaded somewhere (untrained pool => low
        # quality => follow-up arms), or the test is vacuous
        assert any(h.observed.sum() > 1 for h in seq.history)


# =============================================== driven-fleet regressions
def _driven_args(pool):
    pcfgs = [PolicyConfig(kind=k, k=3, n=2, rho=1e9, delta=0.1)
             for k in ("suc", "awc")]
    cloud = SchedulingCloud(pcfgs[0], pool)
    data = SyntheticLM(DataConfig(vocab=VOCAB, seq_len=8, global_batch=2,
                                  seed=0))
    return pcfgs, cloud, data


def test_driven_fleet_t0_returns_empty_result(pool):
    """T=0 used to crash on `action[:, -1]`; it must instead return empty
    trajectories and a fresh state (no rounds played => no prev_mask)."""
    from repro.router import fleet
    pcfgs, cloud, data = _driven_args(pool)
    res = fleet.simulate_fleet_driven(pcfgs, cloud, data, T=0,
                                      prompt_len=8, max_new=8, seed=5)
    assert res.reward.shape == (2, 0) and res.cost.shape == (2, 0)
    assert res.action.shape == (2, 0, 3) and res.observed.shape == (2, 0, 3)
    assert res.state.prev_mask.shape == (2, 3)
    assert (res.state.prev_mask == 0).all() and (res.state.t == 0).all()


def test_driven_fleet_carries_real_key_state(pool):
    """The reconstructed TenantState used to fabricate all-zero PRNG keys;
    it must carry the tenants' live key rows (a synthetic continuation from
    this state would otherwise silently collapse onto PRNGKey(0))."""
    from repro.router import fleet
    from repro.router.service import FleetService
    pcfgs, cloud, data = _driven_args(pool)
    res = fleet.simulate_fleet_driven(pcfgs, cloud, data, T=2,
                                      prompt_len=8, max_new=8, seed=7)
    assert res.state.key.any(), "fabricated all-zero keys"
    # bit-equal to an identically-seeded FleetService run's key rows
    pcfgs2, cloud2, data2 = _driven_args(pool)
    fs = FleetService(pcfgs2, cloud2, data2, seed=7, prompt_len=8, max_new=8)
    fs.run(2)
    want = np.concatenate([np.asarray(s.local.state.key, np.uint32)
                           for s in fs.tenants])
    np.testing.assert_array_equal(res.state.key, want)
    # prev_mask reflects the last round actually played
    np.testing.assert_array_equal(res.state.prev_mask,
                                  res.action[:, -1].astype(np.float32))


# ================================== fault-layer dormancy + round-state safety
def _svc_args(pool, kind="awc"):
    pcfg = PolicyConfig(kind=kind, k=3, n=2, rho=1e9, delta=0.1)
    cloud = SchedulingCloud(pcfg, pool)
    data = SyntheticLM(DataConfig(vocab=VOCAB, seq_len=8, global_batch=2,
                                  seed=0))
    return pcfg, cloud, data


def test_disabled_fault_plan_is_bit_dormant(pool):
    """A wired-but-disabled fault layer (fail_prob 0 everywhere) must be
    bit-equal to a service with no fault layer at all: same RoundLogs,
    same bandit state, zero failures. The chaos machinery may not perturb
    a healthy run."""
    from repro.serving.faults import FaultPlan, HealthPolicy
    def run(**kw):
        pcfg, cloud, data = _svc_args(pool)
        svc = MultiLLMService(pcfg, cloud, data, prompt_len=8, max_new=8,
                              seed=7, dispatch="continuous", **kw)
        return svc, svc.run(4)
    ref_svc, ref_logs = run()
    chaos_svc, chaos_logs = run(
        fault_plan=FaultPlan(fault_seed=123, fail_prob=0.0, spike_prob=0.0),
        health=HealthPolicy())
    for a, b in zip(ref_logs, chaos_logs):
        np.testing.assert_array_equal(a.action, b.action)
        np.testing.assert_array_equal(a.observed, b.observed)
        np.testing.assert_array_equal(a.rewards, b.rewards)
        assert a.cost == b.cost
        assert not b.failed.any()
    np.testing.assert_array_equal(np.asarray(ref_svc.local.mu_hat),
                                  np.asarray(chaos_svc.local.mu_hat))
    np.testing.assert_array_equal(np.asarray(ref_svc.local.c_hat),
                                  np.asarray(chaos_svc.local.c_hat))


def test_disabled_fault_plan_fleet_dormant(pool):
    """Same dormancy contract at fleet level: a FleetService with a
    disabled plan reproduces the no-fault fleet bit for bit."""
    from repro.router.service import FleetService
    from repro.serving.faults import FaultPlan, HealthPolicy
    def run(**kw):
        pcfg, cloud, data = _svc_args(pool, "suc")
        fs = FleetService(pcfg, cloud, data, n_tenants=3, seed=0,
                          prompt_len=8, max_new=8, **kw)
        return fs.run(3)
    ref = run()
    chaos = run(fault_plan=FaultPlan(fault_seed=9, fail_prob=0.0),
                health=HealthPolicy())
    for ra, rb in zip(ref, chaos):
        for a, b in zip(ra, rb):
            np.testing.assert_array_equal(a.action, b.action)
            np.testing.assert_array_equal(a.rewards, b.rewards)
            assert a.cost == b.cost


def test_failed_submit_does_not_leak_inflight(pool):
    """Regression: `_submit` used to increment `inflight` before
    `sched.submit`, so a submit that raised (request batch larger than the
    runner's slot count) left the counter unbalanced and `finish_round`
    wedged forever. The counter must only count successful submissions."""
    pcfg, cloud, data = _svc_args(pool, "suc")
    svc = MultiLLMService(pcfg, cloud, data, prompt_len=8, max_new=8,
                          seed=7, dispatch="continuous",
                          scheduler=cloud.make_scheduler(n_slots=1))
    with pytest.raises(ValueError, match="exceeds"):
        svc.begin_round()           # 2-row request, 1 slot: submit raises
    assert svc._cur.inflight == 0
    svc.sched.drain()               # nothing wedged: drain is a no-op...
    log = svc.finish_round()        # ...and the round can still close
    assert not log.observed.any()


def test_round_state_errors_survive_optimized_mode(pool):
    """Round-lifecycle misuse raises RoundStateError — real exceptions,
    not asserts, so the protection survives `python -O`."""
    from repro.router.service import RoundStateError
    pcfg, cloud, data = _svc_args(pool, "suc")
    svc = MultiLLMService(pcfg, cloud, data, prompt_len=8, max_new=8,
                          seed=7, dispatch="continuous")
    svc.begin_round()
    with pytest.raises(RoundStateError, match="not finished"):
        svc.begin_round()
    with pytest.raises(RoundStateError, match="in flight"):
        svc.finish_round()          # submissions not yet drained
    svc.sched.drain()
    svc.finish_round()
    with pytest.raises(RoundStateError, match="no round"):
        svc.finish_round()


def test_engine_admit_validation_is_not_an_assert(dense_engine):
    """Engine.admit over-budget checks raise ValueError (formerly asserts,
    stripped under -O into silent buffer overruns)."""
    state = dense_engine.init_slots(2, max_out=8)
    prompts = np.ones((1, 4), np.int32)
    lg, cache = dense_engine.prefill(prompts)
    with pytest.raises(ValueError, match="out buffer"):
        dense_engine.admit(state, [0], lg, cache, prompt_len=4,
                           max_new=16, seed=0)
    # (the max_len overflow check is gated off for sliding-window/ssm
    # families like this one — exercised implicitly by full-attention runs)


def test_engine_programs_take_params_as_arguments(dense_engine):
    """The weights reach every jitted engine program as an argument. A
    closure constant would be compiled into each executable: GBs of host
    memory per program at published widths."""
    eng = dense_engine
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    prompts = jnp.zeros((2, 4), jnp.int32)
    state = jax.eval_shape(lambda: eng.init_slots(2))
    for lowered in (eng._prefill_jit.lower(eng.params, prompts),
                    eng._decode_jit.lower(eng.params, state, steps=2),
                    eng._gen.lower(eng.params, prompts,
                                   jax.random.PRNGKey(0), max_new=2)):
        assert len(lowered.as_text()) < param_bytes / 4
