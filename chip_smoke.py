"""On-chip smoke test: the router and its served pool on one TPU.

Runs the system's main path once, in this one process, through the entry
points a user calls, and checks what comes out:

  kernels  the router's Pallas kernels (`topn_lp`, `awc_fw`) compiled for
           the chip at the fleet's shapes — per tenant the grid engine's
           GRID_POINTS λ rows and the 25-point octave ladder over the
           nine-arm pool, vmapped over 4096 tenants — against
           `kernels/ref.py` on the same chip;
  fleet    `router.fleet.simulate_fleet` over the Table-3 pool at 4096
           mixed awc/suc/aic tenants: action sizes match each tenant's
           matroid, everything is finite, and the relaxed solve agrees
           with the retained ``engine="bisect"`` reference within the
           tolerances of tests/test_relax_rounding.py;
  served   the `repro.launch.serve` path (FleetService -> relax -> round ->
           continuous-batching prefill/decode -> feedback) over
           h2o-danube-3-4b and mamba2-780m at their published widths, bf16
           weights (random, from a seed) and bf16 slot caches: every
           request is answered with in-vocabulary tokens and finite
           logprobs, and the engine's prefill + decode logits agree with
           `models.model.forward`.

  python chip_smoke.py               # one chip: the three phases above
  python chip_smoke.py --four-chips  # only the sharded fleet scan on four
                                     # chips, bit-equal to one device

Each phase prints one ``phase {...}`` line with its result, compile and run
times. Where JAX finds no TPU the script exits non-zero and prints no
result. The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

GiB = 2 ** 30
CHIP_HBM = 16 * GiB          # TPU v5e: 16 GiB of HBM per chip
TENANTS = 4096
FLEET_ROUNDS = 32

# bf16 agreement of the engine's prefill/decode with the full-sequence
# forward: both run the same bf16 weights, the engine through its bf16
# cache, so they differ by bf16 rounding of activations and cache entries
LOGIT_RTOL = 0.05            # last prompt position, of the largest |logit|
LOGPROB_ATOL = 0.1           # mean chosen-token logprob per row, nats


def _has_kernel(compiled_text: str) -> bool:
    return "tpu_custom_call" in compiled_text


def _timed(fn, *args):
    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


# ==================================================================== kernels
def phase_kernels(tenants: int = TENANTS) -> dict:
    from repro.core import relax
    from repro.kernels import awc_fw as awc_k
    from repro.kernels import ref
    from repro.kernels import topn_lp as topn_k

    k, g, go = 9, relax.GRID_POINTS, relax.LAM_MAX_EXP + 1
    key = jax.random.split(jax.random.PRNGKey(0), 8)
    # quantized μ plants exact score ties: the kernels must break them like
    # the reference (lower index wins)
    mu = jnp.round(jax.random.uniform(key[0], (tenants, 1, k), jnp.float32,
                                      0.05, 0.95) * 16) / 16
    c = jax.random.uniform(key[1], (tenants, 1, k), jnp.float32, 0.01, 0.6)
    lam = 2.0 ** jax.random.uniform(key[2], (tenants, g, 1), jnp.float32,
                                    -4.0, 8.0)
    score = mu - lam * c                                    # (M, G, K)
    cost = jnp.broadcast_to(c, score.shape)
    n_rows = jax.random.randint(key[3], (tenants, g), 1, k + 1)
    z = jax.random.uniform(key[4], (tenants, 1, k), jnp.float32)
    ladder = jnp.broadcast_to(relax._octave_ladder(), (tenants, 1, go))
    n_awc = jax.random.randint(key[5], (tenants, 1), 1, k + 1)

    topn = jax.jit(jax.vmap(lambda s, cc, n: topn_k.topn_lp(
        s, cc, n, equality=True, interpret=False)))
    awc = jax.jit(jax.vmap(lambda zz, m, cc, lams, n: awc_k.awc_fw(
        zz, m, cc, lams, n, interpret=False)))
    topn_ref = jax.jit(jax.vmap(lambda s, cc, n: ref.topn_lp(
        s, cc, n, equality=True)))
    awc_ref = jax.jit(jax.vmap(ref.awc_fw))
    # the probe half of the reference on the kernel's own gradient: scores
    # formed from one gradient rank identically, whatever ulp the chip's
    # transcendental functions differ by
    probe_ref = jax.jit(jax.vmap(lambda gg, cc, lams, n: jax.vmap(
        lambda g1, c1, l1, n1: ref.topn_lp(
            g1[None, :] - l1[:, None] * c1[None, :],
            jnp.broadcast_to(c1, (l1.shape[0], c1.shape[0])), n1,
            equality=False))(gg, cc, lams, n)))

    rec = {"phase": "kernels", "tenants": tenants,
           "topn_lp_shape": list(score.shape),
           "awc_fw_shape": [list(z.shape), list(ladder.shape)]}
    t = time.perf_counter()
    topn_c = topn.lower(score, cost, n_rows).compile()
    awc_c = awc.lower(z, mu, c, ladder, n_awc).compile()
    rec["compile_s"] = time.perf_counter() - t
    if not (_has_kernel(topn_c.as_text()) and _has_kernel(awc_c.as_text())):
        raise AssertionError("compiled router kernels hold no "
                             "tpu_custom_call: the Pallas kernels did not "
                             "lower for the chip")
    out_topn, t_topn = _timed(topn_c, score, cost, n_rows)
    (out_g, out_costs), t_awc = _timed(awc_c, z, mu, c, ladder, n_awc)
    rec["run_s"] = {"topn_lp": t_topn, "awc_fw": t_awc}
    want_topn = topn_ref(score, cost, n_rows)
    want_g, _ = awc_ref(z, mu, c, ladder, n_awc)
    want_costs = probe_ref(out_g, c, ladder, n_awc)
    # tolerances of tests/test_kernels.py's dispatch tests
    np.testing.assert_allclose(np.asarray(out_topn), np.asarray(want_topn),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(want_g),
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(out_costs), np.asarray(want_costs),
                               atol=1e-5)
    rec["max_abs_err"] = {
        "topn_lp": float(jnp.max(jnp.abs(out_topn - want_topn))),
        "awc_fw_grad": float(jnp.max(jnp.abs(out_g - want_g))),
        "awc_fw_costs": float(jnp.max(jnp.abs(out_costs - want_costs)))}
    return rec


# ====================================================================== fleet
def _fleet_configs(tenants: int, rounds: int):
    from repro.core.policies import PolicyConfig
    from repro.env.llm_profiles import default_rho, paper_pool

    pool = paper_pool("sciq")
    kinds = [("awc", "suc", "aic")[i % 3] for i in range(tenants)]
    pcfgs = [PolicyConfig(kind=kd, k=pool.k, n=4,
                          rho=default_rho(pool, kd, 4), delta=1.0 / rounds)
             for kd in kinds]
    return pool, kinds, pcfgs


def _relaxed_value(kind: str, z, mu) -> float:
    """r̃(z̃; μ) (rewards.relaxed_reward) on the host, in float64."""
    if kind == "awc":
        return float(1.0 - np.prod(1.0 - mu * z))
    if kind == "suc":
        return float(np.sum(mu * z))
    return float(np.exp(np.sum(z * np.log(np.maximum(mu, 1e-9)))))


def _check_relaxed(kinds, n, rho, mu, c, zg, zb, *, test_instances: bool
                   ) -> dict:
    """Grid engine vs the bisect reference, per instance, with the
    tolerances of tests/test_relax_rounding.py::
    test_grid_engine_matches_bisect_reference: objective within 1e-5,
    budget within rho*1.002 + 1e-5, z in the unit box, for SUC/AIC
    sum(z) == n and <= 2 fractional coordinates.

    The last check and the AWC objective hold on the test's instances
    (distinct scores and costs). The fleet's own bandit states
    (``test_instances=False``) tie arms — UCB clipped at 1, LCB at 0 — where
    an LP optimum may mix vertices that differ in more than one swap, and
    where Frank-Wolfe runs whose LP oracles break such ties differently
    end apart: there the AWC gap is reported, not asserted."""
    bad, gaps, awc_over = [], [], 0
    for i, kd in enumerate(kinds):
        m64, c64 = mu[i].astype(np.float64), c[i].astype(np.float64)
        g, b = zg[i].astype(np.float64), zb[i].astype(np.float64)
        gap = _relaxed_value(kd, b, m64) - _relaxed_value(kd, g, m64)
        gaps.append(gap)
        ok = (c64 @ g <= rho[i] * 1.002 + 1e-5
              and np.all(g >= -1e-6) and np.all(g <= 1 + 1e-6))
        if kd == "awc":
            awc_over += gap > 1e-5
            ok = ok and (gap <= 1e-5 or not test_instances)
        else:
            ok = ok and gap <= 1e-5 and abs(g.sum() - n[i]) < 1e-3
            if test_instances:
                ok = ok and int(((g > 1e-5) & (g < 1 - 1e-5)).sum()) <= 2
        if not ok:
            bad.append(i)
    if bad:
        i = bad[0]
        raise AssertionError(
            f"{len(bad)} of {len(kinds)} instance(s) disagree with the "
            f"bisect reference; first {i} ({kinds[i]}, n {n[i]}, rho "
            f"{rho[i]}): grid {zg[i]} bisect {zb[i]} mu {mu[i]} c {c[i]}")
    return {"instances": len(kinds), "worst_objective_gap": max(gaps),
            "awc_gap_over_1e-5": int(awc_over)}


def _test_instances(m: int, k: int, seed: int = 0):
    """``m`` instances as tests/test_relax_rounding.py's make_instance draws
    them, at the pool's ``k`` arms, kinds cycling awc/suc/aic."""
    rng = np.random.default_rng(seed)
    kinds = [("awc", "suc", "aic")[i % 3] for i in range(m)]
    n = rng.integers(1, k, m)
    mu = rng.uniform(0.05, 0.99, (m, k))
    c = rng.uniform(0.01, 0.6, (m, k))
    cheapest = np.cumsum(np.sort(c, axis=1), axis=1)[np.arange(m), n - 1]
    rho = cheapest * rng.uniform(1.05, 2.5, m)
    return kinds, n, rho, mu, c


def _solve_both(kinds, n, rho, mu, c):
    """The relaxed solve of every instance by the grid engine (the wide
    lowering over the Pallas kernels on TPU) and by the bisect reference."""
    from repro.core import relax
    from repro.core import rewards as R

    solve = jax.jit(relax.solve_batch,
                    static_argnames=("kinds_present", "engine"))
    args = (jnp.asarray([R.KIND_INDEX[kd] for kd in kinds], jnp.int32),
            jnp.asarray(mu, jnp.float32), jnp.asarray(c, jnp.float32),
            jnp.asarray(n, jnp.int32), jnp.asarray(rho, jnp.float32))
    present = tuple(sorted({R.KIND_INDEX[kd] for kd in kinds}))
    return [np.asarray(solve(*args, kinds_present=present, engine=e))
            for e in ("grid", "bisect")]


def phase_fleet(tenants: int = TENANTS, rounds: int = FLEET_ROUNDS) -> dict:
    from repro.core import confidence as cb
    from repro.router import fleet

    pool, kinds, pcfgs = _fleet_configs(tenants, rounds)
    cfg = fleet.fleet_config(pcfgs)
    keys = jax.random.split(jax.random.PRNGKey(0), tenants)
    rec = {"phase": "fleet", "tenants": tenants, "rounds": rounds,
           "arms": pool.k}
    t = time.perf_counter()
    res = fleet.simulate_fleet(pool, cfg, T=rounds, keys=keys)
    first = time.perf_counter() - t
    t = time.perf_counter()
    again = fleet.simulate_fleet(pool, cfg, T=rounds, keys=keys)
    rec["run_s"] = time.perf_counter() - t
    rec["compile_s"] = first - rec["run_s"]     # first call less a warm one
    rec["rounds_per_s"] = tenants * rounds / rec["run_s"]
    if not np.array_equal(res.action, again.action):
        raise AssertionError("two runs of the same fleet differ")

    sizes = res.action.sum(-1)                              # (M, T)
    n = np.asarray([p.n for p in pcfgs])[:, None]
    is_awc = np.asarray([kd == "awc" for kd in kinds])[:, None]
    if not np.all(np.where(is_awc, sizes <= n, sizes == n)):
        raise AssertionError("an action's size breaks its tenant's matroid")
    if np.any(res.observed > res.action):
        raise AssertionError("feedback on an arm that was not dispatched")
    for name, arr in [("reward", res.reward), ("cost", res.cost)] + [
            (f"stats.{s}", v) for s, v in res.state.stats.items()]:
        if not np.all(np.isfinite(arr)):
            raise AssertionError(f"non-finite fleet {name}")

    # the relaxed solve on the chip against the bisect reference: on the
    # test's own instance distribution, and on the fleet's final states
    stats = jax.tree.map(jnp.asarray, res.state.stats)
    t_now = jnp.asarray(res.state.t)
    mu_bar = np.asarray(jax.vmap(cb.reward_ucb)(stats, t_now, cfg.delta,
                                                cfg.alpha_mu))
    c_low = np.asarray(jax.vmap(cb.cost_lcb)(stats, t_now, cfg.delta,
                                             cfg.alpha_c))
    n, rho = np.asarray(cfg.n), np.asarray(cfg.rho)
    inst = _test_instances(tenants, pool.k)
    rec["vs_bisect_test_instances"] = _check_relaxed(
        *inst, *_solve_both(*inst), test_instances=True)
    rec["vs_bisect_fleet_states"] = _check_relaxed(
        kinds, n, rho, mu_bar, c_low,
        *_solve_both(kinds, n, rho, mu_bar, c_low), test_instances=False)
    return rec


# ===================================================================== served
def _reference_check(replica, comp) -> dict:
    """The engine's prefill and decode logits against the full-sequence
    `models.model.forward` for one completed request of ``replica``."""
    from repro.models import model as M

    eng = replica.engine
    prompts = np.asarray(comp.request.prompts, np.int32)
    toks = np.asarray(comp.result.tokens, np.int32)
    n_out = np.asarray(comp.result.out_lens)
    s = prompts.shape[1]
    seq = jnp.asarray(np.concatenate([prompts, toks[:, :-1]], axis=1))
    fwd = jax.jit(lambda p, x: M.forward(eng.cfg, p, {"tokens": x})[0])
    logits = np.asarray(fwd(eng.params, seq), np.float32)   # (B, S+n-1, V)

    last, _ = eng.prefill(prompts)
    ref_last = logits[:, s - 1]
    prefill_err = float(np.max(np.abs(np.asarray(last, np.float32)
                                      - ref_last))
                        / np.max(np.abs(ref_last)))
    # the engine's per-row mean chosen-token logprob, teacher-forced
    z = logits[:, s - 1:] / max(eng.temperature, 1e-4)
    logp = z - z.max(-1, keepdims=True)
    logp = logp - np.log(np.exp(logp).sum(-1, keepdims=True))
    chosen = np.take_along_axis(logp, toks[:, :, None], -1)[..., 0]
    live = np.arange(toks.shape[1])[None, :] < n_out[:, None]
    want = (chosen * live).sum(1) / np.maximum(n_out, 1)
    logprob_err = float(np.max(np.abs(want - comp.result.logprobs)))
    if prefill_err > LOGIT_RTOL or logprob_err > LOGPROB_ATOL:
        raise AssertionError(
            f"{replica.name}: engine vs forward: prefill logits off by "
            f"{prefill_err} of their scale (tol {LOGIT_RTOL}), mean logprob "
            f"off by {logprob_err} (tol {LOGPROB_ATOL})")
    return {"prefill_logit_max_rel_err": prefill_err,
            "logit_scale": float(np.max(np.abs(ref_last))),
            "mean_logprob_max_err": logprob_err}


def phase_served(configs=None, *, tenants: int = 4, max_len: int = 512,
                 rounds: int = 3) -> dict:
    from repro.launch import serve

    args = serve.parse_args([
        "--kind", "awc", "--tenants", str(tenants),
        "--max-len", str(max_len), "--rounds", str(rounds)])
    t = time.perf_counter()
    runner, svc, names = serve.build_service(args, configs)
    replicas = svc.cloud.replicas
    jax.block_until_ready([r.engine.params for r in replicas])
    rec = {"phase": "served", "pool": names, "tenants": tenants,
           "slots": serve.SLOTS, "max_len": max_len,
           "build_s": time.perf_counter() - t,
           "params": {r.name: int(sum(x.size for x in
                                      jax.tree.leaves(r.engine.params)))
                      for r in replicas},
           "param_dtype": sorted({str(x.dtype) for r in replicas
                                  for x in jax.tree.leaves(r.engine.params)}),
           "cache_dtype": sorted({str(jnp.dtype(r.engine.dtype))
                                  for r in replicas})}
    round_s, answered, last = [], 0, {}
    for _ in range(rounds):
        t = time.perf_counter()
        logs = runner.step()
        round_s.append(time.perf_counter() - t)
        comps = runner.last_completions
        per_tenant = np.zeros(tenants, int)
        for comp in comps:
            req, res = comp.request, comp.result
            vocab = replicas[req.arm].engine.cfg.vocab
            n_out = np.asarray(res.out_lens)
            if not comp.ok:
                raise AssertionError(f"request {req.rid} failed: "
                                     f"{comp.error}")
            if not (np.all(res.tokens >= 0) and np.all(res.tokens < vocab)):
                raise AssertionError(f"request {req.rid}: token outside "
                                     f"the vocabulary of {vocab}")
            if not np.all(np.isfinite(res.logprobs)):
                raise AssertionError(f"request {req.rid}: non-finite "
                                     "logprob")
            if not np.all((n_out >= 1) & (n_out <= req.max_new)):
                raise AssertionError(f"request {req.rid}: {n_out} tokens")
            per_tenant[req.tenant] += 1
            last[req.arm] = comp
        for i, log in enumerate(logs):
            if np.any(log.observed & ~log.action) \
                    or per_tenant[i] != int(log.observed.sum()):
                raise AssertionError(f"tenant {i}: completions do not "
                                     "match its round's observed arms")
        answered += len(comps)
    rec["requests_answered"] = answered
    rec["first_round_s"] = round_s[0]           # compilation included
    rec["steady_round_s"] = round_s[1:]
    rec["compile_s"] = round_s[0] - min(round_s[1:])
    rec["vs_forward"] = {replicas[a].name: _reference_check(replicas[a], c)
                         for a, c in sorted(last.items())}
    if len(last) != len(replicas):
        raise AssertionError(f"only arms {sorted(last)} served a request "
                             "in the last round")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    rec["peak_bytes_in_use"] = peak
    rec["peak_gib"] = None if peak is None else peak / GiB
    if peak is not None and peak >= CHIP_HBM:
        raise AssertionError(f"peak device memory {peak / GiB:.2f} GiB")
    return rec


# ================================================================ four chips
def phase_four_chips(devices, tenants: int = TENANTS,
                     rounds: int = FLEET_ROUNDS) -> dict:
    from repro.launch.mesh import fleet_smoke

    rec = fleet_smoke(devices, tenants, rounds, workload="mixed")
    # a second, warm run of both: its times less the first's are compilation
    warm = fleet_smoke(devices, tenants, rounds, workload="mixed")
    rec["phase"] = "four_chips"
    rec["compile_s"] = {"sharded": rec["seconds_sharded"]
                        - warm["seconds_sharded"],
                        "single": rec["seconds_single"]
                        - warm["seconds_single"]}
    rec["run_s"] = {"sharded": warm["seconds_sharded"],
                    "single": warm["seconds_single"]}
    rec["rounds_per_s"] = {"sharded": tenants * rounds
                           / warm["seconds_sharded"],
                           "single": tenants * rounds / warm["seconds_single"]}
    if not (rec["sharded"] and rec["devices"] == len(devices)):
        raise AssertionError(f"the fleet did not shard over {len(devices)} "
                             f"devices: {rec}")
    if not (rec["bit_equal"] and warm["bit_equal"]):
        raise AssertionError("sharded fleet differs from the single-device "
                             "reference")
    peaks = rec["peak_bytes"]
    # every chip held its share of the tenants' scan, not just device 0
    if peaks is not None and min(peaks) < 0.5 * max(peaks):
        raise AssertionError(f"uneven per-device peak memory {peaks}")
    return rec


# ======================================================================= main
def _run(phases) -> bool:
    ok = True
    for fn, kw in phases:
        try:
            rec = fn(**kw)
            rec["ok"] = True
        except Exception:            # reported, and fails the run below
            traceback.print_exc()
            rec = {"phase": fn.__name__.removeprefix("phase_"), "ok": False}
            ok = False
        print("phase " + json.dumps(rec), flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fleet scan on four chips "
                         "against its single-device reference")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU found: JAX reports platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {dev.device_kind} x{len(jax.devices())}, "
          f"jax {jax.__version__}, compile cache: {enable_compile_cache()}",
          flush=True)
    if args.four_chips:
        devices = jax.devices()[:4]
        if len(devices) < 4:
            print(f"--four-chips needs 4 TPU chips, found {len(devices)}",
                  file=sys.stderr)
            return 1
        ok = _run([(phase_four_chips, {"devices": devices})])
    else:
        ok = _run([(phase_kernels, {}), (phase_fleet, {}),
                   (phase_served, {})])
    if not ok:
        print("chip smoke FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
