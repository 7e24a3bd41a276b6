"""Chaos serving benchmark: goodput under deterministic fault injection.

Runs the full router protocol (relax -> round -> dispatch -> feedback) for
M tenants against K real reduced-config engines on CPU while a seeded
`serving.faults.FaultPlan` dooms a fraction of request attempts, and
measures what the fault-tolerance machinery costs and saves:

  goodput     — tokens/sec from SUCCESSFUL observations only (failed
                attempts burn wall clock and budget but produce nothing)
  failed_frac — terminal-failure fraction of observations: the zero-reward
                feedback rate the bandit absorbs (App. E.3)
  drain_ticks — mean scheduler ticks per round to drain (continuous mode):
                retries/backoff/timeouts stretch the drain, but the tick
                budget bounds it
  stats       — per-replica failures/retries/crashes/quarantines

The grid sweeps fault rates x {sequential, continuous}. A separate OUTAGE
scenario hard-fails one replica's first submissions and checks the full
failover story end to end: the replica quarantines, `cloud.select` masks
it (renormalized z̃), probation probes readmit it, and every round still
completes.

All faults are drawn from fold_in chains over (fault_seed, replica, rid,
attempt), so a fixed --fault-seed reproduces the exact failure schedule —
the numbers move only with machine speed, never with which requests fail.

Results land in BENCH_chaos.json at the repo root (uploaded by CI as an
artifact). `--baseline PATH` diffs continuous goodput of matching cells
and exits 3 when any regresses by more than `--max-regression` (soft
gate). The JSON also records `goodput_ok`: goodput at the lowest nonzero
fault rate must stay within 2x of fault-free (acceptance, ISSUE 8).

  PYTHONPATH=src python benchmarks/chaos_serve.py \
      [--fault-rates 0.0 0.05 0.3] [--tenants 4] [--replicas 3] \
      [--rounds 6] [--reps 2] [--fault-seed 17] [--smoke] \
      [--baseline BENCH_chaos.json] [--max-regression 0.25] [--json PATH]
"""
import argparse
import dataclasses
import json
import os
import subprocess
import time

VOCAB = 64


def git_commit():
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        sha = subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"], cwd=here,
            text=True).strip()
        dirty = subprocess.run(["git", "diff", "--quiet", "HEAD"],
                               cwd=here).returncode != 0
        return sha + ("-dirty" if dirty else "")
    except Exception:
        return "unknown"


def build_pool(k, *, max_len, arch="h2o-danube-3-4b"):
    """K untrained reduced-config members of one dense (row-deterministic)
    family, so both dispatch modes emit identical tokens."""
    import jax
    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.router.cloud import Replica
    from repro.serving.engine import Engine
    cfg = dataclasses.replace(get_config(arch).reduced(), vocab=VOCAB)
    replicas = []
    for i in range(k):
        params = M.init_params(cfg, jax.random.PRNGKey(i))
        eng = Engine(cfg, params, max_len=max_len, eos_id=0, temperature=0.7)
        replicas.append(Replica(f"{arch}#{i}", eng, 0.001 * (1 + i)))
    return replicas


def make_services(pcfg, cloud, data, m, mode, *, prompt_len, max_new,
                  n_slots, chunk, fault_plan, health):
    from repro.router.service import FleetService, MultiLLMService
    if mode == "continuous":
        fs = FleetService(pcfg, cloud, data, n_tenants=m, n_slots=n_slots,
                          chunk=chunk, prompt_len=prompt_len,
                          max_new=max_new, fault_plan=fault_plan,
                          health=health)
        return fs, fs.tenants
    svcs = [MultiLLMService(pcfg, cloud, data, prompt_len=prompt_len,
                            max_new=max_new, seed=i, tenant=i,
                            dispatch="sequential", fault_plan=fault_plan)
            for i in range(m)]

    class _Seq:
        sched = None

        def step(self):
            for s in svcs:
                s.step()
    return _Seq(), svcs


def bench_cell(pcfg, cloud, data, m, rounds, reps, p, *, prompt_len,
               max_new, batch, n_slots, chunk, fault_seed):
    """Best-of-reps goodput per mode at uniform per-attempt fault rate p.
    Failure accounting (failed_frac, drain ticks, runner stats) is
    deterministic given the fault seed, so it is taken from the last rep."""
    from repro.serving.faults import FaultPlan, HealthPolicy
    plan = FaultPlan(fault_seed=fault_seed, fail_prob=p) if p > 0 else None
    # uniform chaos cell: generous retry budget, quarantine disabled so
    # every cell exercises the retry path, not the failover path (the
    # outage scenario below covers quarantine/readmission)
    health = HealthPolicy(max_retries=2, quarantine_after=10**9)
    cells = {}
    for mode in ("sequential", "continuous"):
        best_goodput = 0.0
        info = {}
        for rep in range(reps + 1):
            runner, svcs = make_services(
                pcfg, cloud, data, m, mode, prompt_len=prompt_len,
                max_new=max_new, n_slots=n_slots, chunk=chunk,
                fault_plan=plan, health=health)
            drain_ticks = []
            t0 = time.perf_counter()
            for _ in range(rounds):
                runner.step()
                if runner.sched is not None:
                    drain_ticks.append(runner.sched.last_drain_ticks)
            dt = time.perf_counter() - t0
            ok_obs = sum(int((h.observed & ~h.failed).sum())
                         for s in svcs for h in s.history)
            failed = sum(int(h.failed.sum())
                         for s in svcs for h in s.history)
            observed = ok_obs + failed
            if rep > 0:     # rep 0 warms the jit caches
                best_goodput = max(best_goodput,
                                   ok_obs * batch * max_new / dt)
                info = {
                    "failed_frac": round(failed / max(observed, 1), 4),
                    "drain_ticks": (round(sum(drain_ticks)
                                          / len(drain_ticks), 1)
                                    if drain_ticks else None),
                    "stats": (runner.sched.stats()
                              if runner.sched is not None else None),
                }
        cells[mode] = dict(info, goodput_tok_s=round(best_goodput, 1))
    return cells


def outage_scenario(pcfg, cloud, data, *, rounds, prompt_len, max_new,
                    n_slots, chunk, fault_seed):
    """Hard outage on replica 0 (its first 4 submissions always fail):
    the full quarantine -> mask -> probation -> readmission cycle must
    play out while every round still completes."""
    from repro.router.service import FleetService
    from repro.serving.faults import FaultPlan, Health, HealthPolicy
    plan = FaultPlan(fault_seed=fault_seed, fail_prob=[1.0, 0.0, 0.0],
                     fail_tick_max=0, rid_window=(0, 4))
    hp = HealthPolicy(max_retries=0, quarantine_after=2, probation_ticks=2,
                      readmit_successes=1)
    fs = FleetService(pcfg, cloud, data, n_tenants=2, n_slots=n_slots,
                      chunk=chunk, prompt_len=prompt_len, max_new=max_new,
                      fault_plan=plan, health=hp)
    logs = fs.run(rounds)
    runner0 = fs.sched.runners[0]
    wedged = any(s._cur is not None for s in fs.tenants)
    return {
        "rounds_completed": len(logs),
        "wedged_tenants": int(wedged),
        "quarantines": runner0.n_quarantines,
        "recovered": runner0.health_state is Health.HEALTHY,
        "health_log": [[t, h.value] for t, h in runner0.health_log],
    }


def diff_baseline(results, base, max_regression):
    """Soft gate: continuous goodput vs a committed BENCH_chaos.json."""
    if base.get("rounds") != results["rounds"] or \
            base.get("fault_seed") != results["fault_seed"]:
        print("# baseline rounds/fault-seed differ — rates not comparable, "
              "skipping gate")
        return 0
    base_cells = {(r["fault_rate"], r["tenants"], r["replicas"]):
                  r["continuous"]["goodput_tok_s"]
                  for r in base.get("results", [])}
    bad = matched = 0
    print(f"# baseline diff vs commit {base.get('commit', '?')} "
          f"(gate {max_regression:.0%})")
    for row in results["results"]:
        old = base_cells.get(
            (row["fault_rate"], row["tenants"], row["replicas"]))
        if old is None or old <= 0:
            continue
        matched += 1
        new = row["continuous"]["goodput_tok_s"]
        ratio = new / old
        flag = "  <-- REGRESSION" if ratio < 1.0 - max_regression else ""
        print(f"  p={row['fault_rate']}: {old:.0f} -> {new:.0f} "
              f"goodput tok/s ({ratio:.2f}x){flag}")
        bad += ratio < 1.0 - max_regression
    if matched == 0:
        print("  (no matching cells — baseline sweep differs)")
    return bad


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault-rates", type=float, nargs="+",
                    default=[0.0, 0.05, 0.3])
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--fault-seed", type=int, default=17)
    ap.add_argument("--baseline", default=None,
                    help="diff continuous goodput against a committed "
                         "BENCH_chaos.json; exit 3 on regression")
    ap.add_argument("--max-regression", type=float, default=0.25)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration (~1-2 min)")
    ap.add_argument("--json", default=None,
                    help="output path (default: BENCH_chaos.json here)")
    args = ap.parse_args(argv)
    if args.smoke:
        # keep --tenants/--rounds at the committed sweep's values so the
        # baseline gate has matching cells; only trim rates and reps
        args.fault_rates = [0.0, 0.05]
        args.reps = 1

    import jax
    from repro.core.policies import PolicyConfig
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.router.cloud import SchedulingCloud

    data = SyntheticLM(DataConfig(vocab=VOCAB, seq_len=args.prompt_len,
                                  global_batch=args.batch, seed=0))
    baseline = None
    if args.baseline:           # read BEFORE writing: the baseline may be
        with open(args.baseline) as fh:          # the output path itself
            baseline = json.load(fh)

    k = args.replicas
    pool = build_pool(k, max_len=args.prompt_len + args.max_new + 8)
    pcfg = PolicyConfig(kind="suc", k=k, n=min(2, k), rho=1e9, delta=0.1)
    cloud = SchedulingCloud(pcfg, pool)
    n_slots = max(4, args.tenants * args.batch)

    out = {"commit": git_commit(), "rounds": args.rounds,
           "backend": jax.default_backend(), "reps": args.reps,
           "fault_seed": args.fault_seed, "results": []}
    print("fault_rate,seq_goodput,cont_goodput,cont_failed_frac,"
          "cont_drain_ticks")
    for p in args.fault_rates:
        cells = bench_cell(pcfg, cloud, data, args.tenants, args.rounds,
                           args.reps, p, prompt_len=args.prompt_len,
                           max_new=args.max_new, batch=args.batch,
                           n_slots=n_slots, chunk=args.chunk,
                           fault_seed=args.fault_seed)
        row = dict(fault_rate=p, tenants=args.tenants, replicas=k, **cells)
        out["results"].append(row)
        print(f"{p},{cells['sequential']['goodput_tok_s']},"
              f"{cells['continuous']['goodput_tok_s']},"
              f"{cells['continuous']['failed_frac']},"
              f"{cells['continuous']['drain_ticks']}")

    out["outage"] = outage_scenario(
        pcfg, cloud, data, rounds=16, prompt_len=args.prompt_len,
        max_new=args.max_new, n_slots=n_slots, chunk=args.chunk,
        fault_seed=args.fault_seed)
    o = out["outage"]
    print(f"# outage: {o['rounds_completed']} rounds, "
          f"{o['quarantines']} quarantine(s), "
          f"recovered={o['recovered']}, wedged={o['wedged_tenants']}")

    # acceptance: low-rate chaos goodput within 2x of fault-free
    by_p = {r["fault_rate"]: r["continuous"]["goodput_tok_s"]
            for r in out["results"]}
    low = min((p for p in by_p if 0 < p <= 0.05), default=None)
    if low is not None and by_p.get(0.0, 0) > 0:
        ratio = by_p[low] / by_p[0.0]
        out["goodput_ok"] = bool(ratio >= 0.5)
        print(f"# goodput(p={low}) / goodput(fault-free) = {ratio:.2f} "
              f"({'OK' if out['goodput_ok'] else 'BELOW 0.5x'})")

    path = args.json or os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "..", "BENCH_chaos.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)
    print(f"# wrote {os.path.abspath(path)}")

    if baseline is not None:
        bad = diff_baseline(out, baseline, args.max_regression)
        if bad:
            print(f"# {bad} cell(s) regressed beyond the "
                  f"{args.max_regression:.0%} gate")
            raise SystemExit(3)


if __name__ == "__main__":
    main()
