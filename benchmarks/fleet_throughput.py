"""Fleet throughput: batched multi-tenant scan, solver engines, host loops.

Measures rounds/sec for M tenants advanced T rounds:
  batched[grid]   — one `fleet.simulate_fleet` call on the grid parametric-
                    LP engine (the default fleet architecture)
  batched[bisect] — same scan on the retained PR-2 reference solver
                    (sequential double-then-bisect) — the baseline the
                    ISSUE-3 acceptance compares against, in the same run
  sequential      — per tenant, per round: ONE jitted protocol step per
                    host call (the seed router architecture), grid engine
  fleet_solo      — M separate single-tenant scans (no tenant batching)

Every (tenants, workload, mode) cell is sampled REPS times interleaved and
the best rate is kept (shared-box noise suppression). Results land in
BENCH_fleet.json at the repo root (where CI uploads it as an artifact) —
rounds/sec per tenant count, solver variant, workload, plus the commit —
so future PRs have a perf trajectory; the recorded sweep is committed.

Acceptance (ISSUE 3): ≥2× batched[grid] vs batched[bisect] at 64 tenants
on CPU, with the AWC/mixed fleets showing the largest gain.
Acceptance (ISSUE 4): ≥3× batched[grid] AWC/mixed rounds/sec at 64 tenants
over the PR-3 committed BENCH_fleet.json (warm Frank-Wolfe + fixed-trip
rounding + sort-free cascade).

`--awc-sweep` adds an AWC-only (N, K) sweep row set (matroid size × pool
slice) to the emitted trajectory. `--baseline PATH` diffs every matching
(workload, tenants, n, k, devices) grid-engine cell against a previously
committed BENCH_fleet.json and exits non-zero when any cell regresses by
more than `--max-regression` (default 20%) — wired into CI as a soft gate
(warn, don't fail: the 2-core shared runner swings more than real
regressions).

`--devices 1 2 8` adds a pod-scale sharded-fleet row set on virtual CPU
devices: each device count runs in a fresh CPU-only subprocess under
`--xla_force_host_platform_device_count=N` (the count locks at jax init)
and times `simulate_fleet(mesh=make_fleet_mesh())` at `--devices-tenants`
tenants (default 4096). The sweep runs before this process imports jax,
so the parent never holds an accelerator its workers might need. Rows
carry a `devices` column plus the worker's `host_cores` — virtual CPU
devices only parallelize up to the physical core count, so scaling
numbers are only meaningful when cores ≥ devices.

  PYTHONPATH=src python benchmarks/fleet_throughput.py \
      [--tenants 1 4 16 64] [--rounds 256] [--kind suc] [--mixed] \
      [--workloads suc awc mixed] [--reps 3] [--awc-sweep] [--smoke] \
      [--devices 1 2 8] [--devices-tenants 4096] [--devices-rounds 32] \
      [--baseline BENCH_fleet.json] [--max-regression 0.2] [--json PATH]
"""
import argparse
import functools
import json
import os
import subprocess
import sys
import time

KINDS_ALL = ("awc", "suc", "aic")


def make_kinds(workload, m):
    if workload == "mixed":
        return [KINDS_ALL[i % 3] for i in range(m)]
    return [workload] * m


def make_fleet_cfg(pool, kinds, T, n=4):
    from repro.core.policies import PolicyConfig
    from repro.env.llm_profiles import default_rho
    from repro.router import fleet
    pcfgs = [PolicyConfig(kind=k, k=pool.k, n=n,
                          rho=default_rho(pool, k, n), delta=1.0 / T)
             for k in kinds]
    return fleet.fleet_config(pcfgs)


def slice_pool(pool, k):
    """The first k arms of the pool as a smaller bandit environment — the
    K axis of the AWC sweep."""
    import dataclasses
    return dataclasses.replace(pool, names=pool.names[:k], mu=pool.mu[:k],
                               mean_cost=pool.mean_cost[:k])


def run_single_tenant_loop(pool, cfg, T, key, step_fn):
    """The pre-fleet shape: one jitted round per host call, T host calls."""
    import jax.numpy as jnp

    from repro.router import fleet
    state = fleet.init_tenant_state(1, pool.k, keys=key[None])
    kinds_present = fleet._kinds_present(cfg)
    for t in range(1, T + 1):
        state, _ = step_fn(state, jnp.float32(t), cfg, kinds_present)
    return state


def bench_engines(pool, kinds, T, reps):
    """Best-of-reps batched rounds/sec for both solver engines, interleaved
    so machine noise hits both paths alike."""
    return bench_engines_cfg(pool, make_fleet_cfg(pool, kinds, T),
                             len(kinds), T, reps)


def bench_host_loops(pool, kinds, T):
    """Rounds/sec for the per-call host loop and the unbatched scan."""
    import jax
    import jax.numpy as jnp

    from repro.router import fleet
    m = len(kinds)
    keys = jax.random.split(jax.random.PRNGKey(0), m)
    solo_cfgs = [make_fleet_cfg(pool, kinds[i:i + 1], T) for i in range(m)]
    mu = jnp.asarray(pool.mu, jnp.float32)
    mc = jnp.asarray(pool.mean_cost, jnp.float32)
    levels = tuple(pool.reward_levels)

    @functools.partial(jax.jit, static_argnames=("kinds_present",))
    def one_round(state, t, cfg1, kinds_present):  # M=1, one protocol round
        return jax.vmap(
            lambda row, c: fleet._tenant_step(row, t, mu, mc, levels, c,
                                              kinds_present)
        )(state, cfg1)

    fleet.simulate_fleet(pool, solo_cfgs[0], T=T, keys=keys[:1])
    for kind in dict.fromkeys(kinds):
        run_single_tenant_loop(pool, solo_cfgs[kinds.index(kind)], 2,
                               keys[0], one_round)

    t0 = time.perf_counter()
    for i in range(m):
        state = run_single_tenant_loop(pool, solo_cfgs[i], T, keys[i],
                                       one_round)
    jax.block_until_ready(state)      # in-order dispatch: last drains all
    dt_seq = time.perf_counter() - t0

    t0 = time.perf_counter()
    for i in range(m):
        fleet.simulate_fleet(pool, solo_cfgs[i], T=T, keys=keys[i:i + 1])
    dt_solo = time.perf_counter() - t0
    return m * T / dt_seq, m * T / dt_solo


def bench_awc_sweep(pool, T, reps, tenants):
    """AWC-only (N, K) sweep: matroid size and pool-slice width — the axes
    the warm Frank-Wolfe path is most sensitive to (FW step count scales
    the LP-oracle chain; K scales every probe row and the rounding trip
    count). Returns trajectory rows tagged with n and k."""
    rows = []
    for k in (5, pool.k):
        sub = slice_pool(pool, k)
        for n in (2, 4, 6):
            if n >= k:
                continue
            kinds = ["awc"] * tenants
            cfg = make_fleet_cfg(sub, kinds, T, n=n)
            rates = bench_engines_cfg(sub, cfg, tenants, T, reps)
            rows.append({"tenants": tenants, "workload": "awc",
                         "n": n, "k": k,
                         "engine_rps": {kk: round(v, 1)
                                        for kk, v in rates.items()},
                         "speedup": round(rates["grid"] / rates["bisect"],
                                          3)})
            print(f"{tenants},{T},awc[n={n},k={k}],"
                  f"{rates['grid']:.1f},{rates['bisect']:.1f},"
                  f"{rows[-1]['speedup']:.2f}")
    return rows


def bench_engines_cfg(pool, cfg, m, T, reps):
    """The shared warmup + interleaved best-of-reps engine timing loop."""
    import jax

    from repro.router import fleet
    keys = jax.random.split(jax.random.PRNGKey(0), m)
    best = {"grid": 0.0, "bisect": 0.0}
    for eng in best:
        fleet.simulate_fleet(pool, cfg, T=T, keys=keys, engine=eng)
    for _ in range(reps):
        for eng in best:
            t0 = time.perf_counter()
            fleet.simulate_fleet(pool, cfg, T=T, keys=keys, engine=eng)
            best[eng] = max(best[eng], m * T / (time.perf_counter() - t0))
    return best


def run_device_worker(n, args):
    """Subprocess body for one --devices cell: this process was spawned
    with N forced host devices; time the sharded fleet scan and emit one
    JSON row on stdout for the parent to collect."""
    import jax

    from repro.env.llm_profiles import paper_pool
    from repro.launch.mesh import make_fleet_mesh
    from repro.router import fleet
    assert jax.device_count() == n, (jax.device_count(), n)
    pool = paper_pool("sciq")
    m, T = args.tenants[0], args.rounds
    wl = (args.workloads or ["awc"])[0]
    cfg = make_fleet_cfg(pool, make_kinds(wl, m), T)
    keys = jax.random.split(jax.random.PRNGKey(0), m)
    mesh = make_fleet_mesh() if n > 1 else None   # N=1: reference path
    axes = fleet.fleet_mesh_axes(m, mesh)
    fleet.simulate_fleet(pool, cfg, T=T, keys=keys, mesh=mesh)   # compile
    best = 0.0
    for _ in range(args.reps):
        t0 = time.perf_counter()
        fleet.simulate_fleet(pool, cfg, T=T, keys=keys, mesh=mesh)
        best = max(best, m * T / (time.perf_counter() - t0))
    print("DEVICE_ROW " + json.dumps(
        {"tenants": m, "workload": wl, "devices": n,
         "tenant_axes": list(axes) if axes else None,
         "host_cores": os.cpu_count(),
         "engine_rps": {"grid": round(best, 1)}}))


def bench_devices(args):
    """The --devices sweep: one subprocess per device count (XLA locks the
    host device count at first jax init, so each N needs a fresh process)."""
    rows = []
    here = os.path.abspath(__file__)
    for wl in args.workloads or ["awc"]:
        for n in args.devices:
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            flags = [f for f in env.get("XLA_FLAGS", "").split()
                     if not f.startswith(
                         "--xla_force_host_platform_device_count")]
            env["XLA_FLAGS"] = " ".join(
                flags + [f"--xla_force_host_platform_device_count={n}"])
            cmd = [sys.executable, here, "--_device-worker", str(n),
                   "--tenants", str(args.devices_tenants),
                   "--rounds", str(args.devices_rounds),
                   "--workloads", wl, "--reps", str(args.reps)]
            out = subprocess.run(cmd, env=env, capture_output=True,
                                 text=True)
            if out.returncode != 0:
                raise RuntimeError(f"device worker N={n} failed:\n"
                                   f"{out.stderr[-2000:]}")
            row = next(json.loads(line[len("DEVICE_ROW "):])
                       for line in out.stdout.splitlines()
                       if line.startswith("DEVICE_ROW "))
            rows.append(row)
            print(f"{row['tenants']},{args.devices_rounds},{wl}"
                  f"[devices={n}],{row['engine_rps']['grid']:.1f},,")
    return rows


def diff_baseline(results, base, max_regression):
    """Soft regression gate: compare grid-engine rounds/sec against a
    committed BENCH_fleet.json cell-by-cell. Returns the number of cells
    regressing by more than ``max_regression`` (fraction)."""
    def cell_key(row):
        return (row["workload"], row["tenants"], row.get("n"), row.get("k"),
                row.get("devices"))

    base_cells = {cell_key(r): r["engine_rps"]["grid"]
                  for r in base.get("results", [])}
    bad = matched = 0
    print(f"# baseline diff vs commit {base.get('commit', '?')} "
          f"(gate {max_regression:.0%})")
    for row in results:
        old = base_cells.get(cell_key(row))
        if old is None or old <= 0:
            continue
        matched += 1
        new = row["engine_rps"]["grid"]
        ratio = new / old
        flag = ""
        if ratio < 1.0 - max_regression:
            bad += 1
            flag = "  <-- REGRESSION"
        print(f"  {row['workload']},{row['tenants']}"
              f"{',' + str(row['n']) + ',' + str(row['k']) if 'n' in row else ''}"
              f": {old:.0f} -> {new:.0f} rps ({ratio:.2f}x){flag}")
    if matched == 0:
        print("  (no matching cells — baseline sweep differs)")
    return bad


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


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, nargs="+", default=[1, 4, 16, 64])
    ap.add_argument("--rounds", type=int, default=256)
    ap.add_argument("--kind", default=None, choices=KINDS_ALL)
    ap.add_argument("--mixed", action="store_true",
                    help="cycle awc/suc/aic across tenants (legacy flag)")
    ap.add_argument("--workloads", nargs="+", default=None,
                    choices=list(KINDS_ALL) + ["mixed"],
                    help="fleet compositions to sweep (default: --kind if "
                         "given, else the representative mixed fleet)")
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved timing repetitions (best kept)")
    ap.add_argument("--host-loops", action="store_true",
                    help="also time the per-call and unbatched host loops")
    ap.add_argument("--awc-sweep", action="store_true",
                    help="add the AWC-only (N, K) sweep row set")
    ap.add_argument("--devices", type=int, nargs="+", default=None,
                    help="sharded-fleet device sweep (subprocess per count)")
    ap.add_argument("--devices-tenants", type=int, default=4096,
                    help="fleet size M for the --devices sweep")
    ap.add_argument("--devices-rounds", type=int, default=32,
                    help="rounds T for the --devices sweep")
    ap.add_argument("--_device-worker", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--baseline", default=None,
                    help="diff grid rounds/sec against a committed "
                         "BENCH_fleet.json; exit non-zero on regression")
    ap.add_argument("--max-regression", type=float, default=0.2,
                    help="baseline-gate threshold (fraction, default 0.2)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration (~1 min)")
    ap.add_argument("--json", default=None,
                    help="output path (default: BENCH_fleet.json here)")
    args = ap.parse_args(argv)

    if getattr(args, "_device_worker") is not None:
        run_device_worker(getattr(args, "_device_worker"), args)
        return

    # the device sweep's workers start before this process touches jax
    device_rows = bench_devices(args) if args.devices else []

    import jax

    from repro.env.llm_profiles import paper_pool
    if args.smoke:
        # keep --rounds at the committed sweep's 256: shorter runs
        # under-measure rounds/sec (fixed dispatch overhead amortizes over
        # the scan) and would trip the --baseline gate spuriously
        args.tenants, args.rounds, args.reps = [1, 16], 256, 2
    if args.workloads:
        workloads = args.workloads
    elif args.kind and not args.mixed:
        workloads = [args.kind]
    else:
        workloads = ["mixed"]

    pool = paper_pool("sciq")
    baseline = None
    if args.baseline:           # read BEFORE writing: the baseline may be
        with open(args.baseline) as fh:          # the output path itself
            baseline = json.load(fh)
    out = {"commit": git_commit(), "rounds": args.rounds,
           "backend": jax.default_backend(), "reps": args.reps,
           "results": []}
    print("tenants,rounds,workload,grid_rps,bisect_rps,engine_speedup")
    for workload in workloads:
        for m in args.tenants:
            kinds = make_kinds(workload, m)
            rates = bench_engines(pool, kinds, args.rounds, args.reps)
            row = {"tenants": m, "workload": workload,
                   "engine_rps": {k: round(v, 1) for k, v in rates.items()},
                   "speedup": round(rates["grid"] / rates["bisect"], 3)}
            if args.host_loops:
                seq, solo = bench_host_loops(pool, kinds, args.rounds)
                row["sequential_rps"] = round(seq, 1)
                row["fleet_solo_rps"] = round(solo, 1)
            out["results"].append(row)
            print(f"{m},{args.rounds},{workload},{rates['grid']:.1f},"
                  f"{rates['bisect']:.1f},{row['speedup']:.2f}")

    if args.awc_sweep:
        sweep_m = 16 if args.smoke else max(args.tenants)
        out["results"].extend(
            bench_awc_sweep(pool, args.rounds, args.reps, sweep_m))

    if args.devices:
        out["host_cores"] = os.cpu_count()
        out["results"].extend(device_rows)

    path = args.json or os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "..", "BENCH_fleet.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)
    print(f"# wrote {os.path.abspath(path)}")

    if baseline is not None:
        bad = diff_baseline(out["results"], baseline, args.max_regression)
        if bad:
            print(f"# {bad} cell(s) regressed beyond the "
                  f"{args.max_regression:.0%} gate")
            # distinct exit code so CI can soft-fail the perf gate while
            # still hard-failing on real crashes in this script
            raise SystemExit(3)


if __name__ == "__main__":
    main()
