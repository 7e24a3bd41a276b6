"""Serving throughput: continuous batching vs sequential per-arm dispatch.

Measures generated tokens/sec and rounds/sec for M tenants running the full
router protocol (relax -> round -> dispatch -> generate -> feedback)
against a shared pool of K real reduced-config engines on CPU:

  sequential — the retained blocking reference: every tenant's round
               dispatches one `Engine.generate` per selected arm, one
               replica at a time (the seed serving architecture).
  continuous — `router.service.FleetService`: all tenants' requests are
               submitted up front, per-replica `ReplicaRunner`s coalesce
               them into shared slot-cache decode batches, and feedback is
               applied asynchronously per completion (App. E.3).

Both modes produce bit-identical outputs on the dense pool used here (see
tests/test_engine.py), so the tokens/sec ratio is a pure scheduling win —
the same tokens, generated in coalesced fixed-shape decode steps instead
of per-tenant-per-arm host calls.

Every (tenants, replicas, mode) cell is sampled REPS times interleaved and
the best rate kept (shared-box noise suppression). Results land in
BENCH_serve.json at the repo root (uploaded by CI as an artifact).
`--baseline PATH` diffs the continuous tokens/sec of matching cells against
a committed BENCH_serve.json and exits with code 3 when any cell regresses
by more than `--max-regression` (default 20%) — a soft gate in CI.

Acceptance (ISSUE 6): continuous ≥ 3× sequential tokens/sec at
8 tenants × 3 replicas on CPU.

  PYTHONPATH=src python benchmarks/serve_throughput.py \
      [--tenants 1 4 8] [--replicas 3] [--rounds 6] [--reps 2] [--smoke] \
      [--baseline BENCH_serve.json] [--max-regression 0.2] [--json PATH]
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
    """K untrained dense pool members (row-deterministic family, so both
    dispatch modes emit identical tokens and the ratio is pure scheduling)."""
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
                  n_slots, chunk):
    from repro.router.service import FleetService, MultiLLMService
    if mode == "continuous":
        fs = FleetService(pcfg, cloud, data, n_tenants=m, n_slots=n_slots,
                          chunk=chunk, prompt_len=prompt_len,
                          max_new=max_new)
        return fs, fs.tenants
    svcs = [MultiLLMService(pcfg, cloud, data, prompt_len=prompt_len,
                            max_new=max_new, seed=i, tenant=i,
                            dispatch="sequential") for i in range(m)]

    class _Seq:
        def run(self, rounds):
            for _ in range(rounds):
                for s in svcs:
                    s.step()
    return _Seq(), svcs


def bench_cell(pcfg, cloud, data, m, rounds, reps, *, prompt_len, max_new,
               batch, n_slots, chunk):
    """Best-of-reps tokens/sec + rounds/sec per mode, interleaved. A fresh
    service set per rep (fresh bandit + slot state) reuses the engines'
    warm jit caches; rep 0 is the warmup and is not kept."""
    best = {"sequential": (0.0, 0.0), "continuous": (0.0, 0.0)}
    for rep in range(reps + 1):
        for mode in best:
            runner, svcs = make_services(
                pcfg, cloud, data, m, mode, prompt_len=prompt_len,
                max_new=max_new, n_slots=n_slots, chunk=chunk)
            t0 = time.perf_counter()
            runner.run(rounds)
            dt = time.perf_counter() - t0
            dispatches = sum(int(h.observed.sum())
                             for s in svcs for h in s.history)
            tokens = dispatches * batch * max_new
            if rep > 0:
                best[mode] = (max(best[mode][0], tokens / dt),
                              max(best[mode][1], m * rounds / dt))
    return best


def diff_baseline(results, base, max_regression, rounds):
    """Soft gate: continuous tokens/sec vs a committed BENCH_serve.json."""
    if base.get("rounds") != rounds:
        print(f"# baseline ran {base.get('rounds')} rounds vs {rounds} — "
              "rates not comparable, skipping gate")
        return 0
    base_cells = {(r["tenants"], r["replicas"]): r["tok_s"]["continuous"]
                  for r in base.get("results", [])}
    bad = matched = 0
    print(f"# baseline diff vs commit {base.get('commit', '?')} "
          f"(gate {max_regression:.0%})")
    for row in results:
        old = base_cells.get((row["tenants"], row["replicas"]))
        if old is None or old <= 0:
            continue
        matched += 1
        new = row["tok_s"]["continuous"]
        ratio = new / old
        flag = "  <-- REGRESSION" if ratio < 1.0 - max_regression else ""
        print(f"  {row['tenants']}x{row['replicas']}: {old:.0f} -> "
              f"{new:.0f} tok/s ({ratio:.2f}x){flag}")
        bad += ratio < 1.0 - max_regression
    if matched == 0:
        print("  (no matching cells — baseline sweep differs)")
    return bad


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, nargs="+", default=[1, 4, 8])
    ap.add_argument("--replicas", type=int, nargs="+", default=[3])
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=1,
                    help="query rows per request (1 = online per-query "
                         "arrival, the continuous-batching regime)")
    ap.add_argument("--slots", type=int, default=0,
                    help="slot-cache size per replica; 0 sizes to the "
                         "worst-case concurrent load (tenants x batch)")
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--baseline", default=None,
                    help="diff continuous tok/s against a committed "
                         "BENCH_serve.json; exit 3 on regression")
    ap.add_argument("--max-regression", type=float, default=0.2)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration (~1-2 min)")
    ap.add_argument("--json", default=None,
                    help="output path (default: BENCH_serve.json here)")
    args = ap.parse_args(argv)
    if args.smoke:
        # keep --rounds at the committed sweep's value: shorter runs
        # under-measure tokens/sec (per-run fixed costs amortize over
        # fewer rounds) and would always trip the baseline gate
        args.tenants, args.replicas = [1, 8], [3]
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
    out = {"commit": git_commit(), "rounds": args.rounds,
           "backend": jax.default_backend(), "reps": args.reps,
           "results": []}
    print("tenants,replicas,seq_tok_s,cont_tok_s,speedup,"
          "seq_rounds_s,cont_rounds_s")
    for k in args.replicas:
        pool = build_pool(k, max_len=args.prompt_len + args.max_new + 8)
        pcfg = PolicyConfig(kind="suc", k=k, n=min(2, k), rho=1e9, delta=0.1)
        cloud = SchedulingCloud(pcfg, pool)
        for m in args.tenants:
            n_slots = args.slots or max(4, m * args.batch)
            rates = bench_cell(pcfg, cloud, data, m, args.rounds, args.reps,
                               prompt_len=args.prompt_len,
                               max_new=args.max_new, batch=args.batch,
                               n_slots=n_slots, chunk=args.chunk)
            row = {"tenants": m, "replicas": k,
                   "tok_s": {md: round(v[0], 1)
                             for md, v in rates.items()},
                   "rounds_s": {md: round(v[1], 2)
                                for md, v in rates.items()},
                   "speedup": round(rates["continuous"][0]
                                    / rates["sequential"][0], 3)}
            out["results"].append(row)
            print(f"{m},{k},{row['tok_s']['sequential']},"
                  f"{row['tok_s']['continuous']},{row['speedup']:.2f},"
                  f"{row['rounds_s']['sequential']},"
                  f"{row['rounds_s']['continuous']}")

    path = args.json or os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "..", "BENCH_serve.json")
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)
    print(f"# wrote {os.path.abspath(path)}")

    if baseline is not None:
        bad = diff_baseline(out["results"], baseline, args.max_regression,
                            args.rounds)
        if bad:
            print(f"# {bad} cell(s) regressed beyond the "
                  f"{args.max_regression:.0%} gate")
            raise SystemExit(3)


if __name__ == "__main__":
    main()
