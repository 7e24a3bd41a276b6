"""Serving launcher: the C2MAB-V router over a pool of deployed models.

Each pool member is built at the widths of the config it is given — the
published config of each ``--pool`` name, with bf16 weights and a bf16
slot cache — from random weights drawn from a seed (``--train-first N``
first trains the cheapest N members briefly on the query stream so the
pool has a quality gradient; that needs configs small enough to train).
It then runs the full local-cloud loop: relax (local) -> round + dispatch
(cloud) -> generation -> feedback. ``--dispatch continuous`` (the default)
serves generation through the slot-indexed continuous-batching scheduler;
``--tenants M`` steps M local servers against the shared pool so their
requests coalesce into per-replica decode batches (the throughput case;
the served cells of the chip benchmark, `chipbench/`, measure it at
published widths). Callers that want other widths pass their own configs
(`main(configs=...)`; the CPU example and tests pass `.reduced()` ones).

After the run each replica's scheduler counters print, the operator's view
without a profiler: the mean host-clock queue wait of an admitted attempt,
tokens decoded, tokens over the slot-steps the decode chunks computed, the
prefill buckets, and the fault counters.

``--fault-rate`` arms the deterministic chaos layer (serving.faults): a
seeded fraction of attempts fail (or crash with ``--crash-on-decode``),
failures feed the bandit as zero-reward observations at the attempted-work
cost, and the terminal failures tenant 0 observed print at the end.

  PYTHONPATH=src python -m repro.launch.serve --kind awc --rounds 3 \
      --pool h2o-danube-3-4b,mamba2-780m --tenants 4 --max-len 512 \
      [--fault-rate 0.2 --fault-seed 7]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core.policies import PolicyConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.router.cloud import Replica, SchedulingCloud
from repro.router.service import FleetService, MultiLLMService
from repro.serving.engine import Engine
from repro.train import optimizer as opt
from repro.train.train_step import make_train_step

VOCAB = 128     # query-stream vocabulary; every member's vocab covers it
SLOTS = 16      # decode slots per replica: at 512 tokens a slot, the default
                # two-member pool at published widths fits one 16 GiB chip
PROMPT_LEN = 8
MAX_NEW = 8


def _train(cfg, params, data: SyntheticLM, steps: int):
    ocfg = opt.AdamWConfig(lr=3e-3, warmup_steps=10, total_steps=steps)
    st = opt.init_adamw(ocfg, params)
    ts = jax.jit(make_train_step(cfg, ocfg, remat=False))
    for s in range(steps):
        b = data.batch(s)
        params, st, mt = ts(params, st, {"tokens": jnp.asarray(b[:, :-1]),
                                         "labels": jnp.asarray(b[:, 1:])})
    print(f"  {cfg.name}: trained to loss {float(mt['loss']):.3f}")
    return params


def build_pool(cfgs, data: SyntheticLM, *, max_len: int,
               train_first: int = 0, train_steps: int = 60, seed: int = 0):
    """One replica per config, at the config's own widths: weights random
    from ``seed`` in the config's dtype (bfloat16 for the published
    configs), slot cache in the same dtype, a per-token price ladder."""
    replicas = []
    for i, cfg in enumerate(cfgs):
        params = M.init_params(cfg, jax.random.PRNGKey(seed + i))
        if i < train_first:
            params = _train(cfg, params, data, train_steps)
        eng = Engine(cfg, params, max_len=max_len, eos_id=0,
                     temperature=0.7, dtype=jnp.dtype(cfg.dtype))
        replicas.append(Replica(cfg.name, eng, 0.001 * (1 + i)))
    return replicas


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--kind", default="awc", choices=["awc", "suc", "aic"])
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--pool", default="h2o-danube-3-4b,mamba2-780m")
    ap.add_argument("--n", type=int, default=2)
    ap.add_argument("--rho", type=float, default=0.6)
    ap.add_argument("--batch-size", type=int, default=1,
                    help="App. E.3 async local-cloud sync batch")
    ap.add_argument("--train-first", type=int, default=0,
                    help="how many pool members to pre-train on the stream")
    ap.add_argument("--max-len", type=int, default=512,
                    help="per-slot cache length (prompt + generated)")
    ap.add_argument("--dispatch", default="continuous",
                    choices=["continuous", "sequential"],
                    help="continuous-batching scheduler vs the blocking "
                         "per-arm reference dispatch")
    ap.add_argument("--tenants", type=int, default=1,
                    help="local servers sharing the pool; >1 coalesces "
                         "tenant requests into shared decode batches")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="chaos mode: per-attempt injected failure "
                         "probability (seeded, reproducible)")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--crash-on-decode", action="store_true",
                    help="doomed attempts crash the engine mid-decode "
                         "instead of failing cleanly (exercises recovery)")
    ap.add_argument("--spike-prob", type=float, default=0.0,
                    help="probability of an injected admission latency "
                         "spike per attempt")
    ap.add_argument("--max-retries", type=int, default=2)
    return ap.parse_args(argv)


def build_service(args: argparse.Namespace, configs=None):
    """The pool, the cloud and the tenants' service for parsed ``args``:
    returns (runner, svc, names) — ``runner`` steps every tenant one round
    (a `FleetService` when ``--tenants`` > 1), ``svc`` is tenant 0's
    `MultiLLMService`. ``configs`` replaces the published configs named by
    ``--pool`` (the CPU example passes `.reduced()` ones)."""
    cfgs = configs or [get_config(nm) for nm in args.pool.split(",")]
    names = [c.name for c in cfgs]
    data = SyntheticLM(DataConfig(vocab=VOCAB, seq_len=32,
                                  global_batch=8, seed=0))
    print(f"building pool of {len(names)} models ...")
    replicas = build_pool(cfgs, data, max_len=args.max_len,
                          train_first=args.train_first)

    pcfg = PolicyConfig(kind=args.kind, k=len(names), n=args.n,
                        rho=args.rho, delta=0.1)
    cloud = SchedulingCloud(pcfg, replicas)
    fault_kw = {}
    if args.fault_rate > 0 or args.spike_prob > 0:
        from repro.serving.faults import FaultPlan, HealthPolicy
        fault_kw = dict(
            fault_plan=FaultPlan(fault_seed=args.fault_seed,
                                 fail_prob=args.fault_rate,
                                 crash_on_decode=args.crash_on_decode,
                                 spike_prob=args.spike_prob),
            health=HealthPolicy(max_retries=args.max_retries))
    if args.tenants > 1:
        fs = FleetService(pcfg, cloud, data, n_tenants=args.tenants,
                          n_slots=SLOTS, prompt_len=PROMPT_LEN,
                          max_new=MAX_NEW,
                          batch_size=args.batch_size, **fault_kw)
        svc = fs.tenants[0]
        runner = fs
    else:
        sched = cloud.make_scheduler(n_slots=SLOTS, **fault_kw) \
            if args.dispatch == "continuous" else None
        svc = MultiLLMService(pcfg, cloud, data, prompt_len=PROMPT_LEN,
                              max_new=MAX_NEW,
                              batch_size=args.batch_size,
                              dispatch=args.dispatch, scheduler=sched,
                              **fault_kw)
        runner = svc
    return runner, svc, names


def counter_lines(names, stats) -> list[str]:
    """One report line per replica from `ContinuousScheduler.stats`."""
    lines = []
    for nm, st in zip(names, stats):
        wait_ms = 1e3 * st["queue_wait_s"] / max(st["admitted"], 1)
        per_step = st["tokens_out"] / max(st["slot_steps"], 1)
        lines.append(
            f"  {nm}: queue wait {wait_ms:.2f} ms mean over "
            f"{st['admitted']} admitted, {st['tokens_out']} tokens out, "
            f"{per_step:.3f} tokens/slot-step, {st['prefill_calls']} "
            f"prefills ({st['prefill_rows']} rows); failures "
            f"{st['failures']} retries {st['retries']} rejected "
            f"{st['rejected']} crashes {st['crashes']} quarantines "
            f"{st['quarantines']} health {st['health']}")
    return lines


def main(argv=None, configs=None):
    """CLI entry point; ``configs`` as in `build_service`."""
    args = parse_args(argv)
    print(f"compile cache: {enable_compile_cache()}")
    runner, svc, names = build_service(args, configs)
    t0 = time.time()
    runner.run(args.rounds)
    dt = time.time() - t0
    s = svc.summary()
    gen_tokens = sum(int(h.observed.sum()) for h in svc.history) \
        * args.tenants * svc.data.cfg.global_batch \
        * (PROMPT_LEN + MAX_NEW)
    print(f"\n{args.rounds} rounds x {args.tenants} tenant(s) in {dt:.1f}s "
          f"({args.rounds * args.tenants / dt:.2f} rounds/s, "
          f"~{gen_tokens / dt:.0f} tok/s incl. prompt)")
    print(f"mean observed reward {s['mean_observed_reward']:.3f}  "
          f"mean cost {s['mean_cost']:.4f}  violation {s['violation']:.4f}")
    print("selections:", dict(zip(names, svc.local.t_mu.astype(int))))
    if svc.sched is not None:
        if args.fault_rate > 0 or args.spike_prob > 0:
            failed = sum(int(h.failed.sum()) for h in svc.history
                         if h.failed is not None)
            print(f"chaos: {failed} terminal failure(s) observed by tenant 0")
        print("replicas:")
        print("\n".join(counter_lines(names, svc.sched.stats())))
    return s


if __name__ == "__main__":
    main()
