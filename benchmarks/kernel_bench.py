"""Kernel micro-bench: interpret-mode correctness cost + XLA-oracle timing.

On CPU the Pallas kernels run in interpret mode (Python), so wall-clock is a
correctness-path number, not a TPU projection; the jnp oracle timing is the
XLA-compiled CPU reference. Both are printed per shape, and `--json PATH`
(CI: BENCH_kernels.json at the repo root, uploaded next to BENCH_fleet.json)
records the sweep so the cross-PR artifact trajectory covers kernels too.
"""
import argparse
import json
import os
import subprocess
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

ROWS = []


def bench(fn, *args, iters=3):
    fn(*args)                      # warm up / compile
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e3


def row(kernel, shape, pallas_ms, oracle_ms):
    ROWS.append({"kernel": kernel, "shape": shape,
                 "pallas_interpret_ms": round(pallas_ms, 2),
                 "jnp_oracle_ms": round(oracle_ms, 2)})
    print(f"{kernel},{shape},{pallas_ms:.1f},{oracle_ms:.1f}")


def _git_commit():
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"], cwd=here,
            text=True).strip()
    except Exception:
        return "unknown"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="also write the sweep as JSON (CI artifact)")
    args = ap.parse_args(argv)
    k0 = jax.random.PRNGKey(0)
    print("# kernel_bench: ms/call (interpret-mode kernel vs jnp oracle)")
    print("kernel,shape,pallas_interpret_ms,jnp_oracle_ms")

    for (b, h, kv, s, d) in [(1, 8, 2, 512, 64), (2, 16, 4, 1024, 128)]:
        q = jax.random.normal(k0, (b, h, s, d))
        k = jax.random.normal(jax.random.fold_in(k0, 1), (b, kv, s, d))
        v = jax.random.normal(jax.random.fold_in(k0, 2), (b, kv, s, d))
        t1 = bench(lambda: ops.flash_attention(q, k, v, bq=128, bk=128,
                                                interpret=True))
        t2 = bench(lambda: ref.flash_attention(q, k, v))
        row("flash_attention", f"B{b}H{h}KV{kv}S{s}D{d}", t1, t2)

    for (b, h, kv, t, d) in [(8, 8, 2, 2048, 64), (4, 16, 4, 8192, 128)]:
        q = jax.random.normal(k0, (b, 1, h, d))
        kc = jax.random.normal(jax.random.fold_in(k0, 1), (b, t, kv, d))
        vc = jax.random.normal(jax.random.fold_in(k0, 2), (b, t, kv, d))
        pos = jnp.int32(t - 1)
        t1 = bench(lambda: ops.decode_attention(q, kc, vc, pos, bk=512,
                                                 interpret=True))
        t2 = bench(lambda: ref.decode_attention(q, kc, vc, pos))
        row("decode_attention", f"B{b}H{h}KV{kv}T{t}D{d}", t1, t2)

    from repro.kernels import topn_lp as tl
    for (b, k) in [(512, 9), (4096, 9), (1024, 128)]:
        score = jax.random.normal(k0, (b, k))
        cost = jax.random.uniform(jax.random.fold_in(k0, 1), (b, k))
        n = jax.random.randint(jax.random.fold_in(k0, 2), (b,), 1, k + 1)
        t1 = bench(lambda: tl.topn_lp(score, cost, n, equality=True,
                                      interpret=True))
        t2 = bench(lambda: ref.topn_lp(score, cost, n, equality=True))
        row("topn_lp", f"B{b}K{k}", t1, t2)

    from repro.kernels import awc_fw as ak
    for (b, k, g) in [(64, 9, 25), (512, 9, 25), (256, 64, 8)]:
        z = jax.random.uniform(k0, (b, k))
        mu = jax.random.uniform(jax.random.fold_in(k0, 1), (b, k),
                                jnp.float32, 0.05, 0.99)
        cost = jax.random.uniform(jax.random.fold_in(k0, 2), (b, k),
                                  jnp.float32, 0.01, 0.6)
        lams = jax.random.uniform(jax.random.fold_in(k0, 3), (b, g),
                                  jnp.float32, 0.0, 8.0)
        n = jax.random.randint(jax.random.fold_in(k0, 4), (b,), 1, k + 1)
        t1 = bench(lambda: ak.awc_fw(z, mu, cost, lams, n, interpret=True))
        t2 = bench(lambda: ref.awc_fw(z, mu, cost, lams, n))
        row("awc_fw", f"B{b}K{k}G{g}", t1, t2)

    for (b, nc, l, h, p, n) in [(1, 8, 128, 8, 64, 64)]:
        xd = jax.random.normal(k0, (b, nc, l, h, p))
        a = -jnp.abs(jax.random.normal(jax.random.fold_in(k0, 1),
                                       (b, nc, l, h))) * 0.1
        acum = jnp.cumsum(a, axis=2)
        bm = jax.random.normal(jax.random.fold_in(k0, 2), (b, nc, l, n))
        cm = jax.random.normal(jax.random.fold_in(k0, 3), (b, nc, l, n))
        t1 = bench(lambda: ops.ssd_chunk(xd, acum, bm, cm,
                                          interpret=True))
        t2 = bench(lambda: ref.ssd_chunk(xd, acum, bm, cm))
        row("ssd_chunk", f"B{b}NC{nc}L{l}H{h}P{p}N{n}", t1, t2)

    if args.json:
        payload = {"commit": _git_commit(),
                   "backend": jax.default_backend(), "results": ROWS}
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"# wrote {os.path.abspath(args.json)}")


if __name__ == "__main__":
    main()
