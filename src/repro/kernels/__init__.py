"""Pallas kernel layer — compute hot-spots with custom TPU kernels.

Kernels (each with a pure-jnp oracle in `ref.py`, interpret-tested; public
jit'd entry points with backend dispatch in `ops.py`):

  flash_attention   — tiled causal/windowed attention (model side)
  decode_attention  — single-token KV-cache attention (serving side)
  ssd_scan          — Mamba2 SSD intra-chunk dual form (model side)
  topn_lp           — top-n-by-score cost reduction over (B, K) rows with
                      traced per-row n: the parametric-LP grid engine's
                      scalar cost probe (bandit side; `core.relax`)
  awc_fw            — the AWC Frank-Wolfe step: multilinear gradient fused
                      with the octave-ladder probes (bandit side)

Every kernel compiles for the TPU unless its caller passes
``interpret=True`` (tests and `benchmarks/kernel_bench.py`, on the CPU).
The router kernels run only on TPU; elsewhere `ops` dispatches to the fused
pure-jnp path (`ops.use_pallas`).
"""
