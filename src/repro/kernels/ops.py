"""jit'd public wrappers for the Pallas kernels.

The router kernels (`topn_lp`, `awc_fw`) are chosen by platform: the
compiled Pallas kernel on TPU, the fused pure-jnp oracle elsewhere. Every
wrapper compiles its kernel unless the caller passes ``interpret=True``,
which only tests and `benchmarks/kernel_bench.py` do (off-TPU).
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import awc_fw as _awc
from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd
from repro.kernels import topn_lp as _topn
from repro.kernels import ref as _ref


def use_pallas() -> bool:
    """Whether the router's probe reductions run through the Pallas kernels
    — and so whether the relax grid engine takes its wide G-way lowering
    with the fused `awc_fw` Frank-Wolfe step. The probes sit inside the
    fleet's jitted scan, where interpret mode is never acceptable: compiled
    kernels on TPU, the fused pure-jnp path elsewhere."""
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, bq: int = _fa.DEFAULT_BQ,
                    bk: int = _fa.DEFAULT_BK, interpret: bool = False):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               bq=bq, bk=bk, interpret=interpret)


def decode_attention(q, k, v, pos, *, bk: int = _dec.DEFAULT_BK,
                     interpret: bool = False):
    return _dec.decode_attention(q, k, v, pos, bk=bk, interpret=interpret)


def ssd_chunk(xd, acum, bm, cm, *, interpret: bool = False):
    return _ssd.ssd_chunk(xd, acum, bm, cm, interpret=interpret)


def topn_lp(score, cost, n, *, equality: bool = True,
            interpret: bool = False):
    """Top-n-by-score cost reduction: score/cost (B, K), n int/(B,) -> (B,).

    The Pallas kernel on TPU, or in interpret mode where the caller asks
    for it; the pure-jnp oracle otherwise."""
    if interpret or use_pallas():
        return _topn.topn_lp(score, cost, n, equality=equality,
                             interpret=interpret)
    return _ref.topn_lp(score, cost, n, equality=equality)


def awc_fw(z, mu, cost, lams, n, *, interpret: bool = False):
    """Fused AWC FW step oracle: gradient + λ-probe cost reductions.

    z/mu/cost (B, K), lams (B, G), n (B,) -> (g (B, K), costs (B, G)).
    Dispatch as in `topn_lp`."""
    if interpret or use_pallas():
        return _awc.awc_fw(z, mu, cost, lams, n, interpret=interpret)
    return _ref.awc_fw(z, mu, cost, lams, n)
