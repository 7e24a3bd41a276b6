"""Pallas TPU flash attention (prefill/train), GQA-aware.

Grid (B, H, n_q, n_kv); the kv axis is innermost so the online-softmax
carry (m, l, acc) lives in VMEM scratch across kv steps — the canonical TPU
flash pattern: HBM->VMEM streaming of K/V blocks, the (bq, bk) score tile
stays in VMEM/VREGs and feeds the MXU with 128-aligned tiles.

Causal/banded block skipping: fully-masked kv blocks are skipped via
``pl.when`` on block indices — queries never pay for keys they cannot see
(this is the structural analogue of a GPU early-exit, TPU-style: the grid
still visits the block but does no HBM read or MXU work).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BQ = 256
DEFAULT_BK = 256


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            bq: int, bk: int, n_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk
    # block is live unless entirely masked out
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    if window is not None:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window) \
            if causal else live

    def body():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    if isinstance(live, bool):
        body()
    else:
        pl.when(live)(body)

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = False):
    """q (B,S,H,hd), k/v (B,T,KV,hd) -> (B,S,H,hd)."""
    b, s, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    n_rep = h // kv
    bq = min(bq, s)
    bk = min(bk, t)
    assert s % bq == 0 and t % bk == 0, (s, bq, t, bk)
    n_q, n_kv = s // bq, t // bk
    scale = 1.0 / math.sqrt(hd)

    # layout: (B, H, S, hd) blocks
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b_, h_, qi, ki, n_rep=n_rep:
                         (b_, h_ // n_rep, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b_, h_, qi, ki, n_rep=n_rep:
                         (b_, h_ // n_rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)
