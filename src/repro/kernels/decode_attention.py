"""Pallas TPU decode attention: one query token vs a long KV cache.

Grid (B, H, n_kv): the cache is streamed HBM->VMEM in bk-sized blocks along
the sequence axis (which is also how the cache is sharded across the "model"
mesh axis — each chip streams its resident slice); the online-softmax carry
sits in VMEM scratch. Slots beyond ``pos`` are masked, so a ring-buffer /
partially-filled cache is handled by the same kernel. ``pos`` may be a
scalar (legacy batched path) or a (B,) vector — one position per cache row,
the slot-indexed layout the continuous-batching serving engine decodes:
every grid row reads its own position out of SMEM, so a single kernel launch
advances slots admitted at different times.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BK = 512


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, bk: int, n_kv: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[pl.program_id(0)]
    k_start = ki * bk

    @pl.when(k_start <= pos)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)              # (1, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)
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

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q, k, v, pos, *, bk: int = DEFAULT_BK,
                     interpret: bool = False):
    """q (B,1,H,hd); cache k/v (B,T,KV,hd); pos scalar or (B,) int32 (last
    valid slot per row)."""
    b, _, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    n_rep = h // kv
    bk = min(bk, t)
    assert t % bk == 0, (t, bk)
    n_kv = t // bk
    scale = 1.0 / math.sqrt(hd)

    qt = jnp.swapaxes(q, 1, 2)                 # (B,H,1,hd)
    kt = jnp.swapaxes(k, 1, 2)                 # (B,KV,T,hd)
    vt = jnp.swapaxes(v, 1, 2)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))

    kernel = functools.partial(_kernel, scale=scale, bk=bk, n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_kv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, hd), lambda b_, h_, ki: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b_, h_, ki, n_rep=n_rep:
                         (b_, h_ // n_rep, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b_, h_, ki, n_rep=n_rep:
                         (b_, h_ // n_rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda b_, h_, ki: (b_, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
    )(pos_arr, qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)
