"""Pallas TPU kernel for the Mamba2 SSD intra-chunk dual form.

Per grid cell (B, NC, H) the kernel computes, entirely in VMEM:
  scores  = (C_c B_c^T) ⊙ L           L[i,j] = exp(acum_i - acum_j)·[j<=i]
  y_intra = scores @ (x·dt)            (chunk, P) — MXU matmuls
  state   = (B_c ⊙ exp(atot - acum))^T @ (x·dt)   (N, P) chunk state
The inter-chunk recurrence (associative scan over NC) stays in XLA — it is
tiny ((B,NC,H,P,N)) and latency-bound, not MXU work.

All decay terms satisfy exp(·) <= 1 inside the causal region, so the kernel
is numerically stable without a running max.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(xd_ref, acum_ref, b_ref, c_ref, y_ref, st_ref):
    xd = xd_ref[0, 0].astype(jnp.float32)        # (L, P)
    ac = acum_ref[0, 0].astype(jnp.float32)      # (L, 1) -> (L,)
    ac = ac[:, 0]
    bm = b_ref[0].astype(jnp.float32)            # (L, N)
    cm = c_ref[0].astype(jnp.float32)            # (L, N)
    l = xd.shape[0]

    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    diff = ac[:, None] - ac[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    lmat = jnp.where(jj <= ii, jnp.exp(diff), 0.0)
    y = jax.lax.dot_general(cb * lmat, xd, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    dec_out = jnp.exp(ac[l - 1] - ac)            # (L,)
    bw = bm * dec_out[:, None]                   # (L, N)
    st = jax.lax.dot_general(bw, xd, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (N, P)
    st_ref[0, 0] = st.astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk(xd, acum, bm, cm, *, interpret: bool = False):
    """Intra-chunk SSD.

    xd (B,NC,L,H,P), acum (B,NC,L,H), bm/cm (B,NC,L,N)
    -> y_intra (B,NC,L,H,P) fp32, states (B,NC,H,P,N) fp32.
    """
    b, nc, l, h, p = xd.shape
    n = bm.shape[-1]
    xt = jnp.moveaxis(xd, 3, 2).reshape(b * nc, h, l, p)        # (BN,H,L,P)
    at = jnp.moveaxis(acum, 3, 2).reshape(b * nc, h, l, 1)      # (BN,H,L,1)
    bt = bm.reshape(b * nc, l, n)
    ct = cm.reshape(b * nc, l, n)

    y, st = pl.pallas_call(
        _kernel,
        grid=(b * nc, h),
        in_specs=[
            pl.BlockSpec((1, 1, l, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, l, 1), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, l, n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, l, n), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, l, p), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * nc, h, l, p), jnp.float32),
            jax.ShapeDtypeStruct((b * nc, h, n, p), jnp.float32),
        ],
        interpret=interpret,
    )(xt, at, bt, ct)
    y = jnp.moveaxis(y.reshape(b, nc, h, l, p), 2, 3)           # (B,NC,L,H,P)
    st = jnp.swapaxes(st.reshape(b, nc, h, n, p), 3, 4)         # (B,NC,H,P,N)
    return y, st
