"""Mamba2 (SSD — state-space duality), chunked training path + O(1) decode.

Layer structure follows the Mamba2 paper: in_proj -> [z | xBC | dt],
depthwise causal conv over xBC, SSD core over heads, gated RMSNorm,
out_proj. B and C come in ``ssm_groups`` groups (head h reads group
h // (H/G)) and the gated norm is taken over each group's share of the
inner width; with one group (mamba2) both are the full-width forms. The SSD
core uses the chunkwise dual form: intra-chunk quadratic
("attention-like", MXU-friendly) term + inter-chunk state recurrence via an
associative scan. ``repro.kernels.ssd_scan`` is the Pallas TPU kernel for the
intra-chunk term; this module is the pure-XLA implementation used on CPU and
as the oracle.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec, rmsnorm
from repro.sharding import shard

CONV_WIDTH = 4


def ssm_schema(cfg):
    d, di, h, n = cfg.d_model, cfg.d_inner, cfg.ssm_heads, cfg.ssm_state
    g = cfg.ssm_groups
    conv_ch = di + 2 * g * n
    proj_out = 2 * di + 2 * g * n + h
    std_o = 0.02 / math.sqrt(2 * max(cfg.n_layers, 1))
    return {
        "w_in": ParamSpec((d, proj_out), ("embed_fsdp", "mlp"), std=0.02),
        "conv_w": ParamSpec((CONV_WIDTH, conv_ch), (None, "mlp"), std=0.02),
        "conv_b": ParamSpec((conv_ch,), ("mlp",), "zeros"),
        "a_log": ParamSpec((h,), ("ssm_heads",), "ones"),
        "d_skip": ParamSpec((h,), ("ssm_heads",), "ones"),
        "dt_bias": ParamSpec((h,), ("ssm_heads",), "zeros"),
        "norm": ParamSpec((di,), ("mlp",), "ones"),
        "w_out": ParamSpec((di, d), ("mlp", "embed_fsdp"), std=std_o),
    }


def _split_proj(cfg, zxbcdt):
    di, gn = cfg.d_inner, cfg.ssm_groups * cfg.ssm_state
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * gn]
    dt = zxbcdt[..., 2 * di + 2 * gn:]
    return z, xbc, dt


def _split_xbc(cfg, xbc):
    """x, B, C of the conv's output; B and C (..., G, N) where G > 1."""
    di, n, g = cfg.d_inner, cfg.ssm_state, cfg.ssm_groups
    xin, bmat, cmat = (xbc[..., :di], xbc[..., di:di + g * n],
                       xbc[..., di + g * n:])
    if g > 1:
        bmat = bmat.reshape(bmat.shape[:-1] + (g, n))
        cmat = cmat.reshape(cmat.shape[:-1] + (g, n))
    return xin, bmat, cmat


def gated_norm(cfg, y, z, scale):
    """RMSNorm of y * silu(z), over each of the ``ssm_groups`` groups of
    the inner width (the whole width for one group)."""
    y = y * jax.nn.silu(z)
    g = cfg.ssm_groups
    if g == 1:
        return rmsnorm(y, scale, cfg.norm_eps)
    shape = y.shape
    y = y.reshape(shape[:-1] + (g, shape[-1] // g))
    return rmsnorm(y, scale.reshape(g, -1), cfg.norm_eps).reshape(shape)


def causal_conv(xbc, w, b):
    """Depthwise causal conv, width CONV_WIDTH. xbc (B,S,C)."""
    pad = jnp.pad(xbc, ((0, 0), (CONV_WIDTH - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xbc.shape[1], :] * w[i] for i in range(CONV_WIDTH))
    return jax.nn.silu(out + b)


def segsum_decay(da):
    """da (..., L) -> cumulative log decay A_cum (inclusive)."""
    return jnp.cumsum(da, axis=-1)


def ssd_chunked(xh, dt, a, bmat, cmat, chunk: int, init_state=None,
                return_state: bool = False):
    """Chunked SSD core.

    xh   (B,S,H,P) head inputs
    dt   (B,S,H)   positive step sizes
    a    (H,)      negative decay rates (A = -exp(a_log))
    bmat (B,S,N), cmat (B,S,N)  (single B/C group, broadcast over heads)
    Returns y (B,S,H,P) [, final_state (B,H,P,N)].
    """
    b, s, h, p = xh.shape
    n = bmat.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    da = (dt * a).reshape(b, nc, chunk, h)                     # log decay/step
    xd = (xh * dt[..., None]).reshape(b, nc, chunk, h, p)
    bm = bmat.reshape(b, nc, chunk, n)
    cm = cmat.reshape(b, nc, chunk, n)

    acum = jnp.cumsum(da, axis=2)                              # (B,NC,L,H) incl
    atot = acum[:, :, -1, :]                                   # (B,NC,H)

    # ---- intra-chunk (quadratic, MXU-friendly) ----
    # L[i,j] = exp(acum_i - acum_j) for j <= i
    diff = acum[:, :, :, None, :] - acum[:, :, None, :, :]     # (B,NC,L,L,H)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    lmat = jnp.where(causal[None, None, :, :, None], jnp.exp(diff), 0.0)
    cb = jnp.einsum("bcin,bcjn->bcij", cm, bm)                 # (B,NC,L,L)
    y_intra = jnp.einsum("bcij,bcijh,bcjhp->bcihp",
                         cb.astype(jnp.float32), lmat, xd.astype(jnp.float32))

    # ---- chunk states ----
    dec_out = jnp.exp(atot[:, :, None, :] - acum)              # (B,NC,L,H)
    states = jnp.einsum("bcln,bclh,bclhp->bchpn",
                        bm.astype(jnp.float32), dec_out,
                        xd.astype(jnp.float32))                # (B,NC,H,P,N)

    # ---- inter-chunk recurrence (associative scan over chunks) ----
    gtot = jnp.exp(atot)                                       # (B,NC,H)

    def op(e1, e2):
        g1, s1 = e1
        g2, s2 = e2
        return g1 * g2, s2 + g2[..., None, None] * s1

    g_sc, s_sc = jax.lax.associative_scan(op, (gtot, states), axis=1)
    # state *before* chunk c = scan result of chunk c-1 (+ init)
    zero = jnp.zeros_like(states[:, :1])
    prev = jnp.concatenate([zero, s_sc[:, :-1]], axis=1)       # (B,NC,H,P,N)
    if init_state is not None:
        gpre = jnp.concatenate(
            [jnp.ones_like(gtot[:, :1]), g_sc[:, :-1]], axis=1)
        prev = prev + gpre[..., None, None] * init_state[:, None]

    dec_in = jnp.exp(acum)                                     # (B,NC,L,H)
    y_inter = jnp.einsum("bcln,bclh,bchpn->bclhp",
                         cm.astype(jnp.float32), dec_in, prev)

    y = (y_intra + y_inter).reshape(b, s, h, p)
    if return_state:
        final = s_sc[:, -1]
        if init_state is not None:
            final = final + g_sc[:, -1][..., None, None] * init_state
        return y, final
    return y


def ssd(xh, dt, a, bmat, cmat, chunk: int, init_state=None,
        return_state: bool = False):
    """`ssd_chunked` for B/C of one group (B,S,N) or of G groups
    (B,S,G,N): each group's heads run as one group, vmapped over groups."""
    if bmat.ndim == 3:
        return ssd_chunked(xh, dt, a, bmat, cmat, chunk,
                           init_state=init_state, return_state=return_state)
    b, s, h, p = xh.shape
    g, n = bmat.shape[2], bmat.shape[3]
    hg = h // g

    def one(xg, dg, ag, bg, cg, ig):
        return ssd_chunked(xg, dg, ag, bg, cg, chunk, init_state=ig,
                           return_state=return_state)

    init = None if init_state is None else \
        init_state.reshape(b, g, hg, p, n)
    out = jax.vmap(one, in_axes=(2, 2, 0, 2, 2, None if init is None else 1),
                   out_axes=(2, 1) if return_state else 2)(
        xh.reshape(b, s, g, hg, p), dt.reshape(b, s, g, hg),
        a.reshape(g, hg), bmat, cmat, init)
    if return_state:
        y, final = out
        return y.reshape(b, s, h, p), final.reshape(b, h, p, n)
    return out.reshape(b, s, h, p)


def apply_ssm(cfg, p, x, *, init_state=None, return_state: bool = False):
    """Full-sequence Mamba2 layer. x (B,S,D)."""
    b, s, _ = x.shape
    di, n, h, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    zxbcdt = jnp.einsum("bsd,dk->bsk", x, p["w_in"])
    z, xbc, dt = _split_proj(cfg, zxbcdt)
    xbc = causal_conv(xbc, p["conv_w"], p["conv_b"])
    xin, bmat, cmat = _split_xbc(cfg, xbc)
    xh = xin.reshape(b, s, h, hp)
    xh = shard(xh, "batch", "seq", "ssm_heads", None)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    out = ssd(xh, dtv, a, bmat, cmat, cfg.ssm_chunk,
              init_state=init_state, return_state=return_state)
    y, final = out if return_state else (out, None)
    y = y + xh.astype(jnp.float32) * p["d_skip"][None, None, :, None]
    y = y.reshape(b, s, di).astype(x.dtype)
    y = gated_norm(cfg, y, z, p["norm"])
    y = jnp.einsum("bsk,kd->bsd", y, p["w_out"])
    if return_state:
        return y, final
    return y


def apply_ssm_prefill(cfg, p, x, *, cache_dtype=None):
    """Full-sequence layer that also emits the decode cache handoff.

    Same math as `apply_ssm` (chunked SSD), but returns, alongside y, the
    cache `apply_ssm_decode` would hold after consuming x token-by-token:
    the final SSD state (mathematically identical to the step recurrence;
    computed by the chunked scan) and the last CONV_WIDTH-1 *raw* xBC
    columns (the causal-conv window, zero-padded on the left exactly like
    the initial decode cache for prompts shorter than the conv width)."""
    b, s, _ = x.shape
    di, n, h, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    zxbcdt = jnp.einsum("bsd,dk->bsk", x, p["w_in"])
    z, xbc_raw, dt = _split_proj(cfg, zxbcdt)
    xbc = causal_conv(xbc_raw, p["conv_w"], p["conv_b"])
    xin, bmat, cmat = _split_xbc(cfg, xbc)
    xh = xin.reshape(b, s, h, hp)
    xh = shard(xh, "batch", "seq", "ssm_heads", None)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    y, final = ssd(xh, dtv, a, bmat, cmat, cfg.ssm_chunk, return_state=True)
    y = y + xh.astype(jnp.float32) * p["d_skip"][None, None, :, None]
    y = y.reshape(b, s, di).astype(x.dtype)
    y = gated_norm(cfg, y, z, p["norm"])
    y = jnp.einsum("bsk,kd->bsd", y, p["w_out"])

    w = CONV_WIDTH - 1
    keep = min(s, w)
    tail = xbc_raw[:, s - keep:]
    if keep < w:
        tail = jnp.pad(tail, ((0, 0), (w - keep, 0), (0, 0)))
    cache = {"conv": tail.astype(cache_dtype or x.dtype),
             "state": final.astype(jnp.float32)}
    return y, cache


# --------------------------------------------------------------- decode
def init_ssm_cache(cfg, batch: int, dtype):
    di, n, h, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    conv_ch = di + 2 * cfg.ssm_groups * n
    return {
        "conv": jnp.zeros((batch, CONV_WIDTH - 1, conv_ch), dtype),
        "state": jnp.zeros((batch, h, hp, n), jnp.float32),
    }


def ssm_cache_axes():
    return {"conv": ("batch", None, "mlp"),
            "state": ("batch", "ssm_heads", None, "state")}


def apply_ssm_decode(cfg, p, x, cache):
    """Single-token step. x (B,1,D)."""
    b = x.shape[0]
    di, n, h, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    zxbcdt = jnp.einsum("bsd,dk->bsk", x, p["w_in"])[:, 0]     # (B,K)
    z, xbc, dt = _split_proj(cfg, zxbcdt)
    window = jnp.concatenate([cache["conv"], xbc[:, None, :]], axis=1)
    conv_out = jnp.einsum("bwc,wc->bc", window, p["conv_w"]) + p["conv_b"]
    conv_out = jax.nn.silu(conv_out)
    new_conv = window[:, 1:]
    xin, bmat, cmat = _split_xbc(cfg, conv_out)
    xh = xin.reshape(b, h, hp)
    dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,H)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    g = jnp.exp(dtv * a)                                       # (B,H)
    xd = xh.astype(jnp.float32) * dtv[..., None]
    if bmat.ndim == 2:
        upd = jnp.einsum("bhp,bn->bhpn", xd, bmat.astype(jnp.float32))
        state = cache["state"] * g[..., None, None] + upd
        y = jnp.einsum("bhpn,bn->bhp", state, cmat.astype(jnp.float32))
    else:                                      # B/C (B,G,N): heads by group
        ng = bmat.shape[1]
        upd = jnp.einsum("bghp,bgn->bghpn", xd.reshape(b, ng, h // ng, hp),
                         bmat.astype(jnp.float32)).reshape(b, h, hp, n)
        state = cache["state"] * g[..., None, None] + upd
        y = jnp.einsum("bghpn,bgn->bghp",
                       state.reshape(b, ng, h // ng, hp, n),
                       cmat.astype(jnp.float32)).reshape(b, h, hp)
    y = y + xh.astype(jnp.float32) * p["d_skip"][None, :, None]
    y = y.reshape(b, di).astype(x.dtype)
    y = gated_norm(cfg, y, z, p["norm"])
    y = jnp.einsum("bk,kd->bd", y, p["w_out"])[:, None, :]
    return y, {"conv": new_conv, "state": state}
