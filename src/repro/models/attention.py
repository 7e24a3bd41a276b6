"""Attention: GQA / MHA / sliding-window / cross; train, prefill and decode.

Three interchangeable implementations (``impl``):
  naive   — one einsum over the full (S,T) score matrix. Simple, but
            materializes O(S·T) intermediates: memory-roofline poison at 32k.
  chunked — double lax.scan online-softmax ("flash" in pure XLA): never
            materializes S×T; for sliding-window configs a banded variant
            only touches the W+L keys a query chunk can see.
  pallas  — the TPU kernel in repro.kernels (validated in interpret mode).

``auto`` picks naive for short sequences and chunked beyond a threshold.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec
from repro.models import rope as rope_mod
from repro.sharding import shard

NEG_INF = -1e30
CHUNKED_THRESHOLD = 2048
Q_CHUNK = 512
KV_CHUNK = 1024


# ------------------------------------------------------------------ schema
def attention_schema(cfg, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    std = 0.02
    std_o = 0.02 / math.sqrt(2 * max(cfg.n_layers, 1))
    s = {
        "wq": ParamSpec((d, h, hd), ("embed_fsdp", "heads", None), std=std),
        "wk": ParamSpec((d, kv, hd), ("embed_fsdp", "kv_heads", None), std=std),
        "wv": ParamSpec((d, kv, hd), ("embed_fsdp", "kv_heads", None), std=std),
        "wo": ParamSpec((h, hd, d), ("heads", None, "embed_fsdp"), std=std_o),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((h, hd), ("heads", None), "zeros")
        s["bk"] = ParamSpec((kv, hd), ("kv_heads", None), "zeros")
        s["bv"] = ParamSpec((kv, hd), ("kv_heads", None), "zeros")
    return s


def _qkv(cfg, p, x, xkv=None):
    xkv = x if xkv is None else xkv
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", xkv, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", xkv, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return q, k, v


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, t, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, t, kv, n_rep, hd)
                            ).reshape(b, t, kv * n_rep, hd)


# ------------------------------------------------------------------ naive
def _attend_naive(q, k, v, *, causal: bool, window: Optional[int],
                  q_offset: int = 0):
    """q (B,S,H,hd), k/v (B,T,KV,hd) -> (B,S,H,hd)."""
    b, s, h, hd = q.shape
    t = k.shape[1]
    k = _repeat_kv(k, h // k.shape[2])
    v = _repeat_kv(v, h // v.shape[2])
    scores = jnp.einsum("bshk,bthk->bhst", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    qpos = jax.lax.broadcasted_iota(jnp.int32, (s, t), 0) + q_offset
    kpos = jax.lax.broadcasted_iota(jnp.int32, (s, t), 1)
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthk->bshk", w, v)


# ------------------------------------------------------------------ chunked
def _attend_chunked(q, k, v, *, causal: bool, window: Optional[int],
                    q_offset: int = 0,
                    q_chunk: int = Q_CHUNK, kv_chunk: int = KV_CHUNK):
    """Online-softmax over kv chunks inside a scan over q chunks.

    Never materializes (S,T). With a sliding window, each q chunk only reads
    the (window + q_chunk) keys it can see (banded slice of a front-padded
    KV), making SWA genuinely O(S*(W+L)).
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    n_rep = h // k.shape[2]
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    q_chunk = min(q_chunk, s)
    assert s % q_chunk == 0, (s, q_chunk)
    scale = 1.0 / math.sqrt(hd)

    banded = window is not None and causal and t == s and q_offset == 0
    if banded:
        band = ((window + q_chunk - 1) // kv_chunk + 1) * kv_chunk
        pad = band  # front pad so every slice is in range
        kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))

    def q_body(_, qi):
        qc = jax.lax.dynamic_slice_in_dim(q, qi * q_chunk, q_chunk, 1)
        qpos = qi * q_chunk + jnp.arange(q_chunk) + q_offset
        if banded:
            # keys visible to this q chunk: [qi*q_chunk - band + 1, qi*q_chunk + q_chunk)
            start = qi * q_chunk + pad - band
            kc = jax.lax.dynamic_slice_in_dim(kp, start, band + q_chunk, 1)
            vc = jax.lax.dynamic_slice_in_dim(vp, start, band + q_chunk, 1)
            kpos = start - pad + jnp.arange(band + q_chunk)
            o = _online_block(qc, kc, vc, qpos, kpos, causal, window, scale)
        else:
            n_kv = t // kv_chunk if t % kv_chunk == 0 else 1
            kv_len = t // n_kv

            def kv_body(carry, kj):
                m, l, acc = carry
                kc = jax.lax.dynamic_slice_in_dim(k, kj * kv_len, kv_len, 1)
                vc = jax.lax.dynamic_slice_in_dim(v, kj * kv_len, kv_len, 1)
                kpos = kj * kv_len + jnp.arange(kv_len)
                sc = (jnp.einsum("bshk,bthk->bhst", qc, kc)
                      .astype(jnp.float32) * scale)
                msk = jnp.ones((q_chunk, kv_len), bool)
                if causal:
                    msk &= kpos[None, :] <= qpos[:, None]
                if window is not None:
                    msk &= kpos[None, :] > qpos[:, None] - window
                sc = jnp.where(msk, sc, NEG_INF)
                m_new = jnp.maximum(m, sc.max(-1))
                p = jnp.exp(sc - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                l_new = l * alpha + p.sum(-1)
                acc_new = acc * alpha[..., None] + jnp.einsum(
                    "bhst,bthk->bhsk", p, vc.astype(jnp.float32))
                return (m_new, l_new, acc_new), None

            m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
            l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
            a0 = jnp.zeros((b, h, q_chunk, hd), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0),
                                          jnp.arange(n_kv))
            o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
            o = jnp.swapaxes(o, 1, 2)  # (B,S,H,hd)
        return None, o

    _, chunks = jax.lax.scan(q_body, None, jnp.arange(s // q_chunk))
    # chunks: (n_q, B, q_chunk, H, hd)
    out = jnp.moveaxis(chunks, 0, 1).reshape(b, s, h, hd)
    return out


def _online_block(qc, kc, vc, qpos, kpos, causal, window, scale):
    """Single-block softmax attention (used by the banded SWA path)."""
    sc = jnp.einsum("bshk,bthk->bhst", qc, kc).astype(jnp.float32) * scale
    msk = jnp.ones((qc.shape[1], kc.shape[1]), bool)
    if causal:
        msk &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        msk &= kpos[None, :] > qpos[:, None] - window
    # padded keys have kpos < 0
    msk &= kpos[None, :] >= 0
    sc = jnp.where(msk, sc, NEG_INF)
    w = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhst,bthk->bshk", w.astype(qc.dtype), vc)
    return o


# ------------------------------------------------------------------ dispatch
def attend(q, k, v, *, causal=True, window=None, q_offset=0, impl="auto"):
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, window=window)
    if impl == "auto":
        impl = "chunked" if q.shape[1] >= CHUNKED_THRESHOLD else "naive"
    if impl == "chunked" and q.shape[1] >= 2 * Q_CHUNK:
        return _attend_chunked(q, k, v, causal=causal, window=window,
                               q_offset=q_offset)
    return _attend_naive(q, k, v, causal=causal, window=window,
                         q_offset=q_offset)


def apply_attention(cfg, p, x, positions, *, causal=True, xkv=None,
                    window="cfg", impl="auto"):
    """Full-sequence attention (train / prefill / encoder / cross)."""
    if window == "cfg":
        window = cfg.sliding_window
    q, k, v = _qkv(cfg, p, x, xkv)
    if xkv is None and cfg.rope != "none":
        rot = rope_mod.positional(cfg, positions)
        q, k = rot(q), rot(k)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    o = attend(q, k, v, causal=causal, window=window, impl=impl)
    o = shard(o, "batch", "seq", "heads", None)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


# ------------------------------------------------------------------ prefill
def apply_attention_prefill(cfg, p, x, positions, cache_len: int, *,
                            causal=True, window="cfg", impl="auto",
                            cache_dtype=None):
    """Full-sequence attention that also emits the decode KV cache slice.

    Same math as `apply_attention`, but the (rope'd) per-layer K/V are kept
    and scattered into a zero-initialised ``cache_len``-long cache at
    slot = position % cache_len — the exact layout `apply_attention_decode`
    writes token-by-token, so decode can continue from position S without
    replaying the prompt. For ring caches (sliding window) only the last
    ``cache_len`` prompt tokens are kept (earlier ones would be masked out
    by the ring validity test anyway). Returns (out (B,S,D), cache)."""
    if window == "cfg":
        window = cfg.sliding_window
    q, k, v = _qkv(cfg, p, x)
    if cfg.rope != "none":
        rot = rope_mod.positional(cfg, positions)
        q, k = rot(q), rot(k)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    o = attend(q, k, v, causal=causal, window=window, impl=impl)
    o = shard(o, "batch", "seq", "heads", None)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])

    b, s, kvh, hd = k.shape
    t = cache_len
    dt = cache_dtype or k.dtype
    keep = min(s, t)
    rows = jnp.arange(b)[:, None]
    slots = positions[:, s - keep:] % t                    # (B, keep)
    ck = jnp.zeros((b, t, kvh, hd), dt).at[rows, slots].set(
        k[:, s - keep:].astype(dt))
    cv = jnp.zeros((b, t, kvh, hd), dt).at[rows, slots].set(
        v[:, s - keep:].astype(dt))
    return out, {"k": ck, "v": cv}


# ------------------------------------------------------------------ decode
def init_cache(cfg, batch: int, max_len: int, dtype):
    hd, kv = cfg.head_dim, cfg.n_kv_heads
    return {
        "k": jnp.zeros((batch, max_len, kv, hd), dtype),
        "v": jnp.zeros((batch, max_len, kv, hd), dtype),
    }


def cache_axes():
    return {"k": ("batch", "cache_seq", "kv_heads", None),
            "v": ("batch", "cache_seq", "kv_heads", None)}


def apply_attention_decode(cfg, p, x, cache, pos, *, window="cfg",
                           cross=False):
    """One-token decode. x (B,1,D); cache k/v (B,T,KV,hd); pos is a scalar
    int32 (all rows at the same position — the legacy batched path) or a
    (B,) int32 vector (slot-indexed serving: every cache row advances at
    its own position, so one step can serve many tenants' requests).

    cross=True: cache holds encoder K/V, no update, no causal mask.
    Sliding-window configs keep a ring-buffer cache of size==window.
    """
    if window == "cfg":
        window = cfg.sliding_window
    b = x.shape[0]
    pos_r = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))   # (B,)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    if not cross:
        k1 = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v1 = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        if "bk" in p:
            k1, v1 = k1 + p["bk"], v1 + p["bv"]
        if cfg.rope != "none":
            rot = rope_mod.positional(cfg, pos_r[:, None])
            q, k1 = rot(q), rot(k1)
        t = cache["k"].shape[1]
        slot = pos_r % t if window is not None else pos_r
        # per-row cache write (vmapped dynamic-update == scatter at slot)
        row_upd = jax.vmap(
            lambda c, u, s: jax.lax.dynamic_update_slice_in_dim(c, u, s, 0))
        cache = {"k": row_upd(cache["k"], k1.astype(cache["k"].dtype), slot),
                 "v": row_upd(cache["v"], v1.astype(cache["v"].dtype), slot)}
    k, v = cache["k"], cache["v"]
    b, t, kvh, hd = k.shape
    h = q.shape[2]
    # Grouped-query form: query head g*rep + r reads KV head g straight from
    # the cache (the head order `_repeat_kv` gives), so each step streams
    # the cache once instead of an H/KV-times repeated copy of it.
    qg = q.reshape(b, kvh, h // kvh, hd)
    # Flash-decode sharding (EXPERIMENTS.md §Perf C1): pin the cache_seq
    # sharding through scores/softmax so SPMD computes per-shard partial
    # attention and combines with tiny all-reduces instead of all-gathering
    # the whole KV cache every step.
    k = shard(k, "batch", "cache_seq", None, None)
    v = shard(v, "batch", "cache_seq", None, None)
    sc = jnp.einsum("bgrk,btgk->bgrt", qg, k).astype(jnp.float32)
    sc = sc / math.sqrt(hd)
    sc = shard(sc, "batch", None, None, "cache_seq")
    if not cross:
        kidx = jnp.arange(t)[None, :]
        if window is not None:
            # ring buffer: valid slots are those written in the last `window`
            # steps: slot index distance from current pos (per row)
            age = (pos_r[:, None] % t - kidx) % t
            valid = (age < jnp.minimum(pos_r[:, None] + 1, t))
        else:
            valid = kidx <= pos_r[:, None]
        sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    w = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
    o = jnp.einsum("bgrt,btgk->bgrk", w, v).reshape(b, 1, h, hd)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), cache
