"""Per-layer blocks for the architecture families; `LAYER_KINDS` holds the
layer kinds a hybrid_moe `layer_pattern` is made of."""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_mlp, apply_norm, mlp_schema,
                                 norm_schema)
from repro.sharding import shard


# ------------------------------------------------------------------ schemas
def dense_block_schema(cfg):
    return {"ln1": norm_schema(cfg), "attn": attn_mod.attention_schema(cfg),
            "ln2": norm_schema(cfg), "mlp": mlp_schema(cfg)}


def moe_block_schema(cfg):
    s = {"ln1": norm_schema(cfg), "attn": attn_mod.attention_schema(cfg),
         "ln2": norm_schema(cfg), "moe": moe_mod.moe_schema(cfg)}
    if cfg.dense_residual:
        s["mlp"] = mlp_schema(cfg)
    return s


def ssm_block_schema(cfg):
    return {"ln1": norm_schema(cfg), "ssm": ssm_mod.ssm_schema(cfg)}


def decoder_block_schema(cfg):
    """Enc-dec decoder block: self-attn + cross-attn + mlp."""
    return {"ln1": norm_schema(cfg), "self": attn_mod.attention_schema(cfg),
            "ln2": norm_schema(cfg), "cross": attn_mod.attention_schema(cfg),
            "ln3": norm_schema(cfg), "mlp": mlp_schema(cfg)}


# ------------------------------------------------------------------ applies
def apply_dense_block(cfg, p, x, positions, *, causal=True, impl="auto",
                      window="cfg"):
    h = attn_mod.apply_attention(cfg, p["attn"], apply_norm(cfg, p["ln1"], x),
                                 positions, causal=causal, impl=impl,
                                 window=window)
    x = x + h
    x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
    return shard(x, "batch", "seq", "embed")


def apply_moe_block(cfg, p, x, positions, *, impl="auto"):
    h = attn_mod.apply_attention(cfg, p["attn"], apply_norm(cfg, p["ln1"], x),
                                 positions, causal=True, impl=impl)
    x = x + h
    xn = apply_norm(cfg, p["ln2"], x)
    y, aux = moe_mod.apply_moe_auto(cfg, p["moe"], xn)
    if cfg.dense_residual:
        y = y + apply_mlp(cfg, p["mlp"], xn)
    return shard(x + y, "batch", "seq", "embed"), aux


def apply_ssm_block(cfg, p, x):
    y = ssm_mod.apply_ssm(cfg, p["ssm"], apply_norm(cfg, p["ln1"], x))
    return shard(x + y, "batch", "seq", "embed")


def apply_decoder_block(cfg, p, x, enc_out, positions, *, impl="auto"):
    h = attn_mod.apply_attention(cfg, p["self"], apply_norm(cfg, p["ln1"], x),
                                 positions, causal=True, impl=impl)
    x = x + h
    h = attn_mod.apply_attention(cfg, p["cross"], apply_norm(cfg, p["ln2"], x),
                                 positions, causal=False, xkv=enc_out,
                                 impl=impl, window=None)
    x = x + h
    x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln3"], x))
    return shard(x, "batch", "seq", "embed")


# ------------------------------------------------------------------ prefill
# Forward-pass variants that also emit the per-layer decode cache slice —
# the prefill->cache handoff the serving engine admits into its slot cache
# (no prompt replay through decode_step).
def apply_dense_block_prefill(cfg, p, x, positions, cache_len, *,
                              impl="auto", cache_dtype=None):
    h, c = attn_mod.apply_attention_prefill(
        cfg, p["attn"], apply_norm(cfg, p["ln1"], x), positions, cache_len,
        impl=impl, cache_dtype=cache_dtype)
    x = x + h
    x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
    return shard(x, "batch", "seq", "embed"), c


def apply_moe_block_prefill(cfg, p, x, positions, cache_len, *,
                            impl="auto", cache_dtype=None):
    h, c = attn_mod.apply_attention_prefill(
        cfg, p["attn"], apply_norm(cfg, p["ln1"], x), positions, cache_len,
        impl=impl, cache_dtype=cache_dtype)
    x = x + h
    xn = apply_norm(cfg, p["ln2"], x)
    y, _ = moe_mod.apply_moe_auto(cfg, p["moe"], xn)
    if cfg.dense_residual:
        y = y + apply_mlp(cfg, p["mlp"], xn)
    return shard(x + y, "batch", "seq", "embed"), c


def apply_ssm_block_prefill(cfg, p, x, *, cache_dtype=None):
    y, c = ssm_mod.apply_ssm_prefill(cfg, p["ssm"],
                                     apply_norm(cfg, p["ln1"], x),
                                     cache_dtype=cache_dtype)
    return shard(x + y, "batch", "seq", "embed"), c


def apply_decoder_block_prefill(cfg, p, x, enc_out, positions, cache_len, *,
                                impl="auto", cache_dtype=None):
    xn = apply_norm(cfg, p["ln1"], x)
    h, c = attn_mod.apply_attention_prefill(
        cfg, p["self"], xn, positions, cache_len, causal=True, window=None,
        impl=impl, cache_dtype=cache_dtype)
    x = x + h
    h = attn_mod.apply_attention(cfg, p["cross"], apply_norm(cfg, p["ln2"], x),
                                 positions, causal=False, xkv=enc_out,
                                 impl=impl, window=None)
    x = x + h
    x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln3"], x))
    return shard(x, "batch", "seq", "embed"), c


# ------------------------------------------------------------------ decode
def apply_dense_block_decode(cfg, p, x, cache, pos, *, window="cfg"):
    xn = apply_norm(cfg, p["ln1"], x)
    h, cache = attn_mod.apply_attention_decode(cfg, p["attn"], xn, cache, pos,
                                               window=window)
    x = x + h
    x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], x))
    return x, cache


def apply_moe_block_decode(cfg, p, x, cache, pos):
    xn = apply_norm(cfg, p["ln1"], x)
    h, cache = attn_mod.apply_attention_decode(cfg, p["attn"], xn, cache, pos)
    x = x + h
    xn = apply_norm(cfg, p["ln2"], x)
    y, _ = moe_mod.apply_moe_auto(cfg, p["moe"], xn)
    if cfg.dense_residual:
        y = y + apply_mlp(cfg, p["mlp"], xn)
    return x + y, cache


def apply_ssm_block_decode(cfg, p, x, cache):
    y, cache = ssm_mod.apply_ssm_decode(cfg, p["ssm"],
                                        apply_norm(cfg, p["ln1"], x), cache)
    return x + y, cache


def apply_decoder_block_decode(cfg, p, x, self_cache, cross_cache, pos):
    xn = apply_norm(cfg, p["ln1"], x)
    h, self_cache = attn_mod.apply_attention_decode(
        cfg, p["self"], xn, self_cache, pos, window=None)
    x = x + h
    xn = apply_norm(cfg, p["ln2"], x)
    h, _ = attn_mod.apply_attention_decode(
        cfg, p["cross"], xn, cross_cache, pos, cross=True, window=None)
    x = x + h
    x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln3"], x))
    return x, self_cache


# ------------------------------------------------------------ hybrid_moe
# One entry per layer kind of a `layer_pattern`. Each layer is pre-norm
# with a residual, x + mixer(norm(x)); weights of one kind are stacked
# under `params`, decode caches under `cache`. The walker in
# `models.model` applies a kind's prefill and decode inside
# `jax.named_scope(scope)`, which names the kind's ops on the device trace.
def _mixer_schema(key, mixer_schema):
    return lambda cfg: {"ln1": norm_schema(cfg), key: mixer_schema(cfg)}


def _held_moe_apply(cfg, p, x, positions=None):
    return x + moe_mod.apply_held_moe(cfg, p["moe"],
                                      apply_norm(cfg, p["ln1"], x))


def _attn_apply(cfg, p, x, positions):
    return x + attn_mod.apply_attention(cfg, p["attn"],
                                        apply_norm(cfg, p["ln1"], x),
                                        positions)


def _attn_prefill(cfg, p, x, positions, cache_len, cache_dtype):
    h, c = attn_mod.apply_attention_prefill(
        cfg, p["attn"], apply_norm(cfg, p["ln1"], x), positions, cache_len,
        cache_dtype=cache_dtype)
    return x + h, c


def _attn_decode(cfg, p, x, cache, pos):
    h, c = attn_mod.apply_attention_decode(
        cfg, p["attn"], apply_norm(cfg, p["ln1"], x), cache, pos)
    return x + h, c


@dataclasses.dataclass(frozen=True)
class LayerKind:
    params: str                 # key of the kind's stacked weights
    cache: Optional[str]        # key of its stacked decode cache, or None
    scope: str                  # named scope of its prefill and decode
    schema: Callable            # cfg -> one layer's schema
    apply: Callable             # (cfg, p, x, positions) -> x
    prefill: Callable           # (cfg, p, x, positions, cache_len, dtype)
                                #   -> (x, cache)
    decode: Callable            # (cfg, p, x, cache, pos) -> (x, cache)
    init_cache: Optional[Callable]  # (cfg, batch, cache_len, dtype)
                                    #   -> one layer's cache
    cache_axes: Optional[Callable]  # () -> that cache's logical axes


LAYER_KINDS = {
    "M": LayerKind(
        params="mamba", cache="ssm", scope="ssm_layer",
        schema=ssm_block_schema,
        apply=lambda cfg, p, x, positions: apply_ssm_block(cfg, p, x),
        prefill=lambda cfg, p, x, positions, cache_len, dt:
            apply_ssm_block_prefill(cfg, p, x, cache_dtype=dt),
        decode=lambda cfg, p, x, cache, pos:
            apply_ssm_block_decode(cfg, p, x, cache),
        init_cache=lambda cfg, batch, cache_len, dt:
            ssm_mod.init_ssm_cache(cfg, batch, dt),
        cache_axes=ssm_mod.ssm_cache_axes),
    "E": LayerKind(
        params="moe", cache=None, scope="moe_layer",
        schema=_mixer_schema("moe", moe_mod.held_moe_schema),
        apply=_held_moe_apply,
        prefill=lambda cfg, p, x, positions, cache_len, dt:
            (_held_moe_apply(cfg, p, x), None),
        decode=lambda cfg, p, x, cache, pos: (_held_moe_apply(cfg, p, x),
                                              None),
        init_cache=None, cache_axes=None),
    "*": LayerKind(
        params="attn", cache="attn", scope="attn_layer",
        schema=_mixer_schema("attn", attn_mod.attention_schema),
        apply=_attn_apply, prefill=_attn_prefill, decode=_attn_decode,
        init_cache=attn_mod.init_cache, cache_axes=attn_mod.cache_axes),
}
