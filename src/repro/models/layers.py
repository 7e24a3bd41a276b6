"""Core layers + the parameter-schema system.

A model is described by a *schema*: a pytree whose leaves are ``ParamSpec``s
(shape, logical sharding axes, init). From one schema we derive:
  - materialized params        (init_from_schema)
  - abstract ShapeDtypeStructs (abstract_from_schema; used by the dry-run)
  - NamedShardings             (via repro.sharding.tree_shardings)
  - exact param counts         (count_from_schema)
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"     # normal | zeros | ones
    std: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, *, shape, std, dtype):
    # jitted so the float32 draw fuses into the cast: eagerly, a bf16 leaf
    # of N elements would hold 8N bytes of float32 temporaries on device
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_from_schema(schema, key, dtype_override: Optional[str] = None):
    leaves, treedef = jax.tree.flatten(schema, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for spec, k in zip(leaves, keys):
        dt = jnp.dtype(dtype_override or spec.dtype)
        if spec.init == "zeros":
            arr = jnp.zeros(spec.shape, dt)
        elif spec.init == "ones":
            arr = jnp.ones(spec.shape, dt)
        else:
            arr = _normal(k, shape=spec.shape, std=spec.std, dtype=dt)
        out.append(arr)
    return jax.tree.unflatten(treedef, out)


def abstract_from_schema(schema, dtype_override: Optional[str] = None):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(dtype_override or s.dtype)),
        schema, is_leaf=is_spec)


def axes_from_schema(schema):
    return jax.tree.map(lambda s: s.axes, schema, is_leaf=is_spec)


def count_from_schema(schema) -> int:
    return sum(math.prod(s.shape)
               for s in jax.tree.leaves(schema, is_leaf=is_spec))


def stack_layers(schema, n_layers: int):
    """Add a leading scanned `layers` dim to every spec in a per-layer schema."""
    return jax.tree.map(
        lambda s: ParamSpec((n_layers,) + s.shape, ("layers",) + s.axes,
                            s.init, s.std, s.dtype),
        schema, is_leaf=is_spec)


# ----------------------------------------------------------------- norms
def rmsnorm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layernorm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm_schema(cfg, d: Optional[int] = None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": ParamSpec((d,), (None,), "ones"),
                "bias": ParamSpec((d,), (None,), "zeros")}
    return {"scale": ParamSpec((d,), (None,), "ones")}


def apply_norm(cfg, p, x):
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


# ----------------------------------------------------------------- mlp
def mlp_schema(cfg, d_model: Optional[int] = None, d_ff: Optional[int] = None):
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    std_in = 0.02
    std_out = 0.02 / math.sqrt(2 * max(cfg.n_layers, 1))
    if cfg.mlp_act == "swiglu":
        return {
            "wi_gate": ParamSpec((d, f), ("embed_fsdp", "mlp"), std=std_in),
            "wi_up": ParamSpec((d, f), ("embed_fsdp", "mlp"), std=std_in),
            "wo": ParamSpec((f, d), ("mlp", "embed_fsdp"), std=std_out),
        }
    return {
        "wi": ParamSpec((d, f), ("embed_fsdp", "mlp"), std=std_in),
        "bi": ParamSpec((f,), ("mlp",), "zeros"),
        "wo": ParamSpec((f, d), ("mlp", "embed_fsdp"), std=std_out),
        "bo": ParamSpec((d,), (None,), "zeros"),
    }


def apply_mlp(cfg, p, x):
    from repro.sharding import shard
    if cfg.mlp_act == "swiglu":
        g = jnp.einsum("...d,df->...f", x, p["wi_gate"])
        u = jnp.einsum("...d,df->...f", x, p["wi_up"])
        h = jax.nn.silu(g) * u
        h = shard(h, "batch", "seq", "mlp") if h.ndim == 3 else h
        return jnp.einsum("...f,fd->...d", h, p["wo"])
    h = jnp.einsum("...d,df->...f", x, p["wi"]) + p["bi"]
    h = jax.nn.gelu(h)
    h = shard(h, "batch", "seq", "mlp") if h.ndim == 3 else h
    return jnp.einsum("...f,fd->...d", h, p["wo"]) + p["bo"]


# ----------------------------------------------------------------- embeddings
def embed_schema(cfg):
    s = {"tok": ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "embed_fsdp"),
                          std=0.02)}
    if not cfg.tie_embeddings:
        s["unembed"] = ParamSpec((cfg.d_model, cfg.vocab),
                                 ("embed_fsdp", "vocab"), std=0.02)
    return s


def embed_tokens(cfg, p, tokens):
    return jnp.take(p["tok"], tokens, axis=0)


def unembed(cfg, p, x):
    w = p["tok"].T if cfg.tie_embeddings else p["unembed"]
    return jnp.einsum("...d,dv->...v", x, w)
