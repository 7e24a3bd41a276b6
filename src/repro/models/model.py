"""Model assembly: schema, forward (train/prefill), decode step, input specs.

Layers are *stacked* (leading ``layers`` dim) and iterated with
``jax.lax.scan`` so 80–126-layer configs compile quickly; hybrid models scan
over groups of ``shared_attn_period`` Mamba2 layers with the weight-shared
attention block applied once per group (no lax.cond — honest cost analysis).
hybrid_moe models walk their ``layer_pattern`` (see `walk_pattern`): one
stack and one cache per layer kind (`blocks.LAYER_KINDS`), the longest
repeated stretch scanned over its unit.
"""
from __future__ import annotations

import contextlib
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, InputShape
from repro.models import attention as attn_mod
from repro.models import blocks
from repro.models import ssm as ssm_mod
from repro.models.layers import (ParamSpec, abstract_from_schema,
                                 apply_norm, axes_from_schema,
                                 count_from_schema, embed_schema,
                                 embed_tokens, init_from_schema, is_spec,
                                 norm_schema, stack_layers, unembed)
from repro.sharding import shard

WHISPER_ENC_FRAMES = 1500     # 30 s of audio at 50 Hz after the conv stub
VLM_VISION_FRACTION = 8       # 1/8 of the sequence is patch embeddings
AUX_LOSS_WEIGHT = 0.01


# ===================================================================== schema
def model_schema(cfg: ArchConfig):
    s: Dict[str, Any] = {"embed": embed_schema(cfg)}
    if cfg.family in ("dense", "vlm"):
        s["layers"] = stack_layers(blocks.dense_block_schema(cfg), cfg.n_layers)
    elif cfg.family == "moe":
        s["layers"] = stack_layers(blocks.moe_block_schema(cfg), cfg.n_layers)
    elif cfg.family == "ssm":
        s["layers"] = stack_layers(blocks.ssm_block_schema(cfg), cfg.n_layers)
    elif cfg.family == "hybrid":
        assert cfg.n_layers % cfg.shared_attn_period == 0
        s["layers"] = stack_layers(blocks.ssm_block_schema(cfg), cfg.n_layers)
        s["shared"] = blocks.dense_block_schema(cfg)   # weight-shared attn block
    elif cfg.family == "hybrid_moe":
        for ch in sorted(set(cfg.layer_pattern)):
            kind = blocks.LAYER_KINDS[ch]
            s[kind.params] = stack_layers(kind.schema(cfg),
                                          cfg.layer_pattern.count(ch))
    elif cfg.family == "audio":
        s["enc_layers"] = stack_layers(blocks.dense_block_schema(cfg),
                                       cfg.n_enc_layers)
        s["enc_lnf"] = norm_schema(cfg)
        s["layers"] = stack_layers(blocks.decoder_block_schema(cfg),
                                   cfg.n_layers)
    else:
        raise ValueError(cfg.family)
    if cfg.family == "vlm":
        s["vision_proj"] = {
            "w": ParamSpec((cfg.d_model, cfg.d_model),
                           ("embed_fsdp", None), std=0.02)}
    s["lnf"] = norm_schema(cfg)
    return s


def init_params(cfg: ArchConfig, key, dtype: Optional[str] = None):
    return init_from_schema(model_schema(cfg), key, dtype or cfg.dtype)


def abstract_params(cfg: ArchConfig, dtype: Optional[str] = None):
    return abstract_from_schema(model_schema(cfg), dtype or cfg.dtype)


def param_axes(cfg: ArchConfig):
    return axes_from_schema(model_schema(cfg))


def param_count(cfg: ArchConfig, experts_only: bool = False) -> int:
    schema = model_schema(cfg)
    if experts_only:
        if not cfg.n_experts:
            return 0
        if cfg.family == "hybrid_moe":
            moe = schema[blocks.LAYER_KINDS["E"].params]["moe"]
            sub = {k: moe[k] for k in ("up", "down")}
        else:
            moe = schema["layers"]["moe"]
            sub = {k: moe[k] for k in ("wi_gate", "wi_up", "wo")}
        return count_from_schema(sub)
    return count_from_schema(schema)


# ===================================================================== utils
def sinusoid(seq: int, d: int, offset=0):
    pos = jnp.arange(seq)[:, None] + offset
    i = jnp.arange(d // 2)[None, :]
    ang = pos / jnp.power(10_000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# When True, layer scans are fully unrolled. Structural-cost probes use this
# (XLA cost_analysis counts a scan body once regardless of trip count);
# production compiles keep scans rolled for compile time.
_UNROLL_SCANS = False


@contextlib.contextmanager
def unroll_scans(enable: bool = True):
    global _UNROLL_SCANS
    prev = _UNROLL_SCANS
    _UNROLL_SCANS = enable
    try:
        yield
    finally:
        _UNROLL_SCANS = prev


def _scan(body, carry, xs, length=None):
    return jax.lax.scan(body, carry, xs, length=length,
                        unroll=True if _UNROLL_SCANS else 1)


def _scan_blocks(body, x, stacked, n: int, remat: bool):
    fn = jax.checkpoint(body) if remat else body

    def step(carry, layer_params):
        return fn(carry, layer_params), None

    x, _ = _scan(step, x, stacked, length=n)
    return x


def _group_stacked(tree, groups: int):
    return jax.tree.map(
        lambda a: a.reshape((groups, a.shape[0] // groups) + a.shape[1:]), tree)


# =============================================================== pattern
def pattern_plan(pattern: str):
    """[(unit, reps)] covering ``pattern`` in order: the stretch that
    repeats one unit and saves the most layers, (unit, reps >= 2), with what
    lies before and after it as units of one repeat."""
    n = len(pattern)
    best = (0, 0, n, 1)                       # saved, offset, unit, reps
    for off in range(n):
        for size in range(1, (n - off) // 2 + 1):
            unit, reps = pattern[off:off + size], 1
            while pattern[off + reps * size:off + (reps + 1) * size] == unit:
                reps += 1
            if (reps - 1) * size > best[0]:
                best = ((reps - 1) * size, off, size, reps)
    _, off, size, reps = best
    plan = [(pattern[:off], 1)] if off else []
    plan.append((pattern[off:off + size], reps))
    if off + size * reps < n:
        plan.append((pattern[off + size * reps:], 1))
    return plan


# the scope wrapper of a layer kind's ops (a seam for tests)
_scope = jax.named_scope


def walk_pattern(cfg: ArchConfig, x, params, caches, phase: str, *args):
    """Apply a hybrid_moe model's layers in ``layer_pattern`` order.

    ``phase`` is a `blocks.LayerKind` field: "apply" (no caches), "prefill"
    or "decode"; ``args`` follow x in its signature. Layer i of kind k uses
    row j of k's stacked weights and caches (j = kind-k layers before i);
    caches ({kind cache key: stacked}) are read and written in place at j.
    The longest repeated stretch (`pattern_plan`) is one `lax.scan` over its
    unit, so a pattern of any length traces each kind a few times.
    Returns (x, caches)."""
    done = {ch: 0 for ch in blocks.LAYER_KINDS}

    def layer(ch, j, x, caches):
        kind = blocks.LAYER_KINDS[ch]
        take = (lambda a: a[j]) if isinstance(j, int) else \
            (lambda a: jax.lax.dynamic_index_in_dim(a, j, keepdims=False))
        p = jax.tree.map(take, params[kind.params])
        fn = getattr(kind, phase)
        with _scope(kind.scope):
            if phase == "apply":
                return fn(cfg, p, x, *args), caches
            if phase == "decode":
                c = jax.tree.map(take, caches[kind.cache]) if kind.cache \
                    else None
                x, c = fn(cfg, p, x, c, *args)
            else:
                x, c = fn(cfg, p, x, *args)
        if kind.cache:
            caches = dict(caches)
            caches[kind.cache] = jax.tree.map(
                lambda big, new: jax.lax.dynamic_update_index_in_dim(
                    big, new.astype(big.dtype), j, 0),
                caches[kind.cache], c)
        return x, caches

    for unit, reps in pattern_plan(cfg.layer_pattern):
        first = dict(done)
        per = {ch: unit.count(ch) for ch in blocks.LAYER_KINDS}

        def body(carry, rep, unit=unit, first=first, per=per):
            x, caches = carry
            seen = {ch: 0 for ch in blocks.LAYER_KINDS}
            for ch in unit:
                j = first[ch] + rep * per[ch] + seen[ch]
                seen[ch] += 1
                x, caches = layer(ch, j, x, caches)
            return (x, caches), None

        if reps == 1:
            (x, caches), _ = body((x, caches), 0)
        else:
            (x, caches), _ = _scan(body, (x, caches), jnp.arange(reps))
        for ch in blocks.LAYER_KINDS:
            done[ch] += per[ch] * reps
    return x, caches


# ===================================================================== forward
def forward(cfg: ArchConfig, params, inputs: Dict[str, Any], *,
            impl: str = "auto", remat: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward. Returns (logits, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "audio":
        return _forward_audio(cfg, params, inputs, impl=impl, remat=remat)

    if cfg.family == "vlm":
        vis = jnp.einsum("bsd,de->bse", inputs["vision_embeds"],
                         params["vision_proj"]["w"])
        txt = embed_tokens(cfg, params["embed"], inputs["tokens"])
        x = jnp.concatenate([vis.astype(txt.dtype), txt], axis=1)
    else:
        x = embed_tokens(cfg, params["embed"], inputs["tokens"])
    b, s, _ = x.shape
    x = shard(x, "batch", "seq", "embed")
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    if cfg.family in ("dense", "vlm"):
        def body(h, p_l):
            return blocks.apply_dense_block(cfg, p_l, h, positions, impl=impl)
        x = _scan_blocks(body, x, params["layers"], cfg.n_layers, remat)
    elif cfg.family == "moe":
        def body(carry, p_l):
            h, a = carry
            h, a_l = blocks.apply_moe_block(cfg, p_l, h, positions, impl=impl)
            return (h, a + a_l)
        x, aux = _scan_blocks(body, (x, aux), params["layers"],
                              cfg.n_layers, remat)
    elif cfg.family == "ssm":
        def body(h, p_l):
            return blocks.apply_ssm_block(cfg, p_l, h)
        x = _scan_blocks(body, x, params["layers"], cfg.n_layers, remat)
    elif cfg.family == "hybrid":
        period = cfg.shared_attn_period
        groups = cfg.n_layers // period
        grouped = _group_stacked(params["layers"], groups)
        shared = params["shared"]

        def group_body(h, p_g):
            h = blocks.apply_dense_block(cfg, shared, h, positions, impl=impl)

            def inner(h2, p_l):
                return blocks.apply_ssm_block(cfg, p_l, h2)
            return _scan_blocks(inner, h, p_g, period, False)

        x = _scan_blocks(group_body, x, grouped, groups, remat)
    elif cfg.family == "hybrid_moe":
        x, _ = walk_pattern(cfg, x, params, {}, "apply", positions)

    x = apply_norm(cfg, params["lnf"], x)
    logits = unembed(cfg, params["embed"], x)
    return logits, aux


def encode_audio(cfg, params, frames, *, impl="auto", remat=False):
    """Run the (bidirectional) audio encoder over stub frame embeddings."""
    b, s_enc, _ = frames.shape
    enc = frames + sinusoid(s_enc, cfg.d_model).astype(frames.dtype)[None]
    enc = shard(enc, "batch", "seq", "embed")
    pos_enc = jnp.broadcast_to(jnp.arange(s_enc)[None], (b, s_enc))

    def enc_body(h, p_l):
        return blocks.apply_dense_block(cfg, p_l, h, pos_enc, causal=False,
                                        impl=impl, window=None)
    enc = _scan_blocks(enc_body, enc, params["enc_layers"],
                       cfg.n_enc_layers, remat)
    return apply_norm(cfg, params["enc_lnf"], enc)


def fill_cross_caches(cfg, params, enc):
    """Cross-attention K/V cache from encoder output (the enc-dec prefill
    handoff the serving path uses before decode_step)."""
    def one_layer(p_l):
        k = jnp.einsum("bsd,dhk->bshk", enc, p_l["cross"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc, p_l["cross"]["wv"])
        if "bk" in p_l["cross"]:
            k = k + p_l["cross"]["bk"]
            v = v + p_l["cross"]["bv"]
        return {"k": k.astype(enc.dtype), "v": v.astype(enc.dtype)}

    return jax.vmap(one_layer)(params["layers"])


def _forward_audio(cfg, params, inputs, *, impl="auto", remat=False):
    frames = inputs["frames"]                        # (B, S_enc, D) stub embeds
    b = frames.shape[0]
    enc = encode_audio(cfg, params, frames, impl=impl, remat=remat)

    tokens = inputs["tokens"]
    b, s_dec = tokens.shape
    x = embed_tokens(cfg, params["embed"], tokens)
    x = x + sinusoid(s_dec, cfg.d_model).astype(x.dtype)[None]
    x = shard(x, "batch", "seq", "embed")
    pos_dec = jnp.broadcast_to(jnp.arange(s_dec)[None], (b, s_dec))

    def dec_body(h, p_l):
        return blocks.apply_decoder_block(cfg, p_l, h, enc, pos_dec, impl=impl)
    x = _scan_blocks(dec_body, x, params["layers"], cfg.n_layers, remat)
    x = apply_norm(cfg, params["lnf"], x)
    return unembed(cfg, params["embed"], x), jnp.zeros((), jnp.float32)


# ===================================================================== prefill
def prefill_len(cfg: ArchConfig, prompt_len: int) -> int:
    """Number of cache positions a prompt of ``prompt_len`` tokens occupies
    after `prefill` (VLM prompts carry a vision-patch prefix)."""
    if cfg.family == "vlm":
        return prompt_len + max(prompt_len // VLM_VISION_FRACTION, 1)
    return prompt_len


def prefill(cfg: ArchConfig, params, inputs: Dict[str, Any], max_len: int, *,
            impl: str = "auto", cache_dtype=None
            ) -> Tuple[jnp.ndarray, Any]:
    """Prompt forward that also emits the decode-cache pytree.

    The prefill half of the serving engine's prefill/decode split: one
    full-sequence forward over the prompt whose per-layer K/V (attention),
    final SSD state + conv window (Mamba2) and cross-attention K/V (enc-dec)
    are written directly into a fresh ``max_len``-long decode cache — the
    same pytree `init_decode_caches` allocates and `decode_step` advances,
    so generation continues from position `prefill_len(cfg, S)` without
    replaying the prompt through the decode path.

    Returns (last_logits (B, V) — the next-token logits, cache).
    """
    w = cfg.sliding_window
    attn_len = min(max_len, w) if w else max_len
    if cfg.family == "audio":
        return _prefill_audio(cfg, params, inputs, attn_len, impl=impl,
                              cache_dtype=cache_dtype)

    if cfg.family == "vlm":
        vis = jnp.einsum("bsd,de->bse", inputs["vision_embeds"],
                         params["vision_proj"]["w"])
        txt = embed_tokens(cfg, params["embed"], inputs["tokens"])
        x = jnp.concatenate([vis.astype(txt.dtype), txt], axis=1)
    else:
        x = embed_tokens(cfg, params["embed"], inputs["tokens"])
    b, s, _ = x.shape
    x = shard(x, "batch", "seq", "embed")
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    cdt = cache_dtype or x.dtype

    def scan_cache(body, h, stacked, n):
        def step(carry, p_l):
            return body(carry, p_l)
        return _scan(step, h, stacked, length=n)

    if cfg.family in ("dense", "vlm"):
        def body(h, p_l):
            return blocks.apply_dense_block_prefill(
                cfg, p_l, h, positions, attn_len, impl=impl, cache_dtype=cdt)
        x, attn_c = scan_cache(body, x, params["layers"], cfg.n_layers)
        cache = {"attn": attn_c}
    elif cfg.family == "moe":
        def body(h, p_l):
            return blocks.apply_moe_block_prefill(
                cfg, p_l, h, positions, attn_len, impl=impl, cache_dtype=cdt)
        x, attn_c = scan_cache(body, x, params["layers"], cfg.n_layers)
        cache = {"attn": attn_c}
    elif cfg.family == "ssm":
        def body(h, p_l):
            return blocks.apply_ssm_block_prefill(cfg, p_l, h,
                                                  cache_dtype=cdt)
        x, ssm_c = scan_cache(body, x, params["layers"], cfg.n_layers)
        cache = {"ssm": ssm_c}
    elif cfg.family == "hybrid":
        period = cfg.shared_attn_period
        groups = cfg.n_layers // period
        grouped = _group_stacked(params["layers"], groups)
        shared = params["shared"]

        def group_body(h, p_g):
            h, ac = blocks.apply_dense_block_prefill(
                cfg, shared, h, positions, attn_len, impl=impl,
                cache_dtype=cdt)

            def inner(h2, p_l):
                return blocks.apply_ssm_block_prefill(cfg, p_l, h2,
                                                      cache_dtype=cdt)
            h, c_g = scan_cache(inner, h, p_g, period)
            return h, (c_g, ac)

        x, (ssm_c, attn_c) = scan_cache(group_body, x, grouped, groups)
        cache = {"ssm": jax.tree.map(
                    lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), ssm_c),
                 "shared_attn": attn_c}
    elif cfg.family == "hybrid_moe":
        cache, _ = init_decode_caches(cfg, b, max_len, cdt)
        x, cache = walk_pattern(cfg, x, params, cache, "prefill", positions,
                                attn_len, cdt)
    else:
        raise ValueError(cfg.family)

    x = apply_norm(cfg, params["lnf"], x[:, -1:])
    return unembed(cfg, params["embed"], x)[:, 0], cache


def _prefill_audio(cfg, params, inputs, attn_len, *, impl="auto",
                   cache_dtype=None):
    frames = inputs["frames"]
    enc = encode_audio(cfg, params, frames, impl=impl)
    cross = fill_cross_caches(cfg, params, enc)

    tokens = inputs["tokens"]
    b, s = tokens.shape
    x = embed_tokens(cfg, params["embed"], tokens)
    x = x + sinusoid(s, cfg.d_model).astype(x.dtype)[None]
    x = shard(x, "batch", "seq", "embed")
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    cdt = cache_dtype or x.dtype

    def body(h, p_l):
        return blocks.apply_decoder_block_prefill(
            cfg, p_l, h, enc, positions, attn_len, impl=impl, cache_dtype=cdt)

    def step(carry, p_l):
        return body(carry, p_l)
    x, self_c = _scan(step, x, params["layers"], length=cfg.n_layers)
    cache = {"self": self_c,
             "cross": jax.tree.map(lambda a: a.astype(cdt), cross)}
    x = apply_norm(cfg, params["lnf"], x[:, -1:])
    return unembed(cfg, params["embed"], x)[:, 0], cache


# ===================================================================== loss
def loss_fn(cfg: ArchConfig, params, batch, *, impl="auto", remat=False):
    logits, aux = forward(cfg, params, batch, impl=impl, remat=remat)
    labels = batch["labels"]
    if cfg.family == "vlm":  # logits cover vision prefix + text; loss on text
        logits = logits[:, -labels.shape[1]:]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    loss = nll.sum() / jnp.maximum(mask.sum(), 1.0)
    metrics = {"nll": loss, "aux": aux}
    return loss + AUX_LOSS_WEIGHT * aux, metrics


# ===================================================================== decode
def init_decode_caches(cfg: ArchConfig, batch: int, max_len: int,
                       dtype=jnp.bfloat16, abstract: bool = False):
    """Per-family cache pytree (+ matching logical axes pytree)."""
    w = cfg.sliding_window
    attn_len = min(max_len, w) if w else max_len

    def stackz(sub, n):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype)
            if abstract else jnp.zeros((n,) + a.shape, a.dtype), sub)

    def shape_only(fn):
        """Never allocate the per-layer template (it can be GBs)."""
        return jax.eval_shape(fn)

    if cfg.family in ("dense", "vlm", "moe"):
        one = shape_only(lambda: attn_mod.init_cache(
            cfg, batch, attn_len, dtype))
        cache = {"attn": stackz(one, cfg.n_layers)}
        axes = {"attn": _with_layers(attn_mod.cache_axes())}
    elif cfg.family == "ssm":
        one = shape_only(lambda: ssm_mod.init_ssm_cache(cfg, batch, dtype))
        cache = {"ssm": stackz(one, cfg.n_layers)}
        axes = {"ssm": _with_layers(ssm_mod.ssm_cache_axes())}
    elif cfg.family == "hybrid":
        one = shape_only(lambda: ssm_mod.init_ssm_cache(cfg, batch, dtype))
        groups = cfg.n_layers // cfg.shared_attn_period
        attn_one = shape_only(lambda: attn_mod.init_cache(
            cfg, batch, attn_len, dtype))
        cache = {"ssm": stackz(one, cfg.n_layers),
                 "shared_attn": stackz(attn_one, groups)}
        axes = {"ssm": _with_layers(ssm_mod.ssm_cache_axes()),
                "shared_attn": _with_layers(attn_mod.cache_axes())}
    elif cfg.family == "hybrid_moe":
        cache, axes = {}, {}
        for ch in sorted(set(cfg.layer_pattern)):
            kind = blocks.LAYER_KINDS[ch]
            if kind.cache:
                one = shape_only(lambda kind=kind: kind.init_cache(
                    cfg, batch, attn_len, dtype))
                cache[kind.cache] = stackz(one, cfg.layer_pattern.count(ch))
                axes[kind.cache] = _with_layers(kind.cache_axes())
    elif cfg.family == "audio":
        self_one = shape_only(lambda: attn_mod.init_cache(
            cfg, batch, attn_len, dtype))
        cross_one = shape_only(lambda: attn_mod.init_cache(
            cfg, batch, WHISPER_ENC_FRAMES, dtype))
        cache = {"self": stackz(self_one, cfg.n_layers),
                 "cross": stackz(cross_one, cfg.n_layers)}
        axes = {"self": _with_layers(attn_mod.cache_axes()),
                "cross": _with_layers(attn_mod.cache_axes())}
    else:
        raise ValueError(cfg.family)
    return cache, axes


def _with_layers(axes_tree):
    return jax.tree.map(lambda t: ("layers",) + t, axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


def decode_step(cfg: ArchConfig, params, tokens, cache, pos):
    """One decode step. tokens (B,1) int32; pos scalar int32 OR (B,) int32.

    Per-row ``pos`` is the slot-cache layout: every row advances at its own
    position, so one jitted step can serve slots admitted at different times
    (continuous batching). Returns (logits (B,1,V), new_cache).
    """
    x = embed_tokens(cfg, params["embed"], tokens)
    if cfg.family == "audio":
        pos_r = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                                 (tokens.shape[0],))
        d = cfg.d_model
        i = jnp.arange(d // 2)[None, :]
        ang = pos_r[:, None] / jnp.power(10_000.0, 2 * i / d)
        sin = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
        x = x + sin[:, None].astype(x.dtype)

    if cfg.family in ("dense", "vlm"):
        def body(h, xs):
            p_l, c_l = xs
            h, c = blocks.apply_dense_block_decode(cfg, p_l, h, c_l, pos)
            return h, c
        x, new = _scan(body, x, (params["layers"], cache["attn"]))
        cache = {"attn": new}
    elif cfg.family == "moe":
        def body(h, xs):
            p_l, c_l = xs
            h, c = blocks.apply_moe_block_decode(cfg, p_l, h, c_l, pos)
            return h, c
        x, new = _scan(body, x, (params["layers"], cache["attn"]))
        cache = {"attn": new}
    elif cfg.family == "ssm":
        def body(h, xs):
            p_l, c_l = xs
            h, c = blocks.apply_ssm_block_decode(cfg, p_l, h, c_l)
            return h, c
        x, new = _scan(body, x, (params["layers"], cache["ssm"]))
        cache = {"ssm": new}
    elif cfg.family == "hybrid":
        period = cfg.shared_attn_period
        groups = cfg.n_layers // period
        grouped_p = _group_stacked(params["layers"], groups)
        grouped_c = _group_stacked(cache["ssm"], groups)
        shared = params["shared"]

        def group_body(h, xs):
            p_g, c_g, ac = xs
            xn = apply_norm(cfg, shared["ln1"], h)
            a, ac = attn_mod.apply_attention_decode(cfg, shared["attn"], xn,
                                                    ac, pos)
            h = h + a
            from repro.models.layers import apply_mlp
            h = h + apply_mlp(cfg, shared["mlp"],
                              apply_norm(cfg, shared["ln2"], h))

            def inner(h2, xs2):
                p_l, c_l = xs2
                h2, c = blocks.apply_ssm_block_decode(cfg, p_l, h2, c_l)
                return h2, c
            h, c_new = _scan(inner, h, (p_g, c_g))
            return h, (c_new, ac)

        x, (new_ssm, new_attn) = _scan(
            group_body, x, (grouped_p, grouped_c, cache["shared_attn"]))
        cache = {"ssm": jax.tree.map(
                    lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), new_ssm),
                 "shared_attn": new_attn}
    elif cfg.family == "audio":
        def body(h, xs):
            p_l, sc, cc = xs
            h, sc = blocks.apply_decoder_block_decode(cfg, p_l, h, sc, cc, pos)
            return h, sc
        x, new = _scan(body, x,
                       (params["layers"], cache["self"], cache["cross"]))
        cache = {"self": new, "cross": cache["cross"]}
    elif cfg.family == "hybrid_moe":
        x, cache = walk_pattern(cfg, x, params, cache, "decode", pos)

    x = apply_norm(cfg, params["lnf"], x)
    return unembed(cfg, params["embed"], x), cache


# ===================================================================== inputs
def input_specs(cfg: ArchConfig, shape: InputShape, *,
                abstract: bool = True, seed: int = 0):
    """Model inputs for a given (arch, shape): ShapeDtypeStructs (dry-run) or
    concrete random arrays (smoke tests). Returns (inputs, logical_axes)."""
    b, s = shape.global_batch, shape.seq_len
    f32 = jnp.dtype(cfg.dtype)
    i32 = jnp.int32

    def mk(shp, dt, maxval=None):
        if abstract:
            return jax.ShapeDtypeStruct(shp, dt)
        key = jax.random.PRNGKey(seed)
        if dt == i32:
            return jax.random.randint(key, shp, 0, maxval or cfg.vocab, i32)
        return jax.random.normal(key, shp, jnp.float32).astype(dt) * 0.02

    kind = shape.kind
    if kind == "decode":
        inputs = {"tokens": mk((b, 1), i32)}
        axes = {"tokens": ("batch", None)}
        return inputs, axes

    if cfg.family == "vlm":
        s_vis = s // VLM_VISION_FRACTION
        s_txt = s - s_vis
        inputs = {"tokens": mk((b, s_txt), i32),
                  "vision_embeds": mk((b, s_vis, cfg.d_model), f32)}
        axes = {"tokens": ("batch", "seq"),
                "vision_embeds": ("batch", "seq", "embed")}
        if kind == "train":
            inputs["labels"] = mk((b, s_txt), i32)
            axes["labels"] = ("batch", "seq")
    elif cfg.family == "audio":
        inputs = {"frames": mk((b, WHISPER_ENC_FRAMES, cfg.d_model), f32),
                  "tokens": mk((b, s), i32)}
        axes = {"frames": ("batch", "seq", "embed"),
                "tokens": ("batch", "seq")}
        if kind == "train":
            inputs["labels"] = mk((b, s), i32)
            axes["labels"] = ("batch", "seq")
    else:
        inputs = {"tokens": mk((b, s), i32)}
        axes = {"tokens": ("batch", "seq")}
        if kind == "train":
            inputs["labels"] = mk((b, s), i32)
            axes["labels"] = ("batch", "seq")
    return inputs, axes
