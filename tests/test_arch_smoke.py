"""Per-architecture smoke tests: every assigned arch instantiates a REDUCED
variant (2 layers, d_model<=512, <=4 experts) and runs one forward/train
step and one decode step on CPU, asserting shapes + finiteness."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import InputShape, get_config, list_archs
from repro.data.pipeline import make_batch
from repro.models import model as M
from repro.train import optimizer as opt
from repro.train.train_step import make_train_step

ARCHS = list_archs()
SMOKE_SHAPE = InputShape("smoke", seq_len=32, global_batch=2, kind="train")


@pytest.fixture(scope="module")
def params_cache():
    return {}


def get_params(cfg, params_cache):
    if cfg.name not in params_cache:
        params_cache[cfg.name] = M.init_params(cfg, jax.random.PRNGKey(0))
    return params_cache[cfg.name]


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_config_invariants(arch):
    cfg = get_config(arch).reduced()
    full = get_config(arch)
    if full.layer_pattern:          # one of each kind, a repeat to scan
        assert set(cfg.layer_pattern) == set(full.layer_pattern)
        assert cfg.n_layers == len(cfg.layer_pattern) <= 5
    else:
        assert cfg.n_layers == 2
    assert cfg.d_model <= 512
    if cfg.n_experts:
        assert cfg.n_experts <= 4
    assert cfg.family == get_config(arch).family


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch, params_cache):
    cfg = get_config(arch).reduced()
    params = get_params(cfg, params_cache)
    inputs, _ = M.input_specs(cfg, SMOKE_SHAPE, abstract=False)
    logits, aux = M.forward(cfg, params, inputs)
    b = SMOKE_SHAPE.global_batch
    assert logits.shape[0] == b and logits.shape[-1] == cfg.vocab
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step_reduces_loss_direction(arch, params_cache):
    cfg = get_config(arch).reduced()
    params = get_params(cfg, params_cache)
    ocfg = opt.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    ostate = opt.init_adamw(ocfg, params)
    step = make_train_step(cfg, ocfg, remat=False)
    inputs, _ = M.input_specs(cfg, SMOKE_SHAPE, abstract=False)
    p1, o1, m1 = step(params, ostate, inputs)
    assert np.isfinite(float(m1["loss"]))
    # params actually moved
    moved = jax.tree.reduce(
        lambda acc, pair: acc, jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), params, p1))
    diffs = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)).max()),
        params, p1))
    assert max(diffs) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_and_cache_update(arch, params_cache):
    cfg = get_config(arch).reduced()
    params = get_params(cfg, params_cache)
    b, max_len = 2, 64
    cache, axes = M.init_decode_caches(cfg, b, max_len, jnp.float32)
    assert jax.tree.structure(cache) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    toks = jnp.ones((b, 1), jnp.int32)
    logits, cache2 = M.decode_step(cfg, params, toks, cache, jnp.int32(3))
    assert logits.shape == (b, 1, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
    # cache was written (some leaf changed)
    changed = any(
        float(jnp.abs(a - b_).max()) > 0
        for a, b_ in zip(jax.tree.leaves(cache), jax.tree.leaves(cache2)))
    assert changed


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_consistency(arch, params_cache):
    """Greedy next-token from full forward == decode path after replaying
    the same prompt through the cache.

    MoE archs run with a no-drop capacity factor (prefill capacity dropping
    is a throughput/quality trade the decode path doesn't replicate)."""
    cfg = get_config(arch).reduced()
    if cfg.family == "vlm":
        pytest.skip("vlm decode starts from text-only cache; covered above")
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=64.0)
    params = get_params(cfg, params_cache)
    b, s = 2, 8
    key = jax.random.PRNGKey(7)
    toks = jax.random.randint(key, (b, s), 0, cfg.vocab, jnp.int32)
    inputs = {"tokens": toks}
    if cfg.family == "audio":
        inputs["frames"] = jnp.zeros((b, M.WHISPER_ENC_FRAMES, cfg.d_model),
                                     jnp.float32)
    logits_full, _ = M.forward(cfg, params, inputs)

    cache, _ = M.init_decode_caches(cfg, b, 32, jnp.float32)
    if cfg.family == "audio":
        # enc-dec: the decode path cross-attends to the encoder output
        enc = M.encode_audio(cfg, params, inputs["frames"])
        cache = {**cache, "cross": M.fill_cross_caches(cfg, params, enc)}
    for t in range(s):
        logits_dec, cache = M.decode_step(cfg, params, toks[:, t:t + 1],
                                          cache, jnp.int32(t))
    np.testing.assert_allclose(
        np.asarray(logits_dec[:, 0]), np.asarray(logits_full[:, -1]),
        atol=2e-3, rtol=2e-3)


def test_full_configs_match_assignment():
    """The full configs carry the exact assigned hyperparameters."""
    spec = {
        "starcoder2-7b": (32, 4608, 36, 4, 18432, 49152),
        "olmoe-1b-7b": (16, 2048, 16, 16, 1024, 50304),
        "zamba2-2.7b": (54, 2560, 32, 32, 10240, 32000),
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "qwen2-vl-72b": (80, 8192, 64, 8, 29568, 152064),
        "qwen1.5-110b": (80, 8192, 64, 8, 49152, 152064),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "llama3-405b": (126, 16384, 128, 8, 53248, 128256),
        "h2o-danube-3-4b": (24, 3840, 32, 8, 10240, 32000),
    }
    for name, (nl, dm, nh, kv, dff, vocab) in spec.items():
        cfg = get_config(name)
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.d_ff, cfg.vocab) == (nl, dm, nh, kv, dff, vocab), name
    m = get_config("mamba2-780m")
    assert (m.n_layers, m.d_model, m.vocab, m.ssm_state) == (
        48, 1536, 50280, 128)
    assert m.n_heads == 0
    o = get_config("olmoe-1b-7b")
    assert o.n_experts == 64 and o.top_k == 8
    a = get_config("arctic-480b")
    assert a.n_experts == 128 and a.top_k == 2 and a.dense_residual
