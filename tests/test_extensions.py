"""Extra coverage: async batching invariants, cross-cache handoff, zoo pool."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import bandit, metrics
from repro.core.policies import PolicyConfig
from repro.env.llm_profiles import default_rho, paper_pool, zoo_pool
from repro.models import model as M


def test_async_batching_reuses_actions():
    """sync_every=B: the action can only change on sync rounds (Fig. 14)."""
    pool = paper_pool("sciq")
    T, B = 120, 10
    pcfg = PolicyConfig(kind="awc", k=pool.k, n=4,
                        rho=default_rho(pool, "awc", 4), delta=1 / T)
    res = bandit.simulate("c2mabv", pool, pcfg, T=T, seeds=2, sync_every=B)
    a = res.action
    for t in range(1, T):
        if t % B != 0:   # non-sync round: mask identical to previous
            assert (a[:, t] == a[:, t - 1]).all(), t


def test_zoo_pool_prices_follow_active_params():
    pool = zoo_pool()
    assert pool.k == 11
    names = list(pool.names)
    # llama3-405b must be the most expensive arm; mamba2-780m near cheapest
    assert pool.mean_cost[names.index("llama3-405b")] == pool.mean_cost.max()
    assert pool.mean_cost[names.index("mamba2-780m")] <= np.median(
        pool.mean_cost)
    # MoE active-param pricing: olmoe (1B active) far cheaper than dense 7B
    assert (pool.mean_cost[names.index("olmoe-1b-7b")]
            < pool.mean_cost[names.index("starcoder2-7b")])


def test_fill_cross_caches_shapes_and_effect():
    cfg = get_config("whisper-large-v3").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    b = 2
    frames = jax.random.normal(jax.random.PRNGKey(1),
                               (b, M.WHISPER_ENC_FRAMES, cfg.d_model))
    enc = M.encode_audio(cfg, params, frames)
    cross = M.fill_cross_caches(cfg, params, enc)
    assert cross["k"].shape == (cfg.n_layers, b, M.WHISPER_ENC_FRAMES,
                                cfg.n_kv_heads, cfg.head_dim)
    # decode with real vs zero cross cache must differ (encoder is attended)
    cache, _ = M.init_decode_caches(cfg, b, 16, jnp.float32)
    toks = jnp.ones((b, 1), jnp.int32)
    lg_zero, _ = M.decode_step(cfg, params, toks, cache, jnp.int32(0))
    lg_real, _ = M.decode_step(cfg, params, toks,
                               {**cache, "cross": cross}, jnp.int32(0))
    assert float(jnp.abs(lg_zero - lg_real).max()) > 1e-4


def test_moe_capacity_drop_actually_drops():
    """Low capacity factor must drop tokens (outputs differ from no-drop)."""
    from repro.models import moe as moe_mod
    from repro.models.layers import init_from_schema
    cfg = get_config("olmoe-1b-7b").reduced()
    p = init_from_schema(moe_mod.moe_schema(cfg), jax.random.PRNGKey(0),
                         "float32")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y_tight, _ = moe_mod.apply_moe(cfg, p, x, capacity_factor=0.25)
    y_loose, _ = moe_mod.apply_moe(cfg, p, x, capacity_factor=64.0)
    assert float(jnp.abs(y_tight - y_loose).max()) > 1e-5
