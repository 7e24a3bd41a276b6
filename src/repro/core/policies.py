"""Online selection policies: C2MAB-V (the paper) + §6 baselines.

Every policy is a pair of pure functions over a flat stats dict so the whole
simulation jit/scan/vmaps:

    act(stats, key, t)                      -> action mask (K,) in {0,1}
    update(stats, feedback, rewards, costs) -> stats        (shared, Eq. 6)

Baselines follow §6: CUCB (constraint-blind), Thompson Sampling,
ε-Greedy (ε_t = min(1, 2√K/√t)), Fixed-arm (Always-GPT-4 / Always-cheap),
OfflineFixed (pre-learned set applied online), and C2MAB-V-Direct
(App. E.3 Eq. 48 — exact discrete argmax over the enumerated action matrix;
jit-able because the enumeration is a static (M,K) matrix).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import confidence as cb
from repro.core import relax
from repro.core import rewards as R
from repro.core import rounding


@dataclasses.dataclass(frozen=True)
class PolicyConfig:
    kind: str                  # reward model: awc | suc | aic
    k: int
    n: int
    rho: float
    delta: float = 0.01        # paper sets δ=1/T in the analysis
    alpha_mu: float = 0.3
    alpha_c: float = 0.05


Act = Callable[..., jnp.ndarray]


def _pad_to_n(mask, scores, n: int, equality: bool):
    """Ensure |S| == n when the matroid is a base (SUC/AIC)."""
    if not equality:
        return mask
    return rounding.pad_to_n_dyn(mask, scores, n, True)


# ===================================================================== C2MAB-V
def c2mabv(cfg: PolicyConfig) -> Act:
    equality = R.equality_constrained(cfg.kind)

    def act(stats, key, t):
        mu_bar = cb.reward_ucb(stats, t, cfg.delta, cfg.alpha_mu)
        c_low = cb.cost_lcb(stats, t, cfg.delta, cfg.alpha_c)
        z = relax.solve_relaxed(cfg.kind, mu_bar, c_low, n=cfg.n, rho=cfg.rho)
        mask = rounding.pairwise_round(z, key)
        return _pad_to_n(mask, mu_bar, cfg.n, equality)

    return act


def c2mabv_direct(cfg: PolicyConfig) -> Act:
    """App. E.3: exact discrete argmax (Eq. 48) — exponential in K."""
    actions = jnp.asarray(relax.enumerate_actions(
        cfg.k, cfg.n, R.equality_constrained(cfg.kind)), jnp.float32)

    def act(stats, key, t):
        mu_bar = cb.reward_ucb(stats, t, cfg.delta, cfg.alpha_mu)
        c_low = cb.cost_lcb(stats, t, cfg.delta, cfg.alpha_c)
        vals = R.set_reward(cfg.kind, actions, mu_bar)
        cost = relax.budget_cost(c_low, actions)
        feas = cost <= cfg.rho
        vals = jnp.where(feas, vals, -jnp.inf)
        any_feas = feas.any()
        best = jnp.where(any_feas, jnp.argmax(vals), jnp.argmin(cost))
        return actions[best]

    return act


# ===================================================================== baselines
def cucb(cfg: PolicyConfig) -> Act:
    """CUCB [Wang & Chen]: UCB means, cost constraint ignored.

    Top-N by UCB is feasible for both matroid types (|S| = N)."""

    def act(stats, key, t):
        mu_bar = cb.reward_ucb(stats, t, cfg.delta, 1.0)
        order = jnp.argsort(-mu_bar)
        ranks = jnp.argsort(order)
        return (ranks < cfg.n).astype(jnp.float32)

    return act


def thompson(cfg: PolicyConfig) -> Act:
    """Beta-posterior TS on rewards (cost-blind, as in §6)."""

    def act(stats, key, t):
        s = stats["mu_hat"] * stats["t_mu"]          # pseudo-successes
        f = stats["t_mu"] - s
        sample = jax.random.beta(key, 1.0 + s, 1.0 + f)
        order = jnp.argsort(-sample)
        ranks = jnp.argsort(order)
        return (ranks < cfg.n).astype(jnp.float32)

    return act


def epsilon_greedy(cfg: PolicyConfig) -> Act:
    """ε_t = min(1, 2√K/√t); explore: uniform N-subset, exploit: top-N μ̂."""

    def act(stats, key, t):
        k1, k2, k3 = jax.random.split(key, 3)
        eps = jnp.minimum(1.0, 2.0 * jnp.sqrt(cfg.k)
                          / jnp.sqrt(jnp.maximum(t.astype(jnp.float32), 1.0)))
        explore = jax.random.uniform(k1) < eps
        rand_scores = jax.random.uniform(k2, (cfg.k,))
        scores = jnp.where(explore, rand_scores, stats["mu_hat"])
        order = jnp.argsort(-scores)
        ranks = jnp.argsort(order)
        return (ranks < cfg.n).astype(jnp.float32)

    return act


def fixed(cfg: PolicyConfig, arm: int) -> Act:
    mask = jnp.zeros((cfg.k,), jnp.float32).at[arm].set(1.0)

    def act(stats, key, t):
        return mask

    return act


def offline_fixed(cfg: PolicyConfig, mask: np.ndarray) -> Act:
    m = jnp.asarray(mask, jnp.float32)

    def act(stats, key, t):
        return m

    return act


# ===================================================================== registry
def make_policy(name: str, cfg: PolicyConfig, **kw) -> Act:
    if name == "c2mabv":
        return c2mabv(cfg)
    if name == "c2mabv_direct":
        return c2mabv_direct(cfg)
    if name == "cucb":
        return cucb(cfg)
    if name == "thompson":
        return thompson(cfg)
    if name == "egreedy":
        return epsilon_greedy(cfg)
    if name == "fixed":
        return fixed(cfg, kw["arm"])
    if name == "offline_fixed":
        return offline_fixed(cfg, kw["mask"])
    raise ValueError(name)
