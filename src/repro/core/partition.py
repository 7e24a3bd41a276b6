"""Partition-matroid constraints (paper App. C.1).

Beyond the cardinality matroid of the main text, the paper's framework
extends to partition matroids: the LLM pool splits into disjoint domain
groups D_1..D_M (maths-tuned, code-tuned, ...) with per-group caps d_j —
"dedicating groups of non-overlapping LLMs specialized in different
subjects". Feasible actions satisfy |S ∩ D_j| <= d_j for every j, plus the
long-term budget.

The relaxed solver reuses the parametric-Lagrangian trick of relax.py:
for a budget multiplier λ the Lagrangian maximizer decomposes per group
(take the top-d_j arms by w - λ·c within each group), cost(λ) is
non-increasing, and mixing the two vertices adjacent to the breakpoint
yields the LP optimum. Rounding applies Algorithm 3 *within groups*, which
preserves both marginals and every group sum.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import confidence as cb
from repro.core import rewards as R
from repro.core.relax import budget_cost

BISECT_ITERS = 48
DOUBLE_ITERS = 24
FW_STEPS = 16


def _top_per_group(score, groups, caps_per_arm):
    """Indicator of the top-d_j arms by score within each group.

    groups (K,) int32 group id per arm; caps_per_arm (K,) = d_{groups[k]}.
    Rank arms within their group by score; select rank < cap."""
    k = score.shape[-1]
    # sort by (group, -score); rank within group = position - group start
    order = jnp.lexsort((-score, groups))
    g_sorted = groups[order]
    start = jnp.searchsorted(g_sorted, g_sorted, side="left")
    rank_sorted = jnp.arange(k) - start
    rank = jnp.zeros((k,), jnp.int32).at[order].set(rank_sorted)
    sel = (rank < caps_per_arm) & (score > -jnp.inf)
    return sel.astype(jnp.float32)


def lp_partition(w, c, groups, caps, rho: float, drop_negative: bool = True):
    """max <w,z> s.t. sum_{D_j} z <= d_j, <c,z> <= rho, z in [0,1]^K."""
    w = w.astype(jnp.float32)
    c = c.astype(jnp.float32)
    groups = jnp.asarray(groups, jnp.int32)
    caps_per_arm = jnp.asarray(caps, jnp.int32)[groups]

    def vertex(lam):
        score = w - lam * c
        if drop_negative:      # inclusive matroid: never take negative score
            score = jnp.where(score > 0, score, -jnp.inf)
        return _top_per_group(score, groups, caps_per_arm)

    z0 = vertex(jnp.float32(0.0))
    cost0 = budget_cost(c, z0)

    def dbl(_, lam):
        zz = vertex(lam)
        return jnp.where(budget_cost(c, zz) > rho, lam * 2.0, lam)

    lam_hi0 = jax.lax.fori_loop(0, DOUBLE_ITERS, dbl, jnp.float32(1.0))
    z_hi0 = vertex(lam_hi0)

    def bis(_, carry):
        lo, hi, z_l, z_h = carry
        mid = 0.5 * (lo + hi)
        z_m = vertex(mid)
        feas = budget_cost(c, z_m) <= rho
        return (jnp.where(feas, lo, mid), jnp.where(feas, mid, hi),
                jnp.where(feas, z_l, z_m), jnp.where(feas, z_m, z_h))

    _, _, z_lo, z_hi = jax.lax.fori_loop(
        0, BISECT_ITERS, bis, (jnp.float32(0.0), lam_hi0, z0, z_hi0))
    c_lo = budget_cost(c, z_lo)
    c_hi = budget_cost(c, z_hi)
    theta = jnp.where(c_lo > c_hi,
                      (rho - c_hi) / jnp.maximum(c_lo - c_hi, 1e-12), 0.0)
    theta = jnp.clip(theta, 0.0, 1.0)
    z_mix = theta * z_lo + (1 - theta) * z_hi
    return jnp.where(cost0 <= rho, z0, z_mix)


def solve_relaxed_partition(kind: str, mu_bar, c_low, groups, caps,
                            rho: float):
    """Fractional z̃ for AWC/SUC/AIC under a partition matroid + budget."""
    if kind == "suc":
        return lp_partition(mu_bar, c_low, groups, caps, rho)
    if kind == "aic":
        w = jnp.log(jnp.clip(mu_bar, R.EPS, 1.0))
        return lp_partition(w, c_low, groups, caps, rho,
                            drop_negative=False)
    if kind == "awc":
        def fw(i, z):
            g = R.awc_multilinear_grad(z, mu_bar)
            v = lp_partition(g, c_low, groups, caps, rho)
            return z + v / FW_STEPS
        return jax.lax.fori_loop(0, FW_STEPS, fw,
                                 jnp.zeros_like(mu_bar, jnp.float32))
    raise ValueError(kind)


def partition_round_np(z, groups, rng: np.random.Generator) -> np.ndarray:
    """Algorithm 3 applied within each group: preserves marginals AND every
    group sum (up to the one fractional unit per group)."""
    from repro.core.rounding import pairwise_round_np
    z = np.asarray(z, np.float64).copy()
    out = np.zeros_like(z)
    for g in np.unique(np.asarray(groups)):
        idx = np.flatnonzero(np.asarray(groups) == g)
        out[idx] = pairwise_round_np(z[idx], rng)
    return out


def make_partition_policy(kind: str, k: int, groups, caps, rho: float,
                          delta: float = 0.01, alpha_mu: float = 0.3,
                          alpha_c: float = 0.05):
    """C2MAB-V over a partition matroid (drop-in `act` for bandit.simulate
    via make_policy-style closure)."""
    from repro.core import rounding

    groups_j = jnp.asarray(groups, jnp.int32)
    caps_j = jnp.asarray(caps, jnp.int32)

    def act(stats, key, t):
        mu_bar = cb.reward_ucb(stats, t, delta, alpha_mu)
        c_low = cb.cost_lcb(stats, t, delta, alpha_c)
        z = solve_relaxed_partition(kind, mu_bar, c_low, groups_j, caps_j,
                                    rho)
        # jit path: global pairwise rounding preserves marginals; per-group
        # sums are integral up to one fractional unit (the numpy host path
        # partition_round_np is exact per group).
        return rounding.pairwise_round(z, key)

    return act


def enumerate_partition_actions(k: int, groups, caps) -> np.ndarray:
    """All feasible subsets of the partition matroid (for small K tests)."""
    import itertools
    groups = np.asarray(groups)
    feas = []
    for bits in itertools.product([0, 1], repeat=k):
        m = np.array(bits, bool)
        ok = all(m[groups == g].sum() <= caps[g]
                 for g in np.unique(groups))
        if ok:
            feas.append(m)
    return np.asarray(feas)
