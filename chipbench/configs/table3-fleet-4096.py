"""Plain reference for the table3-fleet-4096 configuration.

The paper's bandit round (arXiv:2405.16587 §3-4) written out in float64
numpy from its definitions, independent of the program:

  pool          Table-3 prices and sciq quality means (App. E.1) as the
                configuration file states them, costs normalised as the
                paper's statistically based cost model;
  bounds        UCB of rewards, LCB of costs (Lemma 1, Eq. 6);
  relaxed solve SUC: max <mu, z>; AIC: max <ln mu, z>, both over
                {0 <= z <= 1, sum z = n, <c, z> <= rho}, solved exactly by
                walking every breakpoint of the Lagrangian in lambda and
                mixing the two vertices that straddle the budget; and how
                far the best relaxed point that rounds to a given action
                falls below that optimum (AWC's Frank-Wolfe relaxation has
                no such check here);
  set rewards   AWC 1 - prod(1 - mu), SUC sum mu, AIC prod mu;
  feedback      SUC/AIC observe the whole action; AWC observes the action's
                arms in ascending mean cost (lower index first on ties) up
                to and including the first success.

``dtype="bfloat16"`` rounds every intermediate to bfloat16: the control, the
nearest precision below the configuration's float32.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import ml_dtypes
import numpy as np

KINDS = ("awc", "suc", "aic")
LAM_CAP = 2.0 ** 24            # the largest lambda a solve may reach
EPS = 1e-9


def _rounder(dtype: str):
    if dtype == "float64":
        return lambda x: np.asarray(x, np.float64)
    if dtype == "bfloat16":
        return lambda x: np.asarray(x, np.float64).astype(
            ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(dtype)


# ----------------------------------------------------------- pool
def pool(config: Dict) -> Dict[str, np.ndarray]:
    """mu, normalised mean costs and their scale, as the paper's pool:
    expected $ per query = (l_in + E[l_out]) / 1000 * price, normalised so
    the dearest arm sits at 1/1.25."""
    price = np.asarray(config["price_per_1k_tokens"], np.float64)
    dollars = (config["in_tokens"]
               + np.asarray(config["mean_out_tokens"], np.float64)) \
        / 1000.0 * price
    scale = float(dollars.max() * config["cost_headroom"])
    return {"mu": np.asarray(config["mu"], np.float64),
            "mean_cost": dollars / scale, "cost_scale": scale,
            "levels": np.asarray(config["reward_levels"], np.float64)}


def rho_for(config: Dict, kind: str, mean_cost: np.ndarray) -> float:
    """The paper's budget threshold by kind, kept at least 1.1x the
    cheapest n-subset's expected cost so that it binds the same way."""
    typical = float(np.sort(mean_cost)[:config["n"]].sum())
    return max(config["rho_base"][kind],
               typical * config["rho_typical_factor"])


# ----------------------------------------------------------- bounds
def bounds(stats: Dict[str, np.ndarray], t: np.ndarray, delta, alpha_mu,
           alpha_c):
    """(UCB of mu, LCB of c) per tenant and arm, from Eq.-(6) stats."""
    k = stats["mu_hat"].shape[-1]
    t = np.maximum(np.asarray(t, np.float64), 1.0)[:, None]
    delta = np.asarray(delta, np.float64)[:, None]
    num = np.log(2 * math.pi ** 2 * k * t ** 3 / (3 * delta))

    def rad(tk):
        tk = np.asarray(tk, np.float64)
        with np.errstate(divide="ignore"):
            return np.where(tk > 0, np.sqrt(num / (2 * np.maximum(tk, 1.0))),
                            np.inf)

    mu = np.minimum(stats["mu_hat"] + np.asarray(alpha_mu)[:, None]
                    * rad(stats["t_mu"]), 1.0)
    c = np.maximum(stats["c_hat"] - np.asarray(alpha_c)[:, None]
                   * rad(stats["t_c"]), 0.0)
    return mu, c


# ----------------------------------------------------------- relaxed solve
def _vertices(w, c, n, lams, equality, rd):
    """Top-n arms by score w - lam c at every lam (lower index first on
    equal scores); inclusive rows keep only positive scores. -> (M, P, K)."""
    s = rd(w[:, None, :] - rd(lams[:, :, None] * c[:, None, :]))
    order = np.argsort(-s, axis=-1, kind="stable")
    ranks = np.empty_like(order)
    np.put_along_axis(ranks, order, np.arange(s.shape[-1])[None, None],
                      axis=-1)
    v = (ranks < n[:, None, None]).astype(np.float64)
    return np.where(equality[:, None, None], v, v * (s > 0))


def lp(w, c, n, rho, equality, dtype: str = "float64"):
    """max <w, z> s.t. 0 <= z <= 1, sum z (= or <=) n, <c, z> <= rho, per
    row, exactly: the Lagrangian optimum is the top-n vertex by w - lam c;
    cost is non-increasing in lam, so the optimum mixes the two vertices
    that straddle rho at the breakpoint. Where no lambda up to LAM_CAP
    meets rho, the cap's vertex is returned (it then exceeds rho)."""
    rd = _rounder(dtype)
    w, c, rho = rd(w), rd(c), rd(rho)
    m, k = w.shape
    n = np.asarray(n)
    equality = np.broadcast_to(np.asarray(equality, bool), (m,))
    i, j = np.triu_indices(k, 1)
    with np.errstate(divide="ignore", invalid="ignore"):
        cross = rd((w[:, j] - w[:, i]) / (c[:, j] - c[:, i]))
        pos = rd(w / c)
    cand = np.concatenate([cross, pos], axis=1)
    cand = np.where(np.isfinite(cand) & (cand > 0) & (cand < LAM_CAP),
                    cand, LAM_CAP)
    cand = np.sort(cand, axis=1)
    # one point inside every interval between breakpoints, and the cap
    lo = np.concatenate([np.zeros((m, 1)), cand], axis=1)
    hi = np.concatenate([cand, np.full((m, 1), LAM_CAP)], axis=1)
    pts = np.concatenate([np.zeros((m, 1)), 0.5 * (lo + hi),
                          np.full((m, 1), LAM_CAP)], axis=1)
    v = _vertices(w, c, n, pts, equality, rd)
    cost = rd((v * c[:, None, :]).sum(-1))
    feas = cost <= rho[:, None]
    first = np.where(feas.any(1), feas.argmax(1), pts.shape[1] - 1)
    rows = np.arange(m)
    v_hi, c_hi = v[rows, first], cost[rows, first]
    v_lo, c_lo = v[rows, np.maximum(first - 1, 0)], \
        cost[rows, np.maximum(first - 1, 0)]
    with np.errstate(divide="ignore", invalid="ignore"):
        theta = rd(np.where(c_lo > c_hi, (rho - c_hi) / (c_lo - c_hi), 0.0))
    mix = (first > 0) & feas[rows, first]
    theta = np.where(mix, np.clip(theta, 0.0, 1.0), 0.0)
    return rd(theta[:, None] * v_lo + (1.0 - theta[:, None]) * v_hi)


def lp_weights(kinds, mu):
    """The LP's weights per tenant: SUC mu, AIC ln mu."""
    kinds = np.asarray(kinds)[:, None]
    return np.where(kinds == "aic", np.log(np.clip(mu, EPS, 1.0)), mu)


def action_gap(kinds, action, mu, c, n, rho):
    """How far the best relaxed point that rounds to ``action`` falls below
    the LP's optimum, per tenant with an LP relaxation (SUC, AIC).

    An LP-shaped z~ has at most two fractional coordinates, one of them
    chosen, so the points that pairwise rounding can turn into the action
    ``a`` are ``a`` itself and ``a`` with one chosen arm i traded in part
    for one unchosen arm j: z = a + s (e_j - e_i), 0 <= s <= 1, within the
    budget. A sound solve and rounding read 0 to rounding error, whichever
    optimal vertex they pick; a solve that misses the optimum reads its
    shortfall, and an action that no point within the budget rounds to
    reads 1 + |optimum|. 0 for AWC tenants and where the LP cannot meet
    rho. action (M, K)."""
    kinds = np.asarray(kinds)
    w = lp_weights(kinds, mu)
    z_ref = lp(w, c, n, rho, np.ones(len(kinds), bool))
    best = (w * z_ref).sum(-1)
    judged = (kinds != "awc") & ((c * z_ref).sum(-1) <= rho * (1 + 1e-9))
    a = (action > 0).astype(np.float64)
    wa, ca = (w * a).sum(-1), (c * a).sum(-1)
    g = w[:, None, :] - w[:, :, None]          # [m, i, j] = w_j - w_i
    d = c[:, None, :] - c[:, :, None]
    pair = (a[:, :, None] > 0) & (a[:, None, :] == 0)
    with np.errstate(divide="ignore", invalid="ignore"):
        s_b = np.clip((rho - ca)[:, None, None] / d, 0.0, 1.0)
    s = np.stack([np.ones_like(g), np.nan_to_num(s_b)], -1)
    value = wa[:, None, None, None] + s * g[..., None]
    cost = ca[:, None, None, None] + s * d[..., None]
    slack = rho * (1 + 1e-6) + 1e-9
    keep = pair[..., None] & (cost <= slack[:, None, None, None])
    swapped = np.where(keep, value, -np.inf).max((1, 2, 3))
    got = np.maximum(np.where(ca <= slack, wa, -np.inf), swapped)
    gap = np.where(np.isfinite(got), best - got, 1.0 + np.abs(best))
    return np.where(judged, np.maximum(gap, 0.0), 0.0)


def round_marginals(z, rng: np.random.Generator):
    """Each row of z rounded to a 0/1 action with P(arm k) = z_k, by
    systematic sampling: the control's stand-in for the program's pairwise
    rounding."""
    z = np.clip(z, 0.0, 1.0)
    u = rng.random((z.shape[0], 1))
    cum = np.cumsum(z, -1)
    return (np.floor(cum - u) > np.floor(cum - z - u)).astype(np.float64)


# ----------------------------------------------------------- the round
def set_reward(kinds, action, mu, dtype: str = "float64"):
    """r(S; mu) for actions (M, T, K), mu (K,)."""
    rd = _rounder(dtype)
    kinds = np.asarray(kinds)[:, None]
    a = action > 0
    awc = 1.0 - rd(np.prod(rd(np.where(a, 1.0 - mu, 1.0)), -1))
    suc = rd(np.where(a, mu, 0.0).sum(-1))
    aic = rd(np.prod(np.where(a, mu, 1.0), -1))
    return np.where(kinds == "awc", awc, np.where(kinds == "suc", suc, aic))


def spent(cost, dtype: str = "float64"):
    """Each tenant's summed round costs, accumulated round by round: the
    budget side of Eq. (1). cost (M, T)."""
    rd = _rounder(dtype)
    total = np.zeros(cost.shape[0])
    for t in range(cost.shape[1]):
        total = rd(total + rd(cost[:, t]))
    return total


def cascade_order(mean_cost: np.ndarray) -> np.ndarray:
    """AWC query order: ascending mean cost, lower index first on ties."""
    return np.argsort(mean_cost, kind="stable")
