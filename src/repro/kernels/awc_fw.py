"""Pallas TPU kernel fusing the AWC Frank-Wolfe step's gradient + λ probes.

One FW step of the AWC continuous greedy (`core.relax._awc_fw`) needs the
multilinear-extension gradient

    g_k = μ_k · ∏_{j≠k} (1 − μ_j z̃_j)        (log-space, rewards module)

and, for a λ batch (the grid engine's octave ladder), the inclusive-matroid
top-n cost reductions of the Lagrangian scores g − λ·c:

    out_bg = Σ_k cost_bk · [stable_rank(g_b − λ_bg·c_b)_k < n_b][g_bk > λ_bg·c_bk]

Host-level lowerings materialize the (B, K) gradient between the gradient
op and every probe op; this kernel keeps (z̃, μ, c) resident in VMEM,
computes g once per row block, and loops the λ probes over it with the
stable-rank accumulation of `kernels/topn_lp.py` (lower index wins ties;
selection semantics identical to `core.ranks`). Probe λ's are read and
their costs written through masked lane ops, never dynamic lane slices.
The kernel is AWC-specific: ``equality=False`` (the inclusive matroid of
the FW oracle) is baked in.

Outputs: (g (B, K) float32, costs (B, G) float32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.topn_lp import DEFAULT_BB, LANES, NEG, stable_ranks


def _kernel(z_ref, mu_ref, c_ref, lam_ref, n_ref, g_ref, out_ref, *, k: int):
    z = z_ref[...]                                       # (bb, kp)
    mu = mu_ref[...]
    c = c_ref[...]
    lams = lam_ref[...]                                  # (bb, gp)
    n = n_ref[...]                                       # (bb, 1) int32
    valid = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) < k
    gcol = jax.lax.broadcasted_iota(jnp.int32, lams.shape, 1)

    # multilinear gradient, log-space (mirrors rewards.awc_multilinear_grad;
    # padded arms have μ = 0 -> log1p(0) = 0, so they drop out of the sum)
    mu_c = jnp.minimum(mu, 1.0 - 1e-6)
    logs = jnp.log1p(-mu_c * z)
    total = jnp.sum(logs, axis=-1, keepdims=True)
    g = mu_c * jnp.exp(total - logs)
    g_ref[...] = g

    def one_lam(gi, costs):
        sel = gcol == gi
        lam = jnp.sum(jnp.where(sel, lams, 0.0), axis=1, keepdims=True)
        pos = g > lam * c                    # inclusive matroid: s_k > 0
        s = jnp.where(valid, g - lam * c, NEG)
        # arithmetic mask, mirroring core.ranks.topn_lp_cost
        mask = (stable_ranks(s, k) < n).astype(jnp.float32) * pos
        cost = jnp.sum(mask * c, axis=-1, keepdims=True)    # (bb, 1)
        return jnp.where(sel, cost, costs)

    out_ref[...] = jax.lax.fori_loop(0, lams.shape[1], one_lam,
                                     jnp.zeros(lams.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def awc_fw(z, mu, cost, lams, n, *, bb: int = DEFAULT_BB,
           interpret: bool = False):
    """z/mu/cost (B, K); lams (B, G); n (B,) int32 -> (g (B, K), (B, G))."""
    b, k = z.shape
    g_pts = lams.shape[1]
    bp = -(-b // bb) * bb
    kp = -(-k // LANES) * LANES

    def pad(x, fill=0.0):
        out = jnp.full((bp, kp), fill, jnp.float32)
        return out.at[:b, :k].set(x.astype(jnp.float32))

    lam_p = jnp.zeros((bp, g_pts), jnp.float32).at[:b].set(
        lams.astype(jnp.float32))
    nn = jnp.zeros((bp, 1), jnp.int32).at[:b, 0].set(
        jnp.asarray(n, jnp.int32))

    g, costs = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(bp // bb,),
        in_specs=[
            pl.BlockSpec((bb, kp), lambda i: (i, 0)),
            pl.BlockSpec((bb, kp), lambda i: (i, 0)),
            pl.BlockSpec((bb, kp), lambda i: (i, 0)),
            pl.BlockSpec((bb, g_pts), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, kp), lambda i: (i, 0)),
            pl.BlockSpec((bb, g_pts), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, kp), jnp.float32),
            jax.ShapeDtypeStruct((bp, g_pts), jnp.float32),
        ],
        interpret=interpret,
    )(pad(z), pad(mu), pad(cost), lam_p, nn)
    return g[:b, :k], costs[:b]
