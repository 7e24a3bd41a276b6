"""Pallas TPU kernel for the parametric-LP grid engine's inner reduction.

For a (B, K) batch of (score, cost) rows with a traced per-row cardinality n
the kernel returns the *top-n-by-score cost reduction*

    out_b = Σ_k cost_bk · [stable_rank(score_b)_k < n_b]        (B,)

— the scalar cost(λ) probe evaluated for every λ-grid candidate of every
tenant at once (`core.relax` grid engine). Ranks use the shared
stable descending order of `core.ranks` (lower index wins ties, identical to
`lax.top_k`). Each grid cell holds one (BB, Kp) row block in VMEM and
accumulates ranks one real arm column at a time: the column is extracted by
a masked lane reduction and compared against the whole block, so the kernel
uses only 2-D elementwise ops and lane reductions (no dynamic lane slices,
no (B, K, K) pairwise tensor). The fleet's K is the pool size (~10 arms),
so the column loop is short and unrolled. With ``equality=False``
(inclusive matroid, the AWC Frank-Wolfe oracle) entries with score <= 0 are
dropped from the reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30          # score pad: below any real Lagrangian score
DEFAULT_BB = 8       # rows per grid cell (one sublane tile)
LANES = 128          # arm axis padded to the lane width


def stable_ranks(s, k: int):
    """Stable descending ranks of a (bb, kp) block over its first ``k``
    columns: rank_i = #{j < k : s_j > s_i or (s_j == s_i and j < i)}.

    Padded columns (j >= k) score NEG, which never beats a real score, so
    skipping them leaves every real rank unchanged. Column j is read out
    with a masked lane sum (exact: every other term is +0)."""
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ranks = jnp.zeros(s.shape, jnp.int32)
    for j in range(k):
        sj = jnp.sum(jnp.where(col == j, s, 0.0), axis=1, keepdims=True)
        beats = (sj > s) | ((sj == s) & (col > j))
        ranks = ranks + beats.astype(jnp.int32)
    return ranks


def _kernel(score_ref, cost_ref, n_ref, out_ref, *, k: int, equality: bool):
    s = score_ref[...]                                   # (bb, kp)
    c = cost_ref[...]
    n = n_ref[...]                                       # (bb, 1) int32
    # arithmetic mask, mirroring core.ranks.topn_lp_cost
    mask = (stable_ranks(s, k) < n).astype(jnp.float32)
    if not equality:
        mask = mask * (s > 0)
    out_ref[...] = jnp.sum(mask * c, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("equality", "bb", "interpret"))
def topn_lp(score, cost, n, *, equality: bool = True, bb: int = DEFAULT_BB,
            interpret: bool = False):
    """score/cost (B, K); n int or (B,) int32 -> (B,) float32 cost sums."""
    b, k = score.shape
    n = jnp.broadcast_to(jnp.asarray(n, jnp.int32), (b,))
    bp = -(-b // bb) * bb
    kp = -(-k // LANES) * LANES
    s = jnp.full((bp, kp), NEG, jnp.float32)
    s = s.at[:b, :k].set(score.astype(jnp.float32))
    c = jnp.zeros((bp, kp), jnp.float32).at[:b, :k].set(
        cost.astype(jnp.float32))
    nn = jnp.zeros((bp, 1), jnp.int32).at[:b, 0].set(n)

    out = pl.pallas_call(
        functools.partial(_kernel, k=k, equality=equality),
        grid=(bp // bb,),
        in_specs=[
            pl.BlockSpec((bb, kp), lambda i: (i, 0)),
            pl.BlockSpec((bb, kp), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, 1), jnp.float32),
        interpret=interpret,
    )(s, c, nn)
    return out[:b, 0]
