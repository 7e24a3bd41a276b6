"""Mixture-of-Experts: top-k routing + sort-based capacity dispatch.

Dispatch is *grouped by batch row* so all sorting/positioning is a batched
(per-group) op — no global sort collectives. Tokens are scattered into an
(B, E, C, D) expert buffer (capacity-dropped), experts run as one grouped
einsum with weights stationary on the "model"-sharded expert axis (expert
parallelism), and results are gathered back and combined with router gates.

FLOPs are honest: only top_k experts' worth of compute per token (+ capacity
slack), unlike dense all-experts einsum formulations.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec
from repro.sharding import shard


def moe_schema(cfg, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    e, f = cfg.n_experts, cfg.moe_d_ff
    std = 0.02
    std_o = 0.02 / math.sqrt(2 * max(cfg.n_layers, 1))
    # FSDP placement for expert weights (EXPERIMENTS.md §Perf B1): sharding
    # the contracting d_model dim ("d_model") makes every expert einsum a
    # partial-sum, all-reducing full (b,e,cap,f) activation buffers over the
    # data axis per layer; sharding the expert hidden f ("d_ff") instead
    # lets SPMD all-gather the (much smaller) weights ZeRO-style.
    if getattr(cfg, "moe_fsdp_dim", "d_ff") == "d_model":
        wi_axes = ("experts", "embed_fsdp", None)
        wo_axes = ("experts", None, "embed_fsdp")
    else:
        wi_axes = ("experts", None, "embed_fsdp")
        wo_axes = ("experts", "embed_fsdp", None)
    s = {
        "router": ParamSpec((d, e), (None, "experts"), std=std),
        "wi_gate": ParamSpec((e, d, f), wi_axes, std=std),
        "wi_up": ParamSpec((e, d, f), wi_axes, std=std),
        "wo": ParamSpec((e, f, d), wo_axes, std=std_o),
    }
    return s


def route(cfg, p, x):
    """Router logits/top-k. x (B,S,D) -> gates (B,S,K), idx (B,S,K), probs."""
    logits = jnp.einsum("bsd,de->bse", x, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx, probs


def load_balance_loss(cfg, probs, idx):
    """Switch-style aux loss: E * sum_e f_e * P_e."""
    e = cfg.n_experts
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)      # (B,S,K,E)
    f = onehot.sum((0, 1, 2)) / jnp.maximum(onehot.sum(), 1.0)
    pmean = probs.mean((0, 1))
    return e * jnp.sum(f * pmean)


def apply_moe(cfg, p, x, *, capacity_factor: Optional[float] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y, aux_loss). x (B,S,D).

    capacity_factor defaults to cfg.capacity_factor; set it large (>= E/K·S)
    for exact no-drop routing (decode steps and consistency tests)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    if capacity_factor is None:
        capacity_factor = cfg.capacity_factor
    gates, idx, probs = route(cfg, p, x)                     # (B,S,K)
    cap = max(1, int(math.ceil(s * k / e * capacity_factor)))
    cap = min(cap, s * k)

    sk = s * k
    eid = idx.reshape(b, sk)                                 # expert per entry
    gat = gates.reshape(b, sk).astype(x.dtype)
    tok = jnp.repeat(jnp.arange(s), k)[None, :]              # (1,SK) token ids
    tok = jnp.broadcast_to(tok, (b, sk))

    order = jnp.argsort(eid, axis=-1)                        # per-group sort
    se = jnp.take_along_axis(eid, order, axis=-1)            # sorted expert ids
    sg = jnp.take_along_axis(gat, order, axis=-1)
    st = jnp.take_along_axis(tok, order, axis=-1)
    # position within expert segment = rank - first occurrence of expert id
    first = jax.vmap(lambda a: jnp.searchsorted(a, a, side="left"))(se)
    pos = jnp.arange(sk)[None, :] - first
    keep = pos < cap
    dest = jnp.where(keep, se * cap + pos, 0)                # (B,SK)

    xe = jnp.take_along_axis(
        x, st[..., None], axis=1)                            # (B,SK,D) sorted tokens
    bidx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, sk))
    buf = jnp.zeros((b, e * cap, d), x.dtype)
    buf = buf.at[bidx, dest].add(
        jnp.where(keep[..., None], xe, 0).astype(x.dtype))
    buf = buf.reshape(b, e, cap, d)
    buf = shard(buf, "batch", "experts", None, None)

    h_g = jnp.einsum("becd,edf->becf", buf, p["wi_gate"])
    h_u = jnp.einsum("becd,edf->becf", buf, p["wi_up"])
    yb = jnp.einsum("becf,efd->becd", jax.nn.silu(h_g) * h_u, p["wo"])
    yb = shard(yb, "batch", "experts", None, None)
    yb = yb.reshape(b, e * cap, d)

    ye = yb[bidx, dest] * (sg * keep)[..., None]             # (B,SK,D)
    y = jnp.zeros((b, s, d), x.dtype)
    y = y.at[bidx, st].add(ye)
    aux = load_balance_loss(cfg, probs, idx)
    return y, aux


# ==================================================================== EP path
# Expert-parallel dispatch via shard_map + all_to_all (EXPERIMENTS.md §Perf
# B2). XLA's SPMD partitioner cannot shard the data-dependent gather/scatter
# dispatch of `apply_moe` — it replicates the (B, S·K, D) dispatch buffers
# and all-reduces them over the data axis (hundreds of GB per layer for
# arctic-480b). Here the dispatch is MANUAL: routing, sort and scatter are
# device-local; the only cross-device traffic is
#   - one all_to_all over the "model" (expert) axis carrying ~S·K·cf tokens,
#   - its reverse for the combine,
#   - a ZeRO-style all-gather of the layer's expert weights over the fsdp
#     axes (they are stored sharded on the f dim).
# This is the TPU-native analogue of DeepSpeed/MaxText expert parallelism.
def _local_dispatch(x_flat, eid, gat, e: int, cap: int):
    """Device-local capacity dispatch.

    x_flat (N, D) token features per assignment; eid (N,) expert ids;
    gat (N,) gates. Returns buf (e, cap, D), plus (src, slot, keep) to
    invert the dispatch."""
    n, d = x_flat.shape
    order = jnp.argsort(eid)
    se = eid[order]
    first = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(n) - first
    keep = pos < cap
    dest = jnp.where(keep, se * cap + pos, 0)
    buf = jnp.zeros((e * cap, d), x_flat.dtype)
    buf = buf.at[dest].add(jnp.where(keep[:, None], x_flat[order], 0))
    return buf.reshape(e, cap, d), order, dest, keep


def apply_moe_ep(cfg, p, x, *, mesh, batch_axes, expert_axis="model",
                 capacity_factor: Optional[float] = None):
    """shard_map expert-parallel MoE. x (B,S,D) batch-sharded over
    ``batch_axes``; expert weights sharded (experts->model, f->batch_axes)."""
    from jax.sharding import PartitionSpec as P

    if capacity_factor is None:
        capacity_factor = cfg.capacity_factor
    e, k = cfg.n_experts, cfg.top_k
    m_size = mesh.shape[expert_axis]
    e_loc = e // m_size
    fsdp = tuple(a for a in batch_axes if a in mesh.shape)

    def f(router, wi_g, wi_u, wo, x_full):
        bsz, s_full, d = x_full.shape
        # x is batch-sharded over `batch_axes` but REPLICATED over the
        # expert axis: each expert-axis peer takes its own s/m sequence
        # slice so the row's tokens are routed exactly once (not m times).
        seq_split = s_full % m_size == 0 and s_full >= m_size
        if seq_split:
            mi = jax.lax.axis_index(expert_axis)
            s = s_full // m_size
            x_loc = jax.lax.dynamic_slice_in_dim(x_full, mi * s, s, 1)
        else:
            s = s_full
            x_loc = x_full
        b = bsz
        # ---- local routing (router gathered over the expert axis) ----
        router = jax.lax.all_gather(router, expert_axis, axis=1, tiled=True)
        logits = jnp.einsum("bsd,de->bse", x_loc, router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        aux = load_balance_loss(cfg, probs, idx)
        aux = jax.lax.pmean(aux, expert_axis)
        for ax in fsdp:
            aux = jax.lax.pmean(aux, ax)

        n = b * s * k
        cap = max(1, int(math.ceil(n / e * capacity_factor)))
        x_rep = jnp.repeat(x_loc.reshape(b * s, d), k, axis=0)   # (N, D)
        eid = idx.reshape(n)
        gat = gates.reshape(n).astype(x_loc.dtype)
        buf, order, dest, keep = _local_dispatch(x_rep, eid, gat, e, cap)

        # ---- all_to_all: route each expert block to its owner ----
        # buf (e, cap, d) -> (m, e_loc, cap, d); exchange over expert axis
        bufx = buf.reshape(m_size, e_loc, cap, d)
        recv = jax.lax.all_to_all(bufx, expert_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv (m, e_loc, cap, d): tokens from every source shard
        toks = jnp.swapaxes(recv, 0, 1).reshape(e_loc, m_size * cap, d)

        # ---- ZeRO weight gather over the fsdp axes (f dim) ----
        wi_gf, wi_uf, wof = wi_g, wi_u, wo
        for ax in fsdp:
            wi_gf = jax.lax.all_gather(wi_gf, ax, axis=2, tiled=True)
            wi_uf = jax.lax.all_gather(wi_uf, ax, axis=2, tiled=True)
            wof = jax.lax.all_gather(wof, ax, axis=1, tiled=True)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", toks, wi_gf))
        h = h * jnp.einsum("ecd,edf->ecf", toks, wi_uf)
        y = jnp.einsum("ecf,efd->ecd", h, wof)                   # (e_loc,·,d)

        # ---- reverse all_to_all + local combine ----
        y = jnp.swapaxes(y.reshape(e_loc, m_size, cap, d), 0, 1)
        back = jax.lax.all_to_all(y, expert_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        ybuf = back.reshape(e * cap, d)
        ye = ybuf[dest] * (gat[order] * keep)[:, None]
        contrib = jnp.zeros((b * s, d), x_loc.dtype)
        src_tok = (order // k)
        contrib = contrib.at[src_tok].add(ye)
        contrib = contrib.reshape(b, s, d)
        if seq_split:
            # reassemble the full sequence across the expert axis
            contrib = jax.lax.all_gather(contrib, expert_axis, axis=1,
                                         tiled=True)
        return contrib, aux

    bspec = P(fsdp if len(fsdp) > 1 else (fsdp[0] if fsdp else None),
              None, None)
    wi_spec = P(expert_axis, None,
                fsdp if len(fsdp) > 1 else (fsdp[0] if fsdp else None))
    wo_spec = P(expert_axis,
                fsdp if len(fsdp) > 1 else (fsdp[0] if fsdp else None), None)
    in_specs = (P(None, expert_axis), wi_spec, wi_spec, wo_spec, bspec)
    out_specs = (bspec, P())
    smap = jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
    return smap(p["router"], p["wi_gate"], p["wi_up"], p["wo"], x)


def apply_moe_auto(cfg, p, x):
    """EP shard_map path when a mesh with a usable expert axis is active;
    SPMD fallback otherwise (CPU smoke, tiny meshes)."""
    from repro.sharding import _mesh
    mesh = _mesh()
    if mesh is not None and "model" in mesh.shape \
            and cfg.n_experts % mesh.shape["model"] == 0 \
            and cfg.moe_fsdp_dim != "d_model":
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        return apply_moe_ep(cfg, p, x, mesh=mesh, batch_axes=batch_axes)
    return apply_moe(cfg, p, x)
