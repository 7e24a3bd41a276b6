"""Logical-axis sharding with divisibility fallback.

Model code annotates arrays with *logical* axis names; this module maps them
onto whatever mesh is active. A dim is sharded on a candidate mesh-axis tuple
only if (a) every mesh axis in the tuple exists, (b) none is already used by
another dim of the same array, and (c) the dim size is divisible by the
product of the mesh axis sizes. Otherwise the next candidate (or replication)
applies — this is what lets e.g. starcoder2's 36 heads or whisper's 51866
vocab fall back gracefully on a 16-way model axis.
"""
from __future__ import annotations

import contextlib
import math
import threading
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Priority-ordered mesh-axis candidates per logical axis name.  Each candidate
# is a tuple of mesh axes (sharded jointly).
RULES: dict = {
    # data-parallel / fsdp axes
    "batch":      (("pod", "data"), ("data",)),
    "fsdp":       (("pod", "data"), ("data",)),       # param biggest dim
    # fleet tenancy: the M axis of TenantState/FleetConfig (router.fleet)
    "tenants":    (("pod", "data"), ("data",)),
    # tensor-parallel axes
    "heads":      (("model",),),
    "kv_heads":   (("model",),),
    "mlp":        (("model",),),
    "experts":    (("model",),),
    "vocab":      (("model",), ("data",)),
    "embed":      (),                                   # activations: replicated
    "embed_fsdp": (("pod", "data"), ("data",)),        # params: fsdp on d_model
    # sequence axes
    "seq":        (),
    "cache_seq":  (("model",),),                        # decode KV/seq sharding
    "ssm_heads":  (("model",),),
    "state":      (),
    "layers":     (),
    None:         (),
}

_CTX = threading.local()


def _mesh() -> Optional[Mesh]:
    return getattr(_CTX, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    """Activate a mesh for logical sharding (None = no-op, CPU smoke path).

    Build the mesh with `repro.launch.mesh.make_mesh` (Auto axes): on a
    mesh of Explicit axes `shard` would assert instead of constrain."""
    prev = getattr(_CTX, "mesh", None)
    _CTX.mesh = mesh
    try:
        if mesh is not None:
            with jax.sharding.set_mesh(mesh):
                yield mesh
        else:
            yield None
    finally:
        _CTX.mesh = prev


def spec_for(shape: Sequence[int], logical: Sequence[Optional[str]],
             mesh: Optional[Mesh] = None) -> P:
    """PartitionSpec for `shape` given logical axis names (greedy, fallback)."""
    mesh = mesh or _mesh()
    if mesh is None:
        return P()
    assert len(shape) == len(logical), (shape, logical)
    used: set = set()
    parts = []
    for dim, name in zip(shape, logical):
        assigned = None
        for cand in RULES.get(name, ()):  # type: ignore[arg-type]
            if any(a not in mesh.shape for a in cand):
                continue
            if any(a in used for a in cand):
                continue
            size = math.prod(mesh.shape[a] for a in cand)
            if dim % size != 0:
                continue
            assigned = cand if len(cand) > 1 else cand[0]
            used.update(cand)
            break
        parts.append(assigned)
    # trim trailing Nones for tidiness
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def shard(x, *logical: Optional[str]):
    """with_sharding_constraint by logical names; no-op without a mesh."""
    mesh = _mesh()
    if mesh is None:
        return x
    spec = spec_for(x.shape, logical, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(shape: Sequence[int], logical: Sequence[Optional[str]],
                   mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, spec_for(shape, logical, mesh))


def tree_shardings(tree_shapes, tree_axes, mesh: Mesh):
    """Map a pytree of jax.ShapeDtypeStruct + a matching pytree of logical-axes
    tuples to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda s, ax: named_sharding(s.shape, ax, mesh),
        tree_shapes, tree_axes,
        is_leaf=lambda a: isinstance(a, tuple) and all(
            isinstance(e, (str, type(None))) for e in a),
    )
