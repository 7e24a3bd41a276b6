"""Scheduling cloud (paper §4.2, Fig. 3 right).

Hosts the deployed model replicas — ONE pool shared by every tenant local
server — receives fractional z̃ vectors, discretizes them back to actions
S_t (Algorithm 2 for AWC — matroid swap rounding; Algorithm 3 for SUC/AIC —
pairwise rounding) and dispatches generation. The cloud never sees raw user
text — only token batches prepared by the local servers (and in a real
deployment, encrypted blobs).

`round_batch` is the fleet-scale entry point: a jittable batched Algorithm 3
over an (M, K) block of tenant z̃ rows with per-tenant matroid sizes, the
cloud-side half of `router.fleet`. Generation runs either through the
blocking per-arm `dispatch` (the retained sequential reference) or through
`make_scheduler`'s continuous-batching bridge (`serving.scheduler`), where
many tenants' requests coalesce into shared per-replica decode batches.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import rewards as R
from repro.core import rounding
from repro.core.policies import PolicyConfig
from repro.serving.engine import Engine, GenResult
from repro.spans import span


@jax.jit
def round_batch(z, keys, n, kind_ix):
    """Batched discretization for M tenants sharing this cloud.

    z (M, K) fractional selections, keys (M, 2), n (M,) int32 matroid sizes,
    kind_ix (M,) rewards.KIND_INDEX. Pairwise rounding (Algorithm 3 — also
    valid for AWC, App. C.2 ❶) vmapped per row, then padded to the base-
    matroid size for SUC/AIC tenants using z̃ as the fill score."""
    masks = rounding.pairwise_round_batch(z, keys)
    equality = kind_ix != R.KIND_INDEX["awc"]
    return jax.vmap(rounding.pad_to_n_dyn)(masks, z, n, equality)


@dataclasses.dataclass
class Replica:
    """One deployed LLM: an engine + its pricing."""
    name: str
    engine: Engine
    price_per_token: float       # normalized $/token


class SchedulingCloud:
    """One replica pool + rounding service, shared across tenants."""

    def __init__(self, pcfg: PolicyConfig, replicas: Sequence[Replica]):
        if len(replicas) != pcfg.k:     # not an assert: must survive -O
            raise ValueError(f"pool has {len(replicas)} replicas but the "
                             f"policy expects k={pcfg.k}")
        self.pcfg = pcfg
        self.replicas = list(replicas)
        # the pool is immutable: pricing (and anything derived from it, like
        # the AWC cascade order) is computed once here
        self._prices = np.asarray([r.price_per_token for r in self.replicas])
        self._prices.setflags(write=False)

    @property
    def prices(self) -> np.ndarray:
        """Per-replica pricing vector (K,) — the fleet's shared cost side."""
        return self._prices

    def select_batch(self, z: np.ndarray, keys) -> np.ndarray:
        """Jittable batched rounding for M tenants with this cloud's pcfg."""
        m = np.asarray(z).shape[0]
        n = jnp.full((m,), self.pcfg.n, jnp.int32)
        kind_ix = jnp.full((m,), R.KIND_INDEX[self.pcfg.kind], jnp.int32)
        return np.asarray(round_batch(jnp.asarray(z, jnp.float32), keys,
                                      n, kind_ix))

    # ------------------------------------------------------------- rounding
    def select(self, z: np.ndarray, rng: np.random.Generator,
               available: Optional[np.ndarray] = None, *,
               tenant: int = 0) -> np.ndarray:
        """Discretization rounding -> boolean action mask (K,).

        The M = 1 case routes through the same jitted `round_batch` program
        the fleet uses (pairwise rounding + `rounding.pad_to_n_dyn`); the
        numpy reference is retained as `select_np`.

        ``available`` (K,) bool masks quarantined replicas out of the
        selection (failover): z̃ is zeroed on unavailable arms and
        renormalized over the healthy subset (preserving the fractional
        mass up to the healthy count, each entry clipped to [0, 1]) before
        rounding, and the rounded action is intersected with the mask so
        the base-matroid padding can never resurrect a dead arm. A None or
        all-True mask takes the exact unmasked path — bit-equal to a run
        with no fault layer at all. ``tenant`` only labels the span."""
        with span("repro.route.select", tenant=tenant) as sp:
            mask = self._select(z, rng, available)
            sp.set_metadata(arms=int(mask.sum()))
        return mask

    def _select(self, z, rng, available) -> np.ndarray:
        z = np.asarray(z, np.float32)
        if available is not None:
            available = np.asarray(available, bool)
            if available.all():
                available = None          # healthy pool: unmasked path
        if available is not None:
            zq = np.where(available, z, 0.0).astype(np.float32)
            s = float(zq.sum())
            if s > 0.0:
                target = min(float(z.sum()), float(available.sum()))
                zq = np.clip(zq * (target / s), 0.0, 1.0).astype(np.float32)
            z = zq
        key = jax.random.PRNGKey(int(rng.integers(0, 2**31 - 1)))
        mask = self.select_batch(z[None, :], key[None])[0]
        mask = np.asarray(mask, bool)
        if available is not None:
            mask &= available
        return mask

    def select_np(self, z: np.ndarray, rng: np.random.Generator) -> np.ndarray:
        """Retained host-side numpy reference for `select`."""
        if self.pcfg.kind == "awc":
            mask = rounding.swap_round_np(z, self.pcfg.n, rng)
        else:
            mask = rounding.pairwise_round_np(z, rng)
        mask = np.asarray(mask, bool)
        if self.pcfg.kind in ("suc", "aic"):
            mask = _pad_to_n_np(mask, z, self.pcfg.n)
        return mask

    # ------------------------------------------------------------- dispatch
    def realized_cost(self, arm: int, prompts: np.ndarray,
                      out: GenResult) -> float:
        """Statistically-based cost: realized token count x replica price."""
        toks = prompts.shape[1] * prompts.shape[0] + int(out.out_lens.sum())
        return toks * float(self._prices[arm])

    def dispatch(self, arm: int, prompts: np.ndarray, max_new: int,
                 seed: int = 0) -> tuple[GenResult, float]:
        """Run generation on one replica; returns (result, realized cost).

        Blocking sequential reference — the continuous-batching path goes
        through `make_scheduler` + `serving.scheduler.Request` submission."""
        out = self.replicas[arm].engine.generate(prompts, max_new, seed=seed)
        return out, self.realized_cost(arm, prompts, out)

    def make_scheduler(self, *, n_slots: int = 32, chunk: int = 8,
                       max_out: Optional[int] = None, fault_plan=None,
                       health=None, tick_budget: Optional[int] = None):
        """Continuous-batching bridge over this pool: one `ReplicaRunner`
        per replica, shared by every tenant submitting to this cloud.
        ``fault_plan`` / ``health`` (serving.faults) arm the chaos layer;
        ``tick_budget`` bounds each drain (None keeps the default)."""
        from repro.serving.scheduler import ContinuousScheduler, ReplicaRunner
        kw = {} if tick_budget is None else {"tick_budget": tick_budget}
        return ContinuousScheduler(
            [ReplicaRunner(r.engine, n_slots=n_slots, chunk=chunk,
                           max_out=max_out, replica_ix=i,
                           fault_plan=fault_plan, health=health)
             for i, r in enumerate(self.replicas)], **kw)


def _pad_to_n_np(mask: np.ndarray, z: np.ndarray, n: int) -> np.ndarray:
    """Numpy pad-to-base-matroid reference (mirrors `rounding.pad_to_n_dyn`
    with equality semantics: largest-z̃ unselected arms fill up to n)."""
    mask = np.asarray(mask, bool).copy()
    if mask.sum() < n:
        left = np.argsort(-np.where(mask, -np.inf, z))
        for i in left:
            if mask.sum() >= n:
                break
            mask[i] = True
    return mask
