"""Multi-tenant fleet driver (paper §4, Fig. 3 — at fleet scale).

The deployment story is many local servers sharing one scheduling cloud.
Here a *tenant* is one local server's bandit instance; the whole fleet lives
in a flat `TenantState` pytree of (M, K) arrays plus per-tenant
`FleetConfig` scalars (task kind, N, ρ, δ, α's, sync period). One round
advances every tenant at once:

    UCB/LCB -> relax.solve_batch (per-tenant kind via lax.switch)
            -> batched pairwise rounding against the shared replica pool
            -> env draws + partial feedback -> Eq.-(6) update,

all vmapped across tenants, and `simulate_fleet` runs T rounds × M tenants
inside a single jitted lax.scan. `core.bandit.simulate("c2mabv")`
(seeds-as-tenants) and `router.local_server.LocalServer` (M = 1) are thin
wrappers over this path.

Pod scale: the tenant axis carries the logical name "tenants"
(`TENANT_STATE_AXES` / `FLEET_CONFIG_AXES`), which `sharding.RULES` maps
onto the `(pod, data)` mesh axes with the usual divisibility fallback.
`simulate_fleet(mesh=...)` lowers the same scan through `shard_map` —
each device advances its M/ndev tenant rows with the identical per-row
program (no collectives: tenants only share the read-only pool profile),
so the sharded run is bit-identical to the single-device reference, which
is retained as the `mesh=None` path (same discipline as engine="bisect").
When M doesn't divide the tenant mesh axes, `fleet_mesh_axes` returns
None and the single-device path runs — the documented fallback.

Preemption: `simulate_fleet(ckpt_dir=..., ckpt_every=...)` splits the scan
at multiples of ``ckpt_every`` and persists `TenantState` through
`ckpt.checkpoint` (the checkpoint *step* is the round counter). Restart
with the same arguments resumes from the newest checkpoint and — because
segment boundaries align to the same multiples — replays the identical
compiled segments, reproducing the uninterrupted trajectory bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import sharding
from repro.ckpt import checkpoint as ckpt
from repro.core import confidence as cb
from repro.core import relax
from repro.core import rewards as R
from repro.core import rounding
from repro.core.policies import PolicyConfig
from repro.env import cost_model, feedback
from repro.env.llm_profiles import Pool

AWC_IX = R.KIND_INDEX["awc"]


class FleetConfig(NamedTuple):
    """Per-tenant policy scalars, one entry per tenant (all shape (M,))."""
    kind_ix: jnp.ndarray       # int32 index into rewards.KINDS
    n: jnp.ndarray             # int32 matroid size
    rho: jnp.ndarray           # float32 budget threshold
    delta: jnp.ndarray         # float32 confidence level
    alpha_mu: jnp.ndarray      # float32 reward-UCB scale
    alpha_c: jnp.ndarray       # float32 cost-LCB scale
    sync_every: jnp.ndarray    # int32 cloud re-coordination period (App. E.3)

    @property
    def m(self) -> int:
        return self.kind_ix.shape[0]


class TenantState(NamedTuple):
    """The whole fleet's mutable state as a flat, scannable pytree."""
    stats: Dict[str, jnp.ndarray]   # Eq.-(6) running stats, each (M, K)
    prev_mask: jnp.ndarray          # (M, K) last dispatched action
    t: jnp.ndarray                  # (M,) float32 rounds elapsed per tenant
    key: jnp.ndarray                # (M, 2) uint32 per-tenant PRNG keys


# Logical-axis annotations (sharding.RULES maps "tenants" -> (pod, data)).
TENANT_STATE_AXES = TenantState(
    stats={k: ("tenants", None) for k in ("mu_hat", "c_hat", "t_mu", "t_c")},
    prev_mask=("tenants", None), t=("tenants",), key=("tenants", None))
FLEET_CONFIG_AXES = FleetConfig(*((("tenants",),) * len(FleetConfig._fields)))

_AXES_LEAF = (lambda a: isinstance(a, tuple)
              and all(isinstance(e, (str, type(None))) for e in a))


def _axes_to_specs(tree_axes, axes: Tuple[str, ...]):
    """Logical-axes pytree -> PartitionSpec pytree, tenant dim on ``axes``."""
    return jax.tree.map(
        lambda ax: P(*[axes if name == "tenants" else None for name in ax]),
        tree_axes, is_leaf=_AXES_LEAF)


def fleet_mesh_axes(m: int, mesh: Optional[Mesh]) -> Optional[Tuple[str, ...]]:
    """The mesh axes the tenant dim shards over, or None when `spec_for`'s
    divisibility fallback leaves it replicated (M not divisible by the
    tenant mesh axes, or no data/pod axis) — callers then take the
    single-device reference path."""
    if mesh is None:
        return None
    spec = sharding.spec_for((m,), ("tenants",), mesh)
    if not spec:
        return None
    ax = spec[0]
    return ax if isinstance(ax, tuple) else (ax,)


def fleet_config(pcfgs: Sequence[PolicyConfig],
                 sync_every=1) -> FleetConfig:
    """Pack per-tenant PolicyConfigs into the flat fleet layout.

    ``sync_every`` is an int shared by all tenants or a length-M sequence."""
    m = len(pcfgs)
    ks = {p.k for p in pcfgs}
    if len(ks) != 1:
        raise ValueError(f"all tenants must share the replica pool size, "
                         f"got k in {sorted(ks)}")
    sync = np.full(m, sync_every) if np.isscalar(sync_every) else \
        np.asarray(sync_every)
    if sync.shape != (m,):
        raise ValueError(f"sync_every must be a scalar or length-{m} "
                         f"sequence, got shape {sync.shape}")
    return FleetConfig(
        kind_ix=jnp.asarray([R.KIND_INDEX[p.kind] for p in pcfgs], jnp.int32),
        n=jnp.asarray([p.n for p in pcfgs], jnp.int32),
        rho=jnp.asarray([p.rho for p in pcfgs], jnp.float32),
        delta=jnp.asarray([p.delta for p in pcfgs], jnp.float32),
        alpha_mu=jnp.asarray([p.alpha_mu for p in pcfgs], jnp.float32),
        alpha_c=jnp.asarray([p.alpha_c for p in pcfgs], jnp.float32),
        sync_every=jnp.asarray(sync, jnp.int32))


def init_tenant_state(m: int, k: int,
                      keys: Optional[jnp.ndarray] = None,
                      seed: int = 0) -> TenantState:
    if keys is None:
        keys = jax.random.split(jax.random.PRNGKey(seed), m)
    # copy (not view) the caller's keys: the scan donates TenantState
    # buffers, which must never invalidate an array the caller still holds
    return TenantState(stats=cb.init_stats_batch(m, k),
                       prev_mask=jnp.zeros((m, k), jnp.float32),
                       t=jnp.zeros((m,), jnp.float32),
                       key=jnp.array(keys, jnp.uint32))


# ================================================================= per-tenant
def _round_trips(k: int, kinds_present: Tuple[int, ...]) -> Optional[int]:
    """Static rounding-driver choice (see `rounding.pairwise_round` and the
    module docstring's cost model): AWC's Frank-Wolfe z̃ is fractional in
    up to K coordinates, so any AWC tenant forces ≈K−1 merge trips and the
    fixed (K−1)-trip scan — which drops the while driver's per-trip batch
    condition — wins. A SUC/AIC-only fleet's LP-shaped z̃ (≤2 fractional)
    needs one merge, and the while driver's early exit beats any fixed
    trip count; both drivers are bit-identical per row."""
    return k - 1 if AWC_IX in kinds_present else None


def _tenant_act(stats, t, key, cfg: FleetConfig,
                kinds_present: Tuple[int, ...],
                engine: Optional[str] = None,
                fw_steps: Optional[int] = None):
    """One tenant's §4.1+§4.2 step (row shapes): UCB/LCB -> relaxed solve ->
    pairwise rounding -> base-matroid padding. All cfg fields are traced;
    ``kinds_present`` statically prunes the kind dispatch and ``engine``/
    ``fw_steps`` statically select the parametric-LP engine and the AWC
    Frank-Wolfe step count (see relax)."""
    mu_bar = cb.reward_ucb(stats, t, cfg.delta, cfg.alpha_mu)
    c_low = cb.cost_lcb(stats, t, cfg.delta, cfg.alpha_c)
    z = relax.solve_relaxed_ix(cfg.kind_ix, mu_bar, c_low, cfg.n, cfg.rho,
                               kinds_present, engine, fw_steps)
    mask = rounding.pairwise_round(
        z, key, trips=_round_trips(z.shape[-1], kinds_present))
    if kinds_present == (AWC_IX,):
        return mask          # inclusive matroid: padding is the identity
    return rounding.pad_to_n_dyn(mask, mu_bar, cfg.n, cfg.kind_ix != AWC_IX)


def _tenant_step(row: TenantState, t, mu, mean_cost, levels,
                 cfg: FleetConfig, kinds_present: Tuple[int, ...],
                 engine: Optional[str] = None,
                 fw_steps: Optional[int] = None):
    """One protocol round for one tenant (vmapped by the fleet driver)."""
    key, ka, kr, kc = jax.random.split(row.key, 4)
    mask = jax.lax.cond(
        (t - 1) % cfg.sync_every == 0,
        lambda: _tenant_act(row.stats, t, ka, cfg, kinds_present, engine,
                            fw_steps),
        lambda: row.prev_mask)
    x = cost_model.sample_rewards(kr, mu, levels)
    y = cost_model.sample_costs(kc, mean_cost)
    if AWC_IX in kinds_present:
        obs = feedback.observe_ix(cfg.kind_ix, mask, x, mean_cost)
    else:
        obs = mask      # SUC/AIC observe the whole selection; skip the
        # cascade's batched argsorts entirely for AWC-free fleets
    stats = cb.update_stats(row.stats, obs, x, y)
    exp_reward = R.set_reward_ix(cfg.kind_ix, mask, mu)
    cost_t = jnp.sum(y * obs)                 # Eq. (1) charges F_t
    new_row = TenantState(stats=stats, prev_mask=mask,
                          t=t.astype(jnp.float32), key=key)
    return new_row, (exp_reward, cost_t, mask, obs)


# ================================================================== fleet run
def _scan_fleet_impl(state0: TenantState, cfg: FleetConfig, mu, mean_cost,
                     t0, T: int, levels: Tuple[float, ...], unroll: int,
                     kinds_present: Tuple[int, ...],
                     engine: Optional[str] = None,
                     fw_steps: Optional[int] = None):
    """Rounds t0+1 .. t0+T for every tenant row present in ``state0``.

    This is the single trace both lowerings share: `_scan_fleet` jits it
    whole-fleet on one device; `_scan_fleet_sharded` runs it per-shard
    under shard_map (tenant rows are independent, so the per-row program —
    and hence every bit of the trajectory — is identical either way)."""
    def scan_step(state, t):
        return jax.vmap(
            lambda row, c: _tenant_step(row, t, mu, mean_cost, levels, c,
                                        kinds_present, engine, fw_steps)
        )(state, cfg)

    return jax.lax.scan(scan_step, state0, t0 + jnp.arange(1, T + 1),
                        unroll=unroll)


@functools.partial(jax.jit,
                   static_argnames=("T", "levels", "unroll", "kinds_present",
                                    "engine", "fw_steps"),
                   donate_argnums=(0,))
def _scan_fleet(state0: TenantState, cfg: FleetConfig, mu, mean_cost, t0,
                T: int, levels: Tuple[float, ...], unroll: int,
                kinds_present: Tuple[int, ...],
                engine: Optional[str] = None,
                fw_steps: Optional[int] = None):
    return _scan_fleet_impl(state0, cfg, mu, mean_cost, t0, T, levels,
                            unroll, kinds_present, engine, fw_steps)


@functools.partial(jax.jit,
                   static_argnames=("T", "levels", "unroll", "kinds_present",
                                    "engine", "fw_steps", "mesh", "axes"),
                   donate_argnums=(0,))
def _scan_fleet_sharded(state0: TenantState, cfg: FleetConfig, mu, mean_cost,
                        t0, T: int, levels: Tuple[float, ...], unroll: int,
                        kinds_present: Tuple[int, ...],
                        engine: Optional[str], fw_steps: Optional[int],
                        mesh: Mesh, axes: Tuple[str, ...]):
    """`_scan_fleet_impl` under shard_map: tenant rows split over ``axes``
    (the `(pod, data)` tenant mesh axes), pool profile replicated, no
    collectives. TenantState is donated so the carry stays in place on
    each device across scan steps and segments."""
    state_spec = _axes_to_specs(TENANT_STATE_AXES, axes)
    cfg_spec = _axes_to_specs(FLEET_CONFIG_AXES, axes)
    rowp, matp = P(None, axes), P(None, axes, None)

    def body(state0, cfg, mu, mean_cost, t0):
        return _scan_fleet_impl(state0, cfg, mu, mean_cost, t0, T, levels,
                                unroll, kinds_present, engine, fw_steps)

    in_specs = (state_spec, cfg_spec, P(), P(), P())
    out_specs = (state_spec, (rowp, rowp, matp, matp))
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(
        state0, cfg, mu, mean_cost, t0)


def _kinds_present(cfg: FleetConfig) -> Tuple[int, ...]:
    return tuple(sorted(set(np.asarray(cfg.kind_ix).tolist())))


@functools.partial(jax.jit, static_argnames=("kinds_present", "engine",
                                             "fw_steps"))
def _relaxed_batch(stats, t, cfg: FleetConfig,
                   kinds_present: Tuple[int, ...],
                   engine: Optional[str] = None,
                   fw_steps: Optional[int] = None):
    def one(stats_row, t_row, cfg_row):
        mu_bar = cb.reward_ucb(stats_row, t_row, cfg_row.delta,
                               cfg_row.alpha_mu)
        c_low = cb.cost_lcb(stats_row, t_row, cfg_row.delta, cfg_row.alpha_c)
        return relax.solve_relaxed_ix(cfg_row.kind_ix, mu_bar, c_low,
                                      cfg_row.n, cfg_row.rho, kinds_present,
                                      engine, fw_steps)
    return jax.vmap(one)(stats, t, cfg)


def relaxed_batch(stats, t, cfg: FleetConfig, engine: Optional[str] = None,
                  fw_steps: Optional[int] = None):
    """Batched §4.1 local-server step: stats (M, K), t (M,) -> z̃ (M, K).

    This is what a real local-server pod calls per sync round; the cloud
    side then discretizes with `cloud.round_batch`. ``engine`` selects the
    parametric-LP engine (None -> `relax.DEFAULT_ENGINE`); ``fw_steps``
    the AWC Frank-Wolfe step count (None -> `relax.FW_STEPS`)."""
    return _relaxed_batch(stats, t, cfg, _kinds_present(cfg), engine,
                          fw_steps)


@dataclasses.dataclass
class FleetResult:
    reward: np.ndarray     # (M, T) expected set reward r(S_t; μ)
    cost: np.ndarray       # (M, T) realized budget-accounted cost
    action: np.ndarray     # (M, T, K) dispatched masks
    observed: np.ndarray   # (M, T, K) feedback masks
    state: TenantState     # final fleet state (stats/t/keys)
    t0: int = 0            # first round is t0+1 (resumed runs: > 0)


def _ckpt_bounds(t0: int, T: int, ckpt_every: int) -> list:
    """Segment boundaries [t0, ..., T]: every interior boundary is a
    multiple of ``ckpt_every``, so a resumed run replays the *same*
    segment lengths an uninterrupted run compiles — the bit-identical
    resume guarantee rests on this alignment."""
    bounds = [t0]
    if ckpt_every > 0:
        bounds += list(range((t0 // ckpt_every + 1) * ckpt_every, T + 1,
                             ckpt_every))
    if bounds[-1] != T:
        bounds.append(T)
    return bounds


def simulate_fleet(pool: Pool, cfg: FleetConfig, *, T: int,
                   keys: Optional[jnp.ndarray] = None, seed: int = 0,
                   unroll: int = 1,
                   engine: Optional[str] = None,
                   fw_steps: Optional[int] = None,
                   mesh: Optional[Mesh] = None,
                   ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
                   resume: bool = True) -> FleetResult:
    """Advance M tenants T rounds against the shared replica pool.

    Every tenant draws its own rewards/costs (its users' queries) from the
    shared pool profile; per-tenant PRNG keys make trajectories reproducible
    tenant-by-tenant regardless of fleet size. ``engine`` selects the
    parametric-LP engine (None -> `relax.DEFAULT_ENGINE`; "bisect" is the
    sequential reference path kept for equivalence tests and benchmarks);
    ``fw_steps`` the AWC Frank-Wolfe step count (None -> `relax.FW_STEPS`).

    ``mesh`` shards the tenant axis over the mesh's `(pod, data)` axes via
    `_scan_fleet_sharded` (bit-identical to the `mesh=None` single-device
    reference; falls back to it when M doesn't divide the tenant axes).

    ``ckpt_dir``/``ckpt_every`` persist `TenantState` every ``ckpt_every``
    rounds (the checkpoint step is the round counter); with ``resume``
    (default) a rerun picks up from the newest checkpoint and returns the
    remaining rounds t0+1..T (``FleetResult.t0`` marks the resume point),
    bit-identical to the rounds an uninterrupted run would produce."""
    m = cfg.m
    state0 = init_tenant_state(m, pool.k, keys=keys, seed=seed)
    t0 = 0
    if ckpt_dir and resume:
        latest = ckpt.latest_step(ckpt_dir)
        if latest is not None:
            restored, t0 = ckpt.restore(ckpt_dir, state0, step=latest)
            state0 = jax.tree.map(jnp.asarray, restored)
            if t0 > T:
                raise ValueError(f"checkpoint at round {t0} is past T={T}")
    mu = jnp.asarray(pool.mu, jnp.float32)
    mean_cost = jnp.asarray(pool.mean_cost, jnp.float32)
    levels = tuple(pool.reward_levels)
    kinds_present = _kinds_present(cfg)
    axes = fleet_mesh_axes(m, mesh)
    if axes is not None:    # pre-place so donation reuses device buffers
        state0 = jax.device_put(state0, jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            _axes_to_specs(TENANT_STATE_AXES, axes), is_leaf=_AXES_LEAF))

    def run(state, a, n):
        if axes is None:
            return _scan_fleet(state, cfg, mu, mean_cost, jnp.int32(a), n,
                               levels, unroll, kinds_present, engine,
                               fw_steps)
        return _scan_fleet_sharded(state, cfg, mu, mean_cost, jnp.int32(a),
                                   n, levels, unroll, kinds_present, engine,
                                   fw_steps, mesh, axes)

    state, chunks = state0, []
    bounds = _ckpt_bounds(t0, T, ckpt_every if ckpt_dir else 0)
    for a, b in zip(bounds[:-1], bounds[1:]):
        state, out = run(state, a, b - a)
        chunks.append(jax.tree.map(np.asarray, out))
        if ckpt_dir and ckpt_every > 0 and b % ckpt_every == 0:
            ckpt.save(ckpt_dir, b, jax.tree.map(np.asarray, state))
    if chunks:
        rew, cost, act, obs = (np.concatenate(parts, axis=0) for parts in
                               zip(*chunks))
    else:       # resumed at t0 == T: nothing left to run
        rew = cost = np.zeros((0, m), np.float32)
        act = obs = np.zeros((0, m, pool.k), np.float32)
    return FleetResult(reward=np.asarray(rew).T,
                       cost=np.asarray(cost).T,
                       action=np.asarray(act).transpose(1, 0, 2),
                       observed=np.asarray(obs).transpose(1, 0, 2),
                       state=jax.tree_util.tree_map(np.asarray, state),
                       t0=t0)


def simulate_fleet_driven(pcfgs: Sequence[PolicyConfig], cloud, data, *,
                          T: int, prompt_len: int = 8, max_new: int = 8,
                          n_slots: int = 32, chunk: int = 8, seed: int = 0,
                          **service_kw) -> FleetResult:
    """Driven-by-generation fleet rounds: real engines instead of the
    synthetic feedback path.

    Where `simulate_fleet` draws rewards/costs from a synthetic pool
    profile inside one jitted scan, this drives M tenants through
    `router.service.FleetService` against a live `SchedulingCloud`: every
    round each tenant's selected arms become generation requests, the
    shared continuous-batching scheduler coalesces them into per-replica
    decode batches, and measured output quality / realized token costs feed
    the same Eq.-(6) updates. Returns a `FleetResult` whose ``reward`` is
    the mean *observed* quality per round (the synthetic path reports
    expected set reward — the two are comparable in trend, not in value).

    ``service_kw`` passes through to `FleetService` — in particular
    ``fault_plan=``/``health=`` (serving.faults) run the driven fleet
    under deterministic chaos: injected failures arrive as zero-reward
    observations and quarantined replicas are masked out of selection.
    """
    from repro.router.service import FleetService   # lazy: avoids cycle
    fs = FleetService(list(pcfgs), cloud, data, n_slots=n_slots, chunk=chunk,
                      seed=seed, prompt_len=prompt_len, max_new=max_new,
                      **service_kw)
    m, k = len(fs.tenants), pcfgs[0].k
    reward = np.zeros((m, T))
    cost = np.zeros((m, T))
    action = np.zeros((m, T, k), bool)
    observed = np.zeros((m, T, k), bool)
    for t in range(T):
        for i, log in enumerate(fs.step()):
            reward[i, t] = log.rewards[log.observed].mean() \
                if log.observed.any() else 0.0
            cost[i, t] = log.cost
            action[i, t] = log.action
            observed[i, t] = log.observed
    prev_mask = np.asarray(action[:, -1], np.float32) if T > 0 \
        else np.zeros((m, k), np.float32)       # T=0: no round to look at
    state = TenantState(
        stats={key: np.concatenate([np.asarray(s.local.state.stats[key])
                                    for s in fs.tenants])
               for key in fs.tenants[0].local.state.stats},
        prev_mask=prev_mask,
        t=np.asarray([s.local.t for s in fs.tenants], np.float32),
        # the tenants' REAL key rows (generation uses the service's numpy
        # seeds, but the bandit rows carry live PRNG state — fabricating
        # zeros here would silently derail any later synthetic continuation)
        key=np.concatenate([np.asarray(s.local.state.key, np.uint32)
                            for s in fs.tenants]))
    return FleetResult(reward=reward, cost=cost, action=action,
                       observed=observed, state=state)
