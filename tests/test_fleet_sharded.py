"""Pod-scale fleet (router.fleet mesh + checkpoint paths).

Two load-bearing invariants, both engineered rather than hoped for:

- The shard_map lowering of the fleet scan is BIT-IDENTICAL to the
  single-device reference (actions, observations, costs, stats, keys) on
  CPU meshes at 2 and 8 virtual devices — tenants are independent rows, so
  the per-row program is the same either way. (The expected-reward *log*
  keeps the existing 1-ulp batch-width caveat from test_fleet.py.)
- A run killed mid-way and resumed through `ckpt` checkpoints reproduces
  the uninterrupted trajectory bit-for-bit: segment boundaries align to
  ``ckpt_every`` multiples, so the resumed run replays identical compiled
  segments.

Device counts lock at jax init, so multi-device cases run either in a
subprocess with forced host devices (always) or in-process when the
session already has >= 8 devices (the dedicated multi-device CI job).
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core.policies import PolicyConfig
from repro.env.llm_profiles import default_rho, paper_pool
from repro.launch.mesh import make_mesh
from repro.router import fleet

T = 20


@pytest.fixture(scope="module")
def pool():
    return paper_pool("sciq")


def mixed_cfg(pool, m, T=T):
    kinds = [("awc", "suc", "aic")[i % 3] for i in range(m)]
    return fleet.fleet_config(
        [PolicyConfig(kind=k, k=pool.k, n=3, rho=default_rho(pool, k, 3),
                      delta=1.0 / T) for k in kinds])


def assert_bit_equal(got, ref, t0=0):
    """The sharded/resumed-vs-reference discipline: everything bit-equal,
    reward within the documented 1-ulp batch-width caveat."""
    assert np.array_equal(got.action, ref.action[:, t0:])
    assert np.array_equal(got.observed, ref.observed[:, t0:])
    assert np.array_equal(got.cost, ref.cost[:, t0:])
    assert np.allclose(got.reward, ref.reward[:, t0:], atol=1e-6)
    for name in ref.state.stats:
        assert np.array_equal(got.state.stats[name],
                              ref.state.stats[name]), name
    assert np.array_equal(got.state.key, ref.state.key)
    assert np.array_equal(got.state.t, ref.state.t)


# ==================================================== subprocess (any host)
SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(ndev)d"
import json
import jax
import numpy as np
from repro.core.policies import PolicyConfig
from repro.env.llm_profiles import default_rho, paper_pool
from repro.launch.mesh import make_fleet_mesh
from repro.router import fleet

M, T, PODS = %(m)d, 20, %(pods)d
pool = paper_pool("sciq")
kinds = [("awc", "suc", "aic")[i %% 3] for i in range(M)]
pcfgs = [PolicyConfig(kind=k, k=pool.k, n=3, rho=default_rho(pool, k, 3),
                      delta=1.0 / T) for k in kinds]
cfg = fleet.fleet_config(pcfgs)
keys = jax.random.split(jax.random.PRNGKey(5), M)
mesh = make_fleet_mesh(pods=PODS)
axes = fleet.fleet_mesh_axes(M, mesh)
sharded = fleet.simulate_fleet(pool, cfg, T=T, keys=keys, mesh=mesh)
ref = fleet.simulate_fleet(pool, cfg, T=T, keys=keys)
print(json.dumps({
    "ndev": jax.device_count(),
    "axes": list(axes) if axes else None,
    "action": bool(np.array_equal(sharded.action, ref.action)),
    "observed": bool(np.array_equal(sharded.observed, ref.observed)),
    "cost": bool(np.array_equal(sharded.cost, ref.cost)),
    "reward": bool(np.allclose(sharded.reward, ref.reward, atol=1e-6)),
    "stats": bool(all(np.array_equal(sharded.state.stats[n],
                                     ref.state.stats[n])
                      for n in ref.state.stats)),
    "key": bool(np.array_equal(sharded.state.key, ref.state.key)),
}))
"""


@pytest.mark.parametrize("ndev,m,pods,want_axes", [
    (2, 12, 1, ["data"]),            # plain data-axis tenant sharding
    (8, 16, 2, ["pod", "data"]),     # joint (pod, data) tenant axes
    (8, 12, 1, None),                # 12 % 8 != 0: documented fallback
])
def test_sharded_fleet_bit_equal_subprocess(ndev, m, pods, want_axes):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run(
        [sys.executable, "-c", SUBPROC % {"ndev": ndev, "m": m,
                                          "pods": pods}],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ndev"] == ndev
    assert rec["axes"] == want_axes
    for field in ("action", "observed", "cost", "reward", "stats", "key"):
        assert rec[field], (field, rec)


# ================================================= in-process (>= 8 devices)
needs8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(the multi-device CI job)")


@needs8
@pytest.mark.parametrize("mesh_shape,axes_names,m", [
    ((8,), ("data",), 24),
    ((2, 4), ("pod", "data"), 16),
])
def test_sharded_fleet_bit_equal_inprocess(pool, mesh_shape, axes_names, m):
    mesh = make_mesh(mesh_shape, axes_names)
    cfg = mixed_cfg(pool, m)
    keys = jax.random.split(jax.random.PRNGKey(2), m)
    sharded = fleet.simulate_fleet(pool, cfg, T=T, keys=keys, mesh=mesh)
    ref = fleet.simulate_fleet(pool, cfg, T=T, keys=keys)
    assert_bit_equal(sharded, ref)


@needs8
def test_sharded_fleet_nondivisible_falls_back(pool):
    """M=10 on 8 devices: spec_for's divisibility fallback replicates the
    tenant axis, fleet_mesh_axes reports None, and the run still matches
    the reference (it IS the reference path)."""
    mesh = make_mesh((8,), ("data",))
    assert fleet.fleet_mesh_axes(10, mesh) is None
    cfg = mixed_cfg(pool, 10)
    keys = jax.random.split(jax.random.PRNGKey(4), 10)
    got = fleet.simulate_fleet(pool, cfg, T=T, keys=keys, mesh=mesh)
    ref = fleet.simulate_fleet(pool, cfg, T=T, keys=keys)
    assert_bit_equal(got, ref)


@needs8
def test_sharded_resume_bit_equal(pool, tmp_path):
    """Kill-then-resume THROUGH the sharded path reproduces the sharded
    uninterrupted trajectory (checkpointing and shard_map compose)."""
    mesh = make_mesh((8,), ("data",))
    m, every, kill, total = 16, 4, 6, 12
    cfg = mixed_cfg(pool, m, T=total)
    keys = jax.random.split(jax.random.PRNGKey(9), m)
    full = fleet.simulate_fleet(pool, cfg, T=total, keys=keys, mesh=mesh)
    d = str(tmp_path / "ck")
    fleet.simulate_fleet(pool, cfg, T=kill, keys=keys, mesh=mesh,
                         ckpt_dir=d, ckpt_every=every)
    res = fleet.simulate_fleet(pool, cfg, T=total, keys=keys, mesh=mesh,
                               ckpt_dir=d, ckpt_every=every)
    assert res.t0 == (kill // every) * every
    assert_bit_equal(res, full, t0=res.t0)


# ================================================== checkpoint/resume (1 dev)
def test_kill_then_resume_bit_equal(pool, tmp_path):
    """A run killed at round 7 (checkpoint at 4) resumed to T=12 equals the
    uninterrupted no-checkpoint run bit-for-bit on rounds 5..12."""
    from repro.ckpt import checkpoint
    m, every, kill, total = 6, 4, 7, 12
    cfg = mixed_cfg(pool, m, T=total)
    keys = jax.random.split(jax.random.PRNGKey(3), m)
    full = fleet.simulate_fleet(pool, cfg, T=total, keys=keys)
    d = str(tmp_path / "ck")
    part = fleet.simulate_fleet(pool, cfg, T=kill, keys=keys,
                                ckpt_dir=d, ckpt_every=every)
    # the kill leaves only the round-4 checkpoint (7 is not a multiple)
    assert checkpoint.latest_step(d) == 4
    assert np.array_equal(part.action, full.action[:, :kill])
    res = fleet.simulate_fleet(pool, cfg, T=total, keys=keys,
                               ckpt_dir=d, ckpt_every=every)
    assert res.t0 == 4 and res.action.shape[1] == total - 4
    assert_bit_equal(res, full, t0=4)
    # round counter: checkpoints now exist at every later multiple + state.t
    assert checkpoint.latest_step(d) == 12
    assert (res.state.t == total).all()


def test_segmented_checkpointing_matches_plain_run(pool, tmp_path):
    """ckpt_every segmentation itself must not perturb the trajectory:
    a checkpointed run equals the single-scan run bit-for-bit, including a
    ragged final segment (T not a multiple of ckpt_every)."""
    m, total = 5, 11
    cfg = mixed_cfg(pool, m, T=total)
    keys = jax.random.split(jax.random.PRNGKey(8), m)
    plain = fleet.simulate_fleet(pool, cfg, T=total, keys=keys)
    ck = fleet.simulate_fleet(pool, cfg, T=total, keys=keys,
                              ckpt_dir=str(tmp_path / "ck"), ckpt_every=4)
    assert_bit_equal(ck, plain)


def test_resume_at_completion_returns_zero_rounds(pool, tmp_path):
    m, total = 4, 8
    cfg = mixed_cfg(pool, m, T=total)
    keys = jax.random.split(jax.random.PRNGKey(1), m)
    d = str(tmp_path / "ck")
    first = fleet.simulate_fleet(pool, cfg, T=total, keys=keys,
                                 ckpt_dir=d, ckpt_every=4)
    again = fleet.simulate_fleet(pool, cfg, T=total, keys=keys,
                                 ckpt_dir=d, ckpt_every=4)
    assert again.t0 == total and again.action.shape == (m, 0, pool.k)
    for name in first.state.stats:
        assert np.array_equal(again.state.stats[name],
                              first.state.stats[name])


def test_resume_past_T_raises(pool, tmp_path):
    m = 4
    cfg = mixed_cfg(pool, m, T=8)
    keys = jax.random.split(jax.random.PRNGKey(1), m)
    d = str(tmp_path / "ck")
    fleet.simulate_fleet(pool, cfg, T=8, keys=keys, ckpt_dir=d, ckpt_every=4)
    with pytest.raises(ValueError, match="past T"):
        fleet.simulate_fleet(pool, cfg, T=6, keys=keys, ckpt_dir=d,
                             ckpt_every=4)
