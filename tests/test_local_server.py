"""The local server's Eq.-(6) record and round counter
(`router.local_server.LocalServer`).

One observation is one compiled dispatch: the statistics it leaves are
bit-equal to the eager one-hot rows fed to a jitted `update_stats`, one
executable serves every arm, reward and cost of a K, and neither `record`
nor the round counter reads the device. The host counter stays equal to
the device one through every path that moves it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import confidence as cb
from repro.core.policies import PolicyConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import model as M
from repro.router import fleet
from repro.router.cloud import Replica, SchedulingCloud
from repro.router.local_server import LocalServer, _record
from repro.router.service import FleetService
from repro.serving.engine import Engine

VOCAB = 64
STATS = ("mu_hat", "c_hat", "t_mu", "t_c")


def _pcfg(kind, k):
    return PolicyConfig(kind=kind, k=k, n=min(2, k), rho=1e9, delta=0.1)


def _device_t(local):
    return int(np.asarray(local.state.t)[0])


class _Unreadable:
    """Stands in for the device round counter: any read of it raises."""

    def _read(self, *_):
        raise AssertionError("the device round counter was read")

    __array__ = __getitem__ = __int__ = __float__ = __index__ = _read


@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("kind", ["suc", "awc"])
def test_record_is_bit_equal_to_eager_one_hot_rows(kind, k):
    """Rounds of observations over every arm, failures at reward 0, rewards
    and costs of the types the service passes: the statistics match the
    eager one-hot construction bit for bit."""
    local = LocalServer(_pcfg(kind, k))
    update = jax.jit(cb.update_stats)
    want = fleet.init_tenant_state(1, k).stats
    rng = np.random.default_rng(1000 * k + len(kind))
    for _ in range(6):
        local.relaxed_selection()
        for arm in rng.permutation(k)[:rng.integers(1, k + 1)]:
            failed = rng.random() < 0.3
            reward = 0.0 if failed else float(rng.random())
            cost = int(rng.integers(8, 80)) * float(rng.random() * 0.01)
            local.record(int(arm), reward, cost)
            obs = jnp.zeros((1, k), jnp.float32).at[0, arm].set(1.0)
            x = jnp.zeros((1, k), jnp.float32).at[0, arm].set(float(reward))
            y = jnp.zeros((1, k), jnp.float32).at[0, arm].set(float(cost))
            want = update(want, obs, x, y)
    assert local.t_mu.sum() == len(local.log) > 6
    for key in STATS:
        got = np.asarray(local.state.stats[key])
        assert got.dtype == np.float32
        np.testing.assert_array_equal(
            got.view(np.uint32), np.asarray(want[key]).view(np.uint32))


def test_record_compiles_once_per_k_and_never_reads_the_device():
    """After one warm record per K, every arm with rewards and costs of any
    numeric type reuses that executable, and neither `record` nor the `t`
    getter moves data from the device."""
    _record.clear_cache()
    servers = {k: LocalServer(_pcfg("awc", k)) for k in (2, 4)}
    for local in servers.values():
        local.relaxed_selection()
        local.record(0, 0.5, 0.1)
    assert _record._cache_size() == 2
    values = [(0.0, 0), (1, 0.25), (np.float64(0.3), np.float32(0.002)),
              (np.float32(0.7), 1e-3)]
    # the CPU backend lets the guard pass host reads of its arrays, so the
    # device counter is also swapped for one that refuses to be read
    counters = {k: local.state.t for k, local in servers.items()}
    for local in servers.values():
        local._state = local._state._replace(t=_Unreadable())
    with jax.transfer_guard_device_to_host("disallow"):
        for k, local in servers.items():
            for arm in range(k):
                for reward, cost in values:
                    local.record(arm, reward, cost)
                    local.record(np.int64(arm), reward, cost)
            assert local.t == 1
            assert [r.round for r in local.log] == [1] * (1 + 8 * k)
    assert _record._cache_size() == 2
    for k, local in servers.items():
        local._state = local._state._replace(t=counters[k])
        np.testing.assert_array_equal(local.t_mu,
                                      [9.0] + [8.0] * (k - 1))


def test_round_counter_follows_the_state():
    """The host `t` equals the device `state.t` after a relax, after
    `t += 1`, and after the state is replaced whole."""
    local = LocalServer(_pcfg("suc", 4))
    assert local.t == _device_t(local) == 0
    for want in (1, 2, 3):
        local.relaxed_selection()
        assert local.t == _device_t(local) == want
    local.t += 1
    assert local.t == _device_t(local) == 4
    local.state = fleet.init_tenant_state(1, 4)._replace(
        t=jnp.full((1,), 9.0, jnp.float32))
    assert local.t == _device_t(local) == 9
    local.relaxed_selection()
    assert local.t == _device_t(local) == 10
    assert local.state.t.dtype == jnp.float32


@pytest.fixture(scope="module")
def pool():
    cfg = dataclasses.replace(get_config("h2o-danube-3-4b").reduced(),
                              vocab=VOCAB)
    return [Replica(f"m{i}", Engine(cfg, M.init_params(cfg,
                                                       jax.random.PRNGKey(i)),
                                    max_len=32, eos_id=0, temperature=0.7),
                    0.001 * (1 + i))
            for i in range(2)]


def _fleet_args(pool):
    pcfgs = [_pcfg(kind, 2) for kind in ("suc", "awc")]
    cloud = SchedulingCloud(pcfgs[0], pool)
    data = SyntheticLM(DataConfig(vocab=VOCAB, seq_len=8, global_batch=2,
                                  seed=0))
    return pcfgs, cloud, data


def test_round_counter_with_async_batches_and_in_the_driven_fleet(pool):
    """``batch_size`` 2: every other round skips the relax and only counts
    (`t += 1` in `_select_mask`). Each tenant's host and device counters
    agree after every round, and the `TenantState` the driven fleet
    assembles carries the same counts."""
    pcfgs, cloud, data = _fleet_args(pool)
    fs = FleetService(pcfgs, cloud, data, n_slots=4, chunk=4, seed=5,
                      prompt_len=8, max_new=4, batch_size=2)
    relaxes = []
    for svc in fs.tenants:
        relax = svc.local.relaxed_selection
        svc.local.relaxed_selection = (
            lambda relax=relax: relaxes.append(1) or relax())
    for rnd in range(1, 5):
        fs.step()
        for svc in fs.tenants:
            assert svc.local.t == _device_t(svc.local) == rnd
    assert len(relaxes) == 2 * len(fs.tenants)
    pcfgs, cloud, data = _fleet_args(pool)
    res = fleet.simulate_fleet_driven(pcfgs, cloud, data, T=4, n_slots=4,
                                      chunk=4, seed=5, prompt_len=8,
                                      max_new=4, batch_size=2)
    assert res.state.t.dtype == np.float32
    np.testing.assert_array_equal(
        res.state.t, [_device_t(svc.local) for svc in fs.tenants])
    for key in STATS:
        np.testing.assert_array_equal(
            res.state.stats[key],
            np.concatenate([np.asarray(svc.local.state.stats[key])
                            for svc in fs.tenants]))
