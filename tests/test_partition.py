"""Partition-matroid extension (paper App. C.1): solver feasibility +
optimality vs enumeration, and group-respecting rounding."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import partition as pm
from repro.core import rewards as R


def make_instance(seed):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(2, 4))                    # groups
    sizes = rng.integers(1, 4, m)
    k = int(sizes.sum())
    groups = np.repeat(np.arange(m), sizes)
    caps = np.array([int(rng.integers(1, s + 1)) for s in sizes])
    mu = rng.uniform(0.05, 0.95, k)
    c = rng.uniform(0.01, 0.5, k)
    rho = float(c.sum() * rng.uniform(0.3, 0.9))
    return groups, caps, mu, c, rho


@given(st.integers(0, 10_000))
@settings(max_examples=40, deadline=None)
def test_partition_lp_feasible_and_optimal(seed):
    groups, caps, mu, c, rho = make_instance(seed)
    z = np.array(pm.lp_partition(jnp.array(mu, jnp.float32),
                                 jnp.array(c, jnp.float32),
                                 groups, caps, rho))
    assert np.all(z >= -1e-6) and np.all(z <= 1 + 1e-6)
    assert float(np.dot(c, z)) <= rho * 1.002 + 1e-5
    for g in np.unique(groups):
        assert z[groups == g].sum() <= caps[g] + 1e-4
    # >= best integral feasible action (LP relaxation dominates)
    actions = pm.enumerate_partition_actions(len(mu), groups, caps)
    vals = actions @ mu
    vals = np.where(actions @ c <= rho + 1e-9, vals, -np.inf)
    assert float(np.dot(mu, z)) >= vals.max() - 1e-3


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_partition_round_preserves_groups_and_marginals(seed):
    groups, caps, mu, c, rho = make_instance(seed)
    z = np.array(pm.lp_partition(jnp.array(mu, jnp.float32),
                                 jnp.array(c, jnp.float32),
                                 groups, caps, rho), np.float64)
    acc = np.zeros_like(z)
    trials = 600
    for i in range(trials):
        m = pm.partition_round_np(z, groups, np.random.default_rng(i))
        for g in np.unique(groups):
            assert m[groups == g].sum() <= caps[g] + 1e-9
        acc += m
    assert np.allclose(acc / trials, z, atol=0.08)


@pytest.mark.parametrize("kind", ["awc", "suc", "aic"])
def test_partition_policy_runs(kind):
    from repro.core import confidence as cb
    import jax
    groups = np.array([0, 0, 0, 1, 1, 2, 2, 2, 2])
    caps = np.array([2, 1, 2])
    act = pm.make_partition_policy(kind, 9, groups, caps, rho=0.6,
                                   delta=0.1)
    stats = cb.init_stats(9)
    mask = act(stats, jax.random.PRNGKey(0), jnp.asarray(3.0))
    assert mask.shape == (9,)
    assert set(np.unique(np.asarray(mask))) <= {0.0, 1.0}
