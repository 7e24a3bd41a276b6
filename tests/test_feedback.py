"""Property tests for the sort-free AWC cascade (env.feedback).

The rank/threshold formulation must match the retained two-argsort
reference bit-for-bit: same prefix, same stable tie order, across random
masks, duplicate mean-cost ties, and the all-fail / all-succeed edges."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.env import feedback

instances = st.integers(0, 10_000)


def _case(seed):
    rng = np.random.default_rng(seed)
    k = int(rng.integers(2, 12))
    mask = (rng.uniform(size=k) < rng.uniform(0.2, 0.9)).astype(np.float32)
    # mean costs with deliberate duplicates: draw from a coarse grid
    cost = rng.choice(np.linspace(0.05, 0.8, max(2, k // 2)), size=k)
    # rewards hit the success level with varying probability
    rewards = np.where(rng.uniform(size=k) < 0.35, 1.0,
                       rng.choice([0.0, 0.2, 0.6], size=k))
    return (jnp.asarray(mask), jnp.asarray(rewards, jnp.float32),
            jnp.asarray(cost, jnp.float32))


@given(instances)
@settings(max_examples=60, deadline=None)
def test_cascade_rank_matches_argsort_reference(seed):
    mask, rewards, cost = _case(seed)
    got = np.asarray(feedback._awc_cascade(mask, rewards, cost))
    want = np.asarray(feedback._awc_cascade_argsort(mask, rewards, cost))
    assert np.array_equal(got, want), (seed, got, want)


def test_cascade_all_fail_observes_whole_selection():
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0])
    rewards = jnp.asarray([0.2, 1.0, 0.0, 0.6])   # success only off-mask
    cost = jnp.asarray([0.3, 0.1, 0.2, 0.4])
    got = np.asarray(feedback._awc_cascade(mask, rewards, cost))
    assert np.array_equal(got, np.asarray(mask))


def test_cascade_all_succeed_observes_cheapest_only():
    mask = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    rewards = jnp.ones(4)
    cost = jnp.asarray([0.3, 0.1, 0.05, 0.4])
    got = np.asarray(feedback._awc_cascade(mask, rewards, cost))
    assert np.array_equal(got, [0.0, 1.0, 0.0, 0.0])


def test_cascade_duplicate_cost_tie_order():
    """Two selected arms at the same cost: the lower index is queried
    first, so a success there hides the higher index — and a success at
    the higher index still exposes the lower one."""
    cost = jnp.asarray([0.2, 0.2, 0.5])
    mask = jnp.ones(3)
    succ_low = jnp.asarray([1.0, 0.0, 0.0])
    succ_high = jnp.asarray([0.0, 1.0, 0.0])
    got_low = np.asarray(feedback._awc_cascade(mask, succ_low, cost))
    got_high = np.asarray(feedback._awc_cascade(mask, succ_high, cost))
    assert np.array_equal(got_low, [1.0, 0.0, 0.0])
    assert np.array_equal(got_high, [1.0, 1.0, 0.0])
    for rew in (succ_low, succ_high):
        ref = np.asarray(feedback._awc_cascade_argsort(mask, rew, cost))
        assert np.array_equal(
            np.asarray(feedback._awc_cascade(mask, rew, cost)), ref)


def test_cascade_empty_selection():
    mask = jnp.zeros(5)
    rewards = jnp.ones(5)
    cost = jnp.linspace(0.1, 0.5, 5)
    got = np.asarray(feedback._awc_cascade(mask, rewards, cost))
    assert np.array_equal(got, np.zeros(5))


def test_observe_ix_dispatch():
    mask = jnp.asarray([1.0, 1.0, 0.0])
    rewards = jnp.asarray([1.0, 0.0, 0.0])
    cost = jnp.asarray([0.5, 0.1, 0.2])
    awc = np.asarray(feedback.observe_ix(jnp.int32(0), mask, rewards, cost))
    suc = np.asarray(feedback.observe_ix(jnp.int32(1), mask, rewards, cost))
    # cheapest selected arm (idx 1) fails, then idx 0 succeeds -> both seen
    assert np.array_equal(awc, [1.0, 1.0, 0.0])
    assert np.array_equal(suc, np.asarray(mask))
