"""Unit + property tests for the versatile reward models (paper §3)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import rewards as R

MU = st.lists(st.floats(0.01, 0.99), min_size=2, max_size=8)


def masks_of(k):
    return st.lists(st.booleans(), min_size=k, max_size=k)


@given(MU, st.data())
@settings(max_examples=60, deadline=None)
def test_set_reward_definitions(mu, data):
    mu = np.asarray(mu)
    k = len(mu)
    mask = np.asarray(data.draw(masks_of(k)), float)
    sel = mu[mask > 0]
    awc = float(R.set_reward("awc", jnp.array(mask), jnp.array(mu)))
    suc = float(R.set_reward("suc", jnp.array(mask), jnp.array(mu)))
    aic = float(R.set_reward("aic", jnp.array(mask), jnp.array(mu)))
    assert np.isclose(awc, 1 - np.prod(1 - sel), atol=1e-5)
    assert np.isclose(suc, sel.sum(), atol=1e-5)
    assert np.isclose(aic, np.prod(sel) if sel.size else 1.0, atol=1e-5)


@given(MU, st.data())
@settings(max_examples=60, deadline=None)
def test_relaxed_matches_set_on_integral_points(mu, data):
    """Eq. (14): r(S;μ) == r̃(1_S;μ) for all three reward models."""
    mu = np.asarray(mu)
    mask = np.asarray(data.draw(masks_of(len(mu))), float)
    for kind in R.KINDS:
        a = float(R.set_reward(kind, jnp.array(mask), jnp.array(mu)))
        b = float(R.relaxed_reward(kind, jnp.array(mask), jnp.array(mu)))
        assert np.isclose(a, b, atol=1e-5), (kind, a, b)


@given(MU)
@settings(max_examples=40, deadline=None)
def test_monotonicity_in_mu(mu):
    """All reward models are monotone in μ (used by the regret proof)."""
    mu = np.asarray(mu)
    z = np.full(len(mu), 0.7)
    hi = np.clip(mu + 0.05, 0, 1)
    for kind in R.KINDS:
        lo_v = float(R.relaxed_reward(kind, jnp.array(z), jnp.array(mu)))
        hi_v = float(R.relaxed_reward(kind, jnp.array(z), jnp.array(hi)))
        assert hi_v >= lo_v - 1e-6


def test_awc_submodular_diminishing_marginal():
    """Eq. (9): adding an arm to a superset gains less."""
    mu = np.array([0.5, 0.6, 0.7, 0.8])
    small = np.array([1.0, 0, 0, 0])
    big = np.array([1.0, 1.0, 1.0, 0])

    def gain(mask):
        with_k = mask.copy(); with_k[3] = 1
        return (float(R.set_reward("awc", jnp.array(with_k), jnp.array(mu)))
                - float(R.set_reward("awc", jnp.array(mask), jnp.array(mu))))

    assert gain(small) >= gain(big) - 1e-6


def test_awc_multilinear_grad_matches_finite_difference():
    mu = jnp.array([0.3, 0.5, 0.9])
    z = jnp.array([0.2, 0.6, 0.4])
    g = R.awc_multilinear_grad(z, mu)
    eps = 1e-4
    for i in range(3):
        zp = z.at[i].add(eps)
        zm = z.at[i].add(-eps)
        fd = (R.relaxed_reward("awc", zp, mu)
              - R.relaxed_reward("awc", zm, mu)) / (2 * eps)
        assert np.isclose(float(g[i]), float(fd), atol=1e-3)


def test_alpha_constants():
    assert float(R.ALPHA["awc"]) == pytest.approx(1 - 1 / np.e)
    assert R.ALPHA["suc"] == 1.0 and R.ALPHA["aic"] == 1.0
