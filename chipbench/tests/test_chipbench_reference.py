"""The fleet reference's check of the timed path's relaxed solve and
rounding, on instances worked out by hand."""
import numpy as np
import pytest

from bench import cells

FLEET = cells.load_module(cells.reference_path("table3-fleet-4096"),
                          "table3-fleet-4096")

# SUC, n = 1: the optimum mixes arms 0 and 1 half and half (0.5 * 1.0 +
# 0.5 * 0.2 = rho), worth 0.5 * 0.9 + 0.5 * 0.5 = 0.7; mixing arms 0 and 2
# is worth only 0.6 * 0.9 + 0.4 * 0.1 = 0.58
MU = np.array([[0.9, 0.5, 0.1]])
C = np.array([[1.0, 0.2, 0.0]])


@pytest.mark.parametrize("action, gap", [([1, 0, 0], 0.0), ([0, 1, 0], 0.0),
                                         ([0, 0, 1], 0.12)])
def test_action_gap_by_hand(action, gap):
    got = FLEET.action_gap(["suc"], np.array([action]), MU, C,
                           np.array([1]), np.array([0.6]))
    assert got[0] == pytest.approx(gap, abs=1e-12)


def test_action_no_point_within_budget_rounds_to():
    # n = 2, rho 0.5: the optimum keeps z0 = 0.5 (0.45 + 0.3 + 0.1 = 0.85);
    # no point within the budget rounds to {0, 1}
    mu = np.array([[0.9, 0.8, 0.3, 0.2]])
    c = np.array([[1.0, 1.0, 0.0, 0.0]])
    got = FLEET.action_gap(["suc"], np.array([[1, 1, 0, 0]]), mu, c,
                           np.array([2]), np.array([0.5]))
    assert got[0] == pytest.approx(1.85)


def test_awc_tenants_are_not_judged():
    got = FLEET.action_gap(["awc"], np.array([[0, 0, 1]]), MU, C,
                           np.array([1]), np.array([0.6]))
    assert got[0] == 0.0


def test_round_marginals_keeps_sizes_and_marginals():
    z = np.tile([1.0, 0.0, 0.25, 0.75], (20000, 1))
    a = FLEET.round_marginals(z, np.random.default_rng(7))
    assert (a.sum(1) == 2).all() and (a[:, 0] == 1).all()
    assert (a[:, 1] == 0).all()
    assert a[:, 2].mean() == pytest.approx(0.25, abs=0.01)
