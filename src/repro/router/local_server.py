"""Local server (paper §4.1, Fig. 3 left).

Handles user queries, stores feedback, maintains Eq.-(6) running stats, and
solves the *relaxed* constrained problem — only the fractional vector z̃ is
shipped to the scheduling cloud (raw queries and feedback never leave).

Since the fleet refactor this class owns no ad-hoc numpy state: it is the
M = 1 degenerate case of `router.fleet` — its statistics live in a
`TenantState` pytree row and every solve goes through the same jitted
batched path (`fleet.relaxed_batch`) that drives the full fleet.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import confidence as cb
from repro.core.policies import PolicyConfig
from repro.router import fleet
from repro.spans import span


@jax.jit
def _record(stats, arm, reward, cost):
    """One observation's Eq.-(6) update as one compiled dispatch: the
    one-hot rows are built inside the trace, so a traced int32 ``arm`` and
    float32 ``reward``/``cost`` share one executable per K."""
    k = stats["t_mu"].shape[-1]
    row = jnp.zeros((1, k), jnp.float32)
    return cb.update_stats(stats, row.at[0, arm].set(1.0),
                           row.at[0, arm].set(reward),
                           row.at[0, arm].set(cost))


@dataclasses.dataclass
class FeedbackRecord:
    round: int
    arm: int
    reward: float
    cost: float


class LocalServer:
    """Owns user data + bandit statistics; emits relaxed selections."""

    def __init__(self, pcfg: PolicyConfig, tenant: int = 0):
        self.pcfg = pcfg
        self.tenant = tenant
        self._fcfg = fleet.fleet_config([pcfg])
        self.state = fleet.init_tenant_state(1, pcfg.k)
        self.log: list[FeedbackRecord] = []

    # ------------------------------------------------------------ statistics
    # The round counter lives twice: `_t` on the host, read without waiting
    # on the device, and `state.t` for the jitted solve. Every write goes
    # through the `state` or `t` setter, which keep the two equal.
    @property
    def state(self) -> fleet.TenantState:
        return self._state

    @state.setter
    def state(self, value: fleet.TenantState) -> None:
        self._state = value
        self._t = int(np.asarray(value.t)[0])

    @property
    def t(self) -> int:
        return self._t

    @t.setter
    def t(self, value: int) -> None:
        self._t = int(value)
        self._state = self._state._replace(
            t=jnp.full((1,), float(value), jnp.float32))

    @property
    def mu_hat(self) -> np.ndarray:
        return np.asarray(self.state.stats["mu_hat"][0])

    @property
    def c_hat(self) -> np.ndarray:
        return np.asarray(self.state.stats["c_hat"][0])

    @property
    def t_mu(self) -> np.ndarray:
        return np.asarray(self.state.stats["t_mu"][0])

    @property
    def t_c(self) -> np.ndarray:
        return np.asarray(self.state.stats["t_c"][0])

    def relaxed_selection(self) -> np.ndarray:
        """One §4.1 step: UCB/LCB -> relaxed solve -> fractional z̃ (K,)."""
        with span("repro.route.relax", tenant=self.tenant):
            self.t = self.t + 1
            z = fleet.relaxed_batch(self.state.stats, self.state.t,
                                    self._fcfg)
            return np.asarray(z[0])

    def record(self, arm: int, reward: float, cost: float) -> None:
        """Eq. (6) incremental update for one observed arm: one dispatch,
        no device read (fixed dtypes keep one executable per K)."""
        self._state = self._state._replace(
            stats=_record(self._state.stats, np.int32(arm),
                          np.float32(reward), np.float32(cost)))
        self.log.append(FeedbackRecord(self._t, arm, reward, cost))
