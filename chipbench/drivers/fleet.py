"""Driver for fleet configurations: the router's batched bandit scan.

Set-up builds the tenants' policy rows from the configuration file and the
traffic's kind mix, and warms `router.fleet.simulate_fleet` with the first
call. Calls chain: each resumes the tenants where the one before left them,
through the fleet's own checkpoints (kept under the run's TMPDIR), so the
window runs continuing bandits over the configuration's horizon, with
delta = 1/horizon. Once a cohort of tenants reaches the horizon, a fresh
cohort starts at round 0 with keys drawn from the seed. The window repeats
calls until its length has passed; the last call counts to its end.
Tenant-rounds completed over the window's wall time is
`fleet_rounds_per_s`.

The check, once the window has closed, holds a sample of the window's
calls (drawn from the seed, the last call always among them) to the plain
reference beside the configuration:

  matroid       every action has n arms (SUC/AIC) or at most n (AWC);
  feedback      SUC/AIC observe their action; AWC observes a non-empty
                prefix of it in ascending mean cost;
  reward_err    the reported set reward against r(S; mu) of the action;
  counts        each arm's observation count and each tenant's round count
                against the state the call started from and the observed
                masks;
  duplicates    tenants whose mean costs equal another's: costs are
                continuous draws from each tenant's own key, so a repeat is
                one tenant's answers handed to another;
  cost_err      the cohort's summed round costs, up to the last call's end,
                against sum(c_hat * t_c): the budget accounting of Eq. (6);
  action_gap    the relaxed solve and rounding inside the scan, at the first
                round of each sampled call, whose state is the one the call
                started from: how far the best relaxed point that rounds to
                the action falls below the reference's exact LP optimum
                (SUC and AIC tenants; AWC's Frank-Wolfe is not compared).
"""
from __future__ import annotations

import os
import shutil
import tempfile
import time
from typing import Dict, List

import numpy as np

MAX_CALLS = 100_000
KEEP = 3                       # sampled calls kept for the check, besides
                               # the last


class Driver:
    def __init__(self, cell, seed: int, ref, log):
        import jax
        from repro.core.policies import PolicyConfig
        from repro.env.llm_profiles import Pool
        from repro.router import fleet

        self.jax, self.fleet, self.ref, self.log = jax, fleet, ref, log
        cfg, tr = cell.config, cell.traffic
        self.cfg, self.tr = cfg, tr
        self.m, self.T = int(cfg["tenants"]), int(tr["rounds_per_call"])
        self.horizon = int(cfg["horizon"])
        if self.horizon % self.T:
            raise ValueError(f"rounds_per_call {self.T} does not divide "
                             f"the horizon {self.horizon}")
        p = ref.pool(cfg)
        self.mu, self.mean_cost = p["mu"], p["mean_cost"]
        self.pool = Pool(names=tuple(cfg["arms"]), mu=p["mu"],
                         mean_cost=p["mean_cost"], cost_scale=p["cost_scale"],
                         reward_levels=tuple(float(x) for x in p["levels"]))
        k = len(cfg["arms"])
        self.kinds = np.asarray([tr["kinds"][i % len(tr["kinds"])]
                                 for i in range(self.m)])
        self.rho = np.asarray([ref.rho_for(cfg, kd, self.mean_cost)
                               for kd in self.kinds])
        self.n = np.full(self.m, int(cfg["n"]))
        self.delta = np.full(self.m, 1.0 / self.horizon)
        self.alpha_mu = np.full(self.m, float(cfg["alpha_mu"]))
        self.alpha_c = np.full(self.m, float(cfg["alpha_c"]))
        self.fcfg = fleet.fleet_config([
            PolicyConfig(kind=str(kd), k=k, n=int(self.n[i]),
                         rho=float(self.rho[i]), delta=float(self.delta[i]),
                         alpha_mu=float(self.alpha_mu[i]),
                         alpha_c=float(self.alpha_c[i]))
            for i, kd in enumerate(self.kinds)])
        rng = np.random.default_rng(seed)
        self.cohort_seeds = rng.integers(0, 2 ** 31 - 1, MAX_CALLS)
        self.pick = np.random.default_rng(rng.integers(2 ** 63))
        self.kept: List = []           # (state the call started from, result)
        self.calls = 0                 # calls made, set-up's included
        self.window_calls = 0
        self.call_s: List[float] = []
        self.ckpt_root = tempfile.mkdtemp(prefix="chipbench-fleet-")
        self.before = None

    # ------------------------------------------------------------- runs
    def _call(self):
        """One call of ``rounds_per_call`` rounds, resuming the cohort.
        -> (the state it started from, None for a fresh cohort; result)"""
        jax = self.jax
        cohort, t0 = divmod(self.calls * self.T, self.horizon)
        ckpt_dir = os.path.join(self.ckpt_root, str(cohort))
        if t0 == 0:                    # a fresh cohort: no state behind it
            shutil.rmtree(os.path.join(self.ckpt_root, str(cohort - 1)),
                          ignore_errors=True)
            self.before = None
            self.cohort_costs = []
        with jax.profiler.TraceAnnotation("chipbench.simulate_fleet"):
            keys = jax.random.split(
                jax.random.PRNGKey(int(self.cohort_seeds[cohort])), self.m)
            res = self.fleet.simulate_fleet(
                self.pool, self.fcfg, T=t0 + self.T, keys=keys,
                ckpt_dir=ckpt_dir, ckpt_every=self.T)
        before, self.before = self.before, res.state
        self.calls += 1
        self.cohort_costs.append(res.cost)
        return before, res

    def setup(self) -> None:
        self._call()                      # compiles, or loads the cache

    def window(self, seconds: float) -> Dict[str, float]:
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            call = self._call()
            self.call_s.append(time.perf_counter() - t)
            self.window_calls += 1
            # reservoir sample of the calls, drawn from the seed
            if len(self.kept) < KEEP:
                self.kept.append(call)
            else:
                j = self.pick.integers(self.window_calls)
                if j < KEEP:
                    self.kept[j] = call
            self.last = call
            if time.perf_counter() - t0 >= seconds:
                break
        self.wall = time.perf_counter() - t0
        self.attempted = self.window_calls * self.m * self.T
        self.log(f"calls {self.window_calls}, {self.m} tenants x {self.T} "
                 f"rounds each, {self.calls * self.T} rounds since set-up "
                 f"began, call seconds median "
                 f"{float(np.median(self.call_s))}")
        return {"fleet_rounds_per_s": self.attempted / self.wall}

    def free(self) -> None:
        """The scan's state is host numpy; only the checkpoints go."""
        shutil.rmtree(self.ckpt_root, ignore_errors=True)

    # ------------------------------------------------------------ check
    def _start(self, before):
        """Stats and round count a call started from (zeros for a fresh
        cohort), float64."""
        if before is None:
            k = len(self.cfg["arms"])
            z = np.zeros((self.m, k))
            return {"mu_hat": z, "c_hat": z, "t_mu": z, "t_c": z}, \
                np.zeros(self.m)
        return ({k: np.asarray(v, np.float64) for k, v in
                 before.stats.items()}, np.asarray(before.t, np.float64))

    def first_round(self, before):
        """UCB/LCB (float64) of the first round of a call, from the state
        it started from."""
        stats, t = self._start(before)
        return self.ref.bounds(stats, t + 1, self.delta, self.alpha_mu,
                               self.alpha_c)

    def _identities(self, before, res) -> Dict[str, float]:
        ref = self.ref
        act, obs = res.action > 0, res.observed > 0          # (M, T, K)
        awc = self.kinds == "awc"
        sizes = act.sum(-1)
        n = self.n[:, None]
        matroid = int(np.sum(np.where(awc[:, None], sizes > n, sizes != n)))
        order = ref.cascade_order(self.mean_cost)
        a_o, o_o = act[..., order], obs[..., order]
        j = o_o.sum(-1, keepdims=True)
        prefix = a_o & (np.cumsum(a_o, -1) <= j)
        awc_bad = np.any(o_o != prefix, -1) | ((j[..., 0] == 0)
                                               & a_o.any(-1))
        feedback = int(np.sum(np.where(awc[:, None], awc_bad,
                                       np.any(obs != act, -1))))
        want = ref.set_reward(self.kinds, act, self.mu)
        reward_err = float(np.max(np.abs(np.asarray(res.reward, np.float64)
                                          - want)))
        st = res.state.stats
        start, t_start = self._start(before)
        seen = obs.sum(1)
        counts = int(np.sum(st["t_mu"] != start["t_mu"] + seen)
                     + np.sum(st["t_c"] != start["t_c"] + seen)
                     + np.sum(res.state.t != t_start + self.T))
        # costs are continuous draws from each tenant's own key: two
        # tenants with the same mean costs are one tenant's answers twice
        seen_any = st["t_c"].sum(1) > 0
        rows = np.asarray(st["c_hat"])[seen_any]
        dup = len(rows) - len(np.unique(rows, axis=0))
        mu_bar, c_low = self.first_round(before)
        gap = ref.action_gap(self.kinds, res.action[:, 0], mu_bar, c_low,
                             self.n, self.rho)
        return {"matroid": matroid, "feedback": feedback, "counts": counts,
                "duplicates": int(dup), "reward_err": reward_err,
                "action_gap": float(gap.max())}

    def _spent(self, dtype: str = "float64"):
        """The cohort's summed round costs up to the last call's end."""
        return self.ref.spent(np.concatenate(self.cohort_costs, 1)
                              .astype(np.float64), dtype)

    @staticmethod
    def _rel(got, want) -> float:
        return float(np.max(np.abs(got - want) / np.maximum(want, 1e-6)))

    def check(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for before, res in self.kept + [self.last]:
            for k, v in self._identities(before, res).items():
                out[k] = max(out.get(k, 0), v)
        st = self.last[1].state.stats
        acct = (np.asarray(st["c_hat"], np.float64)
                * np.asarray(st["t_c"], np.float64)).sum(1)
        out["cost_err"] = self._rel(acct, self._spent())
        self.failed = 0
        return out

    def control(self) -> Dict[str, float]:
        """The reference in bfloat16 in the program's place: its relaxed
        solve (rounded by the reference's own marginal rounding), set
        rewards and cost accounting, read by the same numbers."""
        ref = self.ref
        rng = np.random.default_rng(int(self.cohort_seeds[-1]))
        gaps = []
        for before, _ in self.kept + [self.last]:
            mu_bar, c_low = self.first_round(before)
            z = ref.lp(ref.lp_weights(self.kinds, mu_bar), c_low, self.n,
                       self.rho, np.ones(self.m, bool), "bfloat16")
            act = ref.round_marginals(z, rng)
            gaps.append(ref.action_gap(self.kinds, act, mu_bar, c_low,
                                       self.n, self.rho).max())
        act = self.last[1].action > 0
        return {"action_gap": float(max(gaps)),
                "reward_err": float(np.max(np.abs(
                    ref.set_reward(self.kinds, act, self.mu, "bfloat16")
                    - ref.set_reward(self.kinds, act, self.mu)))),
                "cost_err": self._rel(self._spent("bfloat16"),
                                      self._spent())}

    # ------------------------------------------------------- per layer
    def layer_context(self) -> Dict:
        return {"arms": len(self.cfg["arms"])}
