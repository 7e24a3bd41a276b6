"""Driver for served-pool configurations: `router.service.FleetService`
over a pool of models at published widths.

Set-up makes each member's weights on the device from the seed (one jitted
call per member, in the served dtype, by the plain reference beside the
configuration), builds the engines, the scheduling cloud and the tenants,
and warms every shape the window uses: each prefill bucket the traffic can
form (one to `slots / rows` requests, through the scheduler's own admission
path), admit, decode and release, then whole rounds with one decode chunk
per request.

The window is a closed loop: `FleetService.step` again and again until the
window's length has passed; the last round counts to its end.

  served_tokens_per_s   generated tokens of completed requests (sum of
                        out_lens) over the window's wall time;
  round_p95_ms          95th percentile, over every tenant-round of the
                        window that sent a request, of the time from the
                        start of the step that holds the round to the
                        tenant's last completion in it.

Timing comes from the benchmark's own wrappers on the live objects: the
scheduler's `submit` (each request's callback is stamped), each tenant's
`begin_round`, each runner's `step` and the scheduler's `drain`, which also
carry the host spans of a traced run.

The check, once the window has closed, the peak memory read and the slot
state freed:

  unanswered    requests submitted in the window without a successful
                completion;
  feedback      per tenant-round: the observed arms are the arms answered
                (SUC: the whole action; AWC: a prefix in price order that
                stops at the first answer at or above the success
                threshold), each reward is the answer's quality recomputed
                here, and each tenant's observation counts match its rounds;
  gap.<member>  for each member's finished requests of rounds drawn from
                the seed, and its longest: the reference's float32
                forward over prompt and served tokens, and the widest gap by
                which a served token's logit lies below the reference's
                best at its position, in units of the standard deviation of
                the reference's logits there (greedy decoding: the served
                token is the program's argmax).
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import time
from typing import Dict, List

import numpy as np

from bench import cells
from bench.stream import QueryStream, StreamConfig, quality


class Driver:
    def __init__(self, cell, seed: int, ref, log):
        import jax
        self.jax, self.ref, self.log = jax, ref, log
        self.cfg, self.tr = cell.config, cell.traffic
        self.members = self.cfg["members"]
        self.seed = seed
        self.rng = np.random.default_rng(seed)

    # ------------------------------------------------------------- build
    def _build(self) -> None:
        jax = self.jax
        import jax.numpy as jnp
        from repro.configs.base import ArchConfig
        from repro.core.policies import PolicyConfig
        from repro.router.cloud import Replica, SchedulingCloud
        from repro.router.service import FleetService
        from repro.serving.engine import Engine

        cfg, tr = self.cfg, self.tr
        names = {f.name for f in dataclasses.fields(ArchConfig)}
        for m in self.members:
            unknown = sorted(set(m["arch"]) - names)
            if unknown:
                # dropped, they would serve another model than the
                # configuration and its reference describe
                raise cells.CellError(
                    f"member {m['name']}: arch keys {unknown} are not "
                    "fields of the program's ArchConfig")
        self.params, replicas = [], []
        for m in self.members:
            key = jax.random.PRNGKey(int(self.rng.integers(2 ** 31 - 1)))
            params = self.ref.init_params(m["arch"], key,
                                          jnp.dtype(cfg["dtype"]))
            jax.block_until_ready(params)
            self.params.append(params)
            arch = ArchConfig(**m["arch"])
            eng = Engine(arch, params, max_len=int(cfg["max_len"]),
                         eos_id=int(cfg["eos_id"]),
                         temperature=float(cfg["temperature"]),
                         dtype=jnp.dtype(cfg["cache_dtype"]))
            replicas.append(Replica(m["name"], eng,
                                    float(m["price_per_token"])))
        pcfg = PolicyConfig(kind=tr["kind"], k=len(replicas), n=int(tr["n"]),
                            rho=float(tr["rho"]), delta=float(tr["delta"]))
        scfg = StreamConfig(
            vocab=int(tr["stream_vocab"]), seq_len=int(tr["prompt_len"]),
            global_batch=int(tr["rows"]),
            seed=int(self.rng.integers(2 ** 31 - 1)))
        self.stream = QueryStream(scfg)
        self.cloud = SchedulingCloud(pcfg, replicas)
        self.fs = FleetService(
            [pcfg] * int(tr["tenants"]), self.cloud, self.stream,
            n_slots=int(cfg["slots"]), chunk=int(cfg["chunk"]),
            seed=int(self.rng.integers(2 ** 31 - 1)),
            prompt_len=int(tr["prompt_len"]), max_new=int(tr["max_new"]),
            success_threshold=float(tr["success_threshold"]))
        for i, svc in enumerate(self.fs.tenants):
            svc.data = QueryStream(scfg, user=i)   # each tenant's own queries
        self._instrument()

    def _instrument(self) -> None:
        """Wrap the live scheduler, runners and tenants: host spans for
        the trace, and the timers and counters the metrics read."""
        jax, fs = self.jax, self.fs
        span = jax.profiler.TraceAnnotation
        self.done_at: Dict[int, float] = {}       # tenant -> last completion
        self.submitted, self.answered, self.failed_n = 0, 0, 0
        self.begin_s: List[float] = []
        self.decodes: List = []                   # (member, [(pos, steps)])
        self.recording = False
        sched = fs.sched
        submit = sched.submit

        def timed_submit(req):
            cb = req.callback

            def stamped(comp):
                self.done_at[comp.request.tenant] = time.perf_counter()
                if self.recording:
                    if getattr(comp, "ok", True):
                        self.answered += 1
                    else:
                        self.failed_n += 1
                if cb is not None:
                    cb(comp)
            req.callback = stamped
            if self.recording:
                self.submitted += 1
            return submit(req)
        sched.submit = timed_submit

        drain = sched.drain

        def spanned_drain(*a, **kw):
            with span("chipbench.drain"):
                return drain(*a, **kw)
        sched.drain = spanned_drain

        prompt_len = int(self.tr["prompt_len"])
        for i, runner in enumerate(sched.runners):
            name = self.members[i]["name"]
            step = runner.step

            def spanned_step(step=step, name=name):
                with span("chipbench.step." + name):
                    return step()
            runner.step = spanned_step
            eng = runner.engine
            decode = eng.decode_chunk

            def recorded(state, steps, decode=decode, runner=runner, i=i):
                if self.recording:
                    rows = []
                    for r in runner.resident.values():
                        for n_out in r.n_out_seen:
                            live = min(steps, r.req.max_new - int(n_out))
                            if live > 0:
                                rows.append((prompt_len + int(n_out), live))
                    self.decodes.append((i, rows))
                return decode(state, steps)
            eng.decode_chunk = recorded

        for svc in fs.tenants:
            begin = svc.begin_round

            def timed_begin(begin=begin):
                with span("chipbench.begin_round"):
                    t = time.perf_counter()
                    begin()
                    if self.recording:
                        self.begin_s.append(time.perf_counter() - t)
            svc.begin_round = timed_begin

    def _warm_buckets(self) -> None:
        """Every prefill bucket the traffic can form — one to slots/rows
        same-length requests admitted together — through each runner's own
        admission, decode, harvest and release, one token each."""
        from repro.serving.scheduler import Request
        rows, s = int(self.tr["rows"]), int(self.tr["prompt_len"])
        per = int(self.cfg["slots"]) // rows
        for runner in self.fs.sched.runners:
            for j in range(1, per + 1):
                for q in range(j):
                    runner.submit(Request(
                        tenant=-1, arm=runner.replica_ix,
                        prompts=self.stream.batch(10 ** 6 + q)[:, :s],
                        max_new=1, seed=q))
                while runner.busy:
                    runner.step()

    def setup(self) -> None:
        self._build()
        self._warm_buckets()
        chunk = int(self.cfg["chunk"])
        for svc in self.fs.tenants:
            svc.max_new = chunk
        for _ in range(int(self.tr["warm_rounds"])):
            self.fs.step()
        for svc in self.fs.tenants:
            svc.max_new = int(self.tr["max_new"])
        self.jax.effects_barrier()

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> Dict[str, float]:
        fs = self.fs
        self.rounds: List = []               # (logs, completions)
        self.recording = True
        tokens = 0
        lat: List[float] = []
        round_s: List[float] = []
        t0 = time.perf_counter()
        while True:
            ts = time.perf_counter()
            self.done_at.clear()
            logs = fs.step()
            comps = fs.last_completions
            tokens += sum(int(np.sum(c.result.out_lens)) for c in comps
                          if getattr(c, "ok", True))
            # a tenant-round that sent no request has no latency
            lat.extend(t - ts for t in self.done_at.values())
            self.rounds.append((logs, list(comps)))
            round_s.append(time.perf_counter() - ts)
            if time.perf_counter() - t0 >= seconds:
                break
        self.wall = time.perf_counter() - t0
        self.recording = False
        self.attempted = self.submitted
        lat_ms = np.asarray(lat) * 1e3
        self.log(f"rounds {len(self.rounds)}, tenant-rounds {len(lat)}, "
                 f"round_median_ms {float(np.median(lat_ms))}, "
                 f"requests {self.submitted}, tokens {tokens}, round_s "
                 f"{[round(x, 3) for x in round_s]}")
        return {"served_tokens_per_s": tokens / self.wall,
                "round_p95_ms": float(np.percentile(lat_ms, 95))}

    def free(self) -> None:
        """Drop the program's serving state; keep the weights, which the
        benchmark made and the reference reads."""
        self.finished = [(c.request.arm, np.asarray(c.request.prompts),
                          np.asarray(c.result.tokens),
                          np.asarray(c.result.out_lens), i)
                         for i, (_, cs) in enumerate(self.rounds)
                         for c in cs if getattr(c, "ok", True)]
        self.fb = self._feedback()
        del self.fs, self.cloud
        gc.collect()

    # ------------------------------------------------------------- check
    def _feedback(self) -> int:
        """Feedback faults over the window's tenant-rounds and the
        tenants' whole histories."""
        succ = self.stream.succ
        thr = float(self.tr["success_threshold"])
        order = np.argsort([m["price_per_token"] for m in self.members],
                           kind="stable")
        bad = 0
        for logs, comps in self.rounds:
            by_tenant = collections.defaultdict(dict)
            for c in comps:
                by_tenant[c.request.tenant][c.request.arm] = c
            for i, log in enumerate(logs):
                got = by_tenant.get(i, {})
                answered = np.zeros_like(log.observed)
                answered[list(got)] = True
                if np.any(answered != log.observed):
                    bad += 1
                    continue
                for arm, c in got.items():
                    q = quality(succ, np.asarray(c.request.prompts),
                                np.asarray(c.result.tokens))
                    if not getattr(c, "ok", True):
                        q = 0.0
                    if abs(q - float(log.rewards[arm])) > 1e-12:
                        bad += 1
                if self.tr["kind"] == "awc":
                    sel = [a for a in order if log.action[a]]
                    want = []
                    for a in sel:
                        want.append(a)
                        if a not in got or float(log.rewards[a]) >= thr:
                            break
                    if sorted(want) != sorted(got):
                        bad += 1
                elif np.any(log.observed != log.action):
                    bad += 1
        for svc in self.fs.tenants:
            seen = sum(h.observed.astype(np.float64) for h in svc.history)
            bad += int(np.sum(np.asarray(svc.local.t_mu) != seen))
        return bad

    def sample(self, arm: int) -> List:
        """Finished requests of one member to compare: every one of
        ``check_rounds`` rounds drawn from the seed (a whole decode batch,
        so that no part of it goes unread), and the longest of the window."""
        mine = [f for f in self.finished if f[0] == arm]
        if not mine:
            return []
        if not hasattr(self, "drawn"):        # one draw for every member
            self.drawn = set(self.rng.permutation(len(self.rounds))[
                :int(self.tr["check_rounds"])].tolist())
        drawn = self.drawn
        longest = int(np.argmax([int(f[3].sum()) for f in mine]))
        return [f for i, f in enumerate(mine) if f[4] in drawn or i == longest]

    def gaps(self, arm: int, precision: str = "f32") -> float:
        """Widest gap of the served tokens below the reference's best
        logit, over the logits' standard deviation at each position. With
        ``precision="fp8"``, the control: the gap of the token that the
        fp8 forward puts first, at the same positions."""
        jnp = self.jax.numpy
        reqs = self.sample(arm)
        if not reqs:
            return float("inf")
        prompts = np.concatenate([r[1] for r in reqs])
        toks = np.concatenate([r[2] for r in reqs])
        n_out = np.concatenate([r[3] for r in reqs])
        s = prompts.shape[1]
        seq = jnp.asarray(np.concatenate([prompts, toks[:, :-1]], 1))
        a = self.members[arm]["arch"]
        params = self.params[arm]
        logits = self.ref.make_forward(a, "f32")(params, seq)[:, s - 1:]
        if precision == "f32":
            chosen = jnp.asarray(toks)
        else:
            ctl = self.ref.make_forward(a, precision)(params, seq)[:, s - 1:]
            chosen = jnp.argmax(ctl, -1)
        best = logits.max(-1)
        got = jnp.take_along_axis(logits, chosen[..., None], -1)[..., 0]
        gap = np.asarray((best - got) / logits.std(-1))
        live = np.arange(toks.shape[1])[None, :] < n_out[:, None]
        return float(np.max(np.where(live, gap, 0.0)))

    def check(self) -> Dict[str, float]:
        out = {"unanswered": self.submitted - self.answered,
               "feedback": self.fb}
        for arm, m in enumerate(self.members):
            out["gap." + m["name"]] = self.gaps(arm)
        self.failed = self.failed_n
        return out

    def control(self) -> Dict[str, float]:
        return {"gap." + m["name"]: self.gaps(arm, "fp8")
                for arm, m in enumerate(self.members)}

    # ------------------------------------------------------- per layer
    def layer_context(self) -> Dict:
        s = int(self.tr["prompt_len"])
        prefill_flops = [0.0] * len(self.members)
        gen_flops = [0.0] * len(self.members)
        for arm, prompts, toks, n_out, _ in self.finished:
            a = self.members[arm]["arch"]
            rows = prompts.shape[0]
            prefill_flops[arm] += rows * sum(
                self.ref.flops_per_token(a, p) for p in range(1, s + 1))
            for n in n_out:
                gen_flops[arm] += sum(self.ref.flops_per_token(a, s + j)
                                      for j in range(1, int(n) + 1))
        return {"members": [m["arch"] for m in self.members],
                "decodes": self.decodes, "begin_s": self.begin_s,
                "model_flops": sum(prefill_flops) + sum(gen_flops),
                "reference": self.ref, "wall_s": self.wall}
