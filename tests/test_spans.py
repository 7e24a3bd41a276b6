"""Host spans and work counters of the served path (`repro.spans`,
`ContinuousScheduler.stats`), at tiny widths through `FleetService`.

A profiler trace of a round holds every span with its counts, nested as the
layers are (admit, decode and harvest inside a tick; relax and select inside
a route) and on the clock of an enclosing annotation; the counters add up to
what the completions carry; and nothing served or learned changes when the
profiler records."""
import collections
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.policies import PolicyConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import model as M
from repro.router.cloud import Replica, SchedulingCloud
from repro.router.service import FleetService
from repro.serving.engine import Engine
from repro.serving.scheduler import ContinuousScheduler, ReplicaRunner, Request

VOCAB = 64
SLOTS = 4
CHUNK = 4
ROWS = 2

SPAN_COUNTS = {
    "repro.route": {"tenant", "round"},
    "repro.route.relax": {"tenant"},
    "repro.route.select": {"tenant", "arms"},
    "repro.tick": {"replica", "tick", "resident_rows"},
    "repro.admit": {"replica", "requests", "rows", "prompt_len", "wait_us",
                    "wait_max_us"},
    "repro.decode": {"replica", "slots", "steps", "live_rows"},
    "repro.harvest": {"replica", "done", "tokens"},
    "repro.feedback": {"tenant", "arm", "rid", "ok", "cascaded"},
}


@pytest.fixture(scope="module")
def engines():
    cfg = dataclasses.replace(get_config("h2o-danube-3-4b").reduced(),
                              vocab=VOCAB)
    return [Engine(cfg, M.init_params(cfg, jax.random.PRNGKey(i)),
                   max_len=32, eos_id=0, temperature=0.7) for i in range(2)]


def fleet(engines, kind="awc", tenants=3, **kw):
    """Tenants x ROWS-row requests into SLOTS slots a replica: more rows
    than slots, so later requests queue behind earlier ones."""
    pcfg = PolicyConfig(kind=kind, k=2, n=2, rho=1e9, delta=0.1)
    cloud = SchedulingCloud(pcfg, [Replica(f"m{i}", e, 0.001 * (1 + i))
                                   for i, e in enumerate(engines)])
    data = SyntheticLM(DataConfig(vocab=VOCAB, seq_len=8, global_batch=ROWS,
                                  seed=0))
    # a success threshold no answer reaches: every AWC round cascades
    return FleetService(pcfg, cloud, data, n_tenants=tenants, n_slots=SLOTS,
                        chunk=CHUNK, seed=3, prompt_len=8, max_new=6,
                        success_threshold=2.0, **kw)


def traced(tmp_path, fn):
    """Run ``fn`` under the profiler inside a ``test.outer`` annotation;
    return the host events as {name: [(start, end, stats)]}."""
    from jax.profiler import ProfileData, TraceAnnotation
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("test.outer"):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    events = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("repro.", "test.")):
                    events[e.name].append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         {k: v for k, v in e.stats}))
    return out, events


def inside(ev, outer):
    return any(o[0] <= ev[0] and ev[1] <= o[1] for o in outer)


def test_one_awc_round_traces_every_span(engines, tmp_path):
    fs = fleet(engines)
    fs.step()                                   # compile outside the trace
    logs, ev = traced(tmp_path, fs.step)
    assert set(SPAN_COUNTS) <= set(ev)
    for name, keys in SPAN_COUNTS.items():
        for e in ev[name]:
            assert set(e[2]) == keys, name
    outer, = ev["test.outer"]
    for name in SPAN_COUNTS:
        assert all(inside(e, [outer]) for e in ev[name]), name
    for name in ("repro.admit", "repro.decode", "repro.harvest"):
        for e in ev[name]:
            ticks = [t for t in ev["repro.tick"]
                     if t[2]["replica"] == e[2]["replica"]]
            assert inside(e, ticks), name
    for name in ("repro.route.relax", "repro.route.select"):
        for e in ev[name]:
            routes = [r for r in ev["repro.route"]
                      if r[2]["tenant"] == e[2]["tenant"]]
            assert inside(e, routes), name
    # feedback fires between ticks, never inside one or inside a route
    for e in ev["repro.feedback"]:
        assert not inside(e, ev["repro.tick"] + ev["repro.route"])
    # the counts say what the round did
    assert sorted(e[2]["tenant"] for e in ev["repro.route"]) == [0, 1, 2]
    assert {e[2]["round"] for e in ev["repro.route"]} == {2}
    comps = fs.last_completions
    assert len(ev["repro.feedback"]) == len(comps)
    assert sorted(e[2]["rid"] for e in ev["repro.feedback"]) == \
        sorted(c.request.rid for c in comps)
    n_cascaded = sum(e[2]["cascaded"] for e in ev["repro.feedback"])
    assert n_cascaded == sum(int(log.action.sum()) - 1 for log in logs)
    assert sum(e[2]["done"] for e in ev["repro.harvest"]) == len(comps)
    assert sum(e[2]["tokens"] for e in ev["repro.harvest"]) == \
        sum(int(c.result.out_lens.sum()) for c in comps)
    assert sum(e[2]["requests"] for e in ev["repro.admit"]) == len(comps)
    assert sum(e[2]["rows"] for e in ev["repro.admit"]) == ROWS * len(comps)
    assert all(e[2]["slots"] == SLOTS and e[2]["steps"] == CHUNK
               and 0 < e[2]["live_rows"] <= SLOTS for e in ev["repro.decode"])
    assert all(e[2]["arms"] == int(log.action.sum())
               for e, log in zip(sorted(ev["repro.route.select"],
                                        key=lambda e: e[2]["tenant"]), logs))


@pytest.mark.parametrize("kind", ["awc", "suc"])
def test_profiler_on_and_off_serve_the_same(engines, tmp_path, kind):
    def run(profile):
        fs = fleet(engines, kind)

        def rounds():
            logs, comps = [], []
            for _ in range(2):
                logs += fs.step()
                comps += fs.last_completions
            return logs, comps
        return fs, traced(tmp_path, rounds)[0] if profile else rounds()

    (fa, (la, comps_a)), (fb, (lb, comps_b)) = run(False), run(True)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(a.action, b.action)
        np.testing.assert_array_equal(a.observed, b.observed)
        np.testing.assert_array_equal(a.rewards, b.rewards)
        assert a.cost == b.cost
    assert len(comps_a) == len(comps_b)
    for ca, cb in zip(comps_a, comps_b):
        assert (ca.request.tenant, ca.request.arm) == \
            (cb.request.tenant, cb.request.arm)
        np.testing.assert_array_equal(ca.result.tokens, cb.result.tokens)
        np.testing.assert_array_equal(ca.result.out_lens, cb.result.out_lens)
        np.testing.assert_array_equal(ca.result.logprobs, cb.result.logprobs)
    for ta, tb in zip(fa.tenants, fb.tenants):
        for key in ("mu_hat", "c_hat", "t_mu", "t_c"):
            np.testing.assert_array_equal(getattr(ta.local, key),
                                          getattr(tb.local, key))


def test_counters_add_up_to_the_completions(engines):
    fs = fleet(engines, "suc")
    comps = []
    for _ in range(2):
        fs.step()
        comps += fs.last_completions
    for arm, st in enumerate(fs.sched.stats()):
        mine = [c for c in comps if c.request.arm == arm]
        assert st["tokens_out"] == sum(int(c.result.out_lens.sum())
                                       for c in mine)
        assert st["admitted"] == len(mine)
        assert st["prefill_rows"] == ROWS * len(mine)
        assert 1 <= st["prefill_calls"] <= len(mine)
        assert st["decode_chunks"] > 0
        assert st["slot_steps"] == SLOTS * CHUNK * st["decode_chunks"]
        assert st["queue_wait_s"] > 0
        assert st["failures"] == st["retries"] == 0


def test_a_request_behind_full_slots_reads_its_wait(engines, tmp_path):
    """Two requests that each fill every slot: the second is queued before
    the first is admitted and admitted only once the first leaves, so it
    waits at least the time between the two admissions."""
    runner = ReplicaRunner(engines[0], n_slots=SLOTS, chunk=CHUNK)
    prompts = np.arange(SLOTS * 8, dtype=np.int32).reshape(SLOTS, 8) % VOCAB
    sched = ContinuousScheduler([runner])

    def serve():
        for seed in (1, 2):
            sched.submit(Request(tenant=0, arm=0, prompts=prompts,
                                 max_new=2 * CHUNK, seed=seed))
        return sched.drain()

    comps, ev = traced(tmp_path, serve)
    first, second = sorted(ev["repro.admit"])
    assert first[2]["requests"] == second[2]["requests"] == 1
    # (the host clock and the trace's clock agree to within microseconds;
    # the two admissions lie two decode chunks apart)
    assert second[2]["wait_us"] * 1e3 >= 0.9 * (second[0] - first[0]) > 0
    assert second[2]["wait_us"] > first[2]["wait_us"]
    assert second[2]["wait_max_us"] == second[2]["wait_us"]
    st, = sched.stats()
    assert st["admitted"] == 2 and len(comps) == 2
    assert st["queue_wait_s"] == pytest.approx(
        1e-6 * (first[2]["wait_us"] + second[2]["wait_us"]))


def test_admitted_counts_every_attempt(engines):
    """Under injected failures each retry is queued and admitted again:
    admissions = submissions + retries, and a retry's wait runs from its
    own requeue."""
    from repro.serving.faults import FaultPlan, HealthPolicy
    fs = fleet(engines, "suc",
               fault_plan=FaultPlan(fault_seed=5, fail_prob=0.5),
               health=HealthPolicy(max_retries=2, quarantine_after=10**9))
    submitted = 0
    for _ in range(3):
        fs.step()
        submitted += len(fs.last_completions)
    stats = fs.sched.stats()
    assert sum(st["retries"] for st in stats) > 0
    for st, runner in zip(stats, fs.sched.runners):
        assert st["admitted"] == runner._n_submitted + st["retries"]
    assert sum(runner._n_submitted for runner in fs.sched.runners) \
        == submitted
