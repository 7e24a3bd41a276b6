"""Host spans of the served path, on the profiler's clock.

`span(name, **counts)` is `jax.profiler.TraceAnnotation`: with no profiler
trace running it records nothing (about a microsecond a span); under
`jax.profiler.start_trace` it writes an event on the host timeline of the
same trace, and on the same clock, as the device's programs, with
``counts`` as the event's typed stats. Counts known only when the work is
done go on with ``set_metadata`` before the span closes:

    with span("repro.harvest", replica=0) as s:
        ...
        s.set_metadata(done=2, tokens=64)

Rules every span keeps:
  * its name is ``repro.<layer>[.<what>]``;
  * it never nests inside another span of the same name;
  * every count is a host value already at hand: a span adds no device
    read, sync or dispatch, so the program's outputs are the same with the
    profiler on or off.

The spans, outermost first:

  repro.route          MultiLLMService.begin_round       tenant, round
  repro.route.relax    LocalServer.relaxed_selection     tenant
  repro.route.select   SchedulingCloud.select            tenant, arms
  repro.tick           ReplicaRunner.step                replica, tick,
                                                         resident_rows
  repro.admit          one prefill bucket of admission   replica, requests,
                                                         rows, prompt_len,
                                                         wait_us, wait_max_us
  repro.decode         the decode_chunk dispatch         replica, slots,
                                                         steps, live_rows
  repro.harvest        the slot pulls through release    replica, done,
                                                         tokens
  repro.feedback       MultiLLMService._on_complete      tenant, arm, rid,
                                                         ok, cascaded

The scheduler's cumulative counters, the same quantities without a
profiler, are in `serving.scheduler.ContinuousScheduler.stats`.
"""
from jax.profiler import TraceAnnotation as span

__all__ = ["span"]
