"""The trace-to-metrics reduction on a constructed trace, with every number
worked out by hand, and the program's spans kept from a recorded one."""
import pytest

from bench import device, kernels, trace
from bench.trace import Event, Trace

V5E = device.peaks_for("TPU v5 lite")
MS = 1e6     # ns


def ev(name, a, b, **stats):
    return Event(name, a * MS, b * MS, stats)


TOPN = ("%topn_lp.32 = f32[1024,1]{1,0:T(8,128)} custom-call(f32[1024,128]"
        "{1,0:T(8,128)} %a, f32[1024,128]{1,0} %b, s32[1024,1]{1,0} %c), "
        "custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
        "{f32[1024,128]{1,0}, f32[1024,128]{1,0}, s32[1024,1]{1,0}}")
AWC = ("%awc_fw.10 = (f32[32,8,128]{2,1,0}, f32[32,8,25]{2,1,0}) custom-call("
       "f32[32,8,128]{2,1,0:T(8,128)S(1)} %z, f32[32,8,128]{2,1,0} %mu, "
       "f32[32,8,128]{2,1,0} %c, f32[32,8,25]{2,1,0} %l, s32[32,8,1]{2,1,0} "
       "%n), custom_call_target=\"tpu_custom_call\", "
       "operand_layout_constraints={f32[32,8,128]{2,1,0}}")
ALLOC = ("%custom-call.95 = s32[9]{0:T(128)S(1)} custom-call(), "
         "custom_call_target=\"AllocateBuffer\"")


def window_trace():
    """A 100 ms window: busy 10-30 (two overlapping ops), 50-60, 95-110
    (clipped to 95-100); idle 0-10, 30-50, 60-95."""
    ops = [ev("fusion.1", 10, 25), ev("fusion.2", 20, 30),
           ev(TOPN, 50, 60), ev(AWC, 95, 110)]
    modules = [ev("jit__scan(1)", 10, 30), ev("jit__scan(2)", 50, 60),
               ev("jit_decode(3)", 95, 110)]
    spans = [ev("chipbench.window", 0, 100),
             ev("chipbench.drain", 0, 100),
             ev("chipbench.begin_round", 32, 48),
             ev("chipbench.step.a", 60, 90)]
    return Trace(busy={"/device:TPU:0": trace.intervals(ops)},
                 modules={"/device:TPU:0": modules}, calls=ops[2:],
                 spans=spans, window=(0.0, 100 * MS))


def test_busy_and_idle_share():
    t = window_trace()
    assert trace.busy_s(t) == pytest.approx(0.035)
    assert trace.idle_share(t) == pytest.approx(65.0)
    assert t.window_s == pytest.approx(0.1)


def test_merge_clips_and_joins():
    got = trace.merge([(5, 8), (0, 3), (2, 4), (9, 20), (6, 7)], 1, 10)
    assert got.tolist() == [[1, 4], [5, 8], [9, 10]]
    assert trace.gaps([(5, 8), (0, 3)], 1, 10).tolist() == \
        [[3, 5], [8, 10]]


def test_program_seconds_inside_the_window():
    got = trace.program_seconds(window_trace())
    assert got == pytest.approx({"jit__scan": 0.030, "jit_decode": 0.005})


def test_gaps_charged_to_the_innermost_span():
    # 0-10 and 60-95 sit in drain and step.a (midpoint 77.5 is in step.a);
    # 30-50 has midpoint 40, inside begin_round
    got = trace.gap_attribution(window_trace())
    assert got == pytest.approx({"drain": 0.010, "begin_round": 0.020,
                                 "step.a": 0.035})


def test_no_device_no_idle_share():
    t = Trace(busy={}, modules={}, calls=[], spans=[], window=(0.0, 1e9))
    assert trace.idle_share(t) is None


def test_kernels_told_apart_by_their_shapes():
    t = window_trace()
    plain = ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)", 0, 1)
    got = [kernels.classify(e) for e in [plain, ev(ALLOC, 0, 1)] + t.calls]
    assert got == [None, None, ("topn_lp", (1024,)), ("awc_fw", (256, 25))]


def test_kernel_roofline_by_hand():
    t = window_trace()
    ctx = {"arms": 9, "peaks": V5E}
    # topn_lp: 1024 rows x (2*9 + 2) floats = 81920 bytes over 819 GB/s,
    # against 10 ms of device time
    want = 100.0 * (81920 / 819e9) / 0.010
    assert kernels.roofline(t, ctx, "topn_lp") == pytest.approx(want)
    # awc_fw: a third of the 15 ms call lies in the window, so a third of
    # its least time over 5 ms
    nbytes = 4 * 256 * (4 * 9 + 2 * 25 + 1)
    want = 100.0 * (nbytes / 819e9) / 3 / 0.005
    assert kernels.roofline(t, ctx, "awc_fw") == pytest.approx(want)


def test_kernel_absent_reads_nothing():
    t = window_trace()
    t.calls = t.calls[1:]
    assert kernels.roofline(t, {"arms": 9, "peaks": V5E}, "topn_lp") is None


# ------------------------------------------------- the program's spans
def test_load_keeps_the_program_spans_with_their_counts():
    import jax
    from jax.profiler import TraceAnnotation
    rec = trace.Recorder()
    rec.start()
    try:
        with TraceAnnotation(trace.WINDOW_SPAN):
            with TraceAnnotation("repro.x", wait_us=1500, requests=3):
                jax.block_until_ready(jax.jit(lambda a: a + 1)(1.0))
            with TraceAnnotation("other.y", requests=9):
                pass
    finally:
        rec.stop()
    t = rec.load()
    x, = [s for s in t.spans if s.name == "repro.x"]
    assert x.stats["wait_us"] == 1500 and x.stats["requests"] == 3
    assert not [s for s in t.spans if s.name == "other.y"]
    assert t.window[0] <= x.start <= x.end <= t.window[1]
    assert trace.spans_in_window(t, "repro.x") == [x]


def test_gaps_charged_to_an_inner_program_span():
    # step.a 0-100 holds repro.tick 10-90, which holds repro.harvest 40-60;
    # idle 20-25 (midpoint in the tick only) and 30-65 (in the harvest)
    spans = [ev("chipbench.window", 0, 100), ev("chipbench.step.a", 0, 100),
             ev("repro.tick", 10, 90), ev("repro.harvest", 40, 60)]
    busy = trace.intervals([ev("f", 0, 20), ev("f", 25, 30),
                            ev("f", 65, 100)])
    t = Trace(busy={"/device:TPU:0": busy}, modules={}, calls=[],
              spans=spans, window=(0.0, 100 * MS))
    assert trace.gap_attribution(t) == pytest.approx(
        {"repro.tick": 0.005, "repro.harvest": 0.035})


def spans_trace():
    """A 100 ms window. Spans that start before it (admit at -5) or at its
    end (feedback at 100) are not the window's."""
    spans = [ev("chipbench.window", 0, 100),
             ev("repro.admit", -5, 2, wait_us=9999, requests=7),
             ev("repro.admit", 10, 12, wait_us=3000, requests=2),
             ev("repro.admit", 40, 41, wait_us=1000, requests=2),
             ev("repro.admit", 99, 105, wait_us=5000, requests=1),
             ev("repro.feedback", 20, 21.5), ev("repro.feedback", 50, 52.5),
             ev("repro.feedback", 100, 130),
             ev("repro.harvest", 30, 33), ev("repro.harvest", 60, 65)]
    return Trace(busy={}, modules={}, calls=[], spans=spans,
                 window=(0.0, 100 * MS))


@pytest.mark.parametrize("name, want", [
    # (3000 + 1000 + 5000) us over 5 requests
    ("queue_wait_ms", 1.8),
    # (1.5 + 2.5) / 2 ms
    ("feedback_ms_per_completion", 2.0),
    # (3 + 5) / 2 ms
    ("harvest_ms_per_tick", 4.0)])
def test_span_readers_by_hand(name, want):
    from bench import cells
    mod = cells.load_module(cells.metric_path(name), name)
    assert mod.read(spans_trace(), {}) == pytest.approx(want)
    t = spans_trace()
    t.spans = t.spans[:1]
    assert mod.read(t, {}) is None
