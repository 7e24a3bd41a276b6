"""The trace-to-metrics reduction on a constructed trace, with every number
worked out by hand."""
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
