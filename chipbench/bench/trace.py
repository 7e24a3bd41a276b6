"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an `.xplane.pb`; `jax.profiler.ProfileData` reads
it. Device planes (`/device:TPU:<n>`) carry one event per executed program
(line "XLA Modules") and per operation inside it (line "XLA Ops"); the host
plane carries the benchmark's own spans (`chipbench.*`) and the program's
(`repro.*`, with their counts as the event's stats), both written by
`jax.profiler.TraceAnnotation`. All timestamps share one clock.

The reduction:
  busy      union of the device's operation intervals inside the window;
  idle      1 - busy / window;
  programs  device seconds per program name (the breakdown's device_ops);
  gaps      the idle intervals, each charged to the innermost host span,
            the benchmark's or the program's, that covers its midpoint
            (the breakdown's idle_gaps).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."
PROGRAM_PREFIX = "repro."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float                # ns
    end: float                  # ns
    stats: Dict[str, object]

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    busy: Dict[str, np.ndarray]       # device plane -> (N, 2) op intervals
    modules: Dict[str, List[Event]]   # device plane -> program events
    calls: List[Event]                # custom-call operations (kernels)
    spans: List[Event]                # host spans: chipbench.* and repro.*
    window: Tuple[float, float]       # ns, from the window span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def module_name(name: str) -> str:
    """Program name without the run suffix: `jit__decode_chunk(12)` ->
    `jit__decode_chunk`."""
    return re.sub(r"\(\d+\)$", "", name)


def intervals(events: Sequence[Event]) -> np.ndarray:
    return np.asarray([(e.start, e.end) for e in events],
                      np.float64).reshape(-1, 2)


def merge(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Union of (N, 2) intervals, clipped to [lo, hi], as sorted disjoint
    pieces (M, 2)."""
    iv = np.asarray(iv, np.float64).reshape(-1, 2)
    iv = np.clip(iv, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if not len(iv):
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(iv) - 1)
    return np.stack([iv[first, 0], reach[last]], 1)


def clipped_ns(e: Event, window: Tuple[float, float]) -> float:
    """The part of an event inside the window."""
    return max(0.0, min(e.end, window[1]) - max(e.start, window[0]))


def gaps(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The idle intervals of [lo, hi] between busy pieces, (G, 2)."""
    m = merge(iv, lo, hi)
    starts = np.concatenate([[lo], m[:, 1]])
    ends = np.concatenate([m[:, 0], [hi]])
    keep = ends > starts
    return np.stack([starts[keep], ends[keep]], 1)


def busy_s(trace: Trace) -> float:
    """Busy seconds inside the window, averaged over the devices traced."""
    per = [float(np.sum(np.diff(merge(iv, *trace.window), axis=1)))
           for iv in trace.busy.values()]
    return sum(per) / len(per) / 1e9 if per else 0.0


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy / window, in percent; None where no device was traced."""
    if not trace.busy or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s(trace) / trace.window_s)


def program_seconds(trace: Trace) -> Dict[str, float]:
    """Device seconds per program inside the window, averaged over devices."""
    tot: Dict[str, float] = collections.Counter()
    n = max(len(trace.modules), 1)
    for evs in trace.modules.values():
        for e in evs:
            d = clipped_ns(e, trace.window)
            if d > 0:
                tot[module_name(e.name)] += d / 1e9 / n
    return dict(tot)


def calls_inside(trace: Trace) -> List[Event]:
    """Custom-call operation events that overlap the window."""
    lo, hi = trace.window
    return [e for e in trace.calls if e.end > lo and e.start < hi]


def modules_named(trace: Trace, name: str) -> List[Event]:
    lo, hi = trace.window
    return [e for evs in trace.modules.values() for e in evs
            if e.end > lo and e.start < hi and module_name(e.name) == name]


def spans_in_window(trace: Trace, name: str) -> List[Event]:
    """The host spans of one name that start inside the window."""
    lo, hi = trace.window
    return [s for s in trace.spans if s.name == name and lo <= s.start < hi]


def gap_attribution(trace: Trace) -> Dict[str, float]:
    """Idle seconds inside the window, by the innermost host span that
    covers each gap's midpoint ("none" where no span does), the benchmark's
    named without its `chipbench.` prefix. Averaged over the devices
    traced."""
    tot: Dict[str, float] = collections.Counter()
    by_name: Dict[str, List[Event]] = collections.defaultdict(list)
    for s in trace.spans:
        if s.name != WINDOW_SPAN:
            by_name[s.name].append(s)
    # spans of one name never overlap: the last one to start before a
    # point is the only one of that name that can cover it
    index = {n: (sorted(v, key=lambda s: s.start),
                 sorted(s.start for s in v)) for n, v in by_name.items()}
    for iv in trace.busy.values():
        for a, b in gaps(iv, *trace.window):
            mid = 0.5 * (a + b)
            best = None
            for spans, starts in index.values():
                i = bisect.bisect_right(starts, mid) - 1
                if i >= 0 and spans[i].end >= mid and (
                        best is None or spans[i].dur < best.dur):
                    best = spans[i]
            name = best.name if best is not None else "none"
            tot[name.removeprefix(SPAN_PREFIX)] += \
                (b - a) / 1e9 / len(trace.busy)
    return dict(tot)


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


# ------------------------------------------------------------- recording
class Recorder:
    """Start and stop the profiler around the window; load what it wrote."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # spans come from TraceAnnotation
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()

    def load(self) -> Trace:
        try:
            paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            return load(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


_KEEP_STATS = ("hlo_module", "hlo_op", "long_name", "program_id")
CALL = re.compile(r"custom[-_]call|pallas|tpu_custom", re.I)


def _event(e, keep=()) -> Event:
    """``keep``: the names of the stats to keep, or None for all."""
    kept = {k: v for k, v in e.stats if keep is None or k in keep}
    return Event(e.name, float(e.start_ns),
                 float(e.start_ns) + float(e.duration_ns), kept)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    busy, modules, calls, spans = {}, {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = None
            for line in plane.lines:
                if line.name == OPS_LINE:
                    rows = []
                    for e in line.events:
                        rows.append((e.start_ns, e.start_ns + e.duration_ns))
                        if CALL.search(e.name):
                            calls.append(_event(e, _KEEP_STATS))
                    ops = np.asarray(rows, np.float64).reshape(-1, 2)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [_event(e) for e in line.events]
            if ops is not None and len(ops):
                busy[plane.name] = ops
            elif modules.get(plane.name):
                busy[plane.name] = intervals(modules[plane.name])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((SPAN_PREFIX, PROGRAM_PREFIX)):
                        spans.append(_event(e, None))
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if not win:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN} span")
    modules = {p: v for p, v in modules.items() if v}
    return Trace(busy=busy, modules=modules, calls=calls, spans=spans,
                 window=(win[0].start, win[0].end))
