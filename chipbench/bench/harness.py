"""One run of one cell: set-up, the measured window, the check, the result.

The result is the last line of standard output, one JSON object:
  correct, attempted, failed, metrics, device[, breakdown], checks
`metrics` holds the cell's end-to-end metrics (``trace=False``) or its
per-layer metrics (``trace=True``). `checks` comes last: every number the
check compared, with its limit. The same pairs are the last lines of
standard error.
"""
from __future__ import annotations

import gc
import json
import sys
import time
from typing import Dict

from bench import cells, device as device_mod, trace as trace_mod


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def limits_for(cell: cells.Cell) -> Dict[str, float]:
    """The check's limits, as the configuration states them."""
    return dict(cell.config["limits"])


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """{name: {"value", "limit"}}; a number with no limit is an error."""
    out = {}
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for the checked number {name!r}")
        out[name] = {"value": value, "limit": limits[name]}
    return out


def is_correct(checks: Dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


class CompileCounter:
    """Counts programs compiled, or loaded from the persistent cache, while
    on: either means a shape the set-up did not warm."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, secs: float, **kw) -> None:
        if self.on and name in self.EVENTS:
            self.n += 1


def make_driver(cell: cells.Cell, seed: int):
    ref = cells.load_module(cells.reference_path(cell.config_name),
                            cell.config_name)
    drv = cells.load_module(cells.driver_path(cell.config["driver"]),
                            cell.config["driver"])
    return drv.Driver(cell, seed, ref, log)


def read_layers(cell: cells.Cell, tr: trace_mod.Trace, ctx: Dict
                ) -> Dict[str, Dict]:
    out = {}
    for m in cell.per_layer:
        mod = cells.load_module(cells.metric_path(m["name"]), m["name"])
        value = mod.read(tr, ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> int:
    import jax

    try:
        devs = device_mod.chips(cell.chips)
        peaks = device_mod.peaks_for(devs[0].device_kind)
    except device_mod.DeviceError as e:
        log(f"chipbench: {e}")
        return 3
    log(f"cell {cell.name}: seed {seed}, {seconds} s, trace {int(trace)}, "
        f"{devs[0].device_kind} x{len(jax.devices())}, jax {jax.__version__}")

    driver = make_driver(cell, seed)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s}")

    counter = CompileCounter()
    rec = trace_mod.Recorder() if trace else None
    if rec:
        # a traced run reads layers, not end-to-end numbers: the mix says
        # how long a traced window needs to be (a few rounds or calls)
        seconds = min(seconds, float(cell.traffic.get("trace_seconds",
                                                      seconds)))
        rec.start()
    counter.on = True
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
        e2e = driver.window(seconds)
    counter.on = False
    if rec:
        rec.stop()
    log(f"compiles_in_window {counter.n}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)

    driver.free()
    gc.collect()
    t = time.perf_counter()
    checks = judge(driver.check(), limits_for(cell))
    log(f"check_s {time.perf_counter() - t}")
    correct = is_correct(checks)

    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": int(driver.attempted),
              "failed": int(driver.failed)}
    if trace:
        tr = rec.load()
        ctx = driver.layer_context()
        ctx.update({"peaks": peaks, "window_s": tr.window_s})
        result["metrics"] = read_layers(cell, tr, ctx)
        dev["busy_s"] = trace_mod.busy_s(tr)
        dev["window_s"] = tr.window_s
        result["device"] = dev
        result["breakdown"] = {
            "device_ops": trace_mod.top(trace_mod.program_seconds(tr)),
            "idle_gaps": trace_mod.top(trace_mod.gap_attribution(tr))}
    else:
        e2e["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        result["metrics"] = {k: {"value": float(v), "unit": units[k]}
                             for k, v in e2e.items() if k in units}
        result["device"] = dev
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0
