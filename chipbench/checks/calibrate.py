"""Readings for the check's limits, on the chip, at a cell's own size.

  python3 chipbench/checks/calibrate.py --workload <cell> \
      --seeds 1,2,...,12 --control-seeds 1,2,3 --seconds <s>

For each seed, in this one process: the cell's driver is set up as a run
sets it up, drives a short window at the cell's own load, frees its state
and reads every number the check compares (the program's readings). For
each control seed it also reads the same numbers with the reference at the
nearest lower precision in the program's place (the control's readings).
One JSON line per seed; the last line gives, per number, the largest
program reading and the smallest control reading, between which the limit
is set. The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from bench import cells, device, harness

    cell = cells.resolve(args.workload)
    device.chips(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    lower, upper = {}, {}
    for seed in seeds:
        d = harness.make_driver(cell, seed)
        d.setup()
        d.window(args.seconds)
        d.free()
        prog = d.check()
        line = {"seed": seed, "program": prog}
        for k, v in prog.items():
            lower[k] = max(lower.get(k, v), v)
        if seed in ctl_seeds:
            ctl = d.control()
            line["control"] = ctl
            for k, v in ctl.items():
                upper[k] = min(upper.get(k, v), v)
        print(json.dumps(line), flush=True)
        del d
        gc.collect()
    print(json.dumps({"workload": cell.name, "lower": lower,
                      "upper": upper,
                      "limits": harness.limits_for(cell)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
