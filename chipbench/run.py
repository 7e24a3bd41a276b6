"""Run one cell of the chip benchmark once.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for. The cell's configuration, traffic mix, driver, reference and
per-layer metric readers are found by name (see `bench/cells.py`). JAX's
persistent compilation cache goes to `JAX_COMPILATION_CACHE_DIR` where that
is set, else to `.jax_cache/` at the root of the checkout, so only the first
run of a cell compiles. Exits non-zero, with no result line, where JAX finds
no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import cells, harness
    try:
        cell = cells.resolve(args.workload)
    except cells.CellError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    return harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            T_START)


if __name__ == "__main__":
    sys.exit(main())
