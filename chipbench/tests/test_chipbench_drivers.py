"""Each cell's driver runs for a second at tiny sizes on the CPU, beside the
command's chip check rather than through it, and its check comes out
correct; the command itself refuses a machine with no TPU."""
import json
import os
import subprocess
import sys

import pytest

from bench import cells, device, harness
from tiny import bench, tiny

BENCH = bench()
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2 ** 31 + 12345          # above 32 signed bits, as the checks draw


@pytest.mark.parametrize("cell", CELLS)
def test_driver_runs_a_second_and_is_correct(cell):
    c = tiny(cells.resolve(cell, BENCH))
    d = harness.make_driver(c, SEED)
    d.setup()
    e2e = d.window(1.0)
    want = {m["name"] for m in c.end_to_end} - {"setup_s"}
    assert want <= set(e2e) and all(e2e[k] > 0 for k in want)
    d.free()
    checks = harness.judge(d.check(), harness.limits_for(c))
    assert harness.is_correct(checks), checks
    assert d.attempted > 0 and d.failed == 0
    ctx = d.layer_context()
    ctx.update({"peaks": device.peaks_for("TPU v5 lite"), "window_s": 1.0})
    if c.config["driver"] == "pool":
        for name in ("route_ms_per_round", "served_mfu"):
            mod = cells.load_module(cells.metric_path(name), name)
            value = mod.read(None, ctx)
            assert value is None or value > 0
        assert ctx["decodes"] and ctx["model_flops"] > 0


def test_same_seed_same_inputs():
    c = tiny(cells.resolve("fleet-suc", BENCH))
    a, b = (harness.make_driver(c, SEED) for _ in range(2))
    assert (a.cohort_seeds == b.cohort_seeds).all()
    assert (a.rho == b.rho).all()


def test_command_refuses_a_machine_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, os.path.join(cells.BENCH_DIR, "run.py"),
         "--workload", "pool-awc-short", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=cells.ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_result_line_keeps_checks_last():
    checks = harness.judge({"gap": 0.5}, {"gap": 1.0})
    assert harness.is_correct(checks)
    assert not harness.is_correct(harness.judge({"gap": 2.0}, {"gap": 1.0}))
    with pytest.raises(KeyError):
        harness.judge({"unlimited": 0.0}, {})
    line = json.dumps({"correct": True, "metrics": {}, "checks": checks})
    assert list(json.loads(line))[-1] == "checks"
