"""BENCHMARK.json: every cell resolves to its files, and the file keeps the
benchmark's own rules on names, units, sources and bounds."""
import json
import os
import re

import pytest

from bench import cells
from tiny import bench

BENCH = cells.load_benchmark()
WITH_HELD_OUT = bench()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell",
                         [w["name"] for w in WITH_HELD_OUT["workloads"]])
def test_cell_resolves_to_its_files(cell):
    c = cells.resolve(cell, WITH_HELD_OUT)
    assert c.config["name"] == c.config_name
    assert os.path.isfile(cells.reference_path(c.config_name))
    assert os.path.isfile(cells.driver_path(c.config["driver"]))
    for m in c.per_layer:
        assert os.path.isfile(cells.metric_path(m["name"]))
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, f"{cell} reports no per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in names
    assert c.chips in (1, 4)


def test_unknown_cell_is_refused():
    with pytest.raises(cells.CellError):
        cells.resolve("no-such-cell", BENCH)


def test_benchmark_keys_names_and_units():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(cells.ROOT, p))
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    cell_names = {w["name"] for w in BENCH["workloads"]}
    metric_names = set()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in metric_names
        metric_names.add(m["name"])
        assert set(m.get("workloads", cell_names)) <= cell_names
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_checked_number_has_a_limit():
    from bench import harness
    for w in BENCH["workloads"]:
        c = cells.resolve(w["name"], BENCH)
        lim = harness.limits_for(c)
        assert all(v >= 0 for v in lim.values())
        if c.config["driver"] == "pool":
            for m in c.config["members"]:
                assert "gap." + m["name"] in lim
