"""BENCHMARK.json: every cell resolves to its files, and the file keeps the
benchmark's own rules on names, units, sources and bounds; each cell's tiny
sizes for the CPU tests, and the pool members' keys against the program's."""
import json
import os
import re

import pytest

from bench import cells
from tiny import bench, tiny

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


# ------------------------------------------------------ tiny sizes
DENSE_TINY = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab": 512}
SSM_TINY = {"n_layers": 2, "d_model": 64, "ssm_state": 16,
            "ssm_head_dim": 16, "ssm_chunk": 8, "vocab": 512}
POOL_TRAFFIC = {"tenants": 2, "prompt_len": 8, "max_new": 8,
                "stream_vocab": 256, "rows": 2, "check_rounds": 1,
                "warm_rounds": 1}


@pytest.mark.parametrize("cell",
                         [w["name"] for w in WITH_HELD_OUT["workloads"]])
def test_tiny_sizes_of_every_cell(cell):
    c = cells.resolve(cell, WITH_HELD_OUT)
    t = tiny(c)
    if c.config["driver"] == "fleet":
        assert t.config["tenants"] == 24
        assert t.traffic["rounds_per_call"] == 4
        return
    for full, small in zip(c.config["members"], t.config["members"]):
        want = dict(full["arch"])
        want.update({"dense": DENSE_TINY, "ssm": SSM_TINY}[
            full["arch"]["family"]])
        assert small["arch"] == want
    assert (t.config["slots"], t.config["max_len"], t.config["chunk"]) == \
        (8, 64, 4)
    assert {k: t.traffic[k] for k in POOL_TRAFFIC} == POOL_TRAFFIC
    assert c.config["members"][0]["arch"]["d_model"] == 3840   # a copy


def pool_cell(family):
    return cells.Cell(
        name="new-pool", chips=1, config_name="new-pool",
        traffic_name="t", traffic={},
        config={"driver": "pool", "members": [
            {"name": "m", "arch": {"family": family, "d_model": 2688,
                                   "n_groups": 8}}]},
        end_to_end=[], per_layer=[])


def test_tiny_sizes_of_a_new_family_come_from_its_reference(
        tmp_path, monkeypatch):
    ref = tmp_path / "new-pool.py"
    ref.write_text("def tiny_arch(arch):\n"
                   "    return {'d_model': 64, 'n_groups': 2}\n")
    monkeypatch.setattr(cells, "reference_path",
                        lambda name: str(tmp_path / (name + ".py")))
    t = tiny(pool_cell("hybrid-moe"))
    assert t.config["members"][0]["arch"] == \
        {"family": "hybrid-moe", "d_model": 64, "n_groups": 2}


def test_a_reference_without_tiny_arch_is_refused(tmp_path, monkeypatch):
    (tmp_path / "new-pool.py").write_text("X = 1\n")
    monkeypatch.setattr(cells, "reference_path",
                        lambda name: str(tmp_path / (name + ".py")))
    with pytest.raises(cells.CellError, match=r"new-pool\.py.*tiny_arch"):
        tiny(pool_cell("dense"))


# ------------------------------------------------- pool members' keys
def arch_fields():
    import dataclasses

    from repro.configs.base import ArchConfig
    return {f.name for f in dataclasses.fields(ArchConfig)}


def test_pool_members_use_only_the_program_s_arch_keys():
    pools = []
    for c in WITH_HELD_OUT["configs"]:
        with open(os.path.join(cells.ROOT, c["file"])) as f:
            config = json.load(f)
        if config["driver"] == "pool":
            pools.append(config)
    assert pools
    for config in pools:
        for m in config["members"]:
            assert set(m["arch"]) <= arch_fields(), m["name"]


def test_pool_driver_refuses_an_unknown_arch_key():
    from bench import harness
    c = tiny(cells.resolve("pool-awc-short", BENCH))
    c.config["members"][1]["arch"]["n_groups"] = 8
    d = harness.make_driver(c, 7)
    with pytest.raises(cells.CellError, match="mamba2-780m.*n_groups"):
        d._build()
    assert not hasattr(d, "params")       # refused before any weight
