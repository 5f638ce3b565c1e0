"""Every cell, configuration and metric of BENCHMARK.json is found by name
from its own file, and the file keeps to the benchmark's contract."""

import json
import re

import pytest

from chipbench import bench

BENCH = bench.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len((bench.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    names += CELLS
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    assert all(0 < m["bound"] <= 0.25 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = bench.cell(name)
    assert cell["config"]["model"]["model_type"]
    assert cell["traffic"]["workers"] >= 1
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]
    moved = {m["moves"] for m in cell["per_layer"]}
    assert moved <= e2e
    # every number the cell's check compares has its limit
    expected = {"grad", "update", "moved_diff"}
    if cell["traffic"]["defense"] != "mean":
        expected |= {"dist", "good"}
    # loss is left out only where no control or fault gives it an upper
    # reading (PERF.md gives the readings)
    assert expected <= set(cell["limits"]) <= expected | {"loss"}


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_and_reference_found_by_name(name):
    cfg = bench.config(name)
    ref = bench.reference(cfg["model"]["model_type"])
    for fn in ("init", "loss", "matmul_params", "mixer_flops_per_token"):
        assert callable(getattr(ref, fn))
    assert {"source", "model", "program", "reduced", "deployment",
            "param_count"} <= set(cfg)
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_found_by_name(metric):
    read = bench.metric_reader(metric["name"])
    assert callable(read)
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    for cell in metric.get("workloads", []):
        assert cell in CELLS


def test_metric_reader_returns_nothing_without_its_events():
    ctx = {"trace": {"ops": {"fusion.1": 0.5}, "window_s": 1.0,
                     "busy_s": 0.5},
           "host": {"steps": 10, "window_s": 1.0, "blocked_s": 0.9},
           "chips": 1, "peaks": bench.peaks("TPU v5 lite"),
           "step_flops": 1e12, "gram_bytes": 1e9}
    assert bench.metric_reader("gram_kernel_ms")(ctx) is None
    assert bench.metric_reader("gram_roofline")(ctx) is None
    assert bench.metric_reader("idle_share")(ctx) == pytest.approx(50.0)
    assert bench.metric_reader("host_ms_per_step")(ctx) == pytest.approx(10.0)


def test_command_and_paths():
    assert BENCH["command"][0] == "python3"
    for word in BENCH["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in BENCH["paths"])
    for p in BENCH["paths"]:
        assert (bench.ROOT / p).is_dir()
    json.dumps(BENCH)
