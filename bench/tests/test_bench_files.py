"""Every cell, configuration, traffic mix and metric is found by name, and
``BENCHMARK.json`` keeps to its schema."""
import importlib
import json
import os
import re

import pytest

from bench import harness, traffic

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


def test_top_level_keys_and_command():
    assert set(BENCH) == TOP
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found(cell):
    w = harness.find(BENCH["workloads"], cell, "workload")
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] == 1 and 1 <= len(w["why"]) <= 200
    tr = traffic.load(w["traffic"])
    driver = importlib.import_module(f"bench.drivers.{tr['kind']}")
    for fn in ("setup", "reseed", "window", "sample"):
        assert callable(getattr(driver, fn))
    limits = json.load(open(os.path.join(
        harness.BENCH_DIR, "limits", f"{cell}.json")))
    assert {"missing", "failed_over_reference", "compared_fewest",
            "pos_gap_max", "pos_gap_median"} <= set(limits)
    run = harness.load_run(cell, 1, 1.0, False)
    assert run.config["name"] == w["config"]
    for m in METRICS:
        if "workloads" in m and cell in m["workloads"]:
            assert m["name"] in {x["name"] for x in METRICS}


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_is_well_formed(name):
    c = harness.find(BENCH["configs"], name, "config")
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["file"].startswith("bench/configs/")
    assert c["source"].startswith("https://")
    cfg = json.load(open(os.path.join(harness.ROOT, c["file"])))
    assert cfg["name"] == name
    assert {"problem", "program", "spec", "dtype", "assumed"} <= set(cfg)
    assert importlib.import_module(
        f"bench.reference.models.{cfg['problem']['model']}")
    assert len(c["reduced"]) <= 16


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_has_a_reader(metric):
    m = next(x for x in METRICS if x["name"] == metric)
    allowed = {"name", "unit", "better", "bound", "source", "workloads",
               "layer", "moves"}
    assert set(m) <= allowed and NAME.match(m["name"])
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")
    for cell in m.get("workloads", []):
        assert cell in CELLS
    if m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    else:
        assert "bound" not in m
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    path = os.path.join(harness.BENCH_DIR, "metrics", f"{metric}.py")
    assert "def read(run)" in open(path).read()


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    def reports(group):
        return [m["name"] for m in BENCH[group]
                if cell in m.get("workloads", [cell])]
    e2e = reports("end_to_end")
    assert "setup_s" in e2e and len(e2e) >= 2
    assert reports("per_layer")
