"""What the benchmark finds by name, and that a later change can add a
configuration, a cell and a per-layer metric as new files alone."""

import hashlib
import json
import re
import time

import pytest

from benchmarks.chip import harness, run

from conftest import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_names_files_that_exist():
    assert SPEC["command"][1] == "benchmarks/chip/run.py"
    assert set(SPEC["paths"]) == {"benchmarks/chip", "tests/benchmark"}
    for c in SPEC["configs"]:
        assert NAME.match(c["name"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (cell.spec["config"], cell.spec["traffic"], cell.chips) == (
            w["config"], w["traffic"], w["chips"])
        harness.load_module("entries", cell.traffic["entry"])
    names = set(harness.metric_names())
    for m in SPEC["per_layer"]:
        assert m["name"] in names
        assert harness.load_module("metrics", m["name"]).UNIT == m["unit"]
    assert [m["name"] for m in SPEC["end_to_end"]] == ["photons_per_s",
                                                      "setup_s"]


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_each_cell_checks_what_its_layers_produce(cell):
    c = harness.load_cell(cell)
    limits = c.spec["limits"]
    assert limits["launched_gap"] == 0
    assert {"totals_z", "fluence_z"} <= set(limits)
    if c.config.get("detectors"):
        assert limits["overflow"] == limits["records_bad"] == 0
        assert "detector_z" in limits
    ref = c.spec["reference"]
    assert ref["photons"] % ref["batches"] == 0
    assert all(s % ref["blocks"] == 0 for s in c.config["shape"])


def _digest(root):
    return {p.relative_to(root).as_posix():
            hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_cell_and_metric_are_new_files_only(tiny_root,
                                                       cpu_cache):
    before = _digest(tiny_root)
    new_metric = tiny_root / "metrics" / "jobs_per_window_s.py"
    assert not new_metric.exists()
    new_metric.write_text(
        'UNIT = "jobs/s"\n\n\ndef read(run):\n'
        '    return len(run.jobs) / run.window_s\n')
    after = _digest(tiny_root)
    assert {k: v for k, v in after.items() if k in before} == before
    # the tiny configurations and cells were added by the fixture the
    # same way: as files that did not exist
    assert "configs/tiny_b1.json" not in _digest(BENCH)
    result = run.measure("tiny.b1", 7, 0.01, True, require_tpu=False,
                         root=tiny_root, t_start=time.perf_counter())
    assert result["metrics"]["jobs_per_window_s"]["unit"] == "jobs/s"
    assert result["metrics"]["jobs_per_window_s"]["value"] > 0
    # counters that need no trace are read in every traced run
    assert 0 < result["metrics"]["lane_occupancy"]["value"] <= 1
    assert "sweep_lockstep_waste" not in result["metrics"]
    assert result["correct"], result["checks"]
