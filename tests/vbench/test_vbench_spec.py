"""BENCHMARK.json: every cell resolves to the files it names, the file
keeps the benchmark's rules, and a cell that a test defines in a
temporary directory loads with new files only."""

from __future__ import annotations

import json
import re

import pytest

from _vbench_tiny import REPO, closed, config, make_root
from vbench import spec, traffic

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "vbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (REPO / p).is_dir()
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves(workload):
    cell = spec.load_cell(REPO, workload)
    assert cell.config["name"] == cell.config_name
    assert {m.name for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    traffic.plan(cell.traffic, 1, 1.0, cell.config["buckets"])
    for m in cell.per_layer:
        e2e = {x.name for x in cell.end_to_end}
        assert m.moves in e2e, f"{m.name} moves {m.moves}, not reported"


def test_names_units_and_keys():
    seen = set()
    for kind, keys in (("configs", {"name", "source", "file", "reduced",
                                    "why"}),
                       ("workloads", {"name", "config", "traffic", "chips",
                                      "why"})):
        for e in BENCH[kind]:
            assert set(e) == keys
            assert NAME.match(e["name"]) and (kind, e["name"]) not in seen
            seen.add((kind, e["name"]))
            assert 1 <= len(e["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
    metric_names = set()
    for e in BENCH["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
        metric_names.add(e["name"])
    for e in BENCH["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert e["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert e["moves"] in metric_names
        metric_names.add(e["name"])
    for e in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
        for w in e.get("workloads", []):
            assert w in CELLS
    assert len(metric_names) == len(BENCH["end_to_end"]) + \
        len(BENCH["per_layer"])
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configs_are_full_width():
    for c in BENCH["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert c["reduced"] == cfg["reduced"] == []
        assert cfg["full"] is True and cfg["backend"] == "pallas"


def test_cell_from_a_temporary_directory(tmp_path):
    """A later change adds a cell, its configuration, its mix and a metric
    as new files and entries; the harness finds them by name."""
    root = make_root(tmp_path, {"tiny.closed": (config("float", 1e-3),
                                                closed(), 1)})
    (root / "vbench" / "metrics" / "answered.py").write_text(
        "def read(run):\n    return float(len(run.requests))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "answered", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "server", "moves": "setup_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(root, "tiny.closed")
    assert cell.config["registry"] == "vit_edge"
    assert cell.traffic["loop"] == "closed"
    assert [m.name for m in cell.per_layer] == ["answered"]
    assert cell.per_layer[0].read(type("R", (), {"requests": [1, 2]})) == 2


def test_unknown_names_are_errors(tmp_path):
    root = make_root(tmp_path, {"tiny.closed": (config("float", 1e-3),
                                                closed(), 1)})
    with pytest.raises(spec.SpecError):
        spec.load_cell(root, "no.such.cell")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"].append({"name": "no_reader", "unit": "s",
                                "better": "lower", "source": "host_clock"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(spec.SpecError):
        spec.load_cell(root, "tiny.closed")
