"""BENCHMARK.json against the contract's limits, and against the files it names."""

import json
import re
from pathlib import Path

from harness import cells

ROOT = Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_shape_and_limits():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "benchmark/run.py"]
    assert MAN["paths"] == ["benchmark"] and 1 <= MAN["run_seconds"] <= 51
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 2 <= len(MAN["workloads"]) <= 24 and 1 <= len(MAN["configs"]) <= 24
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(MAN["workloads"]) // 2)
    names = [m["name"] for m in MAN["end_to_end"] + MAN["per_layer"]] \
        + [w["name"] for w in MAN["workloads"]] \
        + [c["name"] for c in MAN["configs"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(m["name"] for m in MAN["end_to_end"] + MAN["per_layer"])) \
        == len(MAN["end_to_end"]) + len(MAN["per_layer"])
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and NAME.match(w["traffic"])
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in MAN["end_to_end"])


def test_every_cell_resolves_and_reports_enough():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    for w in MAN["workloads"]:
        cell = cells.resolve(w["name"])
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.layer_metrics, w["name"]
        # a per-layer metric is reported only where the metric it moves is,
        # and the manifest's workloads lists say the same as the files
        for lm in cell.layer_metrics:
            assert lm.moves in cell.end_to_end
        listed = {m["name"] for m in MAN["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]])}
        assert listed == {lm.name for lm in cell.layer_metrics}
    for m in MAN["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in e2e[m["moves"]].get("workloads", [w])


def test_configuration_files_state_what_the_issue_asks():
    for c in MAN["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in ("assumed", "guarantees", "parameters", "deployment"):
            assert cfg[key], (c["name"], key)
