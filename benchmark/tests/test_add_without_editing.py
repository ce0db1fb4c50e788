"""The README's worked example, executed: a configuration, a traffic mix and a
per-layer metric are added as NEW files plus one BENCHMARK.json entry each, in
a throw-away copy of the benchmark, and the new cell runs (CPU rehearsal) with
both kinds of run. No file that existed is edited, and the copy's own suite
stays green: no test counts the benchmark's files."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
EXAMPLE = Path(__file__).resolve().parent / "example"


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def overlay(tmp_path_factory) -> Path:
    tmp = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, tmp / "benchmark", ignore=shutil.ignore_patterns(
        "__pycache__", ".pytest_cache"))
    before = _digest(tmp / "benchmark")
    # the program itself is not copied: the throw-away checkout links to it
    for part in ("futuresdr_tpu", "native"):
        os.symlink(ROOT / part, tmp / part)
    # 1. new files, found by name
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(EXAMPLE / sub, tmp / "benchmark" / sub,
                        dirs_exist_ok=True)
    # 2. one entry each in BENCHMARK.json, and the new cell's name in the
    #    ``workloads`` list of every metric it reports
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    add = json.loads((EXAMPLE / "entries.json").read_text())
    for key in ("configs", "workloads", "per_layer"):
        man[key].extend(add[key])
    for cell, names in add["report_in"].items():
        for m in man["end_to_end"] + man["per_layer"]:
            if m["name"] in names:
                m["workloads"].append(cell)
    (tmp / "BENCHMARK.json").write_text(json.dumps(man, indent=1))
    after = _digest(tmp / "benchmark")
    assert {k: after[k] for k in before} == before, "an existing file changed"
    assert len(after) == len(before) + 5        # 2 + 1 + 2 new files
    return tmp


def _run(tmp: Path, trace: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_REHEARSE="1")
    p = subprocess.run(
        [sys.executable, str(tmp / "benchmark" / "run.py"), "--workload",
         "example_paced", "--seed", "3", "--seconds", "2", "--trace",
         str(trace)], cwd=tmp, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_new_cell_resolves_by_name(overlay):
    sys.path[:0] = [str(BENCH.parent), str(BENCH)]
    from harness import cells
    cell = cells.resolve("example_paced", bench_dir=overlay / "benchmark",
                         manifest=overlay / "BENCHMARK.json")
    assert cell.config["parameters"]["n_fft"] == 1024
    assert cell.driver_name == "stream" and cell.traffic["rate_msps"] == 8.0
    names = [m.name for m in cell.layer_metrics]
    assert "sink.frames_per_s" in names                 # the new metric
    assert "gen.late_p95_ms" in names                   # inherited: stream, paced
    assert not any(n.startswith("serve.") for n in names)
    assert set(cell.end_to_end) == {"latency_p50_ms", "latency_p95_ms", "setup_s"}
    # the cells that were there resolve as before
    old = cells.resolve("spectrum_sat", bench_dir=overlay / "benchmark",
                        manifest=overlay / "BENCHMARK.json")
    assert "sink.frames_per_s" not in [m.name for m in old.layer_metrics]


def test_new_cell_runs_end_to_end(overlay):
    line = _run(overlay, 0)
    assert line["rehearse"] is True and line["correct"] is False
    assert set(line["metrics"]) == {"latency_p50_ms", "latency_p95_ms", "setup_s"}
    assert line["failed"] == 0 and line["attempted"] > 0


def test_new_metric_is_read_in_the_traced_run(overlay):
    line = _run(overlay, 1)
    assert line["metrics"]["sink.frames_per_s"]["unit"] == "1/s"
    assert line["metrics"]["sink.frames_per_s"]["value"] > 0
    assert "gen.late_p95_ms" in line["metrics"]
    assert "breakdown" in line and "busy_s" in line["device"]


def test_the_copys_own_suite_stays_green(overlay):
    tests = overlay / "benchmark" / "tests"
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         str(tests), "--ignore", str(tests / Path(__file__).name)],
        cwd=overlay, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0 and " passed" in p.stdout, p.stdout[-3000:]
