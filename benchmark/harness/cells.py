"""Find a cell's files by the names in ``BENCHMARK.json``. Nothing here knows
a cell, a configuration, a mix or a metric by name: a later PR adds files and
entries and edits no file that exists.

    workload  -> its entry in BENCHMARK.json: config + traffic
    config    -> configs/<config>.json  + configs/<config>.py
    traffic   -> traffic/<traffic>.json, whose "driver" names
                 drivers/<driver>.py
    per-layer -> every layer_metrics/<metric>.json listed in BENCHMARK.json
                 whose "drivers"/"configs" admit this cell and whose "moves"
                 names an end-to-end metric this cell reports; its "reader"
                 names layer_metrics/readers/<reader>.py
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]


class BenchmarkError(Exception):
    """The benchmark's own files do not fit together."""


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise BenchmarkError(f"no such module: {path}")
    name = f"bench_{path.parent.name}_{path.stem}".replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchmarkError(f"no such file: {path}")
    return json.loads(path.read_text())


@dataclass
class LayerMetric:
    name: str
    unit: str
    moves: str
    spec: dict                      # layer_metrics/<name>.json
    reader: ModuleType

    def read(self, reading) -> Optional[float]:
        return self.reader.read(reading, self.spec.get("params", {}))


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    config_module: ModuleType
    traffic: dict
    driver_name: str
    driver: ModuleType
    end_to_end: Dict[str, dict]     # the e2e metrics this cell reports
    layer_metrics: List[LayerMetric]


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def unread(manifest: dict, workload: str, metrics) -> List[str]:
    """The per-layer metrics whose ``workloads`` name this cell and that read
    nothing in its traced run: a kernel renamed in the program, or a counter
    gone, shows here instead of leaving its metric quietly out of the line."""
    return sorted(m["name"] for m in manifest["per_layer"]
                  if workload in m.get("workloads", ()) and m["name"] not in metrics)


def resolve(workload: str, bench_dir: Path = BENCH_DIR,
            manifest: Optional[Path] = None) -> Cell:
    bench_dir = Path(bench_dir)
    spec = load_json(manifest or bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchmarkError(
            f"workload {workload!r} is not in BENCHMARK.json "
            f"(has: {', '.join(sorted(cells))})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json(bench_dir.parent / cfg_entry["file"])
    config_module = load_module(
        (bench_dir.parent / cfg_entry["file"]).with_suffix(".py"))
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    driver_name = traffic["driver"]
    driver = load_module(bench_dir / "drivers" / f"{driver_name}.py")
    e2e = {m["name"]: m for m in spec["end_to_end"] if _applies(m, workload)}
    layer = []
    for m in spec["per_layer"]:
        if not _applies(m, workload) or m["moves"] not in e2e:
            continue
        mspec = load_json(bench_dir / "layer_metrics" / f"{m['name']}.json")
        if driver_name not in mspec.get("drivers", [driver_name]):
            continue
        if w["config"] not in mspec.get("configs", [w["config"]]):
            continue
        for key in ("unit", "moves", "layer", "source"):
            if mspec.get(key) != m[key]:
                raise BenchmarkError(
                    f"{m['name']}: {key} is {mspec.get(key)!r} in its file "
                    f"and {m[key]!r} in BENCHMARK.json")
        reader = load_module(bench_dir / "layer_metrics" / "readers"
                             / f"{mspec['reader']}.py")
        layer.append(LayerMetric(m["name"], m["unit"], m["moves"], mspec, reader))
    return Cell(workload, int(w["chips"]), w["config"], w["traffic"], config,
                config_module, traffic, driver_name, driver, e2e, layer)
