#!/usr/bin/env python3
"""Measure cells as the driver does: for each cell ``--sets`` sets of
``--runs`` runs, each run a new process with another seed; per end-to-end
metric each set's median and spread (distance between the quartiles over the
median), the wider of the spreads, and how far the sets' medians differ.

    python3 benchmark/tools/measure_cells.py --cells spectrum_sat,fm_serve_sat \
        [--sets 2 --runs 6 --trace-runs 1 --dump]

Writes every line to ``chiprun_out/measure_<cell>.jsonl`` and prints a table.
``--dump`` also keeps each serving run's raw stamps (``BENCH_DUMP_DIR``) under
``chiprun_out/dumps/``, to try another estimator on the same runs.
A tool: the bounds in BENCHMARK.json were set from its output (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

from _runs import ROOT, manifest, run_cell

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from harness import stats  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--trace-runs", type=int, default=0)
    ap.add_argument("--seed0", type=int, default=1000)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--dump", action="store_true")
    args = ap.parse_args()
    env = {"BENCH_DUMP_DIR": str(ROOT / "chiprun_out" / "dumps")} \
        if args.dump else None
    man = manifest()
    seconds = args.seconds or man["run_seconds"]
    names = [c for c in args.cells.split(",") if c] or \
        [w["name"] for w in man["workloads"]]
    out_dir = ROOT / "chiprun_out"
    os.makedirs(out_dir, exist_ok=True)
    seed = args.seed0
    for cell in names:
        path = out_dir / f"measure_{cell}.jsonl"
        sets = []
        for s in range(args.sets):
            rows = []
            for _ in range(args.runs):
                seed += 1
                r = run_cell(cell, seed, seconds, 0, env=env)
                line = r["line"] or {}
                row = {"cell": cell, "set": s, "seed": seed, "rc": r["rc"],
                       "wall_s": round(r["wall_s"], 1), "line": line,
                       "notes": r["notes"]}
                with open(path, "a") as f:
                    f.write(json.dumps(row) + "\n")
                vals = {k: m["value"] for k, m in line.get("metrics", {}).items()}
                print(f"{cell} set {s} seed {seed} rc={r['rc']} "
                      f"correct={line.get('correct')} "
                      f"failed={line.get('failed')}/{line.get('attempted')} "
                      f"{json.dumps(vals)} wall={r['wall_s']:.0f}s", flush=True)
                if r["rc"]:
                    print(r["stderr_tail"], file=sys.stderr)
                else:
                    rows.append(vals)
            sets.append(rows)
        metrics = sorted({k for rows in sets for v in rows for k in v})
        for m in metrics:
            per_set = [[v[m] for v in rows if m in v] for rows in sets]
            per_set = [p for p in per_set if p]
            # setup_s: each set's first run may compile; the driver leaves it out
            meds = [stats.median(p[1:] if m == "setup_s" and len(p) > 1 else p)
                    for p in per_set]
            sprs = [stats.spread(p[1:] if m == "setup_s" and len(p) > 1 else p)
                    for p in per_set]
            drift = abs(meds[-1] - meds[0]) / abs(meds[0]) if len(meds) > 1 else 0.0
            print(f"SUMMARY {cell} {m}: medians={meds} spreads="
                  f"{[round(x, 5) for x in sprs]} widest={max(sprs):.5f} "
                  f"set-to-set={drift:.5f} -> bound~{max(0.01, 5 * max(sprs)):.4f}",
                  flush=True)
        for t in range(args.trace_runs):
            seed += 1
            r = run_cell(cell, seed, seconds, 1)
            with open(path, "a") as f:
                f.write(json.dumps({"cell": cell, "trace": 1, "seed": seed,
                                    "rc": r["rc"], "line": r["line"],
                                    "notes": r["notes"]}) + "\n")
            print(f"TRACE {cell} seed {seed} rc={r['rc']} "
                  f"{json.dumps(r['line'])}", flush=True)
            if r["rc"]:
                print(r["stderr_tail"], file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
