#!/usr/bin/env python3
"""The one-off sweep that fixed ``rate_msps`` (stream_paced_08) and
``sessions`` (serve_paced_churn): a few fixed values, one process each, on the
chip. A tool, never run by the driver; its raw output is in PERF.md.

    python3 benchmark/tools/find_knee.py --workload spectrum_paced \
        --param rate_msps --values 20,24,28 --seconds 10 [--trace 1]

Each run replaces one traffic parameter through ``BENCH_TRAFFIC_OVERRIDE``;
such a run's line says ``"override"`` and is never ``correct``. The knee is
the largest value at which ``failed`` is 0 and the latency tail has not left
the plateau; the cell then runs at about four fifths of it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from _runs import ROOT, run_cell


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--param", required=True)
    ap.add_argument("--values", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=100)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "knee.jsonl"))
    args = ap.parse_args()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    for i, v in enumerate(args.values.split(",")):
        val = float(v) if "." in v else int(v)
        r = run_cell(args.workload, args.seed + i, args.seconds, args.trace,
                     env={"BENCH_TRAFFIC_OVERRIDE": json.dumps({args.param: val})})
        line = r["line"] or {}
        row = {"workload": args.workload, args.param: val, "rc": r["rc"],
               "attempted": line.get("attempted"), "failed": line.get("failed"),
               "metrics": {k: m["value"] for k, m in
                           line.get("metrics", {}).items()},
               "notes": {k: (r["notes"] or {}).get(k) for k in
                         ("gen_late_p95_ms", "gen_late_max_ms", "latency_max_ms",
                          "drops_at_s", "frames_blocked", "gc", "longest_spans",
                          "dispatch_gap_ms", "tenants", "jax_stages_in_window",
                          "refused",
                          "frames_dropped",
                          "missing", "dispatches", "session_frames",
                          "shed_level", "rest_failed", "errors")},
               "wall_s": round(r["wall_s"], 1)}
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        if r["rc"]:
            print(r["stderr_tail"], file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
