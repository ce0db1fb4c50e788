#!/usr/bin/env python3
"""One traced run of one cell, and what its spans add up to: the tables of
PERF.md section 5 come from this tool's output.

    python3 benchmark/tools/timeline.py --workload <cell> [--seed 1 --seconds 20]

Runs ``benchmark/run.py --trace 1`` in this process (so on the chip it is the
one process that holds it), keeps the ``Reading`` the driver hands back, and
writes ``chiprun_out/timeline_<cell>.json``:

* ``spans``: per span name the count in the window, median and 95th
  percentile in ms, and the sum per unit (frame or dispatch);
* ``threads``: events recorded per thread over the whole run, the busiest
  first (what ``trace_ring`` has to hold);
* ``median_frame``: per span name the median start and end, in ms after the
  ``frame`` (stream) or ``serve_step`` (serve) span of the same ``seq`` began;
* ``joins``: per ``seq``, medians of the upload's tail after the program call
  (``H2D`` end - ``program`` start), of the program measured from the later of
  its call and the ``H2D`` end, and (serve) of the step thread's own phases
  against ``serve_step``.

A tool: the driver never runs it, and no metric reads it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import Counter, defaultdict
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

STEP_THREAD_PHASES = ("lock_wait", "encode", "h2d_put", "compute", "d2h_wait",
                      "decode")


def _label(s) -> str:
    """Blocks are named ``<Class>_<n>``; one row per class and kind."""
    if s.cat in ("block", "park"):
        return f"{s.cat}:{s.name.rsplit('_', 1)[0]}"
    return s.name


def summarize(reading) -> dict:
    from harness import stats

    t0, t1 = reading.window_ns
    units = reading.units_in(t0, t1)
    win = reading.spans_in_window()
    by = defaultdict(list)
    for s in win:
        by[_label(s)].append(s)
    rows = {}
    for name, ss in sorted(by.items()):
        d = [s.dur_ns * 1e-6 for s in ss]
        rows[name] = {
            "n": len(ss), "p50_ms": stats.percentile(d, 50),
            "p95_ms": stats.percentile(d, 95),
            "ms_per_unit": sum(d) / units if units else None,
            "threads": sorted({s.thread for s in ss})[:6]}
    threads = Counter(s.thread for s in reading.spans).most_common(8)

    seqs = defaultdict(dict)
    for s in win:
        if s.args and s.args.get("seq") is not None:
            seqs[s.args["seq"]].setdefault(s.name, []).append(s)
    root = "frame" if reading.driver == "stream" else "serve_step"
    starts, ends = defaultdict(list), defaultdict(list)
    tail, prog, cover, rest = [], [], [], []
    for g in seqs.values():
        if root in g:
            r0 = g[root][0].t0_ns
            for name, ss in g.items():
                starts[name].append((min(s.t0_ns for s in ss) - r0) * 1e-6)
                ends[name].append((max(s.t1_ns for s in ss) - r0) * 1e-6)
        if "program" in g and "H2D" in g:
            p, h = g["program"][0], g["H2D"][0]
            tail.append(max(0, h.t1_ns - p.t0_ns) * 1e-6)
            prog.append((p.t1_ns - max(p.t0_ns, h.t1_ns)) * 1e-6)
        if "serve_step" in g:
            step = g["serve_step"][0]
            mine = [(max(s.t0_ns, step.t0_ns), min(s.t1_ns, step.t1_ns))
                    for n in STEP_THREAD_PHASES for s in g.get(n, ())]
            c = stats.union_length(iv for iv in mine if iv[1] > iv[0])
            cover.append(c / step.dur_ns)
            rest.append((step.dur_ns - c) * 1e-6)
    med = lambda v: stats.percentile(v, 50) if v else None     # noqa: E731
    return {
        "driver": reading.driver, "units_in_window": units,
        "window_s": (t1 - t0) * 1e-9, "spans": rows,
        "threads": [[t, n] for t, n in threads],
        "median_frame": {n: [med(starts[n]), med(ends[n])]
                         for n in sorted(starts, key=lambda n: med(starts[n]))},
        "joins": {"groups": len(seqs),
                  "upload_tail_after_program_call_ms_p50": med(tail),
                  "program_from_later_of_call_and_h2d_end_ms_p50": med(prog),
                  "program_from_later_ms_p95":
                      stats.percentile(prog, 95) if prog else None,
                  "step_thread_phases_share_of_step_p50": med(cover),
                  "step_unnamed_ms_p50": med(rest)}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args()

    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    bench_run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_run)
    from harness import cells

    kept = {}
    resolve = cells.resolve

    def resolve_and_keep(*a, **k):
        cell = resolve(*a, **k)
        drive = cell.driver.run

        def run(r):
            out = drive(r)
            kept["reading"] = out.reading
            return out

        cell.driver.run = run
        return cell

    cells.resolve = resolve_and_keep
    argv = ["--workload", args.workload, "--seed", str(args.seed),
            "--trace", "1"]
    if args.seconds is not None:
        argv += ["--seconds", str(args.seconds)]
    rc = bench_run.main(argv)
    if rc or "reading" not in kept:
        return rc or 1
    out = summarize(kept["reading"])
    os.makedirs(ROOT / "chiprun_out", exist_ok=True)
    path = ROOT / "chiprun_out" / f"timeline_{args.workload}.json"
    path.write_text(json.dumps(out, indent=1))
    print(f"timeline: {path}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
