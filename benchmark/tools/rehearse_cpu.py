#!/usr/bin/env python3
"""Rehearsal 1 of the on-chip-measurement guide: every cell end to end on the
CPU at tiny size, with ``--trace 0`` and ``--trace 1``.

    python3 benchmark/tools/rehearse_cpu.py [--seconds 4]

Finds wrong paths, arguments and control flow before a chip call. Every line
says ``"rehearse": true`` and none is ``correct``; nothing printed here is a
device number.
"""

from __future__ import annotations

import argparse
import sys

from _runs import manifest, run_cell

ENV = {"JAX_PLATFORMS": "cpu", "BENCH_REHEARSE": "1"}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args()
    bad = 0
    for w in manifest()["workloads"]:
        for trace in (0, 1):
            r = run_cell(w["name"], 1, args.seconds, trace, env=ENV)
            line = r["line"] or {}
            ok = r["rc"] == 0 and line.get("rehearse") is True \
                and line.get("correct") is False and line.get("metrics")
            checks = (r["notes"] or {}).get("precheck")
            print(f"{w['name']:16s} trace={trace} rc={r['rc']} "
                  f"{'ok ' if ok else 'BAD'} wall={r['wall_s']:.1f}s "
                  f"metrics={sorted(line.get('metrics', {}))} "
                  f"failed={line.get('failed')}/{line.get('attempted')} "
                  f"precheck={checks}")
            if not ok:
                bad += 1
                print(r["stderr_tail"])
    # without the switch the CPU is refused and nothing is printed
    r = run_cell(manifest()["workloads"][0]["name"], 1, 1, 0,
                 env={"JAX_PLATFORMS": "cpu", "BENCH_REHEARSE": ""})
    refused = r["rc"] != 0 and r["line"] is None
    print(f"no-switch CPU run: rc={r['rc']} "
          f"{'refused, no result' if refused else 'BAD: it ran'}")
    return 1 if bad or not refused else 0


if __name__ == "__main__":
    sys.exit(main())
