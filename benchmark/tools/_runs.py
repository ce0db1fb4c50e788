"""Shared by the tools: run ``benchmark/run.py`` in a child process (this
parent never imports JAX, so the child gets the chip) and parse its last
line."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def run_cell(workload: str, seed: int, seconds: float, trace: int,
             env: dict = None, timeout: float = 1500.0) -> dict:
    """One run; returns ``{"rc", "line", "wall_s", "stderr_tail"}``."""
    cmd = [sys.executable, str(ROOT / "benchmark" / "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, env={**os.environ, **(env or {})},
                       capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    line = None
    if p.returncode == 0 and lines:
        line = json.loads(lines[-1])
    notes = None
    for ln in reversed(p.stderr.splitlines()):
        if ln.startswith('{"notes"'):
            notes = json.loads(ln)["notes"]
            break
    return {"rc": p.returncode, "line": line, "notes": notes,
            "wall_s": time.perf_counter() - t0,
            "stderr_tail": p.stderr[-1500:] if p.returncode else ""}


def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())
