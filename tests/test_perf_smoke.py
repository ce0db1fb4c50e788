"""Tiny-workload smoke of the perf harnesses' CPU paths.

The perf scripts live outside the suite, so an API drift can break one
silently: ``perf/inplace.py`` sat broken from the stream-tag transport change
(``get_full`` grew a tags element) until round 5 because nothing executed it
in CI. Each harness runs here in a subprocess with a workload small enough to
finish in seconds — the assertion is "prints its CSV and exits 0", not any
rate. TPU-needing scripts (fm/wlan/lora sweeps) stay out: their stages have
their own tests."""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SMOKES = [
    ("inplace", ["--runs", "1", "--frames", "20", "--items", "16384"]),
    ("null", ["--runs", "1", "--pipes", "2", "--stages", "2",
              "--samples", "500000"]),
    ("null_rand", ["--runs", "1", "--pipes", "2", "--stages", "2",
                   "--samples", "200000"]),
    ("msg", ["--runs", "1", "--stages", "2", "--burst", "2000"]),
    ("buffer_size", ["--runs", "1", "--samples", "500000",
                     "--sizes", "65536"]),
    ("latency", ["--runs", "1", "--stages", "2", "--samples", "100000"]),
    ("fir", ["--runs", "1", "--pipes", "2", "--stages", "2",
             "--samples", "500000"]),
    ("buffer_rand", ["--runs", "1", "--samples", "200000", "--stages", "2",
                     "--rings", "4096"]),
    ("micro", ["--window", "16384", "--iters", "3"]),
]


@pytest.mark.integration
@pytest.mark.parametrize("name,args", _SMOKES, ids=[s[0] for s in _SMOKES])
def test_perf_harness_smoke(name, args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "perf", f"{name}.py"), *args],
        capture_output=True, text=True, timeout=180, cwd=_ROOT, env=env)
    assert r.returncode == 0, f"{name}: rc={r.returncode}\n{r.stderr[-1500:]}"
    rows = [ln for ln in r.stdout.splitlines() if "," in ln]
    assert len(rows) >= 2, f"{name}: no CSV rows\n{r.stdout[-800:]}"
