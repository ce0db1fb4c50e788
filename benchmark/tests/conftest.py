"""Run by hand and in the rehearsal (``python3 -m pytest benchmark/tests -q``),
not part of tier-1. The CPU is forced: nothing here is a device number."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
