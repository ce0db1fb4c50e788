#!/usr/bin/env python3
"""The control of ``fm_serve_1msps_sc16``'s ``correct``: the cell's own run
with the two matmuls of the FM front end (the tuner's decimating FIR and the
audio resampler, both ``ops/stages._shifted_matvec``) at the device's DEFAULT
precision (on a TPU the operands are rounded to bfloat16) instead of
``HIGHEST``.

    chiprun -- python3 benchmark/tools/fm_sc16_precision_control.py --seed 5

takes ``run.py``'s arguments but ``--workload`` and prints its lines. The run
has to come out ``correct: false`` by ``correctness.abs_tolerance`` (both
readings are in the configuration's file, ``correctness.measured``). On the
CPU both precisions are float32 for the tuner's complex operands and the
control says nothing there.
"""

from __future__ import annotations

import json
import os
import runpy
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[2]


def main() -> None:
    # what run.py would set by exec: set here, or the exec drops the patch
    cfg = json.loads(
        (_ROOT / "benchmark/configs/fm_serve_1msps_sc16.json").read_text())
    os.environ.update(cfg.get("process_env", {}))
    sys.path.insert(0, str(_ROOT))
    from futuresdr_tpu.ops import stages
    shipped = stages._shifted_matvec

    def at_default(ext, W, m, nq, precision=None):
        return shipped(ext, W, m, nq, precision or "bf16")

    stages._shifted_matvec = at_default
    run = _ROOT / "benchmark" / "run.py"
    sys.argv = [str(run), "--workload", "fm_serve_sc16_sat"] + sys.argv[1:]
    runpy.run_path(str(run), run_name="__main__")


if __name__ == "__main__":
    main()
