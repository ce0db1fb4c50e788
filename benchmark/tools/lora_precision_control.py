#!/usr/bin/env python3
"""The control of ``lora_gw_eu868``'s ``correct``: the cell's own run with the
gateway's DFTs (``ops/mxu_fft``: detection, sync and every data symbol) and
its polyphase bank at the device's DEFAULT precision (on a TPU the operands
are rounded to bfloat16) instead of ``HIGHEST``. The 5/4 resampler keeps
``HIGHEST``: ``ops.stages.resample_stage`` has no switch.

    chiprun -- python3 benchmark/tools/lora_precision_control.py --seed 5

takes ``run.py``'s arguments but ``--workload`` and prints its lines. The run
has to come out ``correct: false`` by one of the fidelity limits of the
configuration's file (``correctness.*_measured`` has both readings). On the
CPU both precisions are float32 and the control passes: it says nothing there.
"""

from __future__ import annotations

import runpy
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[2]


def main() -> None:
    sys.path.insert(0, str(_ROOT))
    from futuresdr_tpu.models.lora import rx_stages
    rx_stages._PRECISION = "bf16"
    run = _ROOT / "benchmark" / "run.py"
    sys.argv = [str(run), "--workload", "lora_gw_sat"] + sys.argv[1:]
    runpy.run_path(str(run), run_name="__main__")


if __name__ == "__main__":
    main()
