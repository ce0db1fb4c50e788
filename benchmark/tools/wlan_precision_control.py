#!/usr/bin/env python3
"""The control of ``wlan_rx_20msps``'s ``correct``: the cell's own run with
every matmul of the receiver at the device's DEFAULT precision (on a TPU the
operands are rounded to bfloat16) instead of ``HIGHEST``.

    chiprun -- python3 benchmark/tools/wlan_precision_control.py --seed 5

takes ``run.py``'s arguments but ``--workload`` and prints its lines. The run
has to come out ``correct: false`` by ``llr_err_max_rel`` alone: the PSDUs of
the mix survive the rounding, CFO and LTS SNR pass no matmul. The reading
beside the shipped program's is ``correctness.llr_measured`` of the
configuration's file. On the CPU both precisions are float32 and the control
passes: it says nothing there.
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
    cfg = json.loads((_ROOT / "benchmark/configs/wlan_rx_20msps.json").read_text())
    os.environ.update(cfg.get("process_env", {}))
    sys.path.insert(0, str(_ROOT))
    from futuresdr_tpu.models.wlan import rx_stages
    rx_stages._PRECISION = None
    run = _ROOT / "benchmark" / "run.py"
    sys.argv = [str(run), "--workload", "wlan_rx_sat"] + sys.argv[1:]
    runpy.run_path(str(run), run_name="__main__")


if __name__ == "__main__":
    main()
