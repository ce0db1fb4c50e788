#!/usr/bin/env python3
"""The op map of ``wlan_rx_20msps`` by the scopes INSIDE its one stage
(``sync_short`` … ``pack``), for ``scope_times.py``.

    python3 benchmark/tools/wlan_scopes.py --out OPMAP.json
    python3 benchmark/tools/scope_times.py TRACE.xplane.pb --map OPMAP.json

``scope_times.py --write-map`` maps instructions to STAGE names, and this
receiver is one stage: everything would read ``wlan_rx``. This tool builds the
program exactly as the cell does (``make_kernel``), compiles it on the attached
device and maps each instruction to the first of the receiver's own
``jax.named_scope`` names on its path (the SIGNAL field's 24-step decode
counts under ``signal``). Run it in the chip call that keeps the trace: the
trace of this cell is far above what a call brings back (5 device events per
trellis step), so keep it under ``/tmp`` there and bring back the table.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent), str(BENCH), str(BENCH / "tools")):
    if p not in sys.path:
        sys.path.insert(0, p)

SCOPES = ["wire_decode", "unpack", "sync_short", "sync_long", "signal", "demod",
          "deint_depunct", "viterbi_acs", "traceback", "pack", "wire_encode"]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args()

    import jax

    import scope_times
    from harness import cells

    config = "wlan_rx_20msps"
    cfg = cells.load_json(BENCH / "configs" / f"{config}.json")
    cm = cells.load_module(BENCH / "configs" / f"{config}.py")
    k = cm.make_kernel(cfg, jax.devices()[0].platform != "tpu")
    fn, carry = k.pipeline.compile_wired(
        k.frame_size, k.wire, device=k.inst.device, k=k.k_batch,
        donate=k._donate, packed=k._packed)
    text = fn.lower(carry, *k._warm_parts(jax, k.pipeline.in_dtype)) \
        .compile().as_text()
    doc = {"config": config, "device": jax.devices()[0].device_kind,
           "program": re.search(r"HloModule\s+([\w.\-]+)", text).group(1),
           "scopes": SCOPES,
           "ops": scope_times.opmap_from_hlo(text, set(SCOPES))}
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(doc, indent=0, sort_keys=True) + "\n")
    named = sum(1 for s in doc["ops"].values() if s != scope_times.OTHER)
    print(f"{args.out}: {doc['program']}, {len(doc['ops'])} instructions, "
          f"{named} in a named scope", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
