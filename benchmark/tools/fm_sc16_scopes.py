#!/usr/bin/env python3
"""The op map of ``fm_serve_1msps_sc16``'s step program, for ``scope_times.py``.

    python3 benchmark/tools/fm_sc16_scopes.py --out OPMAP.json
    python3 benchmark/tools/scope_times.py TRACE.xplane.pb --map OPMAP.json

``scope_times.py --write-map`` builds a serving configuration's program with
``build_slot_program(pipe, cap, 1)`` over an input of the pipeline's dtype: for
this configuration that is the sibling's program, not the one the cell runs.
This tool builds the step exactly as the cell's engine does (its wire, its
``uint32`` input) and adds the ``wire_decode`` scope to the stage names and
the page gather / scatter. Run it in the chip call that keeps the trace
(``BENCH_KEEP_TRACE=<dir>``).
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args()

    import jax
    import numpy as np

    import scope_times
    from harness import cells

    config = "fm_serve_1msps_sc16"
    cfg = cells.load_json(BENCH / "configs" / f"{config}.json")
    cm = cells.load_module(BENCH / "configs" / f"{config}.py")
    eng = cm.make_engine(cfg, jax.devices()[0].platform != "tpu")
    pipe, cap = eng.pipeline, eng.table.capacity
    spec = jax.ShapeDtypeStruct
    pages = jax.tree_util.tree_map(
        lambda a: spec((cap,) + tuple(np.shape(a)), np.asarray(a).dtype),
        pipe.init_carry())
    text = eng._program(cap, 1).lower(      # the step as the engine builds it
        pages, spec((cap,), np.int32), spec((cap,), np.bool_),
        spec((cap, eng.frame_size), eng.frame_dtype), spec((cap,), np.bool_)) \
        .compile().as_text()
    eng.shutdown()
    scopes = ["wire_decode"] + [s.name for s in pipe.stages] \
        + ["serve_gather", "serve_scatter"]
    doc = {"config": config, "device": jax.devices()[0].device_kind,
           "program": re.search(r"HloModule\s+([\w.\-]+)", text).group(1),
           "scopes": scopes,
           "ops": scope_times.opmap_from_hlo(text, set(scopes))}
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(doc, indent=0, sort_keys=True) + "\n")
    named = sum(1 for s in doc["ops"].values() if s != scope_times.OTHER)
    print(f"{args.out}: {doc['program']}, {len(doc['ops'])} instructions, "
          f"{named} in a named scope", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
