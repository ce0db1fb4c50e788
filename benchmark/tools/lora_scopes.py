#!/usr/bin/env python3
"""The op map of ``lora_gw_eu868`` by the scopes INSIDE its one stage (``chan``
... ``pack``), for ``scope_times.py``; ``wlan_scopes.py`` for the gateway.

    python3 benchmark/tools/lora_scopes.py --out OPMAP.json
    python3 benchmark/tools/scope_times.py TRACE.xplane.pb --map OPMAP.json

Builds the program exactly as the cell does (``make_kernel``), compiles it on
the attached device and maps each instruction to the first of the gateway's
own ``jax.named_scope`` names on its path. Run it in the chip call that keeps
the trace.

Most of this program's time lies in the bodies of its six ``lax.scan``s, and
``scope_times.opmap_from_hlo`` alone leaves all of it under "(no scope)": it
takes a computation's header only where its signature holds no parenthesis
of its own, and a ``while`` body's parameter is a tuple (``(wide.param: (s32[],
f32[8,41984], ...)) -> ...``), so the body's instructions belong to no
computation and are dropped. ``opmap`` here rewrites such headers to the bare
form before it calls that function, and then gives what is still unnamed
inside a called computation (the window fetch's ``dynamic-slice`` carries no
metadata) the scope of the instruction that calls it (``body=``,
``condition=``, ``calls=``), upwards.
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

SCOPES = ["wire_decode", "unpack", "chan", "resamp", "detect", "demod", "sync",
          "decode", "pack", "wire_encode"]

_TUPLE_HEADER = re.compile(r"^(\s*(?:ENTRY\s+)?%?[\w.\-]+)\s*\(.*\)\s*->.*\{\s*$", re.M)


def opmap(text: str, scopes) -> dict:
    """``scope_times.opmap_from_hlo`` with ``while`` bodies taken in (see the
    module's docstring)."""
    import scope_times as st

    text = _TUPLE_HEADER.sub(r"\1 {", text)
    ops = st.opmap_from_hlo(text, scopes)
    comp_of, caller, comp = {}, {}, None
    for line in text.splitlines():
        m = st._INSTR.match(line)
        if m and comp is not None:
            comp_of[m.group(1)] = comp
            for called in st._CALLS.findall(line):
                caller[called] = m.group(1)
            continue
        m = st._COMP.match(line)
        if m:
            comp = m.group(1)
        elif line.strip() == "}":
            comp = None

    def scope(name):
        for _ in range(8):
            if name is None or ops[name] != st.OTHER:
                break
            name = caller.get(comp_of[name])
        return ops[name] if name else st.OTHER

    return {name: scope(name) for name in ops}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args()

    import jax

    import scope_times
    from harness import cells

    config = "lora_gw_eu868"
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
           "ops": opmap(text, set(SCOPES))}
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(doc, indent=0, sort_keys=True) + "\n")
    named = sum(1 for s in doc["ops"].values() if s != scope_times.OTHER)
    print(f"{args.out}: {len(doc['ops'])} instructions of {doc['program']}, "
          f"{named} under one of {len(SCOPES)} scopes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
