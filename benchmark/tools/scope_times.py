#!/usr/bin/env python3
"""Device self time per named scope (stage, wire prolog/epilog, page
gather/scatter) per dispatch, from a trace kept with ``BENCH_KEEP_TRACE``.

    python3 benchmark/tools/scope_times.py TRACE.xplane.pb[.gz] --map OPMAP.json
    python3 benchmark/tools/scope_times.py --write-map <config> --out OPMAP.json

The harness records with ``enable_hlo_proto`` off, and the device plane of
such a trace has no name-scope or framework-op line (looked at by hand, PR
24): its ``XLA Ops`` events carry XLA's instruction names (``fusion.37``) and
nothing else. The scope of an instruction is in the compiled program's text,
as ``metadata={op_name="jit(step)/vmap(tuner)/mul"}``. So the join needs the
program: ``--write-map`` builds the configuration's program exactly as its
cell does, compiles it on the attached device and writes
``{instruction: scope}``; run it in the same chip call that keeps the trace.
An instruction without a scope of its own takes the scope most of its fused
instructions have (a fusion XLA made of several ops) or, failing that, the
scope of the instruction that made its first operand: the TPU compiler lowers
``bitcast_convert_type`` to a reshape and a shift-and-reduce fusion that carry
no metadata at all (``reshape.123``, 0.40 ms a frame in ``spectrum_sat``).

The table: per program (``XLA Modules`` line) its runs, device ms per run,
and per scope the self time per run (``xplane.self_times``: a ``while`` does
not count its body twice) and its share. Operations that ran outside the
mapped program (the complex pair join of ``ops/xfer.py``, copies) are listed
under their own module's name. A tool, until a ``benchmark`` issue takes the
scopes into ``breakdown``; the driver never runs it.
"""

from __future__ import annotations

import argparse
import gzip
import json
import re
import sys
from collections import Counter, defaultdict
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

OTHER = "(no scope)"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\)\s*->.*)?\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|body|condition|to_apply|branch_computations)="
                    r"\{?%?([\w.\-]+)")
#: the opcode's parenthesis (a type's ``T(8,128)`` follows no space), then the
#: first reference that is an operand and not ``calls=%...``
_FIRST_OPERAND = re.compile(r"=\s.*?\s[a-z][\w\-]*\(.*?(?<![=\{])%([\w.\-]+)")


def scope_of(op_name: str, scopes) -> str:
    """``jit(step)/vmap(tuner)/mul`` -> ``tuner``: the first path component
    that is one of ``scopes``, looked for inside ``vmap(...)``-style wrappers."""
    for part in op_name.split("/"):
        while True:
            if part in scopes:
                return part
            m = re.fullmatch(r"\w+\((.*)\)", part)
            if not m:
                break
            part = m.group(1)
    return OTHER


def opmap_from_hlo(text: str, scopes) -> dict:
    """``{instruction name: scope}`` for every instruction of an optimized
    HLO module's text."""
    own, calls, members = {}, defaultdict(list), defaultdict(list)
    first_operand = {}
    comp = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m and comp is not None:
            name = m.group(1)
            members[comp].append(name)
            n = _OP_NAME.search(line)
            own[name] = scope_of(n.group(1), scopes) if n else OTHER
            calls[name] = _CALLS.findall(line)
            m = _FIRST_OPERAND.search(line)
            if m:
                first_operand[name] = m.group(1)
            continue
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
        elif line.strip() == "}":
            comp = None

    def vote(name, seen) -> Counter:
        c = Counter()
        if own.get(name, OTHER) != OTHER:
            c[own[name]] += 1
            return c
        for called in calls.get(name, ()):
            for inner in members.get(called, ()):
                if inner not in seen:
                    seen.add(inner)
                    c += vote(inner, seen)
        return c

    out = {}
    for name in own:
        c = vote(name, {name})
        out[name] = c.most_common(1)[0][0] if c else OTHER

    def inherited(name, depth=0) -> str:
        if out.get(name, OTHER) != OTHER or depth > 8:
            return out.get(name, OTHER)
        src = first_operand.get(name)
        return inherited(src, depth + 1) if src else OTHER

    return {name: inherited(name) for name in out}


def write_map(config: str, out: Path) -> int:
    """Build ``config``'s program as its cell does and map its instructions."""
    import jax
    import numpy as np

    from harness import cells

    cfg = cells.load_json(BENCH / "configs" / f"{config}.json")
    cm = cells.load_module(BENCH / "configs" / f"{config}.py")
    rehearse = jax.devices()[0].platform != "tpu"
    if hasattr(cm, "make_kernel"):
        k = cm.make_kernel(cfg, rehearse)
        pipe = k.pipeline
        fn, carry = pipe.compile_wired(
            k.frame_size, k.wire, device=k.inst.device, k=k.k_batch,
            donate=k._donate, packed=k._packed)
        lowered = fn.lower(carry, *k._warm_parts(jax, pipe.in_dtype))
        scopes = ["wire_decode", "wire_encode", "unpack"]
    else:
        from futuresdr_tpu.serve.engine import build_slot_program
        eng = cm.make_engine(cfg, rehearse)
        pipe, cap, fs = eng.pipeline, eng.table.capacity, eng.frame_size
        eng.shutdown()
        spec = jax.ShapeDtypeStruct
        pages = jax.tree_util.tree_map(
            lambda a: spec((cap,) + tuple(np.shape(a)), np.asarray(a).dtype),
            pipe.init_carry())
        lowered = build_slot_program(pipe, cap, 1).lower(
            pages, spec((cap,), np.int32), spec((cap,), np.bool_),
            spec((cap, fs), pipe.in_dtype), spec((cap,), np.bool_))
        scopes = ["serve_gather", "serve_scatter"]
    scopes = [s.name for s in pipe.stages] + scopes
    text = lowered.compile().as_text()
    module = re.search(r"HloModule\s+([\w.\-]+)", text).group(1)
    doc = {"config": config, "device": jax.devices()[0].device_kind,
           "program": module, "scopes": scopes,
           "ops": opmap_from_hlo(text, set(scopes))}
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(doc, indent=0, sort_keys=True) + "\n")
    named = sum(1 for s in doc["ops"].values() if s != OTHER)
    print(f"{out}: {module}, {len(doc['ops'])} instructions, {named} in a "
          f"named scope", file=sys.stderr)
    return 0


def module_runs(path: str) -> dict:
    """Device plane -> the ``(name, start_ns, dur_ns)`` of its program runs
    (the ``XLA Modules`` line, which ``xplane.load`` keeps only as a
    fall-back)."""
    from jax.profiler import ProfileData

    from harness import xplane

    raw = gzip.open(path, "rb").read() if str(path).endswith(".gz") \
        else Path(path).read_bytes()
    out = {}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not xplane.is_device_plane(plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                out[plane.name] = sorted(
                    (e.name.split("(", 1)[0], float(e.start_ns),
                     float(e.duration_ns)) for e in line.events)
    return out


def scope_table(trace_path: str, opmap: dict) -> dict:
    """Per program its runs and, per scope, device self time per run."""
    import bisect

    from harness import xplane

    tr = xplane.load(trace_path)
    runs = module_runs(trace_path)
    progs = {}
    for dev, events in sorted(tr.devices.items()):
        if tr.op_line.get(dev) != "XLA Ops":
            continue
        mods = runs.get(dev, [])
        starts = [s for _, s, _ in mods]
        for (name, start, _), ns in zip(events, xplane.self_times(events)):
            i = bisect.bisect_right(starts, start) - 1
            inside = i >= 0 and start < mods[i][1] + mods[i][2]
            mod = mods[i][0] if inside else "(outside any program)"
            p = progs.setdefault(mod, {"scope_ns": defaultdict(float),
                                       "ops": defaultdict(float)})
            scope = opmap["ops"].get(name, OTHER) \
                if mod == opmap["program"] else OTHER
            p["scope_ns"][scope] += ns
            p["ops"][(scope, name)] += ns
        for name, _, dur in mods:
            p = progs.setdefault(name, {"scope_ns": defaultdict(float),
                                        "ops": defaultdict(float)})
            p["runs"] = p.get("runs", 0) + 1
            p["module_ns"] = p.get("module_ns", 0.0) + dur
    out = {}
    for mod, p in progs.items():
        n = max(1, p.get("runs", 0))
        busy = sum(p["scope_ns"].values())
        top = sorted(p["ops"].items(), key=lambda kv: -kv[1])[:8]
        out[mod] = {
            "runs": p.get("runs", 0),
            "module_ms_per_run": p.get("module_ns", 0.0) * 1e-6 / n,
            "op_self_ms_per_run": busy * 1e-6 / n,
            "scopes": {s: {"ms_per_run": ns * 1e-6 / n,
                           "share": ns / busy if busy else 0.0}
                       for s, ns in sorted(p["scope_ns"].items(),
                                           key=lambda kv: -kv[1])},
            "top_ops": [[s, o, ns * 1e-6 / n] for (s, o), ns in top]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?")
    ap.add_argument("--map", dest="opmap")
    ap.add_argument("--write-map", metavar="CONFIG")
    ap.add_argument("--out")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    if args.write_map:
        return write_map(args.write_map, Path(args.out))
    if not args.trace or not args.opmap:
        ap.error("a trace and --map, or --write-map CONFIG --out FILE")
    opmap = json.loads(Path(args.opmap).read_text())
    table = scope_table(args.trace, opmap)
    if args.json:
        print(json.dumps(table, indent=1))
        return 0
    for mod, t in sorted(table.items(), key=lambda kv: -kv[1]["op_self_ms_per_run"]
                         * max(1, kv[1]["runs"])):
        print(f"{mod}: {t['runs']} runs, {t['module_ms_per_run']:.4f} ms a "
              f"run on the device ({t['op_self_ms_per_run']:.4f} in ops)")
        for s, v in t["scopes"].items():
            print(f"    {s:16s} {v['ms_per_run']:9.4f} ms  {v['share']:6.1%}")
        for s, o, ms in t["top_ops"][:5]:
            print(f"      op {o:40s} {ms:9.4f} ms  [{s}]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
