#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide for ``wlan_rx_20msps``: the
receiver's program at its real size (262144-sample frame, sc16 wire, the
shipped carry, slots and lanes) compiled for a v5e that is described, not
attached. ``aot_compile.py`` knows its two programs by name; this is the
third.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_compile_wlan.py [--core]

Proves that the chip's compiler takes the program and that it fits the
chip's memory (``memory_analysis()`` is printed). Nothing runs: no result, no
time. ``--core`` also compiles the Viterbi core alone, uncut and cut into
blocks, at the cell's lane and step counts (the stage A/B of PERF.md section 5).
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
os.environ["FUTURESDR_TPU_AUTOTUNE_CACHE_DIR"] = "off"
_BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_BENCH.parent), str(_BENCH)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--core", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import cells

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def compile_and_report(name, fn, *specs):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).trace(*specs).lower(
            lowering_platforms=("tpu",)).compile()
        print(f"{name}: compiled for {topo.devices[0].device_kind} in "
              f"{time.perf_counter() - t0:.1f} s")
        print(f"  memory_analysis: {compiled.memory_analysis()}")

    from futuresdr_tpu.models.wlan.rx_stages import wlan_rx_stages
    from futuresdr_tpu.ops import viterbi
    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.ops.wire import resolve_wire

    # the backend here is the CPU: the trellis kernels resolve to the
    # interpreter on it, and what is to be proved is Mosaic's compile
    viterbi._interpret = lambda: False
    cfg = cells.load_json(_BENCH / "configs" / "wlan_rx_20msps.json")
    p, exp = cfg["parameters"], cfg["expected_on_chip"]
    sizes = {k: p[k] for k in ("carry_len", "max_psdu", "cand_slots", "lanes")}
    pipe = Pipeline(wlan_rx_stages(**sizes), np.complex64)
    frame = exp["frame_size"]
    wire = resolve_wire(exp["wire"], "tpu")
    parts = wire.encode_host(np.zeros(frame, np.complex64))
    carry = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype),
                                   jax.eval_shape(pipe.init_carry))
    compile_and_report(
        f"wlan_rx_20msps frame={frame} wire={wire.name} {sizes}",
        pipe.wired_fn(wire), carry,
        *[spec(np.shape(q), np.asarray(q).dtype) for q in parts])

    if args.core:
        from futuresdr_tpu.models.wlan import coding
        from futuresdr_tpu.ops.viterbi import (piece_slots, viterbi_blocks,
                                               viterbi_core)
        tables = (coding._PREV_S, coding._PREV_B, coding._BM0, coding._BM1)
        T, L = 16 + 8 * p["max_psdu"] + 6, p["lanes"]
        compile_and_report(
            f"viterbi_core T={T} lanes={L}",
            lambda llr, n: viterbi_core(llr, n, *tables),
            spec((T, 2, L), np.float32), spec((L,), np.int32))
        slots = piece_slots(1152)
        compile_and_report(
            f"viterbi_blocks T={T} lanes={L} piece slots={slots}",
            lambda s, n: viterbi_blocks(s, n, *tables, n_blocks=slots),
            spec((2, L, T), np.float32), spec((L,), np.int32))
    return 0


if __name__ == "__main__":
    sys.exit(main())
