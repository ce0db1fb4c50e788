#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide: compile both configurations'
programs at their real sizes for a v5e that is described, not attached.

    JAX_PLATFORMS=cpu python3 benchmark/tools/aot_compile.py

Proves that the chip's compiler takes the programs and that they fit its
memory (``memory_analysis()`` is printed). Nothing runs: it says nothing about
results or times and is never reported as a chip run. ``jax.default_backend()``
is the CPU here, so trace-time branches that ask for it (the MXU FFT, the
wire default) take their CPU side unless forced: the spectrum chain is
compiled with ``fft_stage(impl="mxu")`` and the sc16 wire named outright,
which is what the chip binds by itself.
"""

from __future__ import annotations

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
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import cells, refs

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def with_sharding(tree):
        return jax.tree_util.tree_map(
            lambda a: spec(np.shape(a), np.asarray(a).dtype
                           if not hasattr(a, "dtype") else a.dtype), tree)

    def compile_and_report(name, fn, *args):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).compile()
        print(f"{name}: compiled for {topo.devices[0].device_kind} in "
              f"{time.perf_counter() - t0:.1f} s")
        print(f"  memory_analysis: {compiled.memory_analysis()}")

    # -- spectrum_fir64_fft2048 at 262144, sc16 wire ---------------------------
    from futuresdr_tpu.ops import fft_stage, fir_stage, mag2_stage
    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.ops.wire import resolve_wire

    cfg = cells.load_json(_BENCH / "configs" / "spectrum_fir64_fft2048.json")
    p, exp = cfg["parameters"], cfg["expected_on_chip"]
    taps = refs.lowpass(cfg["assumed"]["lowpass_cutoff"],
                        p["n_taps"]).astype(np.float32)
    pipe = Pipeline([fir_stage(taps), fft_stage(p["n_fft"], impl="mxu"),
                     mag2_stage()], np.complex64)
    frame = exp["frame_size"]
    wire = resolve_wire(exp["wire"], "tpu")
    parts = wire.encode_host(np.zeros(frame, np.complex64))
    wired = pipe.wired_fn(wire)      # decode prolog + chain + encode epilog
    compile_and_report(
        f"spectrum_fir64_fft2048 frame={frame} wire={wire.name}", wired,
        with_sharding(pipe.init_carry()),
        *[spec(np.shape(q), np.asarray(q).dtype) for q in parts])

    # -- fm_serve_1msps at 64 x 65500 ------------------------------------------
    from futuresdr_tpu.apps.fm_receiver import front_end_stages
    from futuresdr_tpu.serve.engine import build_slot_program

    cfg = cells.load_json(_BENCH / "configs" / "fm_serve_1msps.json")
    exp = cfg["expected_on_chip"]
    cap, fs = exp["capacity"], exp["frame_size"]
    pipe = Pipeline(front_end_stages(), np.complex64)
    step = build_slot_program(pipe, cap, 1)
    lane = pipe.init_carry()
    pages = jax.tree_util.tree_map(
        lambda a: spec((cap,) + tuple(np.shape(a)), a.dtype), lane)
    compile_and_report(
        f"fm_serve_1msps capacity={cap} frame={fs}", step, pages,
        spec((cap,), np.int32), spec((cap,), np.bool_),
        spec((cap, fs), np.complex64), spec((cap,), np.bool_))
    return 0


if __name__ == "__main__":
    sys.exit(main())
