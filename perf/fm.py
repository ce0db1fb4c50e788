#!/usr/bin/env python
"""perf/fm — FM front-end throughput (BASELINE target #3).

Reference: ``examples/fm-receiver/src/main.rs:83-130`` — freq-shift → decimating
FIR → quadrature demod → audio resampler. Two modes, both reusing the app's own
chain (``apps/fm_receiver.py``) so the benchmark measures exactly what ships:

- **CPU block path**: XlatingFir → QuadDemod → rational-resampler FIR through the
  actor runtime (the reference's per-block deployment).
- **device-resident fused** (``--device-resident``): ``front_end_stages()`` as ONE
  carry-chained XLA program over HBM-resident frames, measured with the
  scan-marginal methodology (``utils/measure.run_marginal`` — see
  docs/tpu_notes.md "Measuring device-resident rates").

Rates are reported in input-rate Msamples/s (1 Msps complex in → 48 ksps audio out).
CSV: ``mode,backend,frame,run,msamples_per_sec``.
"""

import argparse
import sys
import time

sys.path.insert(0, ".")
sys.path.insert(0, "..")

import numpy as np


def run_cpu_blocks(n_samples: int) -> float:
    from futuresdr_tpu import Runtime
    from futuresdr_tpu.apps.fm_receiver import build_flowgraph
    from futuresdr_tpu.blocks import NullSource

    fg, _, snk = build_flowgraph(NullSource(np.complex64), offset=100e3,
                                 n_samples=n_samples)
    t0 = time.perf_counter()
    Runtime().run(fg)
    dt = time.perf_counter() - t0
    assert snk.n_received > 0
    return n_samples / dt / 1e6


def run_device_resident(frame_frames: int, k_pair) -> tuple:
    import jax
    from futuresdr_tpu.apps.fm_receiver import front_end_stages
    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.ops.xfer import to_device
    from futuresdr_tpu.utils.measure import run_marginal_retry

    pipe = Pipeline(front_end_stages(offset=100e3), np.complex64)
    frame = pipe.frame_multiple * frame_frames
    # scale scan lengths so one k_lo scan covers ≥2M samples — sub-ms timed
    # windows made fm_msps host-load sensitive (same fix as perf/lora.py)
    scale = max(1, -(-2_000_000 // (k_pair[0] * frame)))
    k_pair = (k_pair[0] * scale, k_pair[1] * scale)
    rng = np.random.default_rng(3)
    host = (rng.standard_normal(frame)
            + 1j * rng.standard_normal(frame)).astype(np.complex64)
    carry0 = jax.device_put(pipe.init_carry())
    x = to_device(host)
    rate = run_marginal_retry(pipe.fn(), carry0, x, k_pair) / 1e6
    return rate, frame


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--runs", type=int, default=3)
    p.add_argument("--cpu-samples", type=int, default=4_000_000)
    p.add_argument("--device-resident", action="store_true",
                   help="also measure the fused carry-chained device pipeline")
    p.add_argument("--frame-frames", type=int, default=1024,
                   help="device frame = frame_multiple × this")
    a = p.parse_args()

    from futuresdr_tpu.tpu.instance import instance
    backend = instance().platform
    print(f"# backend: {backend}", file=sys.stderr)

    print("mode,backend,frame,run,msamples_per_sec")
    for r in range(a.runs):
        rate = run_cpu_blocks(a.cpu_samples)
        print(f"cpu_blocks,{backend},-,{r},{rate:.2f}", flush=True)

    if a.device_resident:
        from futuresdr_tpu.utils.measure import default_k_pair
        k_pair = default_k_pair(backend)
        for r in range(a.runs):
            rate, frame = run_device_resident(a.frame_frames, k_pair)
            print(f"device_resident,{backend},{frame},{r},{rate:.1f}", flush=True)


if __name__ == "__main__":
    main()
