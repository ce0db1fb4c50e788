#!/usr/bin/env python
"""perf/streamed_ab — A/B matrix for the TpuKernel STREAMED path.

History: VERDICT r3 weak-item 1 traced a streamed regression to bench.py
measuring the streamed loop at the device-resident sweep's winning frame size;
this probe has pinned the frame axis ever since. The round-6 wire-codec PR
adds the third axis: the **wire format** (``ops/wire.py`` — f32/bf16/sc16/sc8)
now decides how many bytes each frame pays on the link, and the drain loop is
fully pipelined (H2D(t+1) ∥ compute(t) ∥ D2H(t−1)), so the old read-ahead
on/off hack is superseded by the honest serialization axis: ``depth=1``
(one frame in flight — transfers and compute strictly alternate) vs the
pipelined depth. One run therefore commits the whole
(format × frame × depth) tradeoff as one table.

``--link-mbps H2D,D2H`` installs the rate-throttled fake link
(``ops/xfer.set_fake_link``) so the CPU backend reproduces a link-bound
streamed regime deterministically — under the ``96,62`` slow-link replay
envelope sc16 must sustain ≥ 2× the f32 rate (the codec
halves the bytes of both directions; acceptance gate of the wire-codec PR).

CSV: ``wire,frame,depth,run,msamples_per_sec``.
"""

import argparse
import sys
import time

sys.path.insert(0, ".")
sys.path.insert(0, "..")

import numpy as np


def run_one(wire: str, frame: int, depth: int, n_samples: int) -> float:
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import Head, NullSink, NullSource
    from futuresdr_tpu.config import config
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fft_stage, fir_stage, mag2_stage
    from futuresdr_tpu.tpu import TpuKernel

    config().buffer_size = max(config().buffer_size, 4 * frame * 8)
    taps = firdes.lowpass(0.2, 64).astype(np.float32)
    stages = [fir_stage(taps), fft_stage(2048), mag2_stage()]
    fg = Flowgraph()
    src = NullSource(np.complex64)
    head = Head(np.complex64, n_samples)
    tk = TpuKernel(stages, np.complex64, frame_size=frame,
                   frames_in_flight=depth, wire=wire)
    snk = NullSink(np.float32)
    fg.connect(src, head, tk, snk)
    t0 = time.perf_counter()
    Runtime().run(fg)
    dt = time.perf_counter() - t0
    assert snk.n_received >= (n_samples // frame) * frame
    return n_samples / dt / 1e6


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--runs", type=int, default=3)
    p.add_argument("--depth", type=int, default=8,
                   help="pipelined in-flight depth (depth=1 is always added "
                        "as the serialized A-side)")
    p.add_argument("--seconds", type=float, default=8.0,
                   help="approx wall time per measured run")
    p.add_argument("--wires", default="f32,sc16",
                   help="comma-separated wire formats (ops/wire.py)")
    p.add_argument("--frames", default=None,
                   help="comma-separated frame sizes (default: 512k,2M — the "
                        "r2/r3 pins)")
    p.add_argument("--link-mbps", default=None, metavar="H2D,D2H",
                   help="throttle transfers through the fake link at these "
                        "MB/s (CPU-backend link-bound reproduction; 96,62 "
                        "is the slow-link replay envelope)")
    p.add_argument("--trace", default=None, metavar="OUT_JSON",
                   help="record telemetry spans across the whole matrix and "
                        "write a Chrome-trace JSON artifact (open in Perfetto; "
                        "per-run overlap summaries go to stderr)")
    a = p.parse_args()

    from futuresdr_tpu.tpu.instance import instance
    backend = instance().platform
    print(f"# backend: {backend}", file=sys.stderr)
    if a.trace:
        from futuresdr_tpu.telemetry import spans
        spans.enable(True)
    if a.link_mbps:
        from futuresdr_tpu.ops.xfer import set_fake_link
        h2d, d2h = (float(x) * 1e6 for x in a.link_mbps.split(","))
        set_fake_link(h2d, d2h)
        print(f"# fake link: H2D {h2d / 1e6:.0f} MB/s, D2H {d2h / 1e6:.0f} MB/s",
              file=sys.stderr)

    frames = ([int(f) for f in a.frames.split(",")] if a.frames
              else [1 << 19, 1 << 21])
    all_events = []
    print("wire,frame,depth,run,msamples_per_sec")
    for wire in a.wires.split(","):
        for frame in frames:
            for depth in dict.fromkeys((1, a.depth)):
                # short probe sizes the sustained run
                rate = run_one(wire, frame, depth, frame * 2 * max(depth, 2))
                n = int(max(rate * 1e6 * a.seconds, frame * 2 * max(depth, 2)))
                n = (n // frame) * frame
                for r in range(a.runs):
                    if a.trace:
                        from futuresdr_tpu.telemetry import spans
                        all_events.extend(spans.drain())  # pre-run leftovers
                    rate = run_one(wire, frame, depth, n)
                    print(f"{wire},{frame},{depth},{r},{rate:.2f}", flush=True)
                    if a.trace:
                        evs = spans.drain()
                        rep = spans.overlap_report(evs)
                        all_events.extend(evs)
                        print(f"# overlap {wire}/{frame}/{depth}/{r}: "
                              f"union/sum = {rep['ratio']:.2f} "
                              f"(sum {rep['sum_s']:.2f}s)", file=sys.stderr)
    if a.trace:
        from futuresdr_tpu.telemetry import spans
        spans.export(a.trace, all_events)
        print(f"# trace artifact written to {a.trace}", file=sys.stderr)


if __name__ == "__main__":
    main()
