#!/usr/bin/env python
"""perf/uplink_ab — A/B matrix for the single-shot uplink (round 22).

Three independent host-plane mechanisms land this round, each with a kill
switch, measured here one axis at a time on the deterministic throttled
replay link (default ``96,62``, the slow-link envelope ``perf/hostpath_ab.py``
uses):

* **Transfer coalescing** (``tpu_coalesce``): a quantizing wire's per-frame
  parts (payload + scale) ride ONE contiguous packed buffer per dispatch
  group — one physical H2D start instead of one per part
  (``ops/xfer.PackedLayout`` / ``ops/arena.PackedAlloc``; the device-side
  slicing prolog is fused into the wired program).
* **Zero-copy ingest** (``tpu_zero_copy_ingest``): a REGISTERED read-only
  capture buffer skips the ring-exit staging copy on aliasing wires (f32 /
  bf16), pinned until replay coverage commits (``ops/ingest.py``).
* **Deferred-consume staging** (``tpu_deferred_consume``): at K=1 with the
  codec pool armed, the worker encode reads the ring slot in place and the
  ring consume is deferred until the encode lands — the quantizing wire's
  extra staging copy disappears.

Cells are driven through the mock harness (``futuresdr_tpu.Mocker``) so the
ingest axis can engage (the actor ring hands out writable frames, which are
never eligible), with compile + warm-up OUTSIDE the measured wall — the
round-14 lesson inverted: rather than sizing runs long enough to amortize
XLA compilation, the harness excludes it and sizes runs to ``--seconds`` of
modeled wire time for steady-state confidence. Utilization numbers here are
therefore a few points ABOVE the hostpath harness's compile-inclusive ones
at equal window length.

Chain: rotator → |x|² (carry-bearing, never compute-bound) — the LINK and
the HOST PLANE are what is measured. **Utilization** = achieved Msps over
the COMPUTED wire-format ceiling (``ops/wire.streamed_ceiling_msps``).

Matrix: f32 × {ingest off, on} and sc16 × {per-part, +coalesce, +deferred,
both} at 256k and 2M frames. The 256k cells also assert bit-equality across
the config axes (same input ⇒ identical output regardless of packing /
ingest / deferred staging).

CSV: ``wire,frame,cell,run,msamples_per_sec,utilization``.
"""

import argparse
import sys
import time

sys.path.insert(0, ".")
sys.path.insert(0, "..")

import numpy as np

#: modeled link envelope, set in main() from --link-mbps
_LINK = (96e6, 62e6)


def ceiling_msps(wire: str) -> float:
    from futuresdr_tpu.ops.wire import streamed_ceiling_msps
    return streamed_ceiling_msps(wire, _LINK[0], _LINK[1],
                                 np.complex64, np.float32, 1.0)


def _data(n: int) -> np.ndarray:
    rng = np.random.default_rng(11)
    return (rng.standard_normal(n) + 1j * rng.standard_normal(n)) \
        .astype(np.complex64)


def run_cell(wire: str, frame: int, data: np.ndarray, *, coalesce: bool,
             deferred: bool, register: bool, depth: int = 4) -> tuple:
    """One mock-driven streamed window on the replay link; compile and
    warm-up pay outside the wall. Returns ``(msps, output, extra_metrics)``."""
    from futuresdr_tpu import Mocker
    from futuresdr_tpu.config import config
    from futuresdr_tpu.ops import ingest, mag2_stage, rotator_stage
    from futuresdr_tpu.tpu import TpuKernel

    n = len(data)
    c = config()
    c.tpu_coalesce = coalesce
    c.tpu_deferred_consume = deferred
    try:
        if register:
            ingest.register(data, name="uplink-ab")
        tk = TpuKernel([rotator_stage(0.05), mag2_stage()], np.complex64,
                       frame_size=frame, frames_in_flight=depth, wire=wire)
        m = Mocker(tk)
        m.input("in", data)
        m.init_output("out", n + frame)
        m.init()                 # compile + cost probes outside the wall
        t0 = time.perf_counter()
        m.run()
        dt = time.perf_counter() - t0
        out = m.output("out").copy()
        em = tk.extra_metrics()
    finally:
        ingest.reset()
        c.tpu_coalesce = True
        c.tpu_deferred_consume = True
    return n / dt / 1e6, out, em


#: cell name -> (coalesce, deferred, register); the ingest axis only applies
#: to aliasing wires, the coalesce/deferred axes only to quantizing ones
CELLS = {
    "f32": (("ingest-off", (True, True, False)),
            ("ingest-on", (True, True, True))),
    "sc16": (("per-part", (False, False, False)),
             ("coalesce", (True, False, False)),
             ("deferred", (False, True, False)),
             ("both", (True, True, False))),
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--runs", type=int, default=3)
    p.add_argument("--seconds", type=float, default=1.2,
                   help="modeled wire seconds per measured run")
    p.add_argument("--wires", default="f32,sc16")
    p.add_argument("--frames", default=None,
                   help="comma-separated frame sizes (default 256k,2M)")
    p.add_argument("--link-mbps", default="96,62", metavar="H2D,D2H")
    a = p.parse_args()

    global _LINK
    h2d, d2h = (float(x) * 1e6 for x in a.link_mbps.split(","))
    _LINK = (h2d, d2h)
    from futuresdr_tpu.config import config
    from futuresdr_tpu.ops.xfer import set_fake_link
    set_fake_link(h2d, d2h)
    print(f"# fake link: H2D {h2d / 1e6:.0f} MB/s, D2H {d2h / 1e6:.0f} MB/s",
          file=sys.stderr)

    frames = ([int(f) for f in a.frames.split(",")] if a.frames
              else [1 << 18, 1 << 21])
    print("wire,frame,cell,run,msamples_per_sec,utilization")
    for wire in a.wires.split(","):
        ceil = ceiling_msps(wire)
        for frame in frames:
            config().buffer_size = max(config().buffer_size, 4 * frame * 8)
            n = max(frame * 8, int(ceil * 1e6 * a.seconds) // frame * frame)
            data = _data(n)
            ref_out = None
            for cell, (co, de, reg) in CELLS[wire]:
                # warm the compile cache + arena classes for this config
                run_cell(wire, frame, data[:frame * 4], coalesce=co,
                         deferred=de, register=reg)
                rates, em, out = [], {}, None
                for r in range(a.runs):
                    rate, out, em = run_cell(wire, frame, data, coalesce=co,
                                             deferred=de, register=reg)
                    rates.append(rate)
                    print(f"{wire},{frame},{cell},{r},{rate:.2f},"
                          f"{rate / ceil:.3f}", flush=True)
                # the config axes must be output-invariant (bit-equality is
                # the uplink's core contract; the 256k cells carry it here,
                # the test suite carries replay/fault coverage)
                if frame <= 1 << 18:
                    if ref_out is None:
                        ref_out = out
                    else:
                        np.testing.assert_array_equal(out, ref_out)
                med = sorted(rates)[(len(rates) - 1) // 2]
                extra = (f", h2d starts/frame {em['h2d_starts_per_frame']}, "
                         f"ingest frac {em['ingest_zero_copy_frac']:.2f}, "
                         f"deferred {em['deferred_consume']}")
                print(f"# {wire} frame={frame} {cell}: median {med:.2f} Msps "
                      f"= {med / ceil:.3f}x of the {ceil:.1f} Msps ceiling"
                      f"{extra}", file=sys.stderr)


if __name__ == "__main__":
    main()
