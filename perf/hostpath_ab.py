#!/usr/bin/env python
"""perf/hostpath_ab — A/B for the host-plane executor of the streamed path.

B-side ("on", the round-14 default config): staging arena (``ops/arena.py``),
codec worker pool (``ops/codec_pool.py``), adaptive in-flight credit
controller (``tpu/kernel_block.py``). A-side ("off"): per-frame allocation,
inline synchronous codec, pinned static depth — the pre-round-14 host path
(``host_arena=0``, ``host_codec_workers=0``, ``tpu_inflight=<depth>``).

``--link-mbps H2D,D2H`` (default ``96,62``, a slow-link replay envelope)
installs the rate-throttled fake link so the CPU backend
reproduces a link-bound streamed regime deterministically. Each cell reports
**streamed link utilization**: achieved Msps over the COMPUTED wire-format
ceiling (``ops/wire.streamed_ceiling_msps`` — f32 on 96/62 is 12.0 Msps).

METHODOLOGY (the round-14 lesson): every run
builds a fresh kernel and pays XLA compilation inside the wall, so the
measured window must be LONG relative to it — short windows (≤ 32 frames)
under-report utilization by 20-40% and that error dominated earlier ad hoc
probes of this path. Runs here size themselves to ``--seconds`` of modeled
wire time per measurement.

The chain is deliberately light (rotator + |x|²: carry-bearing but far from
compute-bound on any host), so the LINK and the HOST PLANE are what is
measured — the bench chain's FFT is compute-comparable to the 96/62 wire on
small CI boxes and would mask the host path.

``--smoke`` (the check.sh gate): on the deterministic fake link, assert
(1) arena steady-state allocation is O(1) per frame class — the miss counter
is flat across a sustained window once the in-flight window's buffers have
warmed; (2) fused streamed utilization with the host-plane executor ON is
no worse than the pre-arena baseline.

CSV: ``mode,wire,frame,run,msamples_per_sec,utilization``.
"""

import argparse
import sys
import time

sys.path.insert(0, ".")
sys.path.insert(0, "..")

import numpy as np

#: modeled link envelope, set in main() from --link-mbps
_LINK = (96e6, 62e6)


def set_mode(mode: str, depth: int = 4) -> None:
    """Flip the host-plane executor config and drop the process singletons so
    the next kernel construction re-resolves them."""
    from futuresdr_tpu.config import config
    from futuresdr_tpu.ops import arena as _arena
    from futuresdr_tpu.ops import codec_pool as _codec
    c = config()
    if mode == "off":
        c.host_arena = False
        c.host_codec_workers = 0
        c.tpu_inflight = depth            # pinned static budget
    else:
        c.host_arena = True
        c.host_codec_workers = 2
        c.tpu_inflight = 0                # adaptive credits
    _arena.reset_arena()
    _codec.reset_pool()


def ceiling_msps(wire: str) -> float:
    """Computed wire-format link ceiling for the probe chain (c64 in,
    f32 out, 1:1)."""
    from futuresdr_tpu.ops.wire import streamed_ceiling_msps
    return streamed_ceiling_msps(wire, _LINK[0], _LINK[1],
                                 np.complex64, np.float32, 1.0)


def run_one(wire: str, frame: int, n_samples: int) -> tuple:
    """One streamed run; returns (msps, kernel)."""
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import Head, NullSink, NullSource
    from futuresdr_tpu.config import config
    from futuresdr_tpu.ops import mag2_stage, rotator_stage
    from futuresdr_tpu.tpu import TpuKernel

    config().buffer_size = max(config().buffer_size, 4 * frame * 8)
    fg = Flowgraph()
    src = NullSource(np.complex64)
    head = Head(np.complex64, n_samples)
    tk = TpuKernel([rotator_stage(0.05), mag2_stage()], np.complex64,
                   frame_size=frame, wire=wire)
    snk = NullSink(np.float32)
    fg.connect(src, head, tk, snk)
    t0 = time.perf_counter()
    Runtime().run(fg)
    dt = time.perf_counter() - t0
    assert snk.n_received >= (n_samples // frame) * frame, snk.n_received
    return n_samples / dt / 1e6, tk


def _sized_n(wire: str, frame: int, seconds: float) -> int:
    """Samples for ~``seconds`` of modeled wire time at the format ceiling."""
    n = int(ceiling_msps(wire) * 1e6 * seconds)
    return max(frame * 24, (n // frame) * frame)


def smoke() -> None:
    """The check.sh gate (fast, deterministic fake link)."""
    from futuresdr_tpu.ops import arena as _arena
    wire, frame, seconds = "f32", 1 << 18, 2.5
    ceil = ceiling_msps(wire)
    n = _sized_n(wire, frame, seconds)

    set_mode("off")
    run_one(wire, frame, frame * 8)                      # compile warm-up
    r_off, _ = run_one(wire, frame, n)
    u_off = r_off / ceil

    set_mode("on")
    run_one(wire, frame, frame * 8)                      # warm compile + arena
    ar = _arena.arena()
    assert ar is not None, "host_arena did not arm"
    m0 = ar.stats()["misses"]
    r_on, tk = run_one(wire, frame, n)
    u_on = r_on / ceil
    st = ar.stats()
    miss_delta = st["misses"] - m0
    frames = n // frame
    print(f"# hostpath smoke: off {r_off:.1f} Msps (util {u_off:.2f}) | "
          f"on {r_on:.1f} Msps (util {u_on:.2f}), credits "
          f"{tk._credits.credits}, arena misses +{miss_delta} over "
          f"{frames} frames (hits {st['hits']})")
    # (1) arena steady state: allocation count is O(1) per frame class — a
    # warmed pool serves a sustained window from recycled buffers. The slack
    # covers one window's worth of buffers for a class the warm-up run's
    # shorter window never reached (credit growth mid-run).
    assert miss_delta <= 8, \
        f"arena allocating per frame: +{miss_delta} misses / {frames} frames"
    assert st["hits"] >= frames, st
    # (2) the host-plane executor must not lose throughput vs the pre-arena
    # baseline (tolerance for CI-box noise; the committed artifact carries
    # the precise medians)
    assert r_on >= 0.92 * r_off, \
        f"hostpath executor slower than baseline: {r_on:.2f} vs {r_off:.2f}"
    # the binding-direction utilization floor: the drain loop must keep the
    # replayed link busy, not just beat the old path
    assert u_on >= 0.70, f"streamed link utilization {u_on:.2f} < 0.70"

    # (3) the packed (coalesced) transfer class — single-shot uplink round:
    # a quantizing wire now stages its payload+scale parts as ONE contiguous
    # packed buffer per dispatch group (ops/xfer.PackedLayout backed by
    # ops/arena.PackedAlloc), a NEW arena size class the pre-uplink baseline
    # never allocated. Re-baseline the flatness gate over it: once warmed,
    # the packed class must recycle like every other frame class (misses
    # flat over a sustained window), the kernel must report the coalesced
    # single-start layout, and utilization on the same replay link must sit
    # in the committed bar's neighborhood (the bench median grades against
    # the absolute 0.90 replay bar in perf/regress.py; the smoke window is
    # shorter, so its floor carries CI slack).
    wire = "sc16"
    ceil = ceiling_msps(wire)
    n = _sized_n(wire, frame, seconds)
    run_one(wire, frame, frame * 8)                      # warm the packed class
    m0 = ar.stats()["misses"]
    r_pk, tk = run_one(wire, frame, n)
    u_pk = r_pk / ceil
    st = ar.stats()
    miss_delta = st["misses"] - m0
    frames = n // frame
    em = tk.extra_metrics()
    print(f"# hostpath smoke (packed sc16): {r_pk:.1f} Msps (util "
          f"{u_pk:.2f}), h2d starts/frame {em['h2d_starts_per_frame']}, "
          f"arena misses +{miss_delta} over {frames} frames")
    assert em["uplink_coalesced"] == 1 and em["h2d_starts_per_frame"] == 1, em
    assert miss_delta <= 8, \
        f"packed class allocating per frame: +{miss_delta} / {frames} frames"
    assert u_pk >= 0.80, f"packed streamed utilization {u_pk:.2f} < 0.80"
    print("# hostpath smoke: OK")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--runs", type=int, default=3)
    p.add_argument("--seconds", type=float, default=6.0,
                   help="modeled wire seconds per measured run")
    p.add_argument("--wires", default="f32,sc16")
    p.add_argument("--frames", default=None,
                   help="comma-separated frame sizes (default 256k,2M)")
    p.add_argument("--link-mbps", default="96,62", metavar="H2D,D2H")
    p.add_argument("--smoke", action="store_true",
                   help="fast gate: arena O(1) steady-state allocation + "
                        "utilization no worse than the pre-arena baseline")
    a = p.parse_args()

    global _LINK
    h2d, d2h = (float(x) * 1e6 for x in a.link_mbps.split(","))
    _LINK = (h2d, d2h)
    from futuresdr_tpu.ops.xfer import set_fake_link
    set_fake_link(h2d, d2h)
    print(f"# fake link: H2D {h2d / 1e6:.0f} MB/s, D2H {d2h / 1e6:.0f} MB/s",
          file=sys.stderr)

    if a.smoke:
        smoke()
        return

    from futuresdr_tpu.ops import arena as _arena
    frames = ([int(f) for f in a.frames.split(",")] if a.frames
              else [1 << 18, 1 << 21])
    print("mode,wire,frame,run,msamples_per_sec,utilization")
    for wire in a.wires.split(","):
        ceil = ceiling_msps(wire)
        for frame in frames:
            n = _sized_n(wire, frame, a.seconds)
            for mode in ("off", "on"):
                set_mode(mode)
                run_one(wire, frame, frame * 8)          # compile warm-up
                rates = []
                for r in range(a.runs):
                    rate, tk = run_one(wire, frame, n)
                    rates.append(rate)
                    print(f"{mode},{wire},{frame},{r},{rate:.2f},"
                          f"{rate / ceil:.3f}", flush=True)
                med = sorted(rates)[(len(rates) - 1) // 2]
                extra = ""
                if mode == "on":
                    st = _arena.arena().stats()
                    extra = (f", credits {tk._credits.credits}, arena "
                             f"hits/misses {st['hits']}/{st['misses']}")
                print(f"# {mode} {wire} frame={frame}: median {med:.2f} Msps "
                      f"= {med / ceil:.3f}x of the {ceil:.1f} Msps ceiling"
                      f"{extra}", file=sys.stderr)


if __name__ == "__main__":
    main()
