#!/usr/bin/env python
"""perf/devchain_ab — A/B for the device-graph fusion pass (runtime/devchain.py).

The B-side is the per-hop frame plane: ``TpuH2D → TpuStage×3 → TpuD2H``, every
stage its own per-frame jit dispatch with the intermediate frame materialized
between blocks (run with ``FSDR_NO_DEVCHAIN=1``). The A-side is the SAME
flowgraph with the fusion pass on: the three stages collapse into ONE fused
TpuKernel program per frame, optionally megabatched (``frames_per_dispatch`` =
K frames per program call via ``lax.scan``). Throughput is wall-clock over a
NullSource→Head stream; per-frame dispatch counts come from the blocks' own
metrics (TpuStage dispatch counters on the B-side, the fused kernel's
dispatch counter through the devchain metrics bridge on the A-side).

``--fanout`` A/Bs the BROADCAST fusion pass instead: a 1→2 ``TpuKernel``
fan-out (producer FIR feeding a decimating-FIR branch and a |x|² branch over
STREAM edges). Unfused, the intermediate crosses the host↔device link once
DOWN (producer D2H) and TWICE UP (each branch re-uploads the broadcast
samples) per frame — 3× the input bytes on the H2D wire and 3 compute
dispatches per frame. Fused (``TpuFanoutKernel``), the input uploads ONCE and
one multi-output program serves both branches: link bytes/frame drop to 1×
upload and dispatches/frame to 1. ``--link-mbps H2D,D2H`` replays a measured
link envelope through the deterministic fake link (``ops/xfer.set_fake_link``)
so the CPU backend reproduces a link-bound regime (96/62 MB/s, a slow-link
envelope); H2D byte accounting comes from the always-on
``fsdr_xfer_bytes_total{direction="h2d"}`` counter.

``--dag`` A/Bs the GENERAL-DAG fusion pass (round 13): the frame-plane
DIAMOND ``broadcast → two decim-4 FIR branches → add-merge → |x|²`` (the
WLAN ``sync → {demod, chan-est} → decode`` closure, ``TpuMergeStage``) and
the stream-plane NESTED fan-out ``prod → {a → {c, d}, b}`` (a broadcast
inside a branch). Per-hop, the nested shape pays every interior hop on the
host↔device link BOTH ways per frame and the diamond pays one dispatch per
device block; fused (``TpuDagKernel``) each region is ONE multi-output
dispatch per frame whose D2H bills exactly the SINK payloads — interior-edge
transfer bytes drop to ZERO (asserted via ``fsdr_xfer_bytes_total``).

``--smoke`` (the check.sh gate) asserts counts only: linear dispatches per
frame 3 → 1; fan-out fused H2D bytes/frame == 1× upload with
dispatches/frame == 1; DAG fused dispatches/frame == 1 with interior-edge D2H
bytes == 0. The sweeps print CPU-backend, fake-link rates: orientation for a
developer, never a device metric (no cell of ``BENCHMARK.json`` runs an
unfused, a fan-out or a DAG region).

CSV: ``mode,frame,k,run,msamples_per_sec,frames,dispatches,dispatch_per_frame``
(+ ``h2d_bytes_per_frame`` in fan-out mode, ``shape`` +
``d2h_bytes_per_frame`` in DAG mode).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, ".")
sys.path.insert(0, "..")

import numpy as np


def _build(frame: int):
    from futuresdr_tpu import Flowgraph
    from futuresdr_tpu.blocks import Head, NullSink, NullSource
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage, mag2_stage
    from futuresdr_tpu.tpu import TpuD2H, TpuH2D, TpuStage
    return Flowgraph, NullSource, Head, TpuH2D, TpuStage, TpuD2H, NullSink, \
        firdes, fir_stage, mag2_stage


def run_one(mode: str, frame: int, k: int, n_samples: int) -> tuple:
    """One flowgraph run; returns (msps, frames, dispatches)."""
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import Head, NullSink, NullSource
    from futuresdr_tpu.config import config
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage, mag2_stage
    from futuresdr_tpu.tpu import TpuD2H, TpuH2D, TpuStage

    config().buffer_size = max(config().buffer_size, 4 * frame * 8)
    old_k = config().tpu_frames_per_dispatch
    config().tpu_frames_per_dispatch = k
    if mode == "unfused":
        os.environ["FSDR_NO_DEVCHAIN"] = "1"
    else:
        os.environ.pop("FSDR_NO_DEVCHAIN", None)
    try:
        t1 = firdes.lowpass(0.25, 64).astype(np.float32)
        t2 = firdes.lowpass(0.2, 64).astype(np.float32)
        t3 = firdes.lowpass(0.15, 64).astype(np.float32)
        fg = Flowgraph()
        src = NullSource(np.complex64)
        head = Head(np.complex64, n_samples)
        h2d = TpuH2D(np.complex64, frame_size=frame)
        sts = [TpuStage([fir_stage(t1, name="a")], np.complex64),
               TpuStage([fir_stage(t2, name="b")], np.complex64),
               TpuStage([fir_stage(t3, name="c")], np.complex64)]
        d2h = TpuD2H(np.complex64)
        snk = NullSink(np.complex64)
        fg.connect_stream(src, "out", head, "in")
        fg.connect_stream(head, "out", h2d, "in")
        prev = h2d
        for st in sts:
            fg.connect_inplace(prev, "out", st, "in")
            prev = st
        fg.connect_inplace(prev, "out", d2h, "in")
        fg.connect_stream(d2h, "out", snk, "in")
        t0 = time.perf_counter()
        Runtime().run(fg)
        dt = time.perf_counter() - t0
        assert snk.n_received >= (n_samples // frame) * frame, snk.n_received
        if mode == "unfused":
            frames = n_samples // frame
            dispatches = sum(st._dispatches for st in sts)
            assert dispatches == 3 * frames, (dispatches, frames)
        else:
            m = sts[0].extra_metrics()
            assert m.get("fused_devchain"), "fusion did not engage"
            frames = m["devchain_frames"]
            dispatches = m["devchain_dispatches"]
        return n_samples / dt / 1e6, frames, dispatches
    finally:
        config().tpu_frames_per_dispatch = old_k
        os.environ.pop("FSDR_NO_DEVCHAIN", None)


def _h2d_bytes() -> float:
    from futuresdr_tpu.telemetry import prom
    return prom.counter("fsdr_xfer_bytes_total",
                        labelnames=("direction",)).get(direction="h2d")


def _d2h_bytes() -> float:
    from futuresdr_tpu.telemetry import prom
    return prom.counter("fsdr_xfer_bytes_total",
                        labelnames=("direction",)).get(direction="d2h")


def run_fanout(mode: str, frame: int, k: int, n_samples: int) -> tuple:
    """One 1→2 stream-plane fan-out run; returns
    (msps, frames, dispatches, h2d_bytes_per_frame)."""
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import Head, NullSink, NullSource
    from futuresdr_tpu.config import config
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage, mag2_stage
    from futuresdr_tpu.tpu import TpuKernel

    config().buffer_size = max(config().buffer_size, 4 * frame * 8)
    old_k = config().tpu_frames_per_dispatch
    config().tpu_frames_per_dispatch = k
    if mode == "unfused":
        os.environ["FSDR_NO_DEVCHAIN"] = "1"
    else:
        os.environ.pop("FSDR_NO_DEVCHAIN", None)
    try:
        t1 = firdes.lowpass(0.25, 64).astype(np.float32)
        t2 = firdes.lowpass(0.2, 64).astype(np.float32)
        fg = Flowgraph()
        src = NullSource(np.complex64)
        head = Head(np.complex64, n_samples)
        prod = TpuKernel([fir_stage(t1, name="p")], np.complex64,
                         frame_size=frame)
        b1 = TpuKernel([fir_stage(t2, decim=4, name="b1")], np.complex64,
                       frame_size=frame)
        b2 = TpuKernel([mag2_stage()], np.complex64, frame_size=frame)
        s1 = NullSink(np.complex64)
        s2 = NullSink(np.float32)
        fg.connect_stream(src, "out", head, "in")
        fg.connect_stream(head, "out", prod, "in")
        fg.connect_stream(prod, "out", b1, "in")   # broadcast port group
        fg.connect_stream(prod, "out", b2, "in")
        fg.connect_stream(b1, "out", s1, "in")
        fg.connect_stream(b2, "out", s2, "in")
        bytes0 = _h2d_bytes()
        t0 = time.perf_counter()
        Runtime().run(fg)
        dt = time.perf_counter() - t0
        h2d = _h2d_bytes() - bytes0
        n_frames = n_samples // frame
        assert s1.n_received >= n_frames * (frame // 4), s1.n_received
        assert s2.n_received >= n_frames * frame, s2.n_received
        if mode == "unfused":
            frames = n_frames
            dispatches = sum(kk._dispatches for kk in (prod, b1, b2))
        else:
            m = prod.extra_metrics()
            assert m.get("fused_devchain"), "fan-out fusion did not engage"
            frames = m["devchain_frames"]
            dispatches = m["devchain_dispatches"]
        return n_samples / dt / 1e6, frames, dispatches, h2d / max(1, frames)
    finally:
        config().tpu_frames_per_dispatch = old_k
        os.environ.pop("FSDR_NO_DEVCHAIN", None)


def run_dag(mode: str, shape: str, frame: int, k: int, n_samples: int) -> tuple:
    """One general-DAG run (round-13 fusion pass); returns
    ``(msps, frames, dispatches, d2h_bytes_per_frame)``.

    ``shape="diamond"`` — frame plane: ``TpuH2D → broadcast → two decim-4
    FIR branches → TpuMergeStage(add, |x|²) → TpuD2H`` (the WLAN
    ``sync → {demod, chan-est} → decode`` closure). Per-hop this pays one jit
    dispatch per device block per frame; fused it is ONE multi-output
    dispatch with every interior edge device-resident.

    ``shape="nested"`` — stream plane: ``prod → {a → {c, d}, b}`` TpuKernels
    (a broadcast inside a branch). Per-hop EVERY member pays its own
    D2H+H2D link crossing per frame — the interior-edge traffic the fused
    ``TpuDagKernel`` eliminates (D2H bills exactly the SINK payloads)."""
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import Head, NullSink, NullSource
    from futuresdr_tpu.config import config
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import add_merge_stage, fir_stage, mag2_stage
    from futuresdr_tpu.tpu import TpuD2H, TpuH2D, TpuKernel, TpuStage
    from futuresdr_tpu.tpu.frames import TpuMergeStage

    config().buffer_size = max(config().buffer_size, 4 * frame * 8)
    old_k = config().tpu_frames_per_dispatch
    config().tpu_frames_per_dispatch = k
    if mode == "unfused":
        os.environ["FSDR_NO_DEVCHAIN"] = "1"
    else:
        os.environ.pop("FSDR_NO_DEVCHAIN", None)
    try:
        t1 = firdes.lowpass(0.25, 64).astype(np.float32)
        t2 = firdes.lowpass(0.2, 64).astype(np.float32)
        fg = Flowgraph()
        src = NullSource(np.complex64)
        head = Head(np.complex64, n_samples)
        fg.connect_stream(src, "out", head, "in")
        n_frames = n_samples // frame
        if shape == "diamond":
            h2d = TpuH2D(np.complex64, frame_size=frame)
            b1 = TpuStage([fir_stage(t1, decim=4, name="b1")], np.complex64)
            b2 = TpuStage([fir_stage(t2, decim=4, name="b2")], np.complex64)
            mg = TpuMergeStage(add_merge_stage(2), [mag2_stage()])
            d2h = TpuD2H(np.float32)
            snk = NullSink(np.float32)
            fg.connect_stream(head, "out", h2d, "in")
            fg.connect_inplace(h2d, "out", b1, "in")
            fg.connect_inplace(h2d, "out", b2, "in")
            fg.connect_inplace(b1, "out", mg, "in0")
            fg.connect_inplace(b2, "out", mg, "in1")
            fg.connect_inplace(mg, "out", d2h, "in")
            fg.connect_stream(d2h, "out", snk, "in")
            probes = [b1, b2, mg]
            fused_probe = mg
            sink_check = lambda: snk.n_received >= n_frames * (frame // 4)
        else:
            prod = TpuKernel([fir_stage(t1, name="p")], np.complex64,
                             frame_size=frame)
            a = TpuKernel([fir_stage(t2, name="a")], np.complex64,
                          frame_size=frame)
            b = TpuKernel([mag2_stage()], np.complex64, frame_size=frame)
            c = TpuKernel([fir_stage(t2, decim=4, name="c")], np.complex64,
                          frame_size=frame)
            d = TpuKernel([mag2_stage()], np.complex64, frame_size=frame)
            s_c, s_d, s_b = (NullSink(np.complex64), NullSink(np.float32),
                             NullSink(np.float32))
            fg.connect_stream(head, "out", prod, "in")
            fg.connect_stream(prod, "out", a, "in")
            fg.connect_stream(prod, "out", b, "in")
            fg.connect_stream(a, "out", c, "in")
            fg.connect_stream(a, "out", d, "in")
            fg.connect_stream(c, "out", s_c, "in")
            fg.connect_stream(d, "out", s_d, "in")
            fg.connect_stream(b, "out", s_b, "in")
            probes = [prod, a, b, c, d]
            fused_probe = prod
            sink_check = lambda: s_b.n_received >= n_frames * frame
        bytes0 = _d2h_bytes()
        t0 = time.perf_counter()
        Runtime().run(fg)
        dt = time.perf_counter() - t0
        d2h = _d2h_bytes() - bytes0
        assert sink_check()
        if mode == "unfused":
            frames = n_frames
            dispatches = sum(p._dispatches for p in probes)
        else:
            m = fused_probe.extra_metrics()
            assert m.get("fused_devchain"), "DAG fusion did not engage"
            frames = m["devchain_frames"]
            dispatches = m["devchain_dispatches"]
        return n_samples / dt / 1e6, frames, dispatches, d2h / max(1, frames)
    finally:
        config().tpu_frames_per_dispatch = old_k
        os.environ.pop("FSDR_NO_DEVCHAIN", None)


def _dag_smoke(frame: int = 32768, n_frames: int = 12) -> None:
    """CI gate for the general-DAG pass (ISSUE 9 acceptance): both DAG
    shapes fuse to ONE dispatch per frame, and the fused side's
    INTERIOR-edge D2H traffic is ZERO — its marginal D2H bytes/frame equal
    exactly the SINK payloads (``fsdr_xfer_bytes_total``; the marginal
    between a 1× and a 2× run cancels the constant compile-time
    carry/fence transfers, leaving pure per-frame wire traffic). The
    per-hop nested run pays every interior hop on the D2H wire (and the
    matching re-uploads on H2D) — the bounce the fusion deletes."""
    from futuresdr_tpu.ops.xfer import set_fake_link

    def marginal(mode, shape):
        r1, f1, d1, b1 = run_dag(mode, shape, frame, 1, frame * n_frames)
        r2, f2, d2, b2 = run_dag(mode, shape, frame, 1, frame * n_frames * 2)
        bpf = (b2 * f2 - b1 * f1) / (f2 - f1)
        return r2, f2, d2, bpf

    prev = set_fake_link(96e6, 62e6)         # the slow-link replay envelope
    try:
        # nested (kernel plane): sinks are b (f32, 1:1), c (c64, 1:4),
        # d (f32, 1:1) → 4f + 2f + 4f = 10·frame bytes/frame on the f32 wire
        sink_bytes = 10 * frame
        r_u, f_u, d_u, b_u = marginal("unfused", "nested")
        r_f, f_f, d_f, b_f = marginal("fused", "nested")
        print(f"# dag smoke (nested): unfused {d_u / f_u:.0f} disp/frame, "
              f"{b_u / frame:.1f} B/sample D2H vs fused {d_f / f_f:.0f} "
              f"disp/frame, {b_f / frame:.1f} B/sample D2H", file=sys.stderr)
        assert d_u / f_u >= 5.0, (d_u, f_u)
        assert d_f / f_f <= 1.0, (d_f, f_f)
        # fused D2H == exactly the sink payloads → interior-edge bytes == 0
        assert abs(b_f - sink_bytes) < 1e-6, (b_f, sink_bytes)
        # per-hop pays the interior hops too (prod 8f + a 8f on top)
        assert b_u >= sink_bytes + 12 * frame, (b_u, sink_bytes)
        # diamond (frame plane): one f32 sink at 1:4 → frame bytes/frame;
        # interior edges are device-resident on BOTH sides — the fused win
        # here is dispatches/frame (3 member programs + merge → 1)
        r_u, f_u, d_u, b_u = marginal("unfused", "diamond")
        r_f, f_f, d_f, b_f = marginal("fused", "diamond")
        print(f"# dag smoke (diamond): unfused {d_u / f_u:.0f} disp/frame "
              f"vs fused {d_f / f_f:.0f} disp/frame, {b_f / frame:.2f} "
              f"B/sample D2H", file=sys.stderr)
        assert d_u / f_u >= 3.0, (d_u, f_u)
        assert d_f / f_f <= 1.0, (d_f, f_f)
        assert abs(b_f - frame) < 1e-6, (b_f, frame)   # sink payload only
    finally:
        set_fake_link(prev.h2d_bps if prev else None,
                      prev.d2h_bps if prev else None)
    print("DAG SMOKE OK")


def _fanout_smoke(frame: int = 32768, n_frames: int = 12) -> None:
    """CI gate: fan-out fusion engages, the fused side bills exactly ONE
    input upload per MARGINAL frame on the H2D wire with one dispatch per
    frame. Bytes/frame is the marginal between a 1× and a 2× run — each run
    pays an identical constant of carry/fence uploads at compile
    (``init_carry`` → ``to_device`` is billed), which the marginal cancels,
    leaving exactly the per-frame wire traffic."""
    from futuresdr_tpu.ops.xfer import set_fake_link

    def marginal(mode):
        r1, f1, d1, b1 = run_fanout(mode, frame, 1, frame * n_frames)
        r2, f2, d2, b2 = run_fanout(mode, frame, 1, frame * n_frames * 2)
        bpf = (b2 * f2 - b1 * f1) / (f2 - f1)
        return r2, f2, d2, bpf

    upload = frame * 8                       # c64 input, f32 pair wire
    prev = set_fake_link(96e6, 62e6)         # the slow-link replay envelope
    try:
        r_u, f_u, d_u, b_u = marginal("unfused")
        r_f, f_f, d_f, b_f = marginal("fused")
    finally:
        set_fake_link(prev.h2d_bps if prev else None,
                      prev.d2h_bps if prev else None)
    print(f"# fanout smoke: unfused {d_u / f_u:.0f} disp/frame, "
          f"{b_u / upload:.2f}x upload on H2D vs fused {d_f / f_f:.0f} "
          f"disp/frame, {b_f / upload:.2f}x upload", file=sys.stderr)
    assert d_u / f_u >= 3.0, (d_u, f_u)
    assert d_f / f_f <= 1.0, (d_f, f_f)
    # fused H2D bytes == exactly one upload per marginal frame
    assert abs(b_f - upload) < 1e-6, (b_f, upload)
    # unfused re-uploads the broadcast intermediate once per branch (3x)
    assert b_u >= 2.5 * upload, (b_u, upload)
    print("FANOUT SMOKE OK")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--runs", type=int, default=3)
    p.add_argument("--seconds", type=float, default=6.0,
                   help="approx wall time per measured run")
    p.add_argument("--frames", default="16384,65536,262144",
                   help="comma-separated frame sizes")
    p.add_argument("--ks", default="1,4,16",
                   help="comma-separated frames_per_dispatch for the fused side")
    p.add_argument("--smoke", action="store_true",
                   help="CI mode: one tiny config per suite (linear + "
                        "fan-out), assert the fused paths engage, dispatches "
                        "drop 3x→1x per frame, fan-out H2D bytes bill 1x "
                        "upload")
    p.add_argument("--fanout", action="store_true",
                   help="run the 1→2 broadcast-fusion suite instead of the "
                        "linear chain")
    p.add_argument("--dag", action="store_true",
                   help="run the general-DAG suite (frame-plane diamond "
                        "broadcast→merge + stream-plane nested fan-out) "
                        "instead of the linear chain")
    p.add_argument("--link-mbps", default=None, metavar="H2D,D2H",
                   help="replay a link envelope through the deterministic "
                        "fake link (e.g. 96,62, a slow-link envelope)")
    a = p.parse_args()

    from futuresdr_tpu.tpu.instance import instance
    backend = instance().platform
    print(f"# backend: {backend}", file=sys.stderr)

    if a.link_mbps and not a.smoke:
        from futuresdr_tpu.ops.xfer import set_fake_link
        up, down = (float(x) * 1e6 for x in a.link_mbps.split(","))
        set_fake_link(up, down)
        print(f"# fake link: H2D {up / 1e6:.0f} MB/s, D2H {down / 1e6:.0f} "
              f"MB/s", file=sys.stderr)

    if a.smoke:
        frame, n = 16384, 16384 * 24
        r_u, f_u, d_u = run_one("unfused", frame, 1, n)
        r_f, f_f, d_f = run_one("fused", frame, 1, n)
        print(f"# smoke: unfused {d_u / f_u:.0f} dispatch/frame vs fused "
              f"{d_f / f_f:.0f} dispatch/frame", file=sys.stderr)
        assert d_u / f_u >= 3.0, (d_u, f_u)
        assert d_f / f_f <= 1.0, (d_f, f_f)
        print("SMOKE OK")
        _fanout_smoke()
        _dag_smoke()
        return

    frames = [int(f) for f in a.frames.split(",")]
    ks = [int(k) for k in a.ks.split(",")]
    if a.dag:
        print("shape,mode,frame,k,run,msamples_per_sec,frames,dispatches,"
              "dispatch_per_frame,d2h_bytes_per_frame")
        for shape in ("diamond", "nested"):
            for frame in frames:
                cases = [("unfused", 1)] + [("fused", k) for k in ks]
                for mode, k in cases:
                    rate, _f, _d, _b = run_dag(mode, shape, frame, k,
                                               frame * 8)
                    n = int(max(rate * 1e6 * a.seconds, frame * 8))
                    n = (n // frame) * frame
                    for r in range(a.runs):
                        rate, fr, disp, bpf = run_dag(mode, shape, frame, k, n)
                        print(f"{shape},{mode},{frame},{k},{r},{rate:.2f},"
                              f"{fr},{disp},{disp / max(1, fr):.2f},"
                              f"{bpf:.0f}", flush=True)
        return
    if a.fanout:
        print("mode,frame,k,run,msamples_per_sec,frames,dispatches,"
              "dispatch_per_frame,h2d_bytes_per_frame")
        for frame in frames:
            cases = [("unfused", 1)] + [("fused", k) for k in ks]
            for mode, k in cases:
                rate, _f, _d, _b = run_fanout(mode, frame, k, frame * 8)
                n = int(max(rate * 1e6 * a.seconds, frame * 8))
                n = (n // frame) * frame
                for r in range(a.runs):
                    rate, fr, disp, bpf = run_fanout(mode, frame, k, n)
                    print(f"{mode},{frame},{k},{r},{rate:.2f},{fr},{disp},"
                          f"{disp / max(1, fr):.2f},{bpf:.0f}", flush=True)
        return
    print("mode,frame,k,run,msamples_per_sec,frames,dispatches,dispatch_per_frame")
    for frame in frames:
        cases = [("unfused", 1)] + [("fused", k) for k in ks]
        for mode, k in cases:
            # short probe sizes the sustained run
            rate, _f, _d = run_one(mode, frame, k, frame * 8)
            n = int(max(rate * 1e6 * a.seconds, frame * 8))
            n = (n // frame) * frame
            for r in range(a.runs):
                rate, fr, disp = run_one(mode, frame, k, n)
                print(f"{mode},{frame},{k},{r},{rate:.2f},{fr},{disp},"
                      f"{disp / max(1, fr):.2f}", flush=True)


if __name__ == "__main__":
    main()
