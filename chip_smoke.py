#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the repo's main paths ONCE, in ONE process, through the entry points a
user calls, at the shipped widths, on a locally attached TPU, and checks every
result against a plain numpy/scipy reference written here (never against
another XLA program, never by bit-identity):

  device    jax.devices() is a TPU the peaks table knows; raw complex64
            device_put → compute → np.asarray measured for exactness
  streamed  source → Head → TpuKernel([fir64, fft2048, mag2]) → VectorSink by
            Runtime().run, 64 frames of 262144, wire f32 and the chip default
  fm_app    apps.fm_receiver.build_flowgraph(use_tpu=True) vs use_tpu=False;
            the station hops carrier mid-run and one ctrl-port retune follows
  serve     ServeEngine over the FM front end behind a ControlPort: 64
            sessions over two tenants admitted through REST, 8 frames each,
            one leave, one join, one lane retune under load
  pallas    every kernel of ops/pallas_kernels.py compiled by Mosaic at the
            shapes the repo uses, matched against its XLA route; then the
            default-on channelizer_stage(impl="auto") through a flowgraph
  wlan_rx   apps.wlan_rx.build_flowgraph(use_tpu=True): a seeded capture of 8
            frames of 802.11a/g packets, all eight rates; payloads against
            what was sent, record entries against models/wlan/reference.py
            (numpy float64), and one frame's LLRs pulled back from the device
  lora_gw   apps.lora_gw.build_flowgraph(use_tpu=True): a seeded capture of 24
            frames with one packet on every (channel, SF) of the EU868 gateway
            (8 x SF7 ... SF12, the SF12 ones seventeen frames long); payloads
            against what was sent, record entries against the benchmark's
            float64 receiver (benchmark/harness/refs_lora.py)
  multichip (only with --devices N > 1) the spectrum chain data-sharded over
            N devices and examples/sharded_spectrum.py, matched against the
            single-device run, every device holding a shard

Each phase prints one JSON line. The LAST stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`` and is
printed only when every phase of the full list ran at full size and passed;
any phase that raises, mismatches its reference or ran on the wrong platform
ends the process with a traceback and a non-zero exit code. Without a TPU the
run fails in the ``device`` phase.

``--rehearse`` is the CPU dress rehearsal (tier-1 runs it): tiny sizes, Pallas
in interpret mode, platform ``cpu`` accepted. It proves the script, not the
chip: every line it prints says ``"rehearse": true``. A rehearsal, or a
``--phases`` subset, that passes exits 0 but never prints the ``ok`` line: its
last line is ``{"complete": false, "rehearse": ..., "phases": [...],
"skipped": [...], "device": {...}}``. Wall-clock figures printed here are
smoke timings in every mode and are written nowhere as a metric.

Run on the chip:   python3 chip_smoke.py            (one chip)
                   python3 chip_smoke.py --devices 4 (adds the multichip phase)
Rehearse on CPU:   JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import importlib.util
import json
import os
import socket
import sys
import tempfile
import threading
import time
import urllib.request
import wave
from pathlib import Path

_ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(_ROOT))

# two runs of one commit must execute the same programs: the autotune pick
# store (~/.cache/futuresdr_tpu) stays out of it, as in tests/conftest.py
os.environ["FUTURESDR_TPU_AUTOTUNE_CACHE_DIR"] = "off"

import numpy as np  # noqa: E402
from scipy import signal  # noqa: E402

N_TAPS, N_FFT = 64, 2048
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(AssertionError):
    """A phase's result is wrong (as opposed to the phase crashing)."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# shared context: device stamp, compile meter, one JSON line per phase
# ---------------------------------------------------------------------------

class CompileMeter:
    """Every XLA program build of the process, from jax's own monitoring
    events: seconds are set-up time; a build after a phase's warm-up is a
    recompile in the hot loop. A persistent-cache hit still counts as a build
    (it is one jit miss) — just a fast one."""

    def __init__(self):
        import jax
        self.events = []          # (perf_counter at end, seconds, fun_name)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == _BACKEND_COMPILE:
            self.events.append((time.perf_counter(), float(seconds),
                                str(kw.get("fun_name", "?"))))

    def _event(self, event, **kw):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def mark(self) -> int:
        return len(self.events)

    def since(self, mark: int) -> dict:
        ev = self.events[mark:]
        return {"compiles": len(ev),
                "compile_s": round(sum(s for _, s, _ in ev), 3)}

    def after(self, t: float, mark: int = 0) -> list:
        """Names of programs built after wall-clock ``t`` (since ``mark``)."""
        return [name for t_end, _, name in self.events[mark:] if t_end > t]


class Ctx:
    def __init__(self, args):
        import jax
        from futuresdr_tpu.runtime.buffer import circular
        from futuresdr_tpu.tpu.instance import ensure_compile_cache

        self.args = args
        self.rehearse = bool(args.rehearse)
        self.seed = int(args.seed)
        self.meter = CompileMeter()
        self.cache_dir = ensure_compile_cache()
        self.cache_entries_before = self.cache_entries()
        devs = jax.devices()
        self.device = devs[0]
        try:
            libtpu = importlib.metadata.version("libtpu")
        except importlib.metadata.PackageNotFoundError:
            libtpu = None
        self.stamp = {
            "rehearse": self.rehearse,
            "platform": self.device.platform,
            "device_kind": self.device.device_kind,
            "device_count": len(devs),
            "jax": jax.__version__,
            "jaxlib": importlib.metadata.version("jaxlib"),
            "libtpu": libtpu,
            "ring": "native" if circular.available() else "portable",
            "compile_cache_dir": self.cache_dir,
        }

    @property
    def on_tpu(self) -> bool:
        return self.device.platform == "tpu"

    def cache_entries(self) -> int:
        p = Path(self.cache_dir)
        return sum(1 for f in p.iterdir() if f.is_file()) if p.is_dir() else 0

    def emit(self, phase: str, mark: int, t0: float, **fields) -> None:
        line = {"phase": phase, "ok": True, **self.stamp,
                **self.meter.since(mark),
                "smoke_wall_s": round(time.perf_counter() - t0, 2), **fields}
        print(json.dumps(line), flush=True)


def rel_err(got, want) -> float:
    scale = max(1e-30, float(np.max(np.abs(want))))
    return float(np.max(np.abs(np.asarray(got) - want))) / scale


def snr_db(got, want) -> float:
    err = float(np.sum(np.abs(np.asarray(got, np.float64) - want) ** 2))
    return float("inf") if err == 0.0 else \
        10.0 * np.log10(float(np.sum(np.abs(want) ** 2)) / err)


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------

def phase_device(ctx: Ctx) -> None:
    import jax
    import jax.numpy as jnp

    from futuresdr_tpu.ops.xfer import to_device, to_host
    from futuresdr_tpu.utils.roofline import CHIP_PEAKS, _kind_to_chip

    t0, mark = time.perf_counter(), ctx.meter.mark()
    check(ctx.on_tpu or ctx.rehearse,
          f"no TPU: jax.devices()[0] is {ctx.device.platform!r} "
          f"({ctx.device.device_kind!r}); the CPU is only accepted under "
          f"--rehearse")
    chip = _kind_to_chip(ctx.device.device_kind)
    if ctx.on_tpu:
        check(chip in CHIP_PEAKS,
              f"device_kind {ctx.device.device_kind!r} maps to no entry of "
              f"utils/roofline.CHIP_PEAKS")
        check(ctx.args.devices <= len(jax.devices()),
              f"--devices {ctx.args.devices} but jax sees {len(jax.devices())}")

    # the complex pair shim, measured: a RAW complex64 device_put → compute →
    # np.asarray (conj and ×2 are exact in floating point) next to the shim
    rng = np.random.default_rng(ctx.seed)
    host = (rng.standard_normal(1 << 20)
            + 1j * rng.standard_normal(1 << 20)).astype(np.complex64)
    want = np.conj(host) * np.float32(2.0)
    f = jax.jit(lambda v: jnp.conj(v) * 2.0)
    raw = np.asarray(f(jax.device_put(host, ctx.device)))
    raw_exact = bool(raw.dtype == np.complex64 and np.array_equal(raw, want))
    shim = to_host(f(to_device(host, ctx.device)))
    check(np.array_equal(shim, want), "pair-shim complex64 round trip inexact")
    dev_arr = jax.device_put(host, ctx.device)
    check({d.platform for d in dev_arr.devices()} == {ctx.device.platform},
          "device_put landed on another platform")

    ctx.emit("device", mark, t0, chip=chip,
             raw_complex64_roundtrip_exact=raw_exact,
             shim_complex64_roundtrip_exact=True,
             compile_cache_entries_before=ctx.cache_entries_before)


# ---------------------------------------------------------------------------
# phase: streamed — the headline chain through the actor runtime
# ---------------------------------------------------------------------------

def spectrum_stages():
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fft_stage, fir_stage, mag2_stage
    taps = firdes.lowpass(0.2, N_TAPS).astype(np.float32)
    return taps, [fir_stage(taps), fft_stage(N_FFT), mag2_stage()]


def ref_spectrum(x: np.ndarray, taps: np.ndarray) -> np.ndarray:
    """fir64 → fft2048 → |x|² in float64 numpy/scipy, zero initial state."""
    y = signal.lfilter(taps.astype(np.float64), 1.0, x.astype(np.complex128))
    spec = np.fft.fft(y.reshape(-1, N_FFT), axis=1)
    return (spec.real ** 2 + spec.imag ** 2).reshape(-1)


def _first_item_sink(dtype):
    """A VectorSink that stamps when its first item arrived: everything the
    program compiles after that instant is a recompile in the hot loop."""
    from futuresdr_tpu.blocks import VectorSink

    class FirstItemSink(VectorSink):
        t_first = None

        async def work(self, io, mio, meta):
            if self.t_first is None and len(self.input.slice()):
                self.t_first = time.perf_counter()
            await super().work(io, mio, meta)

    return FirstItemSink(dtype)


def phase_streamed(ctx: Ctx) -> None:
    import jax

    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import Head, VectorSource
    from futuresdr_tpu.config import config
    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.ops.wire import measure_snr_db
    from futuresdr_tpu.tpu import TpuKernel

    frame = config().tpu_frame_size          # the shipped default: 262144
    n_frames = 4 if ctx.rehearse else 64
    n = n_frames * frame
    rng = np.random.default_rng(ctx.seed + 1)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)) \
        .astype(np.complex64)
    taps, _ = spectrum_stages()
    want = ref_spectrum(x, taps)

    # which FFT route the chain binds on this backend, read off the lowered
    # program of the public Pipeline: the MXU four-step form has no fft op
    pipe = Pipeline(spectrum_stages()[1], np.complex64)
    text = jax.jit(pipe.fn()).lower(
        pipe.init_carry(),
        jax.ShapeDtypeStruct((frame,), np.complex64)).as_text()
    mxu_fft = "stablehlo.fft" not in text
    if ctx.on_tpu:
        check(mxu_fft, "the chain lowered to an XLA fft op on the TPU: the "
                       "MXU four-step FFT did not engage at n=2048")

    # f32 first, then the chip's own default (auto → sc16 off-CPU); the
    # rehearsal names sc16 outright so the quantizing route is rehearsed too
    for wire_arg, expect in (("f32", "f32"),
                             ("sc16" if ctx.rehearse else None, "sc16")):
        t0, mark = time.perf_counter(), ctx.meter.mark()
        fg = Flowgraph()
        tk = TpuKernel(spectrum_stages()[1], np.complex64, wire=wire_arg)
        snk = _first_item_sink(np.float32)
        fg.connect(VectorSource(x), Head(np.complex64, n), tk, snk)
        Runtime().run(fg)
        got = snk.items()
        m = tk.extra_metrics()

        check(got.shape == want.shape, f"{got.shape} != {want.shape}")
        check(np.all(np.isfinite(got)), "non-finite output")
        check(m["wire"] == expect, f"wire {m['wire']!r}, expected {expect!r}")
        check(m["frame_size"] == frame and m["frames_dispatched"] == n_frames,
              f"dispatched {m['frames_dispatched']} x {m['frame_size']}")
        check(m["h2d_starts_per_frame"] == 1,
              f"h2d_starts_per_frame {m['h2d_starts_per_frame']}")
        check(not m.get("fused_devchain"), "a lone TpuKernel fused a devchain")
        check(tk.inst.platform == ctx.device.platform, "kernel on wrong platform")
        live = {d.platform for a in jax.live_arrays() for d in a.devices()}
        check(live == {ctx.device.platform},
              f"live arrays on {live}, expected {ctx.device.platform}")
        fields = {}
        if expect == "f32":
            err = rel_err(got, want)
            check(err <= 1e-3, f"f32 wire: rel err {err:.3g} > 1e-3")
            fields["rel_err"] = err
        else:
            # two crossings quantize (input IQ, output spectrum): the floor is
            # the codec's own measured SNR minus a stated 20 dB margin
            codec = measure_snr_db("sc16")
            got_snr = snr_db(got, want)
            check(got_snr >= codec - 20.0,
                  f"sc16 wire: SNR {got_snr:.1f} dB < codec {codec:.1f} - 20")
            check(m["uplink_coalesced"] == 1, "sc16 uplink not coalesced")
            fields.update(snr_db=round(got_snr, 1),
                          codec_snr_db=round(codec, 1), snr_margin_db=20.0)
        hot = ctx.meter.after(snk.t_first, mark)
        check(not hot, f"programs compiled after the first output: {hot}")
        ctx.emit("streamed", mark, t0, wire=m["wire"], frames=n_frames,
                 frame_size=frame, samples=n, mxu_fft=mxu_fft,
                 h2d_starts_per_frame=m["h2d_starts_per_frame"],
                 uplink_coalesced=m["uplink_coalesced"],
                 deferred_consume=m["deferred_consume"],
                 frames_in_flight_credits=m["inflight_credits"],
                 fused_devchain=bool(m.get("fused_devchain")),
                 compiles_after_warmup=len(hot), **fields)


# ---------------------------------------------------------------------------
# the FM front end in plain numpy/scipy (serve's reference)
# ---------------------------------------------------------------------------

def fm_signal(n: int, f_tone: float, rate: float = 1e6, dev: float = 50e3,
              carrier: float = 0.0, phase: float = 0.0,
              hop=None) -> np.ndarray:
    """Constant-envelope FM of one audio tone (float64 phase, then c64).
    ``hop=(at, carrier2)``: from sample ``at`` on the carrier is ``carrier2``,
    phase continuous — what a tuner retuned at ``at`` has to follow."""
    t = np.arange(n) / rate
    car = 2 * np.pi * carrier * t
    if hop is not None:
        at, carrier2 = hop
        car[at:] = car[at] + 2 * np.pi * carrier2 * (t[at:] - t[at])
    ph = (dev / f_tone) * np.sin(2 * np.pi * f_tone * t + phase) + car
    return np.exp(1j * ph).astype(np.complex64)


def ref_fm_front_end(x: np.ndarray, retune_at: int = -1,
                     theta: float = 0.0) -> np.ndarray:
    """apps.fm_receiver.front_end_stages() at its defaults, zero initial
    state, float64: xlating decimating FIR (÷4) → FM discriminator →
    24/125 polyphase resampler. ``retune_at``/``theta``: from input sample
    ``retune_at`` on the tuner runs at phase increment ``theta`` (phase
    continuous, the stage's retune grammar); before it, at 0."""
    from futuresdr_tpu.apps.fm_receiver import AUDIO_RATE, SAMPLE_RATE
    from futuresdr_tpu.dsp import firdes

    D = 4
    h = firdes.lowpass(0.5 / D * 0.8, 128).astype(np.float32).astype(np.float64)
    xd = x.astype(np.complex128)
    y = signal.upfirdn(h, xd, 1, D)[:len(x) // D]
    if retune_at >= 0:
        rot = np.exp(1j * theta * (np.arange(len(x)) - retune_at))
        y_post = signal.upfirdn(h, xd * rot, 1, D)[:len(x) // D]
        y[retune_at // D:] = y_post[retune_at // D:]
    prev = np.concatenate([[1.0 + 0j], y[:-1]])
    audio = SAMPLE_RATE / (2 * np.pi * 75e3) * np.angle(y * np.conj(prev))
    g = np.gcd(AUDIO_RATE, SAMPLE_RATE)
    I, Dr = AUDIO_RATE // g, SAMPLE_RATE // g
    r = max(I, Dr)
    rs = (firdes.kaiser_lowpass(0.5 / r * 0.8, 0.1 / r) * I) \
        .astype(np.float32).astype(np.float64)
    return signal.upfirdn(rs, audio, I, Dr)[:len(audio) * I // Dr]


# ---------------------------------------------------------------------------
# phase: fm_app
# ---------------------------------------------------------------------------

def _gated_source(iq: np.ndarray, gate: threading.Event):
    """Emits the first half of ``iq``, waits for ``gate``, emits the rest —
    so a retune posted while it waits lands mid-run by construction."""
    import asyncio

    from futuresdr_tpu import Kernel

    class GatedSource(Kernel):
        def __init__(self):
            super().__init__()
            self.pos = 0
            self.output = self.add_stream_output("out", np.complex64)

        async def work(self, io, mio, meta):
            limit = len(iq) if gate.is_set() else len(iq) // 2
            out = self.output.slice()
            take = min(len(out), limit - self.pos)
            if take > 0:
                out[:take] = iq[self.pos:self.pos + take]
                self.pos += take
                self.output.produce(take)
            if self.pos == len(iq):
                io.finished = True
            elif take > 0:
                io.call_again = True
            else:
                await asyncio.sleep(0.002)       # parked on the gate
                io.call_again = True

    return GatedSource()


def _read_wav(path: str) -> np.ndarray:
    with wave.open(path, "rb") as w:
        pcm = np.frombuffer(w.readframes(w.getnframes()), np.int16)
    return pcm.astype(np.float64) / 32767.0


def phase_fm_app(ctx: Ctx) -> None:
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        _fm_app(ctx, Path(tmp))


def _fm_app(ctx: Ctx, tmp: Path) -> None:
    from futuresdr_tpu import Pmt, Runtime
    from futuresdr_tpu.apps.fm_receiver import AUDIO_RATE, build_flowgraph
    from futuresdr_tpu.blocks import VectorSource
    from futuresdr_tpu.config import config

    t0, mark = time.perf_counter(), ctx.meter.mark()
    rate, f_tone, offset, offset2 = 1e6, 1000.0, 150e3, -100e3
    frame = (config().tpu_frame_size // 500) * 500    # the kernel's own rounding
    n = (4 if ctx.rehearse else 16) * frame           # >= 4 M samples at 262000
    half = n // 2                                     # a whole number of frames
    n_out = n // 4 * 24 // 125                        # ÷4, then 24/125

    # the TPU path, retuned once mid-run through the ctrl port: the station
    # hops from ``offset`` to ``offset2`` at sample ``half`` and the tuner is
    # retuned to follow it once the whole first half's audio is out (so the
    # retune lands on the frame that starts at ``half``). The host path gets
    # the same station on ``offset`` throughout: a retune that is dropped,
    # late or not phase continuous shows in the audio diff and the tone check
    gate = threading.Event()
    wav_tpu = str(tmp / "fm_tpu.wav")
    fg, chain, sink = build_flowgraph(
        _gated_source(fm_signal(n, f_tone, rate, carrier=offset,
                                hop=(half, offset2)), gate),
        input_rate=rate, offset=offset, audio_path=wav_tpu, use_tpu=True)
    running = Runtime().start(fg)
    deadline = time.monotonic() + 600
    t_first = None
    while sink.n_written < n_out // 2:           # all first-half audio is out
        check(time.monotonic() < deadline,
              f"{sink.n_written} of {n_out // 2} first-half audio samples "
              f"within 600 s")
        if t_first is None and sink.n_written:
            t_first = time.perf_counter()
        time.sleep(0.005)
    check(sink.n_written == n_out // 2,
          f"{sink.n_written} audio samples out before the gate opened, "
          f"expected {n_out // 2}")
    t_first = t_first or time.perf_counter()     # at the latest: before the retune
    reply = running.handle.call_sync(
        chain, "ctrl", Pmt.map({"stage": "tuner",
                                "phase_inc": -2 * np.pi * offset2 / rate}))
    check(reply == Pmt.ok(), f"ctrl retune rejected: {reply!r}")
    gate.set()
    running.wait_sync()
    m = chain.extra_metrics()
    hot = ctx.meter.after(t_first, mark)
    check(not hot, f"programs compiled after the first audio (retune "
                   f"included): {hot}")
    check(chain.inst.platform == ctx.device.platform, "kernel on wrong platform")
    check(m["frames_dispatched"] == n // frame,
          f"dispatched {m['frames_dispatched']} frames of {m['frame_size']}")

    # the host block path of the same app: numpy/scipy blocks, no XLA
    wav_host = str(tmp / "fm_host.wav")
    fg, _, _ = build_flowgraph(
        VectorSource(fm_signal(n, f_tone, rate, carrier=offset)),
        input_rate=rate, offset=offset, audio_path=wav_host, use_tpu=False)
    Runtime().run(fg)

    a, b = _read_wav(wav_tpu), _read_wav(wav_host)
    check(abs(len(a) - n_out) <= 48 and abs(len(b) - n_out) <= 48,
          f"audio lengths {len(a)}/{len(b)}, expected ~{n_out}")
    k = min(len(a), len(b))
    skip = AUDIO_RATE // 100                     # 10 ms of filter transient
    # at the hop the tuner's 128-tap history still holds the old carrier and
    # the 4533-tap resampler (6 MHz virtual rate) smears it over 36 audio
    # samples: 2 ms either side of the hop are left out, as ``skip`` is
    guard = AUDIO_RATE // 500
    halves = [slice(skip, n_out // 2 - guard), slice(n_out // 2 + guard, k)]
    diff = max(float(np.max(np.abs(a[h] - b[h]))) for h in halves)
    # 16-bit PCM on both sides (1 LSB = 3.1e-5) and, on the chip's default
    # sc16 wire, block-floating IQ in and audio out: 1e-3 full scale stated
    check(diff <= 1e-3, f"TPU vs host audio differ by {diff:.3g} > 1e-3")
    peaks = []
    for h in halves:                             # the tone, before AND after
        seg = a[h]
        spec = np.abs(np.fft.rfft(seg * np.hanning(len(seg))))
        freq = np.fft.rfftfreq(len(seg), 1.0 / AUDIO_RATE)
        peak = float(freq[np.argmax(spec[5:]) + 5])
        check(abs(peak - f_tone) <= 2 * AUDIO_RATE / len(seg) + 1.0,
              f"recovered tone at {peak:.1f} Hz, modulated {f_tone:.1f} Hz")
        amp = float(np.max(np.abs(seg)))
        check(abs(amp - 50e3 / 75e3) < 0.02, f"audio amplitude {amp:.3f}")
        peaks.append(peak)

    ctx.emit("fm_app", mark, t0, samples=n, frame_size=m["frame_size"],
             wire=m["wire"], retune="ok", retune_at_sample=half,
             retune_hop_hz=offset2 - offset, compiles_after_warmup=len(hot),
             audio_max_abs_diff=diff, audio_tolerance=1e-3,
             tone_hz=peaks[0], tone_hz_after_retune=peaks[1])


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(method: str, url: str, body=None):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as r:
        return r.status, r.read()


def phase_serve(ctx: Ctx) -> None:
    from futuresdr_tpu import Runtime
    from futuresdr_tpu.apps.fm_receiver import front_end_stages
    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.runtime.ctrl_port import ControlPort
    from futuresdr_tpu.serve.api import register_app, unregister_app
    from futuresdr_tpu.serve.engine import ServeEngine, default_buckets

    t0, mark = time.perf_counter(), ctx.meter.mark()
    n_sess = 4 if ctx.rehearse else max(default_buckets())     # 64
    n_frames = 8
    eng = ServeEngine(Pipeline(front_end_stages(), np.complex64),
                      frame_size=2000 if ctx.rehearse else 65536, app="fm",
                      buckets=(1, 2, 4) if ctx.rehearse else None)
    fs = eng.frame_size                       # 65536 → 65500 (÷4, ×24/125)
    register_app(eng)
    port = _free_port()
    cp = ControlPort(Runtime().handle, bind=f"127.0.0.1:{port}")
    cp.start()
    base = f"http://127.0.0.1:{port}/api/serve/fm"
    theta = 2 * np.pi * 10e3 / 1e6            # the lane retune: +10 kHz
    leave_at, retune_at = 3, 4                # before these frame indices
    try:
        def admit(tenant):
            st, body = _http("POST", f"{base}/session/", {"tenant": tenant})
            check(st == 201, f"admit -> {st}")
            return json.loads(body)["sid"]

        # every stream its own tone, phase and tenant; made in bulk up front
        streams, sids = {}, []
        for i in range(n_sess):
            sid = admit("gold" if i % 2 == 0 else "bronze")
            sids.append(sid)
            streams[sid] = fm_signal(n_frames * fs, 500.0 + 37.0 * i,
                                     phase=0.1 * i)
        joiner = fm_signal((n_frames - leave_at) * fs, 3100.0)
        first = {sid: 0 for sid in sids}      # frame index a session joined at
        out = {sid: [] for sid in sids}
        leaver, retuned = sids[1], sids[2]
        live = list(sids)

        def run_frame(t):
            for sid in live:
                k = t - first[sid]
                check(eng.submit(sid, streams[sid][k * fs:(k + 1) * fs]),
                      f"submit refused for {sid} at frame {t}")
            while eng.step():                 # idle step == fully drained
                pass
            for sid in live:
                out[sid].extend(eng.results(sid))

        # warm-up: the resident bucket compiles on frame 0, and one retune (to
        # the increment the lane already has) compiles the lane surgery
        run_frame(0)
        st, _ = _http("POST", f"{base}/session/{sids[3]}/ctrl/",
                      {"stage": "tuner", "params": {"phase_inc": 0.0}})
        check(st == 200, f"warm retune -> {st}")
        warm, builds_warm = ctx.meter.mark(), eng.compiles

        for t in range(1, n_frames):
            if t == leave_at:
                st, _ = _http("DELETE", f"{base}/session/{leaver}/")
                check(st == 200, f"leave -> {st}")
                live.remove(leaver)
                sid = admit("bronze")
                streams[sid], first[sid], out[sid] = joiner, t, []
                live.append(sid)
                joined = sid
            if t == retune_at:
                st, _ = _http("POST", f"{base}/session/{retuned}/ctrl/",
                              {"stage": "tuner",
                               "params": {"phase_inc": theta}})
                check(st == 200, f"lane retune -> {st}")
            run_frame(t)

        st, body = _http("GET", f"{base}/")
        desc = json.loads(body)
        check(st == 200 and desc["dispatches"] >= n_frames, f"describe: {st}")
        st, body = _http("GET", f"http://127.0.0.1:{port}/metrics")
        check(st == 200 and b"fsdr_" in body, "/metrics did not answer")
        hot = ctx.meter.since(warm)
        check(hot["compiles"] == 0 and eng.compiles == builds_warm,
              f"compiles after the resident bucket was warm: {hot}, engine "
              f"builds {builds_warm} -> {eng.compiles}: "
              f"{[e[2] for e in ctx.meter.events[warm:]]}")
        check(eng.inst.platform == ctx.device.platform, "engine on wrong platform")

        # every session against the plain reference of the same front end on
        # the same frames. Audio peaks at 2/3; f32 FIR/atan2/resampler against
        # float64 lands ~1e-5, 5e-4 absolute is the stated line
        tol, worst = 5e-4, 0.0
        for sid in out:
            frames = n_frames - first[sid]
            if sid == leaver:
                frames = leave_at
            got = np.concatenate(out[sid])
            want = ref_fm_front_end(
                streams[sid][:frames * fs],
                retune_at=retune_at * fs if sid == retuned else -1,
                theta=theta)
            check(got.shape == want.shape,
                  f"{sid}: {got.shape} != {want.shape}")
            check(np.all(np.isfinite(got)), f"{sid}: non-finite audio")
            d = float(np.max(np.abs(got - want)))
            worst = max(worst, d)
            check(d <= tol, f"session {sid}: |audio - ref| = {d:.3g} > {tol}")
        ctx.emit("serve", mark, t0, sessions=n_sess, tenants=2,
                 frames_per_session=n_frames, frame_size=fs,
                 session_frames=sum(len(v) for v in out.values()),
                 resident_buckets=eng.resident_buckets(), capacity=eng.capacity,
                 leave=leaver, join=joined, lane_retune=retuned,
                 engine_program_builds=eng.compiles,
                 compiles_after_warmup=hot["compiles"],
                 audio_max_abs_err=worst, audio_tolerance=tol)
    finally:
        cp.stop()
        unregister_app("fm")
        eng.shutdown()


# ---------------------------------------------------------------------------
# phase: pallas — every kernel compiled by Mosaic, matched to its XLA route
# ---------------------------------------------------------------------------

def pallas_cases(rehearse: bool) -> list:
    """``(name, kernel, pallas_stages, xla_stages, in_dtype, frame, tol)`` per
    case, ``kernel`` its key in ``pallas_kernels.DEFAULT_BLOCKS``: the stage
    with ``impl="pallas"`` (block=None, so the default block shape) against
    the same stage on its XLA route, at the shapes the repo uses. On a TPU
    backend the kernels resolve ``interpret=False`` (Mosaic); under the
    rehearsal they interpret. Shared with tests/test_on_chip.py, the kernels'
    standing check."""
    from futuresdr_tpu.blocks.pfb import pfb_default_taps
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fft_stage, fir_stage
    from futuresdr_tpu.ops.stages import (channelizer_stage, fir_fft_stage,
                                          quad_demod_stage, resample_stage,
                                          rotator_stage)

    c64, f32 = np.complex64, np.float32
    big, mid = (1 << 14, 1 << 14) if rehearse else (1 << 19, 1 << 18)

    def lp(cut, nt):
        return firdes.lowpass(cut, nt).astype(np.float32)

    t64, t8x64 = lp(0.2, N_TAPS), pfb_default_taps(64, 8)
    return [
        ("fir16", "fir", [fir_stage(lp(0.2, 16), impl="pallas")],
         [fir_stage(lp(0.2, 16), impl="os")], f32, big, 1e-3),
        ("fir48", "fir", [fir_stage(lp(0.2, 48), impl="pallas")],
         [fir_stage(lp(0.2, 48), impl="os")], f32, big, 1e-3),
        # the channelizer_stage default prototype (12 taps/branch), and the
        # sweep harness's N=64, K=8
        ("pfb_n16_k12", "pfb", [channelizer_stage(16, impl="pallas")],
         [channelizer_stage(16, impl="matmul")], c64, mid, 1e-3),
        ("pfb_n64_k8", "pfb", [channelizer_stage(64, t8x64, impl="pallas")],
         [channelizer_stage(64, t8x64, impl="matmul")], c64, mid, 1e-3),
        # the FM front end's ratios: ÷4 with its 128-tap lowpass (2-D W), and
        # the 24/125 audio resampler (3-D W); frames divisible by 4 and 125
        ("poly_fir_2d_decim4", "poly_fir",
         [fir_stage(lp(0.1, 128), decim=4, impl="pallas")],
         [fir_stage(lp(0.1, 128), decim=4, impl="poly")], c64, mid, 1e-3),
        ("poly_fir_3d_24_125", "poly_fir",
         [resample_stage(24, 125, impl="pallas")],
         [resample_stage(24, 125, impl="poly")], f32,
         (mid // 4 // 125) * 125, 1e-3),
        ("fir_fft_64_2048", "fir_fft", [fir_fft_stage(t64, N_FFT)],
         [fir_stage(t64, impl="os"), fft_stage(N_FFT)], c64, mid, 1e-3),
        # phase ramps reach ~3e3 rad at 262144 samples, where float32 spacing
        # is 2.4e-4: the two sin/cos implementations may differ by that much
        ("rotator", "rotator", [rotator_stage(0.013, impl="pallas")],
         [rotator_stage(0.013, impl="xla")], c64, mid, 2e-3),
        ("quad_demod", "quad_demod", [quad_demod_stage(0.7, impl="pallas")],
         [quad_demod_stage(0.7, impl="xla")], c64, mid, 1e-3),
    ]


def run_pallas_case(case, seed: int, on_tpu: bool) -> dict:
    """Compile and run one case (two frames, so the carry crosses a frame
    edge); returns its record or raises. No route here catches a kernel's
    compile error."""
    import jax

    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.ops.xfer import to_device, to_host
    from futuresdr_tpu.tpu.instance import instance

    name, kernel, st_pallas, st_xla, dtype, frame, tol = case
    dev = instance().device
    rng = np.random.default_rng(seed)
    host = rng.standard_normal(2 * frame).astype(np.float32)
    if dtype == np.complex64:
        host = (host + 1j * rng.standard_normal(2 * frame)).astype(np.complex64)
    if kernel == "quad_demod":
        # constant envelope keeps angle(x·conj(x₋₁)) away from the ±π branch
        # cut, where two correct implementations may differ by 2π
        host = np.exp(1j * np.cumsum(rng.uniform(-1.0, 1.0, 2 * frame))) \
            .astype(np.complex64)

    outs = []
    for stages in (st_pallas, st_xla):
        pipe = Pipeline(stages, dtype)
        fn = jax.jit(pipe.fn())
        carry = jax.device_put(pipe.init_carry(), dev)
        if stages is st_pallas:
            text = fn.lower(carry, to_device(host[:frame], dev)).as_text()
            mosaic = "tpu_custom_call" in text
            if on_tpu:
                check(mosaic, f"{name}: no Mosaic custom call in the lowered "
                              f"module — the kernel did not compile for real")
        ys = []
        for k in range(2):
            carry, y = fn(carry, to_device(host[k * frame:(k + 1) * frame], dev))
            ys.append(to_host(y))
        outs.append(np.concatenate(ys))
    got, want = outs
    check(got.shape == want.shape, f"{name}: {got.shape} != {want.shape}")
    check(np.all(np.isfinite(got)), f"{name}: non-finite output")
    err = rel_err(got, want)
    check(err <= tol, f"{name}: rel err {err:.3g} vs XLA route > {tol}")
    return {"case": name, "kernel": kernel, "frame": frame, "mosaic": mosaic,
            "rel_err_vs_xla": err, "tol": tol}


def phase_pallas(ctx: Ctx) -> None:
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.blocks.pfb import pfb_default_taps
    from futuresdr_tpu.ops.pallas_kernels import DEFAULT_BLOCKS
    from futuresdr_tpu.ops.stages import channelizer_stage
    from futuresdr_tpu.tpu import TpuKernel

    t0, mark = time.perf_counter(), ctx.meter.mark()
    records = [run_pallas_case(c, ctx.seed + 10 + i, ctx.on_tpu)
               for i, c in enumerate(pallas_cases(ctx.rehearse))]
    covered = {r["kernel"] for r in records}
    check(covered == set(DEFAULT_BLOCKS),
          f"cases cover {sorted(covered)}, kernels are {sorted(DEFAULT_BLOCKS)}")

    # the default-on route: channelizer_stage(impl="auto") is the Pallas PFB
    # on a TPU. Through a flowgraph, against a plain numpy PFB analysis bank.
    N, frame = 16, 4096 if ctx.rehearse else 65536
    n = 4 * frame
    rng = np.random.default_rng(ctx.seed + 30)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)) \
        .astype(np.complex64)
    fg = Flowgraph()
    tk = TpuKernel([channelizer_stage(N)], np.complex64, frame_size=frame,
                   wire="f32")
    snk = VectorSink(np.complex64)
    fg.connect(VectorSource(x), tk, snk)
    Runtime().run(fg)
    got = snk.items()
    # y[s, c] = N·ifft_c( Σ_k h[kN + c] · xrev[s − k, c] ), xrev the
    # commutated (branch-reversed) input rows, zero initial state
    h = np.asarray(pfb_default_taps(N), np.float64)
    K = -(-len(h) // N)
    hk = np.zeros(K * N)
    hk[:len(h)] = h
    hk = hk.reshape(K, N)
    rows = np.concatenate([np.zeros((K - 1, N), np.complex128),
                           x.astype(np.complex128).reshape(-1, N)[:, ::-1]])
    v = sum(hk[k] * rows[K - 1 - k:K - 1 - k + n // N] for k in range(K))
    want = (np.fft.ifft(v, axis=1) * N).reshape(-1)
    err = rel_err(got, want)
    check(got.shape == want.shape and err <= 1e-3,
          f"channelizer_stage(auto) flowgraph: rel err {err:.3g} > 1e-3")
    ctx.emit("pallas", mark, t0, kernels=records,
             channelizer_auto={"n_channels": N, "frame": frame,
                               "pallas": ctx.on_tpu, "rel_err": err})


# ---------------------------------------------------------------------------
# phase: wlan_rx
# ---------------------------------------------------------------------------

#: rehearsal sizes of the receiver (the chip runs the shipped defaults)
_WLAN_SMALL = dict(frame_size=16384, carry_len=12288, max_psdu=400,
                   cand_slots=32, lanes=16)
#: LLRs of the device program against the float64 reference: float32 DFT,
#: division and pilot phase read 1.1e-5 on the CPU (tests/test_wlan_rx_stages
#: .py); the chip's own sine, cosine and reciprocal are allowed ten times the
#: test's limit. The program is probed AS SHIPPED (its matmuls name their own
#: precision); on the chip the same probe with the matmuls at the device's
#: default is the control and has to miss the limit.
_WLAN_LLR_TOL = 1.5e-3
#: a record's mean |LLR| against the reference's, relative (the benchmark's
#: limit, benchmark/configs/wlan_rx_20msps.json, has the readings)
_WLAN_LLR_MEAN_RTOL = 3e-4


def _wlan_capture(rng, n: int, lengths) -> tuple:
    """Packets one after another, rates in turn, gaps of SIFS or DIFS +
    backoff, CFO within +-100 kHz, SNR 12 dB + 3 dB per coded bit + U(0, 6)
    over a fixed noise floor: ``(samples, [psdu])``."""
    from futuresdr_tpu.models.wlan import MCS_TABLE, Mac, encode_frame
    n0 = 1e-4
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)) * np.sqrt(n0 / 2)
    mac, sent, pos = Mac(), [], 0
    rates = list(MCS_TABLE)
    while True:
        pos += 320 if rng.random() < 0.35 else 680 + 180 * int(rng.integers(16))
        rate = rates[len(sent) % 8]
        lo, hi = lengths[int(rng.integers(len(lengths)))]
        psdu = mac.frame(bytes(rng.integers(0, 256, int(rng.integers(lo, hi)) - 28,
                                            dtype=np.uint8)))
        s = encode_frame(psdu, rate, int(rng.integers(1, 128))).astype(np.complex128)
        if pos + len(s) + 400 > n:
            return x.astype(np.complex64), sent
        snr_db = 12.0 + 3.0 * MCS_TABLE[rate].n_bpsc + rng.uniform(0, 6)
        gain = np.sqrt(10 ** (snr_db / 10) * n0 / (52 / 4096))
        cfo = 2 * np.pi * rng.uniform(-1e5, 1e5) / 20e6
        x[pos:pos + len(s)] += gain * s * np.exp(
            1j * (cfo * np.arange(len(s)) + rng.uniform(0, 2 * np.pi)))
        sent.append(psdu)
        pos += len(s)


def phase_wlan_rx(ctx: Ctx) -> None:
    import jax
    import jax.numpy as jnp

    from futuresdr_tpu import Runtime
    from futuresdr_tpu.apps.wlan_rx import build_flowgraph
    from futuresdr_tpu.blocks import VectorSource
    from futuresdr_tpu.config import config
    from futuresdr_tpu.models.wlan import MCS_TABLE, payload_from_mpdu
    from futuresdr_tpu.models.wlan import reference as ref
    from futuresdr_tpu.models.wlan import rx_stages

    t0, mark = time.perf_counter(), ctx.meter.mark()
    sizes = dict(_WLAN_SMALL) if ctx.rehearse else {}
    frame = sizes.get("frame_size", config().tpu_frame_size)
    carry = sizes.get("carry_len", ref.CARRY_LEN)
    n_frames = 8
    x, sent = _wlan_capture(
        np.random.default_rng([ctx.seed, 26]), n_frames * frame,
        [(28, 128), (129, 400)] if ctx.rehearse
        else [(28, 128), (129, 600), (1000, 1534)])
    fg, kernel, rx = build_flowgraph(VectorSource(x), use_tpu=True, **sizes)
    Runtime().run(fg)
    m = kernel.extra_metrics()
    check(kernel.inst.platform == ctx.device.platform, "kernel on wrong platform")
    check(m["frames_dispatched"] == n_frames and m["frame_size"] == frame,
          f"dispatched {m['frames_dispatched']} frames of {m['frame_size']}")
    check(rx.frames == [payload_from_mpdu(p) for p in sent],
          f"{len(rx.frames)} payloads with a good FCS, {len(sent)} sent")
    totals = rx.extra_metrics()
    check(totals["overflow"] == 0 and totals["fcs_bad"] == 0, f"totals {totals}")

    # record entries against the float64 reference, frame by frame
    frames = x.reshape(n_frames, frame)
    n_ref = n_frames if ctx.rehearse else 3
    want = [p for j in range(n_ref) for p in ref.receive_frame(
        frames[j], frames[j - 1] if j else None, carry)[0]]
    cfo_err = snr_err = mean_err = 0.0
    for got, w in zip(rx.packets, want):
        check((got["lts_start"], got["rate"], got["length"], got["psdu"])
              == (w.lts_start, w.rate, w.length, w.psdu),
              f"entry at {got['lts_start']} differs from the reference's "
              f"at {w.lts_start}")
        cfo_err = max(cfo_err, abs(got["cfo"] - w.cfo))
        snr_err = max(snr_err, abs(got["snr_db"] - w.snr_db))
        mean_err = max(mean_err, abs(got["llr_mean"] - w.llr_mean) / w.llr_mean)
    check(len(rx.packets) >= len(want) > 0, "fewer entries than the reference")
    check(cfo_err <= 2e-6 and snr_err <= 0.05 and mean_err <= _WLAN_LLR_MEAN_RTOL,
          f"CFO off by {cfo_err:.3g} rad/sample, LTS SNR by {snr_err:.3g} dB, "
          f"mean |LLR| by {mean_err:.3g} of itself")

    # one frame's LLRs pulled back from the device (frame 1 behind frame 0's
    # carry) and held to the reference's: the program as shipped, then, on
    # the chip, the control
    hist = frames[0][-carry:]
    ref_pkts, _ = ref.receive_window(np.concatenate([hist, frames[1]]), 0,
                                     keep_trace=True)

    def llr_error(stage) -> tuple:
        _, _, taps = jax.jit(stage.fn.probe)(
            jnp.stack([jnp.real(hist), jnp.imag(hist)]), jnp.asarray(frames[1]))
        taps = {k: np.asarray(v) for k, v in taps.items()}
        err, n = 0.0, 0
        for w in ref_pkts[:12]:
            lane = next((i for i in range(len(taps["slot"])) if taps["lane_ok"][i]
                         and taps["lts"][taps["slot"][i]] == w.lts_start), None)
            check(lane is not None, f"no lane for the packet at {w.lts_start}")
            rows = slice(taps["lane_to"][lane] - len(w.trace["eq"]),
                         taps["lane_to"][lane])
            nb = MCS_TABLE[ref.RATES[w.rate]].n_bpsc
            llr = taps["llr"][rows].reshape(-1, 6, 48)[:, :nb] \
                .transpose(0, 2, 1).reshape(-1)
            err = max(err, float(np.max(np.abs(llr - w.trace["llrs"]))))
            n += len(llr)
        return err, n

    llr_err, n_llr = llr_error(kernel.pipeline.stages[0])
    check(n_llr > 0 and llr_err <= _WLAN_LLR_TOL,
          f"LLRs off by {llr_err:.3g} over {n_llr} values")
    llr_err_default = None
    if ctx.on_tpu:                  # the CPU's default precision is float32
        rx_stages._PRECISION = None
        try:
            llr_err_default, _ = llr_error(rx_stages.wlan_rx_stages(
                **{k: v for k, v in sizes.items() if k != "frame_size"})[0])
        finally:
            rx_stages._PRECISION = "highest"
        check(llr_err_default > 3 * _WLAN_LLR_TOL,
              f"the control (matmuls at the device's default precision) reads "
              f"{llr_err_default:.3g}: the LLR check would not catch it")
    ctx.emit("wlan_rx", mark, t0, samples=len(x), frame_size=frame,
             wire=m["wire"], packets_sent=len(sent),
             packets_emitted=totals["psdus"], entries_checked=len(want),
             cfo_err_max=cfo_err, snr_err_max_db=snr_err,
             llr_mean_err_max_rel=mean_err, llr_err_max=llr_err,
             llr_err_default_precision=llr_err_default,
             llr_tolerance=_WLAN_LLR_TOL, llrs_checked=n_llr)


# ---------------------------------------------------------------------------
# phase: lora_gw
# ---------------------------------------------------------------------------

_LORA_SMALL = dict(n_channels=4, sfs=(7, 8, 9), max_payload={7: 48, 8: 32, 9: 24},
                   ldro_from_sf=9, done_slots=8)


def phase_lora_gw(ctx: Ctx) -> None:
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "benchmark"))
    from harness import refs_lora as R

    from futuresdr_tpu import Runtime
    from futuresdr_tpu.apps.lora_gw import build_flowgraph
    from futuresdr_tpu.blocks import VectorSource
    from futuresdr_tpu.config import config
    from futuresdr_tpu.models.lora.rx_stages import EU868_MAX_PAYLOAD

    t0, mark = time.perf_counter(), ctx.meter.mark()
    sizes = dict(_LORA_SMALL) if ctx.rehearse else {}
    n_ch, sfs = sizes.get("n_channels", 8), sizes.get("sfs", (7, 8, 9, 10, 11, 12))
    max_len = sizes.get("max_payload", EU868_MAX_PAYLOAD)
    ldro_from = sizes.get("ldro_from_sf", 11)
    frame, n_frames, n0 = config().tpu_frame_size, 24, 1e-2
    rng = np.random.default_rng([ctx.seed, 33])
    n = n_frames * frame
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)) * np.sqrt(n0 / 2)
    sent = []
    for c in range(n_ch):               # one packet a branch, anywhere it fits
        for sf in sfs:
            de = R.ldro(sf, ldro_from)
            payload = rng.integers(0, 256, int(rng.integers(13, max_len[sf] + 1)),
                                   dtype=np.uint8).tobytes()
            dur = R.packet_chips(sf, len(payload), de) * n_ch * R.SLOT / R.BW
            R.add_packet(x, n_ch, c, sf, payload, rng.uniform(4096, n - 4096 - dur),
                         rng.uniform(6, 10), n0, rng.uniform(-10e3, 10e3),
                         rng.uniform(0, 1), de)
            sent.append((c, sf, payload))
    x = x.astype(np.complex64)
    fg, kernel, rx = build_flowgraph(VectorSource(x), use_tpu=True, **sizes)
    Runtime().run(fg)
    m = kernel.extra_metrics()
    check(kernel.inst.platform == ctx.device.platform, "kernel on wrong platform")
    check(m["frames_dispatched"] == n_frames and m["frame_size"] == frame,
          f"dispatched {m['frames_dispatched']} frames of {m['frame_size']}")
    got = [(p["channel"], p["sf"], p["payload"]) for p in rx.packets if p["crc_ok"]]
    check(sorted(got) == sorted(sent),
          f"{len(got)} payloads with a good CRC, {len(sent)} sent")
    totals = rx.extra_metrics()
    check(totals["overflow"] == 0 and totals["crc_bad"] == 0, f"totals {totals}")

    # record entries against the float64 receiver, frame after frame
    gw = R.Gateway(n_ch, sfs, max_len, ldro_from)
    want = [r for j in range(n_frames) for r in gw.frame(x[j * frame:(j + 1) * frame])[0]]
    check(len(want) == len(rx.packets), "as many entries as the reference's")
    cfo_err = tau_err = share_err = 0.0
    for p, w in zip(rx.packets, want):
        key = ("channel", "sf", "start", "end", "length", "n_sym", "payload")
        check([p[k] for k in key] == [w[k] for k in key],
              f"entry {[p[k] for k in key[:4]]} differs from the reference's "
              f"{[w[k] for k in key[:4]]}")
        cfo_err = max(cfo_err, abs(p["cfo_hz"] - w["cfo_hz"]))
        tau_err = max(tau_err, abs(p["timing"] - w["timing"]))
        share_err = max(share_err, abs(p["share"] - w["share"]) / w["share"])
    check(cfo_err <= 0.05 and tau_err <= 1e-4 and share_err <= 1e-4,
          f"CFO off by {cfo_err:.3g} Hz, timing by {tau_err:.3g} chips, the mean "
          f"peak share by {share_err:.3g} of itself")
    ctx.emit("lora_gw", mark, t0, samples=len(x), frame_size=frame, wire=m["wire"],
             packets_sent=len(sent), packets_emitted=totals["packets"],
             entries_checked=len(want), cfo_err_max_hz=cfo_err,
             timing_err_max_chips=tau_err, share_err_max_rel=share_err)


# ---------------------------------------------------------------------------
# phase: multichip (only when asked: --devices N > 1)
# ---------------------------------------------------------------------------

def phase_multichip(ctx: Ctx) -> None:
    import jax

    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.shard import ShardRunner, shard_pipeline

    t0, mark = time.perf_counter(), ctx.meter.mark()
    D = ctx.args.devices
    check(len(jax.devices()) >= D, f"{D} devices asked, {len(jax.devices())} seen")
    frame = 1 << 14 if ctx.rehearse else 1 << 18
    taps, stages = spectrum_stages()
    pipe = Pipeline(stages, np.complex64)
    prog = shard_pipeline(pipe, mode="data", n_devices=D, frame_size=frame,
                          name="chip_smoke")
    check(prog is not pipe, "shard plan declined: program is not sharded")
    runner = ShardRunner(prog, frame, k=1, name="chip_smoke")
    rng = np.random.default_rng(ctx.seed + 40)
    groups = [(rng.standard_normal((D, frame))
               + 1j * rng.standard_normal((D, frame))).astype(np.complex64)
              for _ in range(4)]
    placed = prog.place(groups[0])
    holders = {s.device for s in placed.addressable_shards}
    check(len(holders) == D, f"input shards on {len(holders)} devices, not {D}")
    for leaf in jax.tree_util.tree_leaves(prog.init_carry()):
        check(len(leaf.sharding.device_set) == D,
              "a carry leaf is not spread over every device")
    outs = [runner.run_group(g) for g in groups]
    check(runner.dispatches == len(groups), "more than one dispatch per group")
    # each shard is an independent stream: against the single-device run of
    # the same rows, and that against the numpy reference
    single = jax.jit(pipe.fn())
    worst_single = worst_ref = 0.0
    for d in range(D):
        carry = jax.device_put(pipe.init_carry(), jax.devices()[0])
        for g, got in zip(groups, outs):
            carry, y = single(carry, jax.device_put(g[d], jax.devices()[0]))
            worst_single = max(worst_single,
                               rel_err(got[d].reshape(-1), np.asarray(y)))
        want = ref_spectrum(np.concatenate([g[d] for g in groups]), taps)
        got_d = np.concatenate([o[d].reshape(-1) for o in outs])
        worst_ref = max(worst_ref, rel_err(got_d, want))
    check(worst_single <= 1e-4, f"sharded vs single device: {worst_single:.3g}")
    check(worst_ref <= 1e-3, f"sharded vs numpy: {worst_ref:.3g}")

    # the multi-chip example, in this process (one process owns the chips)
    spec = importlib.util.spec_from_file_location(
        "sharded_spectrum", _ROOT / "examples" / "sharded_spectrum.py")
    ex = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ex)
    common = ["--frames", "4", "--frame-size", str(frame)]
    with contextlib.redirect_stdout(sys.stderr):     # its own report lines
        y_d = ex.main(["--devices", str(D)] + common)
        y_1 = ex.main(["--devices", "1"] + common)
    ex_holders = {s.device for s in y_d.addressable_shards}
    check(len(ex_holders) == D,
          f"example output shards on {len(ex_holders)} devices, not {D}")
    ex_err = rel_err(np.asarray(y_d), np.asarray(y_1))
    check(ex_err <= 1e-3, f"sharded_spectrum D={D} vs D=1: {ex_err:.3g}")
    ctx.emit("multichip", mark, t0, devices=D, frame_size=frame,
             shard_holders=sorted(str(d) for d in holders),
             data_shard_rel_err_vs_single=worst_single,
             data_shard_rel_err_vs_numpy=worst_ref,
             example_rel_err_vs_single=ex_err,
             example_shard_holders=sorted(str(d) for d in ex_holders))


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--devices", type=int, default=1,
                    help="device count to drive; > 1 adds the multichip phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated signal")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dress rehearsal: tiny sizes, Pallas interpreted, "
                         "platform cpu accepted (proves the script, not the "
                         "chip)")
    ap.add_argument("--phases", default="",
                    help="comma-separated subset (default: all); device "
                         "always runs. A subset is not the chip check: it "
                         "prints no ok line")
    args = ap.parse_args(argv)

    from futuresdr_tpu.config import config
    if args.rehearse:
        config().tpu_frame_size = 16384
        # what a chip resolves "auto" to, so the rehearsal walks the same code
        config().tpu_wire_format = "sc16"

    t_start = time.perf_counter()
    ctx = Ctx(args)
    phases = {"streamed": phase_streamed, "fm_app": phase_fm_app,
              "serve": phase_serve, "pallas": phase_pallas,
              "wlan_rx": phase_wlan_rx, "lora_gw": phase_lora_gw,
              "multichip": phase_multichip}
    full = [p for p in phases if p != "multichip" or args.devices > 1]
    want = [p for p in args.phases.split(",") if p] or full
    skipped = [p for p in full if p not in want]
    phase_device(ctx)
    for name in want:
        phases[name](ctx)
    total = ctx.meter.since(0)
    print(json.dumps({"phase": "summary", **ctx.stamp, **total,
                      "persistent_cache_hits": ctx.meter.cache_hits,
                      "compile_cache_entries_before": ctx.cache_entries_before,
                      "compile_cache_entries_after": ctx.cache_entries(),
                      "phases": ["device"] + want, "skipped": skipped,
                      "smoke_wall_s": round(time.perf_counter() - t_start, 2),
                      "claim": None}), flush=True)
    import jax
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}
    if args.rehearse or skipped:
        # passed, but not the chip check: no line a driver could take for it
        print(json.dumps({"complete": False, "rehearse": ctx.rehearse,
                          "phases": ["device"] + want, "skipped": skipped,
                          "device": device}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
