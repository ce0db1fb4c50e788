#!/usr/bin/env python
"""SSB receiver (reference: ``examples/ssb/src/main.rs`` — file replay → SSB
product detector → audio).

The chain runs as REAL blocks on the seify HAL's file-replay driver (the same
path a live SDR would take; ``hw/__init__.py`` FileDriver):

    SeifySource(driver=file) → XlatingFir(BFO shift + analytic bandpass,
    decim) → Apply(real) [product detector] → Agc → WavSink / AudioSink

The XlatingFir rotates the BFO to DC and applies a one-sided 300..3000 Hz
analytic bandpass, so only the chosen sideband survives; taking the real
part is the product detector — the block twin of
``models/misc.ssb_demodulate``.

With no ``--input``, a two-tone USB test transmission (700 + 1900 Hz) is
synthesized to a temp file and demodulated back; the script then checks the
recovered audio spectrum peaks at those tones (a self-validating loopback).

Run: ``python examples/ssb_rx.py --wav /tmp/ssb.wav``
     ``python examples/ssb_rx.py --input capture.cf32 --bfo 12000 --sideband lsb``
"""

import argparse
import sys
import tempfile

sys.path.insert(0, ".")
sys.path.insert(0, "..")

import numpy as np

from futuresdr_tpu import Flowgraph, Runtime
from futuresdr_tpu.blocks import Agc, Apply, SeifyBuilder, VectorSink, WavSink, \
    XlatingFir


def sideband_taps(fs: float, sideband: str, audio_bw: float,
                  n_taps: int = 257) -> np.ndarray:
    """Analytic (one-sided) bandpass selecting [300, audio_bw] Hz (USB) or the
    mirror (LSB) at BASEBAND — applied after the XlatingFir's BFO rotation;
    the Hamming-windowed design of `models/misc.py:98`."""
    lo, hi = (300.0, audio_bw) if sideband == "usb" else (-audio_bw, -300.0)
    f1, f2 = sorted((lo / fs, hi / fs))
    k = np.arange(n_taps) - (n_taps - 1) / 2
    h = (np.exp(2j * np.pi * f2 * k) - np.exp(2j * np.pi * f1 * k)) / \
        (2j * np.pi * k + 1e-30)
    h[(n_taps - 1) // 2] = 2 * np.pi * (f2 - f1) / (2 * np.pi)
    h *= np.hamming(n_taps)
    return h.astype(np.complex64)


def synthesize_usb(fs: float, bfo: float, seconds: float,
                   tones=(700.0, 1900.0)) -> np.ndarray:
    """Two-tone USB transmission at the BFO offset (upper sideband only:
    analytic tones e^{j2πft} translated by the BFO)."""
    t = np.arange(int(fs * seconds)) / fs
    sig = sum(np.exp(2j * np.pi * (bfo + f) * t) for f in tones)
    sig = sig / np.abs(sig).max() * 0.5
    noise = (np.random.default_rng(9).standard_normal((len(t), 2)) @
             np.array([1, 1j])) * 0.01
    return (sig + noise).astype(np.complex64)


def main(argv=None):
    p = argparse.ArgumentParser(description="SSB receiver on the file-replay HAL")
    p.add_argument("--input", default=None, help="cf32 IQ capture (default: "
                   "synthesize a two-tone USB test signal)")
    p.add_argument("--rate", type=float, default=256e3)
    p.add_argument("--bfo", type=float, default=12e3,
                   help="carrier offset of the SSB signal in the capture")
    p.add_argument("--sideband", choices=("usb", "lsb"), default="usb")
    p.add_argument("--audio-bw", type=float, default=3000.0)
    p.add_argument("--decim", type=int, default=4)
    p.add_argument("--wav", default=None, help="write demodulated audio here")
    p.add_argument("--audio", action="store_true",
                   help="play via the soundcard (AudioSink) instead of a WAV")
    a = p.parse_args(argv)

    synthesized = a.input is None
    tmp_path = None
    if synthesized:
        tmp = tempfile.NamedTemporaryFile(suffix=".cf32", delete=False)
        synthesize_usb(a.rate, a.bfo, 0.6).tofile(tmp.name)
        a.input = tmp_path = tmp.name
        print(f"# no --input: synthesized two-tone USB test signal → {a.input}")

    try:
        return _run(a, synthesized)
    finally:
        if tmp_path is not None:
            import os
            try:
                os.unlink(tmp_path)
            except OSError:
                pass


def _run(a, synthesized: bool) -> int:
    fs_audio = a.rate / a.decim
    fg = Flowgraph()
    src = (SeifyBuilder()
           .args(f"driver=file,path={a.input},repeat=false,throttle=false")
           .sample_rate(a.rate).build_source())
    bp = XlatingFir(sideband_taps(a.rate, a.sideband, a.audio_bw),
                    decim=a.decim, offset_freq=a.bfo, sample_rate=a.rate)
    detector = Apply(lambda x: x.real.astype(np.float32) * 2.0,
                     np.complex64, np.float32)
    agc = Agc(np.float32, reference=0.3, adjustment_rate=1e-2, mode="block")
    probe = VectorSink(np.float32)
    fg.connect(src, bp, detector, agc)
    if a.audio:
        from futuresdr_tpu.blocks import AudioSink
        fg.connect(agc, AudioSink(int(fs_audio)))
    else:
        wav = a.wav or "ssb_audio.wav"
        fg.connect(agc, WavSink(wav, int(fs_audio)))
    fg.connect_stream(agc, "out", probe, "in")       # analysis tap
    Runtime().run(fg)

    audio = probe.items()
    print(f"# demodulated {len(audio)} audio samples at {fs_audio:.0f} Hz")
    if len(audio) > 1024:
        spec = np.abs(np.fft.rfft(audio[1024:] * np.hanning(len(audio) - 1024)))
        freqs = np.fft.rfftfreq(len(audio) - 1024, 1.0 / fs_audio)
        top = freqs[np.argsort(spec)[-6:]]
        peaks = sorted(set(round(f / 50) * 50 for f in top))
        print(f"# dominant audio tones (Hz, 50 Hz bins): {peaks}")
        if synthesized:
            for want in (700.0, 1900.0):
                assert any(abs(f - want) <= 50 for f in top), \
                    f"expected {want} Hz tone missing from {sorted(top)}"
            print("# loopback OK: both test tones recovered")
    return 0


if __name__ == "__main__":
    sys.exit(main())
