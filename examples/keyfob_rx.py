#!/usr/bin/env python
"""Keyfob OOK transceiver (reference: ``examples/keyfob/src/main.rs`` —
capture replay → envelope → Manchester slicer; tx: bits → OOK burst).

rx chain, as REAL blocks on the seify file-replay HAL (``hw/__init__.py``):

    SeifySource(driver=file) → Apply(|x|) [envelope] → Fir(lowpass) →
    VectorSink → host Manchester slicer (``models/misc.ook_demodulate``)

tx chain:

    ook_modulate(bits) × carrier → FileSink (a cf32 burst any SDR could play)

With no ``--input``, the script first runs its OWN tx to a temp capture
(default key code 0xA53C96, 24 bits), then decodes it back and checks the
bits — a self-validating loopback.

Run: ``python examples/keyfob_rx.py``                    (tx → rx loopback)
     ``python examples/keyfob_rx.py --input burst.cf32`` (decode a capture)
     ``python examples/keyfob_rx.py tx --out burst.cf32``
"""

import argparse
import sys
import tempfile

sys.path.insert(0, ".")
sys.path.insert(0, "..")

import numpy as np

from futuresdr_tpu import Flowgraph, Runtime
from futuresdr_tpu.blocks import Apply, FileSink, Fir, SeifyBuilder, VectorSink, \
    VectorSource
from futuresdr_tpu.dsp import firdes
from futuresdr_tpu.models.misc import ook_demodulate, ook_modulate


def key_bits(code: int, n_bits: int) -> np.ndarray:
    return np.array([(code >> (n_bits - 1 - i)) & 1 for i in range(n_bits)],
                    dtype=np.uint8)


def run_tx(out_path: str, code: int, n_bits: int, fs: float, bit_rate: float,
           carrier: float) -> None:
    """bits → Manchester OOK envelope → carrier burst → cf32 file."""
    env = ook_modulate(key_bits(code, n_bits), fs, bit_rate)
    t = np.arange(len(env)) / fs
    iq = (env * np.exp(2j * np.pi * carrier * t)).astype(np.complex64)
    pad = np.zeros(int(fs * 0.002), np.complex64)          # leading silence
    fg = Flowgraph()
    fg.connect(VectorSource(np.concatenate([pad, iq, pad])),
               FileSink(out_path, np.complex64))
    Runtime().run(fg)
    print(f"# tx: {n_bits}-bit code 0x{code:X} → {out_path}")


def run_rx(in_path: str, n_bits: int, fs: float, bit_rate: float):
    """Replay the capture through the envelope chain; slice on the host."""
    fg = Flowgraph()
    src = (SeifyBuilder()
           .args(f"driver=file,path={in_path},repeat=false,throttle=false")
           .sample_rate(fs).build_source())
    envelope = Apply(lambda x: np.abs(x).astype(np.float32),
                     np.complex64, np.float32)
    # smooth over ~1/4 bit period: kills carrier ripple, keeps edges sharp
    n_taps = max(8, int(fs / bit_rate) // 4) | 1
    lp = Fir(firdes.lowpass(1.5 * bit_rate / fs, n_taps).astype(np.float32),
             np.float32)
    vs = VectorSink(np.float32)
    fg.connect(src, envelope, lp, vs)
    Runtime().run(fg)
    env = vs.items()
    print(f"# rx: {len(env)} envelope samples")
    return ook_demodulate(env, fs, bit_rate, n_bits)


def main(argv=None):
    p = argparse.ArgumentParser(description="keyfob OOK tx/rx on the file-replay HAL")
    p.add_argument("mode", nargs="?", choices=("rx", "tx"), default="rx")
    p.add_argument("--input", default=None, help="cf32 capture to decode "
                   "(default: synthesize via the tx path first)")
    p.add_argument("--out", default=None, help="tx: write the burst here")
    p.add_argument("--code", type=lambda s: int(s, 0), default=0xA53C96)
    p.add_argument("--bits", type=int, default=24)
    p.add_argument("--rate", type=float, default=250e3)
    p.add_argument("--bit-rate", type=float, default=1000.0)
    p.add_argument("--carrier", type=float, default=20e3,
                   help="carrier offset inside the capture")
    a = p.parse_args(argv)

    if a.mode == "tx":
        run_tx(a.out or "keyfob_burst.cf32", a.code, a.bits, a.rate,
               a.bit_rate, a.carrier)
        return 0

    loopback = a.input is None
    tmp_path = None
    try:
        if loopback:
            tmp = tempfile.NamedTemporaryFile(suffix=".cf32", delete=False)
            run_tx(tmp.name, a.code, a.bits, a.rate, a.bit_rate, a.carrier)
            a.input = tmp_path = tmp.name

        bits = run_rx(a.input, a.bits, a.rate, a.bit_rate)
        if bits is None:
            print("# no keyfob burst found")
            return 1
        code = int("".join(map(str, bits)), 2)
        print(f"# decoded {a.bits}-bit code: 0x{code:X}")
        if loopback:
            assert code == a.code, \
                f"loopback mismatch: 0x{code:X} != 0x{a.code:X}"
            print("# loopback OK: code round-tripped")
        return 0
    finally:
        if tmp_path is not None:
            import os
            try:
                os.unlink(tmp_path)
            except OSError:
                pass


if __name__ == "__main__":
    sys.exit(main())
