#!/usr/bin/env python
"""perf/wlan — WLAN RX throughput: frames decoded per second.

Reference: ``perf/wlan/rx.rs`` (full 802.11 RX chain vs GNU Radio's wifi_rx).
Synthesizes a burst stream of QPSK-1/2 frames with noise, then measures full RX
(detect → sync → equalize → Viterbi → MAC check) throughput.
CSV: ``run,n_frames,payload_len,decoded,elapsed_secs,frames_per_sec,msamples_per_sec``.
"""

import argparse
import sys
import time

sys.path.insert(0, ".")
sys.path.insert(0, "..")

import numpy as np

from futuresdr_tpu.models.wlan import encode_frame, decode_stream, decode_stream_batch, Mac


def run_device_resident(bucket: int, modulation: str, k_pair) -> tuple:
    """The OFDM demod hot loop (CFO → batched FFT64 → equalize → CPE → max-log
    demap, ``models/wlan/jax_demod.py``) carry-chained over HBM-resident symbol
    frames, scan-marginal methodology (BASELINE target #4; reference hot loop:
    ``examples/wlan/src/bin/loopback.rs:60-95`` / ``perf/wlan/rx.rs``)."""
    import jax
    from futuresdr_tpu.models.wlan.consts import PILOT_POLARITY, SYM_LEN
    from futuresdr_tpu.models.wlan.jax_demod import _compiled
    from futuresdr_tpu.ops.xfer import to_device
    from futuresdr_tpu.utils.measure import run_marginal_retry, scaled_k_pair

    run, consts = _compiled(modulation, bucket)  # noqa: SLF001 — perf probes the hot loop directly
    rng = np.random.default_rng(21)
    frame = bucket * SYM_LEN
    # scan-window scaling (utils/measure.scaled_k_pair): scan windows of tens
    # of ms sit inside per-dispatch jitter; the shared floor conditions the
    # marginal on every backend
    k_pair = scaled_k_pair(k_pair, frame, jax.default_backend())
    host = (rng.standard_normal(frame)
            + 1j * rng.standard_normal(frame)).astype(np.complex64)
    H = (rng.standard_normal(64) + 1j * rng.standard_normal(64)).astype(np.complex64)
    H[np.abs(H) < 0.3] = 1.0                      # keep the equalizer well-conditioned
    pol = PILOT_POLARITY[np.arange(bucket) % len(PILOT_POLARITY)].astype(np.float32)
    mask = np.ones(bucket, np.float32)
    dH, dpol, dmask = to_device(H), to_device(pol), to_device(mask)
    dconsts = tuple(to_device(np.asarray(c)) for c in consts)
    cfo, ph0 = np.float32(1e-4), np.float32(0.0)

    # dH rides in the scan CARRY, not the closure: a device array captured as a
    # jit closure constant forces a host readback at MLIR-embedding time.
    # Arguments and carries never take that path.
    def step(carry, body):
        return carry, run(body, carry, dpol, dmask, cfo, ph0, *dconsts)

    carry0 = dH
    x = to_device(host)
    rate = run_marginal_retry(step, carry0, x, k_pair) / 1e6
    return rate, frame


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--runs", type=int, default=3)
    p.add_argument("--frames", type=int, default=200)
    p.add_argument("--payload", type=int, default=256)
    p.add_argument("--mcs", default="qpsk_1_2")
    p.add_argument("--snr-db", type=float, default=25.0)
    p.add_argument("--batch", action="store_true",
                   help="batched Viterbi (one lax.scan for all frames)")
    p.add_argument("--device-resident", action="store_true",
                   help="scan-marginal OFDM demod hot loop on the device")
    p.add_argument("--bucket", type=int, default=1024,
                   help="symbols per device frame (device-resident mode)")
    a = p.parse_args()

    if a.device_resident:
        from futuresdr_tpu.tpu.instance import instance
        backend = instance().platform
        print(f"# backend: {backend}", file=sys.stderr)
        from futuresdr_tpu.models.wlan.consts import MCS_TABLE
        modulation = MCS_TABLE[a.mcs].modulation
        from futuresdr_tpu.utils.measure import default_k_pair
        k_pair = default_k_pair(backend)
        print("mode,backend,modulation,frame,run,msamples_per_sec")
        for r in range(a.runs):
            rate, frame = run_device_resident(a.bucket, modulation, k_pair)
            print(f"device_resident,{backend},{modulation},{frame},{r},{rate:.1f}",
                  flush=True)
        return
    if a.batch:
        from futuresdr_tpu.tpu.instance import instance
        print(f"# backend: {instance().platform}", file=sys.stderr)

    rng = np.random.default_rng(0)
    mac = Mac()
    parts = []
    for i in range(a.frames):
        psdu = mac.frame(bytes(rng.integers(0, 256, a.payload, dtype=np.uint8)))
        parts += [encode_frame(psdu, a.mcs), np.zeros(300, np.complex64)]
    sig = np.concatenate(parts)
    sigma = np.sqrt(np.mean(np.abs(sig) ** 2) * 10 ** (-a.snr_db / 10) / 2)
    sig = (sig + sigma * (rng.standard_normal(len(sig))
                          + 1j * rng.standard_normal(len(sig)))).astype(np.complex64)

    decode = decode_stream_batch if a.batch else decode_stream
    print("run,n_frames,payload_len,decoded,elapsed_secs,frames_per_sec,msamples_per_sec")
    for r in range(a.runs):
        t0 = time.perf_counter()
        raw = decode(sig)
        # full RX includes the MAC FCS check (reference decoder.rs validates
        # before announcing) — a lucky SIGNAL parity on a false sync must not
        # count as a decoded frame
        from futuresdr_tpu.models.wlan.mac import payload_from_mpdu
        decoded = [f for f in raw if payload_from_mpdu(f.psdu) is not None]
        dt = time.perf_counter() - t0
        print(f"{r},{a.frames},{a.payload},{len(decoded)},{dt:.3f},"
              f"{len(decoded) / dt:.1f},{len(sig) / dt / 1e6:.2f}", flush=True)


if __name__ == "__main__":
    main()
