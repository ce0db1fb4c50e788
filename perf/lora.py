#!/usr/bin/env python
"""perf/lora — LoRa RX throughput: frames decoded / s and samples / s.

Reference role: the LoRa example's RX chain throughput (dechirp + FFT peak-detect,
`examples/lora/src/{frame_sync,fft_demod}.rs`).
CSV: ``run,sf,cr,n_frames,decoded,elapsed_secs,frames_per_sec,msamples_per_sec``.
"""

import argparse
import sys
import time

sys.path.insert(0, ".")
sys.path.insert(0, "..")

import numpy as np

from futuresdr_tpu.models.lora import (LoraParams, modulate_frame, detect_frames,
                                       demodulate_frame)


_PIPE_CACHE: dict = {}       # sf -> Pipeline (stable jit identity across runs,
#                              the memoization perf/wlan.py's _compiled has)


def run_device_resident(sf: int, symbols_per_frame: int, k_pair) -> tuple:
    """Dechirp + batched FFT + argmax (the ``FftDemod`` hot loop,
    ``examples/lora/src/fft_demod.rs``) as a carry-chained device pipeline over
    HBM-resident frames, scan-marginal methodology (BASELINE target #5)."""
    import jax
    from futuresdr_tpu.ops.stages import Pipeline, lora_demod_stage
    from futuresdr_tpu.ops.xfer import to_device, to_host
    from futuresdr_tpu.utils.measure import run_marginal_retry, scaled_k_pair

    pipe = _PIPE_CACHE.get(sf)
    if pipe is None:
        pipe = _PIPE_CACHE[sf] = Pipeline([lora_demod_stage(sf)], np.complex64)
    frame = (1 << sf) * symbols_per_frame
    backend = jax.default_backend()
    # scan-window scaling (shared discipline, utils/measure.scaled_k_pair):
    # small frames make sub-ms timed windows where scheduler noise dominated
    # (r4: 58-182 Msps spread on CPU); accelerator dispatch jitter needs far
    # larger windows still. This is the FASTEST chain in the suite (~2-4 Gsps
    # on-chip), so the shared 512M-sample accel floor buys only ~0.2 s of
    # compute per k_lo scan, inside per-dispatch jitter — floor LoRa's window
    # at 2G samples (~1 s scans) so the k_hi−k_lo delta dwarfs the jitter like
    # the slower chains' already do
    k_pair = scaled_k_pair(k_pair, frame, backend,
                           min_lo_items=None if backend == "cpu"
                           else 2_048_000_000)
    rng = np.random.default_rng(11)
    host = (rng.standard_normal(frame)
            + 1j * rng.standard_normal(frame)).astype(np.complex64)
    carry0 = jax.device_put(pipe.init_carry())
    x = to_device(host)
    if backend != "cpu":
        # untimed single-dispatch warmup before the measured scans: the FIRST
        # dispatch of a process pays device and transfer set-up, and letting
        # it land inside run_marginal's first timed window made run 1 a cold
        # outlier
        _, y = pipe.fn()(carry0, x)
        to_host(y)
    rate = run_marginal_retry(pipe.fn(), carry0, x, k_pair) / 1e6
    return rate, frame


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--runs", type=int, default=3)
    p.add_argument("--frames", type=int, default=100)
    p.add_argument("--sf", type=int, default=7)
    p.add_argument("--cr", type=int, default=2)
    p.add_argument("--device-resident", action="store_true",
                   help="scan-marginal dechirp+FFT+argmax hot loop on the device")
    p.add_argument("--symbols-per-frame", type=int, default=2048)
    p.add_argument("--soft", dest="soft", action="store_true", default=None,
                   help="force soft decoding (LoraParams default is soft-on)")
    p.add_argument("--no-soft", dest="soft", action="store_false",
                   help="force the hard path — pin this to compare across "
                        "rounds that straddled the r4 soft-default flip")
    a = p.parse_args()

    if a.device_resident:
        from futuresdr_tpu.tpu.instance import instance
        backend = instance().platform
        print(f"# backend: {backend}", file=sys.stderr)
        from futuresdr_tpu.utils.measure import default_k_pair
        k_pair = default_k_pair(backend)
        print("mode,backend,sf,frame,run,msamples_per_sec")
        for r in range(a.runs):
            rate, frame = run_device_resident(a.sf, a.symbols_per_frame, k_pair)
            print(f"device_resident,{backend},{a.sf},{frame},{r},{rate:.1f}",
                  flush=True)
        return

    params = (LoraParams(sf=a.sf, cr=a.cr) if a.soft is None
              else LoraParams(sf=a.sf, cr=a.cr, soft_decoding=a.soft))
    rng = np.random.default_rng(0)
    parts = []
    for i in range(a.frames):
        payload = bytes(rng.integers(0, 256, 32, dtype=np.uint8))
        parts += [modulate_frame(payload, params),
                  np.zeros(4 * params.n, np.complex64)]
    sig = np.concatenate(parts)
    sig = (sig + 0.05 * (rng.standard_normal(len(sig))
                         + 1j * rng.standard_normal(len(sig)))).astype(np.complex64)

    print("run,sf,cr,n_frames,decoded,elapsed_secs,frames_per_sec,msamples_per_sec")
    for r in range(a.runs):
        t0 = time.perf_counter()
        decoded = 0
        for s in detect_frames(sig, params):
            res = demodulate_frame(sig, s, params)
            if res is not None and res[1]:
                decoded += 1
        dt = time.perf_counter() - t0
        print(f"{r},{a.sf},{a.cr},{a.frames},{decoded},{dt:.3f},"
              f"{decoded / dt:.1f},{len(sig) / dt / 1e6:.2f}", flush=True)


if __name__ == "__main__":
    main()
