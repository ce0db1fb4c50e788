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
    """The whole on-device receiver (``models/wlan/rx_stages.py``: detect →
    align → SIGNAL → demod → Viterbi → records) through ``Pipeline``,
    carry-chained over an HBM-resident frame of ``bucket`` symbols' worth of
    air holding packets of ``modulation``, scan-marginal methodology
    (BASELINE target #4; reference: ``perf/wlan/rx.rs``)."""
    import jax
    from futuresdr_tpu.models.wlan.consts import MCS_TABLE, SYM_LEN
    from futuresdr_tpu.models.wlan.rx_stages import wlan_rx_stages
    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.ops.xfer import to_device
    from futuresdr_tpu.utils.measure import run_marginal_retry, scaled_k_pair

    frame = bucket * SYM_LEN
    mcs = next(n for n, m in MCS_TABLE.items() if m.modulation == modulation)
    rng = np.random.default_rng(21)
    mac, parts, n = Mac(), [], 0
    while True:                                 # 200-byte packets at SIFS
        burst = encode_frame(
            mac.frame(bytes(rng.integers(0, 256, 172, dtype=np.uint8))), mcs)
        if n + len(burst) + 320 > frame:
            break
        parts += [burst, np.zeros(320, np.complex64)]
        n += len(burst) + 320
    host = np.concatenate(parts + [np.zeros(frame - n, np.complex64)])
    host = (host + 1e-3 * (rng.standard_normal(frame)
                           + 1j * rng.standard_normal(frame))).astype(np.complex64)
    pipe = Pipeline(wlan_rx_stages(carry_len=8192, max_psdu=256, cand_slots=32,
                                   lanes=16), np.complex64)
    k_pair = scaled_k_pair(k_pair, frame, jax.default_backend())
    rate = run_marginal_retry(pipe.fn(), pipe.init_carry(), to_device(host),
                              k_pair) / 1e6
    return rate, frame


def run_viterbi_core(unrolls=(1, 4, 8, 16, 32), lanes: int = 128,
                     max_psdu: int = 4095, live: int = 45,
                     longest: int = 12294) -> None:
    """Stage A/B of the Viterbi alone at the lane and step counts of the
    benchmark's ``wlan_rx_sat`` cell: ``live`` of ``lanes`` lanes hold
    packets, the longest ``longest`` steps. Uncut (``viterbi_core``, a packet
    a lane) per ``UNROLL``: microseconds per step of the longest packet,
    forward pass and traceback together; then cut into blocks
    (``viterbi_blocks``, piece slots as the receiver sizes them) per ``BLOCK``
    and ``CHUNK``: microseconds per step of the passes' serial length. The
    module's constants are patched for the sweep and put back."""
    import jax
    import jax.numpy as jnp
    from futuresdr_tpu.models.wlan import coding
    from futuresdr_tpu.ops import viterbi

    tables = (coding._PREV_S, coding._PREV_B, coding._BM0, coding._BM1)
    T = 16 + 8 * max_psdu + 6
    rng = np.random.default_rng(3)
    steps = np.zeros(lanes, np.int32)
    steps[:live] = rng.integers(246, longest, live)
    steps[0] = longest
    llr = jnp.asarray(rng.standard_normal((T, 2, lanes)).astype(np.float32))

    def median_ms(run, *args) -> float:
        run(*args).block_until_ready()
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            run(*args).block_until_ready()
            times.append(time.perf_counter() - t0)
        return sorted(times)[2] * 1e3

    shipped = (viterbi.UNROLL, viterbi.BLOCK, viterbi.CHUNK)
    print("backend,form,unroll,lanes,serial_steps,ms_per_call,us_per_step")
    try:
        for u in unrolls:
            viterbi.UNROLL = u
            ms = median_ms(jax.jit(lambda a, b: viterbi.viterbi_core(a, b, *tables)),
                           llr, jnp.asarray(steps))
            print(f"{jax.default_backend()},uncut,{u},{lanes},{longest},{ms:.3f},"
                  f"{ms * 1e3 / longest:.3f}", flush=True)
        viterbi.UNROLL = shipped[0]
        stream = jnp.transpose(llr, (1, 2, 0))                     # [2, L, T]
        for block, chunk in ((1024, 1024), (1024, 512), (1024, 128),
                             (512, 1024), (2048, 1024)):
            viterbi.BLOCK, viterbi.CHUNK = block, chunk
            slots = viterbi.piece_slots(-(-4664 * 216 // block) + lanes)
            ms = median_ms(jax.jit(lambda a, b: viterbi.viterbi_blocks(
                a, b, *tables, n_blocks=slots)), stream, jnp.asarray(steps))
            pieces = int(np.sum(-(-steps // block)))
            serial = -(-pieces // chunk) * (block + 2 * viterbi.OVERLAP)
            print(f"{jax.default_backend()},blocks_{block}_x{chunk},{shipped[0]},"
                  f"{pieces}_of_{slots},{serial},{ms:.3f},{ms * 1e3 / serial:.3f}",
                  flush=True)
    finally:
        viterbi.UNROLL, viterbi.BLOCK, viterbi.CHUNK = shipped


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
                   help="scan-marginal rate of the on-device receiver")
    p.add_argument("--bucket", type=int, default=1024,
                   help="symbols per device frame (device-resident mode)")
    p.add_argument("--viterbi-core", action="store_true",
                   help="time ops/viterbi.viterbi_core and viterbi_blocks alone")
    a = p.parse_args()

    if a.viterbi_core:
        run_viterbi_core()
        return

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
