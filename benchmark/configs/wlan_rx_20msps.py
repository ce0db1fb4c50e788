"""Builder of ``wlan_rx_20msps``: a monitor-mode receiver of one 20 MHz
802.11a/g channel, the whole receiver one device program per frame
(``futuresdr_tpu/models/wlan/rx_stages.py``).

The ``stream`` driver's interface, used in a way ``benchmark/README.md`` does
not spell out, because this is the first configuration whose output items are
records and not samples:

    make_kernel(cfg, rehearse)             -> the TpuKernel block
    make_input(cfg, seed, n_frames, frame) -> the capture, complex64
    reference(cfg, x, history)             -> ONE RECORD BLOCK (int32,
        frame/8 words, the program's layout) built from the float64
        receiver's decode of (the last carry_len samples of history, x); the
        driver concatenates such blocks as it concatenates the program's
    judge(cfg, got, want, rehearse)        -> both are concatenated record
        blocks; they are parsed and compared packet by packet, not as numbers
    frame_cost(cfg, frame, wire)           -> what a receiver needs per frame

The reference, the transmitter and the record reader are the benchmark's own
(``harness/refs_wlan.py``); the program is imported only through ``TpuKernel``
and the stage constructor ``wlan_rx_stages``.
"""

from __future__ import annotations

import math

import numpy as np

from harness import costs
from harness import refs_wlan as W

_STAGE_KEYS = ("carry_len", "max_psdu", "cand_slots", "lanes")
#: operations a trellis step needs: 64 states x 2 candidates x (2 adds + 1
#: compare), and one traceback step (2)
TRELLIS_OPS = 64 * 2 * 3 + 2


def _sizes(cfg: dict, frame: int) -> dict:
    """The stage's sizes and the capture's length classes at this frame size:
    the shipped ones, or the rehearsal's where the frame is the rehearsal's."""
    out = dict(cfg["parameters"], length_classes=cfg["assumed"]["length_classes"])
    if frame == cfg["rehearsal"]["frame_size"]:
        out.update(cfg["rehearsal"])
    return out


def make_kernel(cfg: dict, rehearse: bool):
    from futuresdr_tpu.models.wlan.rx_stages import wlan_rx_stages
    from futuresdr_tpu.tpu import TpuKernel

    p = cfg["parameters"]
    frame = cfg["rehearsal"]["frame_size"] if rehearse else p["frame_size"]
    s = _sizes(cfg, frame)
    return TpuKernel(wlan_rx_stages(**{k: s[k] for k in _STAGE_KEYS}),
                     np.dtype(p["in_dtype"]), frame_size=frame,
                     frames_in_flight=p["frames_in_flight"], wire=p["wire"])


def schedule(cfg: dict, seed: int, n_frames: int, frame: int) -> list:
    """What is on the air, without the samples: ``(position, rate, length,
    scrambler seed, cfo rad/sample, phase, snr_db)`` per packet."""
    a = cfg["assumed"]
    rng = np.random.default_rng(seed)
    classes = _sizes(cfg, frame)["length_classes"]
    weights = np.array([c[2] for c in classes], float)
    short = [W.RATE_NAMES.index(r) for r in a["rates_short"]]
    out, pos, n = [], 0, n_frames * frame
    while True:
        pos += 320 if rng.random() < a["p_sifs"] else 680 + 180 * int(rng.integers(16))
        c = int(rng.choice(len(classes), p=weights / weights.sum()))
        length = int(rng.integers(classes[c][0], classes[c][1] + 1))
        rate = int(rng.choice(short, p=list(a["rates_short"].values()))) if c == 0 \
            else int(rng.integers(8))
        if pos + W.packet_samples(rate, length) > n:
            return out
        cfo = 2 * math.pi * rng.uniform(-a["cfo_max_hz"], a["cfo_max_hz"]) / 20e6
        out.append((pos, rate, length, int(rng.integers(1, 128)), cfo,
                    rng.uniform(0, 2 * math.pi),
                    12.0 + 3.0 * W.RATES[rate][2] + rng.uniform(0, 6)))
        pos += W.packet_samples(rate, length)


def sent_psdus(cfg: dict, seed: int, n_frames: int, frame: int) -> list:
    """The PSDUs of the capture in order of arrival (tests)."""
    rng = np.random.default_rng([seed, 1])
    return [W.mpdu(rng.integers(0, 256, s[2] - 28, dtype=np.uint8).tobytes(), i)
            for i, s in enumerate(schedule(cfg, seed, n_frames, frame))]


def make_input(cfg: dict, seed: int, n_frames: int, frame: int) -> np.ndarray:
    n = n_frames * frame
    n0 = cfg["assumed"]["noise_power"]
    rng = np.random.default_rng([seed, 2])
    x = np.empty(n, np.complex64)
    x.real = rng.standard_normal(n, np.float32) * np.float32(math.sqrt(n0 / 2))
    x.imag = rng.standard_normal(n, np.float32) * np.float32(math.sqrt(n0 / 2))
    psdus = sent_psdus(cfg, seed, n_frames, frame)
    for (pos, rate, _, scr, cfo, phase, snr_db), psdu in zip(
            schedule(cfg, seed, n_frames, frame), psdus):
        s = W.transmit(psdu, rate, scr)
        gain = math.sqrt(10 ** (snr_db / 10) * n0 / (52 / 4096))
        x[pos:pos + len(s)] += (gain * s * np.exp(
            1j * (cfo * np.arange(len(s)) + phase))).astype(np.complex64)
    return x


def reference(cfg: dict, x: np.ndarray, history=None) -> np.ndarray:
    s = _sizes(cfg, len(x))
    hist = np.zeros(s["carry_len"], np.complex128)
    if history is not None and len(history):
        h = np.asarray(history)[-s["carry_len"]:]
        hist[len(hist) - len(h):] = h
    packets, counts = W.receive_window(np.concatenate([hist, x]), s["carry_len"])
    return W.build_block(packets, counts, len(x) // 8, s["lanes"])


def judge(cfg: dict, got: np.ndarray, want: np.ndarray, rehearse: bool):
    """Every frame's good-FCS packets the same, in the same order, as the
    reference's: (LTS start, rate, LENGTH, bytes) equal; CFO, LTS SNR and the
    mean |LLR| the trellis was fed within their tolerances; header counts
    consistent; no overflow. Records without a good FCS (false alarms) are
    counted and not compared. The bytes of this mix survive a receiver whose
    matmuls round their operands to bfloat16 (the TPU's default), and CFO
    and SNR never pass a matmul: the mean |LLR| is what holds the program's
    precision (DFT of the long symbols, DFT of every data symbol, the one-hot
    deinterleave and depuncture), so its limit is relative and tight."""
    c = cfg["correctness"]
    frame = (cfg["rehearsal"] if rehearse else cfg["expected_on_chip"])["frame_size"]
    words = frame // 8
    d = {"frames": 0, "packets": 0, "fcs_bad": 0, "overflow": 0, "mismatch": 0,
         "cfo_err_max": 0.0, "snr_err_max_db": 0.0, "llr_err_max_rel": 0.0,
         "why": None}

    def fail(why):
        d["mismatch"] += 1
        d["why"] = d["why"] or why

    if got.shape != want.shape or len(got) % words or not len(got):
        fail(f"shape {got.shape} against {want.shape}, blocks of {words}")
        return False, d
    for j in range(len(got) // words):
        head, mine = W.parse_block(got[j * words:(j + 1) * words])
        _, ref = W.parse_block(want[j * words:(j + 1) * words])
        d["frames"] += 1
        if head is None:
            fail(f"block {j}: no header")
            continue
        d["overflow"] += head["overflow"]
        if not (head["detected"] >= head["aligned"] >= head["signal_ok"]
                >= head["emitted"] == len(mine) and head["lanes_decoded"] >= len(mine)):
            fail(f"block {j}: header {head}")
        good = [p for p in mine if W.fcs_ok(p["psdu"])]
        d["fcs_bad"] += len(mine) - len(good)
        ref = [p for p in ref if W.fcs_ok(p["psdu"])]
        d["packets"] += len(ref)
        key = lambda p: (p["lts_start"], p["rate"], p["length"], p["psdu"])
        if [key(p) for p in good] != [key(p) for p in ref]:
            fail(f"block {j}: {len(good)} good packets against {len(ref)}")
            continue
        for p, r in zip(good, ref):
            d["cfo_err_max"] = max(d["cfo_err_max"], abs(p["cfo"] - r["cfo"]))
            d["snr_err_max_db"] = max(d["snr_err_max_db"],
                                      abs(p["snr_db"] - r["snr_db"]))
            d["llr_err_max_rel"] = max(
                d["llr_err_max_rel"],
                abs(p["llr_mean"] - r["llr_mean"]) / r["llr_mean"])
    ok = not d["mismatch"] and not d["overflow"] \
        and d["cfo_err_max"] <= c["cfo_tolerance"] \
        and d["snr_err_max_db"] <= c["snr_tolerance_db"] \
        and d["llr_err_max_rel"] <= c["llr_tolerance_rel"]
    return bool(ok), d


def needed_flops(n_samples: int, packets: list) -> float:
    """Operations a receiver needs for ``n_samples`` of air holding ``packets``
    (rate, length), by the conventions of ``harness/costs.py``:

    * lag-16 autocorrelation and power, per sample: a complex multiply (6),
      complex and real running sums (add + subtract: 4 + 2), |x|² (3),
      magnitude and threshold (4): 19;
    * per packet the 64-tap LTS correlation, complex x complex (8 per MAC)
      over the 481 lags of the search window, and a 24-step SIGNAL decode;
    * per OFDM symbol (2 LTS + SIGNAL + data): CFO rotation (6 x 64), an FFT
      of 5·64·log2 64, per used carrier a complex division (11) and the pilot
      phase (6), per coded bit one max-log LLR (3 per level pair, taken as 6);
    * per trellis step 64 states x 2 candidates x (2 adds + 1 compare) = 384,
      and one traceback step (2), over the steps the packets HAVE, not over
      padding, lanes or packets decoded twice.
    """
    ops = 19.0 * n_samples
    for rate, length in packets:
        n_sym = W.n_symbols(rate, length)
        steps = 16 + 8 * length + 6
        ops += 481 * 64 * 8 + 24 * TRELLIS_OPS
        ops += (n_sym + 3) * (6 * 64 + 5 * 64 * 6 + 52 * 17)
        ops += n_sym * 48 * W.RATES[rate][2] * 6
        ops += steps * TRELLIS_OPS
    return ops


def frame_cost(cfg: dict, frame: int, wire: str) -> dict:
    """Per frame: the operations the mix's packets need (the schedule of seed
    0 over 16 frames stands for every seed: the draws are the same law) and
    the bytes that must cross HBM: the wire's samples in, the record block
    out; under ``kernels`` what the trellis's two Pallas kernels need
    (``viterbi``: the data steps the packets have, SERVICE and tail
    included, at ``TRELLIS_OPS`` a step; SIGNAL's 24 steps are decoded
    elsewhere)."""
    sched = schedule(cfg, 0, 16, frame)
    flops = needed_flops(16 * frame, [(s[1], s[2]) for s in sched]) / 16
    steps = sum(16 + 8 * s[2] + 6 for s in sched) / 16
    return {"flops": float(flops),
            "bytes": float(frame * cfg["wire_bytes"][wire] + frame // 8 * 4),
            "kernels": {"viterbi": costs.trellis_cost(steps, TRELLIS_OPS)}}
