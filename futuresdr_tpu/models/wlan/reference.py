"""The 802.11a/g receiver as plain numpy float64: what ``rx_stages`` is held to.

One window at a time, one packet at a time, no jit, no buckets, no native
library: ``ofdm.detect_packets`` → ``ofdm.sync_long`` → ``estimate_channel`` →
DFT-64 → ``equalize("ls")`` → ``demap_llrs`` → ``coding.deinterleave`` /
``depuncture`` → the numpy trellis of ``coding.viterbi_decode`` (kept here as
:func:`viterbi_numpy`, because ``viterbi_decode`` prefers the native library
and the jitted scan) → seed recovery and descrambling.

A *window* is the last ``carry_len`` samples of the previous frame followed by
this frame. Ownership, the same rule as the device program's:

* every detection of the window is aligned and its SIGNAL field read; a packet
  is decoded if its SIGNAL field is valid (even parity, a RATE of the table,
  LENGTH ≥ 1) and its last sample lies inside the window (beyond the window
  the samples are taken as zeros, so the LTS search of a packet near the end
  never reads a shorter segment than elsewhere);
* in order of arrival, a decoded packet whose scrambler seed was recovered
  (``seed_ok``) claims its span; a detection that aligns inside a claimed span
  is dropped (``phy.decode_stream``'s rule), worked out anew in every window:
  nothing is remembered from one window to the next but the samples;
* a kept packet is emitted by the frame in which its last sample lies, so a
  packet that ended in the carry is claimed again and not emitted again.

Departures from gr-ieee802-11 / upstream ``examples/wlan``: the LS equaliser
only (no STA/LMS tracking); CFO from the two long symbols alone, so
|CFO| < 2π/128 rad/sample = 156 kHz at 20 Msps (no STS coarse estimate);
the plateau detector's power floor is relative to the window's maximum.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from . import coding, ofdm
from .consts import MCS_TABLE, SYM_LEN

__all__ = ["CARRY_LEN", "SEARCH_LEN", "RxPacket", "receive_window",
           "receive_frame", "viterbi_numpy", "RATES"]

#: samples kept from the previous frame: the longest packet (4095 bytes at
#: 6 Mbit/s: 320 + 80 + 1366·80 = 109 680 samples) and room for a detection
#: that fires before the burst
CARRY_LEN = 110_592
#: ``ofdm.sync_long``'s search window
SEARCH_LEN = 320 + 224
#: the eight rates in the order of their index in a record
RATES = tuple(MCS_TABLE)
_RATE_BITS = {m.rate_bits: i for i, m in enumerate(MCS_TABLE.values())}


@dataclass
class RxPacket:
    lts_start: int          # window index of the first long symbol
    rate: int               # index into RATES
    length: int             # LENGTH of the SIGNAL field, bytes
    cfo: float              # rad/sample, from the two long symbols
    snr_db: float           # LTS-repetition estimate
    seed_ok: bool
    psdu: bytes
    end: int                # window index one past the last sample
    steps: int = 0          # trellis steps: 16 + 8·LENGTH + 6
    llr_mean: float = 0.0   # mean |LLR| of the 2·steps values the trellis is fed
    trace: dict = field(default_factory=dict)   # H, eq, llrs, mother-code llrs


def viterbi_numpy(llrs: np.ndarray, n_bits: int) -> np.ndarray:
    """The numpy trellis of ``coding.viterbi_decode``, float64, 64 states."""
    n_steps = min(len(llrs) // 2, n_bits)
    lam = np.asarray(llrs[:2 * n_steps], np.float64).reshape(n_steps, 2)
    ps, pb, b0, b1 = coding._PREV_S, coding._PREV_B, coding._BM0, coding._BM1
    rows = np.arange(len(ps))
    metrics = np.full(len(ps), -1e18)
    metrics[0] = 0.0
    pick = np.empty((n_steps, len(ps)), np.int64)
    for t in range(n_steps):
        cand = metrics[ps] + b0 * lam[t, 0] + b1 * lam[t, 1]
        pick[t] = np.argmax(cand, axis=1)
        metrics = cand[rows, pick[t]]
    state, out = 0, np.empty(n_steps, np.uint8)
    for t in range(n_steps - 1, -1, -1):
        out[t] = pb[state, pick[t, state]]
        state = ps[state, pick[t, state]]
    return out[:n_bits]


def _parse_signal(bits: np.ndarray) -> Optional[tuple]:
    """(rate index, LENGTH) of a valid SIGNAL field, else None."""
    if int(bits[:18].sum()) % 2:
        return None
    rate = sum(int(bits[i]) << (3 - i) for i in range(4))
    length = sum(int(bits[5 + i]) << i for i in range(12))
    if rate not in _RATE_BITS or length < 1:
        return None
    return _RATE_BITS[rate], length


_SEEDS = np.stack([coding._keystream(s)[:16] for s in range(1, 128)])


def _descramble(bits: np.ndarray) -> tuple:
    """Recover the seed from the 16 SERVICE bits (zeros before scrambling)
    and descramble: ``(bits, seed_ok)``; an unmatched prefix is descrambled
    with the default seed, as ``phy._finish_frame`` does."""
    match = np.nonzero((_SEEDS == bits[None, :16]).all(axis=1))[0]
    seed = int(match[0]) + 1 if len(match) else 0b1011101
    return coding.descramble(bits, seed), bool(len(match))


def receive_window(window: np.ndarray, emit_from: int = 0,
                   keep_trace: bool = False) -> tuple:
    """Decode one window. Returns ``(packets, counts)``: the packets this
    window's frame emits (those whose last sample has index ≥ ``emit_from``),
    in order of arrival, and the counts a record header carries (beside them,
    under ``candidates``, every detection's start and LTS start)."""
    w = np.asarray(window, np.complex128)
    n = len(w)
    padded = np.concatenate([w, np.zeros(SEARCH_LEN, np.complex128)])
    counts = {"detected": 0, "aligned": 0, "signal_ok": 0, "emitted": 0,
              "steps": 0, "candidates": []}
    out: List[RxPacket] = []
    claimed_to = -1
    for start in ofdm.detect_packets(w):
        counts["detected"] += 1
        _, lts, cfo = ofdm.sync_long(padded, start, SEARCH_LEN)
        counts["candidates"].append((start, lts))
        if lts + 128 + SYM_LEN > n:
            continue
        counts["aligned"] += 1
        head = padded[lts:lts + 128 + SYM_LEN] \
            * np.exp(-1j * cfo * np.arange(128 + SYM_LEN))
        H = ofdm.estimate_channel(head, 0)
        sig = ofdm.equalize(ofdm.ofdm_demodulate_symbols(head[128:], 1), H, 0)
        sig_llr = ofdm.demap_llrs(sig.reshape(-1), "bpsk")
        parsed = _parse_signal(
            viterbi_numpy(coding.deinterleave(sig_llr, 48, 1), 24))
        if parsed is None:
            continue
        counts["signal_ok"] += 1
        rate, length = parsed
        mcs = MCS_TABLE[RATES[rate]]
        steps = 16 + 8 * length + 6
        n_sym = -(-steps // mcs.n_dbps)
        end = lts + 128 + SYM_LEN * (1 + n_sym)
        if end > n or lts < claimed_to:
            continue
        counts["steps"] = max(counts["steps"], steps)
        off = 128 + SYM_LEN
        body = padded[lts + off:end] \
            * np.exp(-1j * cfo * (np.arange(n_sym * SYM_LEN) + off))
        eq = ofdm.equalize(ofdm.ofdm_demodulate_symbols(body, n_sym), H, 1)
        llrs = ofdm.demap_llrs(eq.reshape(-1), mcs.modulation)
        mother = coding.depuncture(
            coding.deinterleave(llrs, mcs.n_cbps, mcs.n_bpsc), mcs.coding_rate)
        bits, seed_ok = _descramble(viterbi_numpy(mother, steps))
        if not seed_ok:
            continue
        claimed_to = end
        if end <= emit_from:
            continue
        counts["emitted"] += 1
        psdu = np.packbits(bits[16:16 + 8 * length], bitorder="little").tobytes()
        out.append(RxPacket(lts, rate, length, float(cfo),
                            _lts_snr_db(head[:128]), True, psdu, end, steps,
                            float(np.abs(mother[:2 * steps]).mean()),
                            {"H": H, "sig_llr": sig_llr, "eq": eq, "llrs": llrs,
                             "mother": mother} if keep_trace else {}))
    return out, counts


def _lts_snr_db(lts: np.ndarray) -> float:
    """SNR from the two identical long symbols (``frame_equalizer.rs:64``):
    their difference is noise alone, their mean power signal plus noise."""
    noise = float(np.mean(np.abs(lts[:64] - lts[64:]) ** 2)) / 2 + 1e-20
    total = float(np.mean(np.abs(lts) ** 2))
    return 10.0 * math.log10(max(total - noise, 1e-20) / noise)


def receive_frame(x: np.ndarray, history: Optional[np.ndarray] = None,
                  carry_len: int = CARRY_LEN, keep_trace: bool = False) -> tuple:
    """``receive_window`` over (the last ``carry_len`` samples of ``history``,
    zeros where there is none) + ``x``; ``lts_start`` and ``end`` of the
    packets are made relative to the first sample of ``x``."""
    hist = np.zeros(carry_len, np.complex128)
    if history is not None and len(history):
        h = np.asarray(history)[-carry_len:]
        hist[carry_len - len(h):] = h
    packets, counts = receive_window(np.concatenate([hist, x]), carry_len,
                                     keep_trace)
    for p in packets:
        p.lts_start -= carry_len
        p.end -= carry_len
    return packets, counts
