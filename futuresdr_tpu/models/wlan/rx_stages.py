"""A whole 802.11a/g receiver as ONE fixed-shape device program per frame.

``TpuKernel(wlan_rx_stages(), np.complex64)`` is the receiver: samples in,
*records* out. Per frame, in one traced program with static shapes and no
host round trip: short-preamble detection over carry + frame, long-preamble
alignment and CFO, channel estimate and SIGNAL field for every candidate at
once, the data symbols of all packets as one flat batch (DFT-64 as a matmul,
equalise, pilot phase, max-log LLRs of the packet's own modulation),
deinterleave + depuncture, the K = 7 Viterbi over all packets at once
(``ops/viterbi.viterbi_blocks``: time cut into pieces, the pieces of all
packets side by side in two Pallas kernels, traceback on the device; the
SIGNAL field's 24 steps through ``viterbi_core``, candidates as lanes), seed
recovery, descrambling and byte packing.
How many packets a frame holds, their rates and lengths are data: ragged work
inside a fixed-shape program. ``models/wlan/reference.py`` is the same
receiver in numpy float64 and states the ownership rule both follow.

The output of a frame of ``n`` samples is ``n // 8`` int32 words (``ratio``
1/8), the first stage of ``ops/stages`` whose items are records:

======================  ====================================================
words                   content
======================  ====================================================
``0 … 15``              header: magic, detected, aligned, signal_ok, emitted,
                        overflow, steps of the longest lane, lanes decoded,
                        symbols demodulated, PSDU words used, lanes, trellis
                        pieces decoded (``ops/viterbi.viterbi_blocks``: the
                        passes, and so the Viterbi's time, follow it), 0 …
``16 … 16 + 8·lanes``   one 8-word entry per emitted packet, in order of
                        arrival: LTS start relative to the frame's first
                        sample (negative: it began in an earlier frame), rate
                        index (``reference.RATES``), LENGTH, CFO (float32
                        bits, rad/sample), LTS SNR (float32 bits, dB),
                        ``seed_ok``, word offset of its PSDU in the byte
                        area, mean |LLR| of the mother-code values its
                        trellis was fed (float32 bits)
the rest                the PSDUs, each padded to whole words, little-endian
======================  ====================================================

Nothing in a record depends on the stream position. Bytes are packed on the
device, not in the host block: decoded bits would be eight times the downlink.
``overflow`` counts what a capacity turned away (candidate slots, lanes,
symbols, LENGTH above ``max_psdu``, PSDU words); at the defaults the symbol
and word capacities are the standard's own bounds and only slots and lanes
(256 candidates and 128 packets per window; the benchmark's busy channel holds
45 on average and 66 at most) can fill. The mean |LLR| is there for the
receiver's keeper (a packet's soft values sag before its FCS fails) and for
whoever holds the program to a reference: it is the one number of an entry
that every matmul of the program passes through. Precision: float32
throughout; every matmul that stands in for an FFT, a correlation or a
permutation is ``Precision.HIGHEST``. The TPU's default rounds the operands
to bfloat16: the PSDUs of a busy channel at 15 dB and more still come out
right, the LLRs keep 8 bits and the mean |LLR| moves by about 1e-3 of
itself (``benchmark/tools/wlan_precision_control.py``: the control that the
benchmark's ``judge`` fails).
"""

from __future__ import annotations

from fractions import Fraction
from functools import reduce
from typing import List

import numpy as np

from ...ops.stages import Stage
from ...ops.viterbi import BLOCK, piece_slots, viterbi_blocks, viterbi_core
from . import coding
from .consts import (DATA_CARRIERS, FFT_SIZE, LTS_FREQ, MCS_TABLE,
                     MODULATION_TABLES, PILOT_CARRIERS, PILOT_POLARITY,
                     PILOT_VALUES, SYM_LEN, lts_time)
from .reference import CARRY_LEN, SEARCH_LEN

__all__ = ["wlan_rx_stages", "parse_records", "record_counters", "MAGIC",
           "HEADER_WORDS", "ENTRY_WORDS"]

MAGIC = 0x57_4C_41_4E          # "WLAN"
HEADER_WORDS = 16
ENTRY_WORDS = 8
_HEAD = 128 + SYM_LEN          # two long symbols + SIGNAL
_PAD = 1024                    # zeros beyond the window: every slice's rows fit
_PRECISION = "highest"         # of every matmul (the control patches it)
_THRESHOLD, _MIN_RUN = 0.56, 32    # the plateau detector's (ofdm.detect_packets)
_MCS = list(MCS_TABLE.values())
_N_DBPS = np.array([m.n_dbps for m in _MCS], np.int32)
_N_BPSC = np.array([m.n_bpsc for m in _MCS], np.int32)
_RATE_BITS = np.array([m.rate_bits for m in _MCS], np.int32)
_ROW = 2 * int(_N_DBPS.max())  # mother-code values of one symbol, at most
_USED = np.concatenate([DATA_CARRIERS, PILOT_CARRIERS]) % FFT_SIZE


def _dft_used() -> np.ndarray:
    """DFT-64 restricted to the 52 used bins (48 data, then 4 pilots) as a
    real ``[128, 104]`` matrix acting on ``[re | im]`` rows."""
    n = np.arange(FFT_SIZE)[:, None] * _USED[None, :]
    c, s = np.cos(2 * np.pi * n / FFT_SIZE), -np.sin(2 * np.pi * n / FFT_SIZE)
    return np.block([[c, s], [-s, c]]).astype(np.float32)


def _lts_toeplitz() -> np.ndarray:
    """``np.correlate(seg, lts_symbol, "valid")`` as ``[re | im] @ T``."""
    ref = lts_time()[96:160].astype(np.complex128)
    n_out = SEARCH_LEN - 63
    t = np.zeros((SEARCH_LEN, n_out), np.complex128)
    for j in range(64):
        t[np.arange(n_out) + j, np.arange(n_out)] = np.conj(ref[j])
    return np.block([[t.real, t.imag], [-t.imag, t.real]]).astype(np.float32)


def _depuncture_matrix() -> np.ndarray:
    """``[8·288, 432]`` one-hot: row ``m·288 + bit·48 + carrier`` of a symbol's
    LLRs (rate ``m``) to its place in the symbol's mother-code values, the
    FIRST outputs of the symbol's trellis steps in columns 0 … 215 and the
    SECOND in 216 … 431 (the two as planes, never interleaved: an array whose
    minor dimension is 2 costs the TPU 64 times its size); punctured places
    stay empty (an erasure is a zero LLR)."""
    out = np.zeros((len(_MCS), 48 * 6, _ROW), np.float32)
    for m, mcs in enumerate(_MCS):
        kept = np.nonzero(np.resize(coding._PUNCTURE[mcs.coding_rate],
                                    2 * mcs.n_dbps))[0]
        _, j = coding._interleaver_perms(mcs.n_cbps, mcs.n_bpsc)
        src = j[np.arange(mcs.n_cbps)]          # deinterleaved k reads vals[j[k]]
        out[m, (src % mcs.n_bpsc) * 48 + src // mcs.n_bpsc,
            kept % 2 * (_ROW // 2) + kept // 2] = 1.0
    return out.reshape(-1, _ROW)


def _pam_tables(modulation: str) -> tuple:
    """Per-axis levels and bit masks of a Gray constellation (the per-axis
    max-log form of ``jax_demod._compiled``): I bits are the low index bits."""
    table = MODULATION_TABLES[modulation]
    n_bpsc = int(np.log2(len(table)))
    n_i = (n_bpsc + 1) // 2
    n_q = n_bpsc - n_i
    lvl_i = table[np.arange(1 << n_i)].real.astype(np.float32)
    lvl_q = table[np.arange(1 << n_q) << n_i].imag.astype(np.float32)
    return n_i, n_q, lvl_i, lvl_q


def _scrambler_phases() -> tuple:
    """The 127 keystreams are one m-sequence at 127 phases: the sequence and
    the phase of each seed 1…127."""
    base = coding._keystream(1)
    twice = np.concatenate([base, base])
    phase = np.array([next(p for p in range(127)
                           if np.array_equal(twice[p:p + 127],
                                             coding._keystream(s)))
                      for s in range(1, 128)], np.int32)
    return base, phase


def record_counters(frame: np.ndarray) -> dict:
    """Seven counts of one landed record block's header (``Stage.counters``:
    what ``TpuKernel`` adds to the ``emit`` span while tracing)."""
    if len(frame) < HEADER_WORDS or int(frame[0]) != MAGIC:
        return {}
    h = frame[1:7]
    return {"wlan_detected": int(h[0]), "wlan_aligned": int(h[1]),
            "wlan_signal_ok": int(h[2]), "wlan_emitted": int(h[3]),
            "wlan_overflow": int(h[4]), "wlan_steps": int(h[5]),
            "wlan_pieces": int(frame[11])}


def parse_records(block: np.ndarray) -> tuple:
    """One record block → ``(header dict, [packet dict])`` on the host."""
    block = np.asarray(block, np.int32)
    head = dict(record_counters(block))
    if not head:
        return {}, []
    lanes = int(block[10])
    entries = block[HEADER_WORDS:HEADER_WORDS + ENTRY_WORDS * lanes] \
        .reshape(lanes, ENTRY_WORDS)
    area = block[HEADER_WORDS + ENTRY_WORDS * lanes:].view(np.uint8)
    packets = []
    for e in entries[:head["wlan_emitted"]]:
        length, off = int(e[2]), 4 * int(e[6])
        packets.append({
            "lts_start": int(e[0]), "rate": int(e[1]), "length": length,
            "cfo": float(e[3:4].view(np.float32)[0]),
            "snr_db": float(e[4:5].view(np.float32)[0]),
            "seed_ok": bool(e[5]), "steps": 16 + 8 * length + 6,
            "llr_mean": float(e[7:8].view(np.float32)[0]),
            "psdu": area[off:off + length].tobytes()})
    return head, packets


def wlan_rx_stages(carry_len: int = CARRY_LEN, max_psdu: int = 4095,
                   cand_slots: int = 256, lanes: int = 128) -> List[Stage]:
    """The receiver as a one-stage pipeline (see the module docstring).

    ``carry_len``: samples kept from the previous frame, at least the longest
    packet ``max_psdu`` allows plus its preamble; ``cand_slots`` detections and
    ``lanes`` decoded packets per window, more are counted as overflow (the
    four are sizes: tests and rehearsals shrink them). The data trellis is
    cut into pieces of 1024 steps with 128 steps of run-in and run-out each,
    decoded 1024 pieces a pass (``ops/viterbi.viterbi_blocks``; on the v5e
    31.6 Msps against 10.4 uncut, one lane a packet, and 17.3 with each pass a
    loop of XLA ops: ``PERF.md`` section 6). The stage's ``fn.probe`` is the
    same trace returning ``(carry, records, taps)``: candidate starts, LTS
    starts, ``H``, equalised symbols and LLRs, for the tests and the smoke
    that compare step by step with the float64 reference.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    HI = _PRECISION
    C, L = int(cand_slots), int(lanes)
    CL = int(carry_len)
    T = 16 + 8 * int(max_psdu) + 6                 # trellis steps, at most
    R = -(-T // int(_N_DBPS.min()))                # symbols of one packet
    assert CL >= 320 + SYM_LEN * (1 + R), "carry shorter than the longest packet"
    BW = -(-int(max_psdu) // 4)                    # PSDU words of one packet
    dft = _dft_used()
    toep = _lts_toeplitz()
    depunct = _depuncture_matrix()
    ks_base, ks_phase = _scrambler_phases()
    seeds16 = np.stack([coding._keystream(s)[:16] for s in range(1, 128)])
    lts_ref = np.real(np.where(LTS_FREQ != 0, LTS_FREQ, 1.0))  # -26..26
    ref_used = np.array([lts_ref[k + 26] for k in
                         np.concatenate([DATA_CARRIERS, PILOT_CARRIERS])],
                        np.float32)
    sig_perm = coding._interleaver_perms(48, 1)[1]
    pam = {m: _pam_tables(m) for m in ("bpsk", "qpsk", "qam16", "qam64")}
    tables = (coding._PREV_S, coding._PREV_B, coding._BM0, coding._BM1)

    def slide48(a):
        """out[k] = a[k] + … + a[k+47], as a fixed tree of float32 adds (a
        running sum over 370 000 samples would lose the quiet windows)."""
        s = a
        for sh in (1, 2, 4, 8):
            s = s[:-sh] + s[sh:]
        s32 = s[:-16] + s[16:]
        n = a.shape[0] - 47
        return s32[:n] + s[32:32 + n]

    def rows_at(planes, starts, width):
        """``planes[:, s : s + width]`` for every ``s``: ``(re [n, width], im
        [n, width])``. The planes are read as rows of 128 samples: the rows
        that hold a slice are gathered whole, then turned left by ``s % 128``
        in seven conditional rolls. (A batch of ``dynamic_slice`` is a loop on
        the TPU compiler, one trip and several device events a slice: 6.1 ms
        for a window's 4664 symbols against 1.1 so; my chip run, PR 26.)"""
        k = -(-(width + 127) // 128)               # rows that hold any slice
        at = starts[:, None] // 128 + jnp.arange(k, dtype=jnp.int32)[None, :]
        rows = planes.reshape(2, -1, 128)
        got = jnp.take(rows, jnp.minimum(at, rows.shape[1] - 1), axis=1)
        got = got.reshape(2, starts.shape[0], k * 128)
        for b in range(7):
            turn = ((starts >> b) & 1)[None, :, None] == 1
            got = jnp.where(turn, jnp.roll(got, -(1 << b), axis=2), got)
        return got[0, :, :width], got[1, :, :width]

    def nth_true(mask, n_slots):
        """Indices of the first ``n_slots`` set entries (len(mask) if fewer)
        and how many are set."""
        rank = jnp.cumsum(mask.astype(jnp.int32))
        idx = jnp.searchsorted(rank, jnp.arange(1, n_slots + 1, dtype=jnp.int32),
                               side="left")
        return idx.astype(jnp.int32), rank[-1]

    def pam_llrs(v, levels, n_bits):
        """Max-log LLR of each bit of one Gray PAM axis: best level with the
        bit set against best level with it clear."""
        d = [-(v - float(lv)) ** 2 for lv in levels]
        best = lambda ds: reduce(jnp.maximum, ds)
        return [best([d[i] for i in range(len(d)) if (i >> b) & 1])
                - best([d[i] for i in range(len(d)) if not (i >> b) & 1])
                for b in range(n_bits)]

    def equalise(yr, yi, Hr, Hi, pol, n_bpsc):
        """LS equalisation, pilot phase, max-log LLRs. ``yr/yi`` ``[·, 52]``
        used bins (48 data, 4 pilots); ``n_bpsc`` per row or a modulation
        name. Returns LLRs ``[·, 288]`` (bit·48 + carrier; unused bits 0) and
        the equalised, phase-corrected data carriers."""
        d = Hr * Hr + Hi * Hi
        d = jnp.where(d > 0, d, 1.0)
        er, ei = (yr * Hr + yi * Hi) / d, (yi * Hr - yr * Hi) / d
        want = jnp.asarray(PILOT_VALUES, jnp.float32)[None, :] * pol
        zr, zi = jnp.sum(er[:, 48:] * want, axis=1), jnp.sum(ei[:, 48:] * want, axis=1)
        nz = jnp.sqrt(zr * zr + zi * zi)
        nz = jnp.where(nz > 0, nz, 1.0)[:, None]
        zr, zi = zr[:, None] / nz, zi[:, None] / nz
        dr = er[:, :48] * zr + ei[:, :48] * zi
        di = ei[:, :48] * zr - er[:, :48] * zi
        zero = jnp.zeros_like(dr)
        out = None
        for name, nb in (("bpsk", 1), ("qpsk", 2), ("qam16", 4), ("qam64", 6)):
            if isinstance(n_bpsc, str) and n_bpsc != name:
                continue
            n_i, n_q, lvl_i, lvl_q = pam[name]
            cols = pam_llrs(dr, lvl_i, n_i) + pam_llrs(di, lvl_q, n_q)
            cols = jnp.concatenate(cols + [zero] * (6 - nb), axis=1)
            out = cols if isinstance(n_bpsc, str) or out is None else \
                jnp.where((n_bpsc == nb)[:, None], cols, out)
        return out, dr, di

    def probe(carry, x):
        n = x.shape[0]
        W = CL + n
        NW = n // 8
        NB = NW - HEADER_WORDS - ENTRY_WORDS * L   # words of the byte area
        SMAX = -(-(W // SYM_LEN) // 8) * 8         # symbols a window can hold
        # the byte area holds what a window can carry at 54 Mbit/s (27 bytes
        # a symbol) plus one packet begun earlier: it cannot overflow
        assert 4 * NB >= 27 * (n // SYM_LEN + 1) + max_psdu + 4 * L, \
            f"frame of {n} samples: record block too small for {L} lanes"
        wr = jnp.concatenate([carry[0], jnp.real(x)])
        wi = jnp.concatenate([carry[1], jnp.imag(x)])
        new_carry = jnp.stack([wr[-CL:], wi[-CL:]])
        padded = jnp.pad(jnp.stack([wr, wi]), ((0, 0), (0, _PAD + -W % 128)))
        dft_c = jnp.asarray(dft)                   # one constant, three uses

        # -- sync_short: ofdm.detect_packets ----------------------------------
        with jax.named_scope("sync_short"):
            ar = slide48(wr[:-16] * wr[16:] + wi[:-16] * wi[16:])[1:]
            ai = slide48(wi[:-16] * wr[16:] - wr[:-16] * wi[16:])[1:]
            p = slide48(wr * wr + wi * wi)[1:W - 63]
            M = W - 64
            above = (jnp.sqrt(ar * ar + ai * ai)
                     > _THRESHOLD * jnp.maximum(p, 1e-12)) & (p > 1e-4 * jnp.max(p))
            gaps = jnp.cumsum((~above).astype(jnp.int32))
            first = above & ~jnp.concatenate([jnp.zeros(1, bool), above[:-1]])
            ahead = jnp.concatenate(
                [gaps[_MIN_RUN - 1:], jnp.full(_MIN_RUN - 1, -1, jnp.int32)])
            run_s, n_runs = nth_true(first & (ahead == gaps), C)
            run_ok = jnp.arange(C) < n_runs
            run_e = jnp.searchsorted(gaps, gaps[jnp.minimum(run_s, M - 1)] + 1,
                                     side="left").astype(jnp.int32)

            def accept(skip, r):
                s, e, ok = r
                s = jnp.maximum(s, skip)
                ok = ok & (e - s >= _MIN_RUN)
                return jnp.where(ok, e + 160, skip), (s, ok)

            _, (start, cand) = lax.scan(accept, jnp.int32(-1),
                                        (run_s, run_e, run_ok))
            start = jnp.where(cand, start, 0)
            overflow = jnp.maximum(n_runs - C, 0)

        # -- sync_long: ofdm.sync_long ----------------------------------------
        with jax.named_scope("sync_long"):
            seg_r, seg_i = rows_at(padded, start, SEARCH_LEN)
            corr = jnp.matmul(jnp.concatenate([seg_r, seg_i], axis=1),
                              jnp.asarray(toep), precision=HI)
            nc = SEARCH_LEN - 63
            mag = jnp.sqrt(corr[:, :nc] ** 2 + corr[:, nc:] ** 2)
            k = jnp.arange(nc)[None, :]

            def at(a, idx):
                return jnp.take_along_axis(
                    a, jnp.clip(idx, 0, a.shape[1] - 1)[:, None], axis=1)[:, 0]

            p1 = jnp.argmax(mag, axis=1).astype(jnp.int32)
            p2 = jnp.argmax(jnp.where((k >= p1[:, None] - 8) & (k < p1[:, None] + 8),
                                      0.0, mag), axis=1).astype(jnp.int32)
            lo, hi = jnp.minimum(p1, p2), jnp.maximum(p1, p2)
            back = (p1 >= 64) & (at(mag, p1 - 64) > 0.5 * at(mag, p1))
            alone = jnp.where(back, p1 - 64, p1)
            paired = hi - lo == 64
            lo = jnp.where(paired, lo, alone)
            hi = jnp.where(paired, hi, alone + 64)
            going = jnp.ones(C, bool)
            for _ in range(nc // 64):              # the CP-ghost guard
                going = going & (hi + 64 < nc) & \
                    (at(mag, hi + 64) > 0.8 * jnp.maximum(at(mag, lo), 1e-12))
                lo, hi = jnp.where(going, hi, lo), jnp.where(going, hi + 64, hi)
            m64 = jnp.arange(64)[None, :]
            a_r = jnp.take_along_axis(seg_r, lo[:, None] + m64, axis=1)
            a_i = jnp.take_along_axis(seg_i, lo[:, None] + m64, axis=1)
            b_r = jnp.take_along_axis(seg_r, hi[:, None] + m64, axis=1)
            b_i = jnp.take_along_axis(seg_i, hi[:, None] + m64, axis=1)
            cfo = jnp.arctan2(jnp.sum(a_r * b_i - a_i * b_r, axis=1),
                              jnp.sum(a_r * b_r + a_i * b_i, axis=1)) / 64.0
            lts = start + lo
            aligned = cand & (lts + _HEAD <= W)

        # -- signal: channel estimate, SIGNAL symbol, 24-step Viterbi ----------
        with jax.named_scope("signal"):
            lts_c = jnp.where(aligned, lts, 0)
            ph = -cfo[:, None] * jnp.arange(_HEAD, dtype=jnp.float32)[None, :]
            hr0, hi0 = rows_at(padded, lts_c, _HEAD)
            hr = hr0 * jnp.cos(ph) - hi0 * jnp.sin(ph)
            hi_ = hr0 * jnp.sin(ph) + hi0 * jnp.cos(ph)
            dn = jnp.sum((hr[:, :64] - hr[:, 64:128]) ** 2
                         + (hi_[:, :64] - hi_[:, 64:128]) ** 2, axis=1) / 128 + 1e-20
            tot = jnp.sum(hr[:, :128] ** 2 + hi_[:, :128] ** 2, axis=1) / 128
            snr_db = 10.0 * jnp.log10(jnp.maximum(tot - dn, 1e-20) / dn)
            mean = jnp.concatenate([hr[:, :64] + hr[:, 64:128],
                                    hi_[:, :64] + hi_[:, 64:128]], axis=1) * 0.5
            Hf = jnp.matmul(mean, dft_c, precision=HI)
            Hr, Hi = Hf[:, :52] * ref_used, Hf[:, 52:] * ref_used
            sf = jnp.matmul(jnp.concatenate([hr[:, 144:], hi_[:, 144:]], axis=1),
                            dft_c, precision=HI)
            sig_llr, _, _ = equalise(sf[:, :52], sf[:, 52:], Hr, Hi,
                                     jnp.ones((C, 1), jnp.float32), "bpsk")
            sig_llr = sig_llr[:, :48]
            sig_in = sig_llr[:, sig_perm].reshape(C, 24, 2)
            sb = viterbi_core(jnp.transpose(sig_in, (1, 2, 0)),
                              jnp.full(C, 24, jnp.int32),
                              *tables).astype(jnp.int32)          # [24, C]
            parity = jnp.sum(sb[:18], axis=0) % 2 == 0
            code = sb[0] * 8 + sb[1] * 4 + sb[2] * 2 + sb[3]
            is_rate = code[:, None] == jnp.asarray(_RATE_BITS)[None, :]   # [C, 8]
            length = jnp.sum(sb[5:17] << jnp.arange(12)[:, None], axis=0)
            signal_ok = aligned & parity & jnp.any(is_rate, axis=1) & (length >= 1)
            rate = jnp.argmax(is_rate, axis=1).astype(jnp.int32)
            n_dbps = jnp.sum(is_rate * jnp.asarray(_N_DBPS)[None, :], axis=1)
            steps = 22 + 8 * length
            n_sym = (steps + jnp.maximum(n_dbps, 1) - 1) // jnp.maximum(n_dbps, 1)
            end = lts + _HEAD + SYM_LEN * n_sym
            whole = signal_ok & (end <= W)
            fits_len = length <= max_psdu
            want = whole & fits_len
            # a second detection of one packet decodes to the same bits:
            # whatever the first one's fate, the rule drops the second
            same = (lts[:, None] == lts[None, :]) & want[None, :] & \
                (jnp.arange(C)[None, :] < jnp.arange(C)[:, None])
            want = want & ~jnp.any(same, axis=1)
            sym_to = jnp.cumsum(jnp.where(want, n_sym, 0))
            lane_of = jnp.cumsum(want.astype(jnp.int32)) - 1
            decode = want & (lane_of < L) & (sym_to <= SMAX)
            overflow = overflow + jnp.sum((whole & ~fits_len) | (want & ~decode))
            slot, n_lanes = nth_true(decode, L)
            lane_ok = jnp.arange(L) < n_lanes
            slot = jnp.where(lane_ok, slot, 0)

            def lane(a):
                return jnp.take(a, slot, axis=0)

            l_lts, l_cfo, l_rate = lane(lts_c), lane(cfo), lane(rate)
            l_steps = jnp.where(lane_ok, lane(steps), 0)
            l_nsym = jnp.where(lane_ok, lane(n_sym), 0)
            l_len = jnp.where(lane_ok, lane(length), 0)
            l_Hr, l_Hi = lane(Hr), lane(Hi)

        # -- demod: every data symbol of every packet, one flat batch ----------
        with jax.named_scope("demod"):
            l_to = jnp.cumsum(l_nsym)                              # [L]
            i = jnp.arange(SMAX, dtype=jnp.int32)
            s_lane = jnp.minimum(jnp.searchsorted(
                l_to, i, side="right", method="compare_all"), L - 1).astype(jnp.int32)
            s_ok = i < l_to[-1]
            s_k = i - (jnp.take(l_to, s_lane) - jnp.take(l_nsym, s_lane))
            rel = _HEAD + SYM_LEN * s_k + 16                       # from the LTS
            pos = jnp.where(s_ok, jnp.take(l_lts, s_lane) + rel, 0)
            ph = -jnp.take(l_cfo, s_lane)[:, None] * \
                (rel[:, None] + jnp.arange(64)[None, :]).astype(jnp.float32)
            yr0, yi0 = rows_at(padded, pos, 64)
            yf = jnp.matmul(jnp.concatenate(
                [yr0 * jnp.cos(ph) - yi0 * jnp.sin(ph),
                 yr0 * jnp.sin(ph) + yi0 * jnp.cos(ph)], axis=1),
                dft_c, precision=HI)
            pol = jnp.take(jnp.asarray(PILOT_POLARITY, jnp.float32),
                           (1 + s_k) % len(PILOT_POLARITY))
            s_rate = jnp.take(l_rate, s_lane)
            llr, eq_r, eq_i = equalise(
                yf[:, :52], yf[:, 52:], jnp.take(l_Hr, s_lane, axis=0),
                jnp.take(l_Hi, s_lane, axis=0), pol[:, None],
                jnp.take(jnp.asarray(_N_BPSC), s_rate))
            llr = jnp.where(s_ok[:, None], llr, 0.0)               # [SMAX, 288]

        # -- deint_depunct: to the mother code, one row per symbol -------------
        with jax.named_scope("deint_depunct"):
            by_rate = (llr[:, None, :] * (s_rate[:, None] == jnp.arange(8)[None, :])
                       [:, :, None]).reshape(SMAX, -1)
            rows = jnp.matmul(by_rate, jnp.asarray(depunct), precision=HI)
            # a packet's rows laid end to end: per rate and plane, the rows
            # cut to the rate's width and flattened; a packet's stream is then
            # ONE slice a plane, from its first symbol, of the row of its rate
            at = (l_to - l_nsym) * jnp.take(jnp.asarray(_N_DBPS), l_rate)
            stream = jnp.stack([jax.vmap(lambda m, o: lax.dynamic_slice(
                jnp.stack([jnp.pad(
                    rows[:, j * _ROW // 2:j * _ROW // 2 + mcs.n_dbps].reshape(-1),
                    (0, SMAX * (_ROW // 2 - mcs.n_dbps) + T)) for mcs in _MCS]),
                (m, o), (1, T))[0])(l_rate, at) for j in (0, 1)])   # [2, L, T]
            fed = jnp.arange(T, dtype=jnp.int32)[None, :] < l_steps[:, None]
            l_llr = jnp.sum(jnp.where(fed[None], jnp.abs(stream), 0.0), axis=(0, 2)) \
                / jnp.maximum(2 * l_steps, 1).astype(jnp.float32)

        # piece slots for all packets: a window's symbols carry at most 216
        # steps each, and each packet ends in one partial piece
        n_pieces = piece_slots(-(-SMAX * int(_N_DBPS.max()) // BLOCK) + L)
        bits = viterbi_blocks(stream, l_steps, *tables,
                              n_blocks=n_pieces).T                  # [T, L]

        # -- pack: seed, descramble, bytes, ownership, records -----------------
        with jax.named_scope("pack"):
            hit = jnp.all(bits[:16].T[:, None, :] == jnp.asarray(seeds16)[None],
                          axis=2)                                  # [L, 127]
            seed_ok = jnp.any(hit, axis=1) & lane_ok
            seed = jnp.where(seed_ok, jnp.argmax(hit, axis=1), 0b1011101 - 1)
            ks = jnp.asarray(np.resize(ks_base, T + 127))
            key = jax.vmap(lambda p_: lax.dynamic_slice(ks, (p_,), (T,)))(
                jnp.take(jnp.asarray(ks_phase), seed)).T           # [T, L]
            # time stays the major axis: bits → bytes → words are reshapes
            plain = (bits ^ key)[16:16 + 8 * max_psdu].astype(jnp.uint32)
            byte = jnp.sum(plain.reshape(max_psdu, 8, L)
                           << jnp.arange(8, dtype=jnp.uint32)[None, :, None], axis=1)
            byte = jnp.where(jnp.arange(max_psdu)[:, None] < l_len[None, :], byte, 0)
            byte = jnp.pad(byte, ((0, 4 * BW - max_psdu), (0, 0))).reshape(BW, 4, L)
            words = jnp.sum(byte << (8 * jnp.arange(4, dtype=jnp.uint32))[None, :, None],
                            axis=1, dtype=jnp.uint32).T            # [L, BW]

            c_seed = decode & jnp.take(seed_ok, jnp.clip(lane_of, 0, L - 1))

            def claim(to, r):
                s, e, ok = r
                ok = ok & (s >= to)
                return jnp.where(ok, e, to), ok

            _, kept = lax.scan(claim, jnp.int32(-1), (lts, end, c_seed))
            emit = kept & (end > CL)
            nwords = (length + 3) // 4
            w_to = jnp.cumsum(jnp.where(emit, nwords, 0))
            room = emit & (w_to <= NB)
            overflow = overflow + jnp.sum(emit & ~room)
            e_slot, n_emit = nth_true(room, L)
            e_ok = jnp.arange(L) < n_emit
            e_slot = jnp.where(e_ok, e_slot, 0)

            def rec(a):
                return jnp.where(e_ok, jnp.take(a, e_slot), 0)

            e_words, e_to = rec(nwords), jnp.where(e_ok, jnp.take(w_to, e_slot), 0)
            e_to = jnp.where(e_ok, e_to, jnp.max(e_to))
            e_lane = jnp.clip(jnp.take(lane_of, e_slot), 0, L - 1)
            f32 = lambda a: lax.bitcast_convert_type(a.astype(jnp.float32), jnp.int32)
            entries = jnp.stack(
                [rec(lts - CL), rec(rate), rec(length), rec(f32(cfo)),
                 rec(f32(snr_db)), e_ok.astype(jnp.int32), rec(w_to - nwords),
                 jnp.where(e_ok, jnp.take(f32(l_llr), e_lane), 0)], axis=1)
            q = jnp.arange(NB, dtype=jnp.int32)
            q_rec = jnp.minimum(jnp.searchsorted(
                e_to, q, side="right", method="compare_all"), L - 1).astype(jnp.int32)
            q_in = q - (jnp.take(e_to, q_rec) - jnp.take(e_words, q_rec))
            area = jnp.take(words.reshape(-1),
                            jnp.take(e_lane, q_rec) * BW + jnp.clip(q_in, 0, BW - 1))
            area = jnp.where(q < e_to[-1], area, 0)
            header = jnp.zeros(HEADER_WORDS, jnp.int32).at[:12].set(jnp.stack([
                jnp.int32(MAGIC), jnp.sum(cand), jnp.sum(aligned),
                jnp.sum(signal_ok), n_emit, overflow, jnp.max(l_steps), n_lanes,
                l_to[-1], e_to[-1], jnp.int32(L),
                jnp.sum((l_steps + BLOCK - 1) // BLOCK)]).astype(jnp.int32))
            out = jnp.concatenate([
                header, entries.reshape(-1),
                lax.bitcast_convert_type(area, jnp.int32)])
        taps = dict(start=start, cand=cand, lts=lts, aligned=aligned, Hr=Hr,
                    Hi=Hi, sig_llr=sig_llr, slot=slot, lane_ok=lane_ok,
                    sym_ok=s_ok, eq_r=eq_r, eq_i=eq_i, llr=llr,
                    lane_rate=l_rate, lane_to=l_to)
        return new_carry, out, taps

    def fn(carry, x):
        return probe(carry, x)[:2]

    fn.probe = probe

    def init_carry(dtype):
        return jnp.zeros((2, CL), jnp.float32)

    return [Stage(fn, init_carry, ratio=Fraction(1, 8), out_dtype=np.int32,
                  frame_multiple=8, name="wlan_rx", counters=record_counters)]
