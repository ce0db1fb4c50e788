"""The benchmark's own 802.11a/g transmitter, float64 receiver and record
reader (IEEE 802.11-2020 clause 17), restated here from the standard and
from ``futuresdr_tpu/models/wlan`` (``consts.py``, ``coding.py``, ``ofdm.py``,
``phy.py``, ``mac.py``, ``reference.py``; PR 26) so that no later PR can move
them: nothing here imports the program. numpy float64, no jit, no native
library.

The receiver follows ``models/wlan/reference.py`` rule for rule (window =
the last ``carry_len`` samples of the previous frame + this frame; detection,
LTS alignment, SIGNAL; a packet whose seed was recovered claims its span, in
order of arrival, worked out anew in every window; a packet is emitted by
the frame in which its last sample lies). One difference of form: the
trellis runs over all packets of a window at once (numpy arrays with a packet
axis, the same float64 additions and comparisons per packet), because 24
windows of 45 packets one Python step at a time would take minutes.

Departures from gr-ieee802-11 / upstream ``examples/wlan``: LS equaliser
only; CFO from the two long symbols alone (|CFO| < 156 kHz at 20 Msps), no
STS coarse CFO; the plateau detector's power floor is relative to the
window's maximum.
"""

from __future__ import annotations

import math
import struct
import zlib
from typing import List

import numpy as np

FFT, CP, SYM = 64, 16, 80
SEARCH_LEN = 320 + 224
PILOTS = np.array([-21, -7, 7, 21])
DATA = np.array([k for k in range(-26, 27) if k != 0 and k not in PILOTS])
PILOT_VALUES = np.array([1.0, 1.0, 1.0, -1.0])
PILOT_POLARITY = np.array([
    1, 1, 1, 1, -1, -1, -1, 1, -1, -1, -1, -1, 1, 1, -1, 1, -1, -1, 1, 1, -1, 1, 1, -1,
    1, 1, 1, 1, 1, 1, -1, 1, 1, 1, -1, 1, 1, -1, -1, 1, 1, 1, -1, 1, -1, -1, -1, 1, -1, 1,
    -1, -1, 1, -1, -1, 1, 1, 1, 1, 1, -1, -1, 1, 1, -1, -1, 1, -1, 1, -1, 1, 1, -1, -1, -1,
    1, 1, -1, -1, -1, -1, 1, -1, -1, 1, -1, 1, 1, 1, 1, -1, 1, -1, 1, -1, 1, -1, -1, -1,
    -1, -1, 1, -1, 1, 1, -1, 1, -1, 1, 1, 1, -1, -1, 1, -1, -1, -1, 1, 1, 1, -1, -1, -1,
    -1, -1, -1, -1])
LTS_FREQ = np.array([
    1, 1, -1, -1, 1, 1, -1, 1, -1, 1, 1, 1, 1, 1, 1, -1, -1, 1, 1, -1, 1, -1, 1, 1, 1, 1,
    0,
    1, -1, -1, 1, 1, -1, 1, -1, 1, -1, -1, -1, -1, -1, 1, 1, -1, -1, 1, -1, 1, -1, 1, 1, 1,
    1], np.float64)
_STS = np.zeros(53, np.complex128)
for _k, _s in {-24: 1, -20: -1, -16: 1, -12: -1, -8: -1, -4: -1,
               4: -1, 8: -1, 12: 1, 16: 1, 20: 1, 24: 1}.items():
    _STS[_k + 26] = math.sqrt(13.0 / 6.0) * _s * (1 + 1j)

#: name, modulation, coded bits per carrier, coding rate, RATE bits
RATES = (("bpsk_1_2", "bpsk", 1, "1/2", 0b1101), ("bpsk_3_4", "bpsk", 1, "3/4", 0b1111),
         ("qpsk_1_2", "qpsk", 2, "1/2", 0b0101), ("qpsk_3_4", "qpsk", 2, "3/4", 0b0111),
         ("qam16_1_2", "qam16", 4, "1/2", 0b1001), ("qam16_3_4", "qam16", 4, "3/4", 0b1011),
         ("qam64_2_3", "qam64", 6, "2/3", 0b0001), ("qam64_3_4", "qam64", 6, "3/4", 0b0011))
RATE_NAMES = tuple(r[0] for r in RATES)
_PUNCTURE = {"1/2": np.array([1, 1], bool), "2/3": np.array([1, 1, 1, 0], bool),
             "3/4": np.array([1, 1, 1, 0, 0, 1], bool)}


def n_dbps(rate: int) -> int:
    num, den = map(int, RATES[rate][3].split("/"))
    return 48 * RATES[rate][2] * num // den


def n_symbols(rate: int, length: int) -> int:
    return -(-(16 + 8 * length + 6) // n_dbps(rate))


def packet_samples(rate: int, length: int) -> int:
    return 320 + SYM * (1 + n_symbols(rate, length))


# -- constellations (17.3.5.8), Gray-coded, bits LSB first -----------------------

def _table(mod: str) -> np.ndarray:
    if mod == "bpsk":
        return np.array([-1.0, 1.0], np.complex128)
    bits = {"qpsk": 1, "qam16": 2, "qam64": 3}[mod]
    lvl = {1: np.array([-1, 1]) / math.sqrt(2),
           2: np.array([-3, -1, 3, 1]) / math.sqrt(10),
           3: np.array([-7, -5, -1, -3, 7, 5, 1, 3]) / math.sqrt(42)}[bits]
    idx = np.arange(1 << (2 * bits))
    return lvl[idx & ((1 << bits) - 1)] + 1j * lvl[idx >> bits]


_TABLES = {m: _table(m) for m in ("bpsk", "qpsk", "qam16", "qam64")}


def _to_grid(freq_m26_26: np.ndarray) -> np.ndarray:
    spec = np.zeros(FFT, np.complex128)
    spec[np.arange(-26, 27) % FFT] = freq_m26_26
    return spec


_LTS_SYM = np.fft.ifft(_to_grid(LTS_FREQ))
PREAMBLE = np.concatenate([np.tile(np.fft.ifft(_to_grid(_STS))[:16], 10),
                           _LTS_SYM[-32:], _LTS_SYM, _LTS_SYM])


# -- bit plane: scrambler, K = 7 code, puncturing, interleaver --------------------

def keystream(seed: int) -> np.ndarray:
    out, state = np.empty(127, np.uint8), seed & 0x7F
    for i in range(127):
        fb = ((state >> 6) ^ (state >> 3)) & 1
        out[i] = fb
        state = ((state << 1) | fb) & 0x7F
    return out


_KEYS = np.stack([keystream(s) for s in range(128)])


def scramble(bits: np.ndarray, seed: int) -> np.ndarray:
    return (bits ^ np.resize(_KEYS[seed], len(bits))).astype(np.uint8)


_G = [np.array([(g >> (6 - j)) & 1 for j in range(7)], np.uint8) for g in (0o133, 0o171)]


def conv_encode(bits: np.ndarray) -> np.ndarray:
    out = np.empty(2 * len(bits), np.uint8)
    out[0::2] = np.convolve(bits, _G[0])[:len(bits)] & 1
    out[1::2] = np.convolve(bits, _G[1])[:len(bits)] & 1
    return out


def _perm(n_cbps: int, n_bpsc: int) -> np.ndarray:
    """``j[k]``: where the interleaver puts coded bit ``k`` of a symbol."""
    s = max(n_bpsc // 2, 1)
    k = np.arange(n_cbps)
    i = (n_cbps // 16) * (k % 16) + k // 16
    return s * (i // s) + (i + n_cbps - (16 * i // n_cbps)) % s


def _trellis():
    nxt = lambda s, b: ((b << 6) | s) >> 1
    out = lambda s, b, g: bin(((b << 6) | s) & g).count("1") & 1
    prev = [[] for _ in range(64)]
    for s in range(64):
        for b in range(2):
            prev[nxt(s, b)].append((s, b))
    ps = np.array([[p[0][0], p[1][0]] for p in prev])
    pb = np.array([[p[0][1], p[1][1]] for p in prev])
    o0 = np.array([[out(s, b, 0o133) for b in range(2)] for s in range(64)]) * 2.0 - 1
    o1 = np.array([[out(s, b, 0o171) for b in range(2)] for s in range(64)]) * 2.0 - 1
    return ps, pb, o0[ps, pb], o1[ps, pb]


_PS, _PB, _BM0, _BM1 = _trellis()


def viterbi_batch(llrs: List[np.ndarray], steps: List[int]) -> List[np.ndarray]:
    """Soft Viterbi over the rate-1/2 mother code, each packet traced back
    from state 0 at its own last step: ``coding.viterbi_decode``'s numpy
    trellis with a packet axis."""
    P, T = len(llrs), max(steps, default=0)
    lam = np.zeros((T, P, 2))
    for i, (l, n) in enumerate(zip(llrs, steps)):
        lam[:n, i] = np.asarray(l[:2 * n], np.float64).reshape(n, 2)
    metrics = np.full((P, 64), -1e18)
    metrics[:, 0] = 0.0
    pick = np.empty((T, P, 64), np.int8)
    for t in range(T):
        cand = metrics[:, _PS] + _BM0 * lam[t, :, 0, None, None] \
            + _BM1 * lam[t, :, 1, None, None]
        pick[t] = cand[..., 1] > cand[..., 0]            # a tie keeps 0 (argmax)
        metrics = np.maximum(cand[..., 0], cand[..., 1])
    n, rows = np.asarray(steps), np.arange(P)
    state, bits = np.zeros(P, np.int64), np.zeros((T, P), np.uint8)
    for t in range(T - 1, -1, -1):                       # every packet at once
        d = pick[t, rows, state]
        bits[t] = np.where(t < n, _PB[state, d], 0)
        state = np.where(t < n, _PS[state, d], state)
    return [bits[:k, i] for i, k in enumerate(steps)]


# -- MAC framing -----------------------------------------------------------------

def mpdu(payload: bytes, seq: int) -> bytes:
    """A data MPDU with a good FCS (``mac.mpdu_from_payload``)."""
    hdr = struct.pack("<HH", 0x0008, 0) + b"\x42" * 6 + b"\x23" * 6 + b"\xff" * 6 \
        + struct.pack("<H", (seq & 0xFFF) << 4)
    return hdr + payload + struct.pack("<I", zlib.crc32(hdr + payload) & 0xFFFFFFFF)


def fcs_ok(psdu: bytes) -> bool:
    return len(psdu) >= 28 and \
        struct.pack("<I", zlib.crc32(psdu[:-4]) & 0xFFFFFFFF) == psdu[-4:]


# -- transmitter -------------------------------------------------------------------

def _ofdm(points: np.ndarray, first_symbol: int) -> np.ndarray:
    n = len(points)
    pol = PILOT_POLARITY[(first_symbol + np.arange(n)) % 127]
    spec = np.zeros((n, FFT), np.complex128)
    spec[:, DATA % FFT] = points
    spec[:, PILOTS % FFT] = PILOT_VALUES[None, :] * pol[:, None]
    t = np.fft.ifft(spec, axis=1)
    return np.concatenate([t[:, -CP:], t], axis=1).reshape(-1)


def _map(bits: np.ndarray, mod: str) -> np.ndarray:
    nb = int(math.log2(len(_TABLES[mod])))
    return _TABLES[mod][(bits.reshape(-1, nb) << np.arange(nb)).sum(axis=1)]


def transmit(psdu: bytes, rate: int, seed: int) -> np.ndarray:
    """PSDU → baseband packet: preamble, SIGNAL, data symbols (17.3.2)."""
    _, mod, n_bpsc, cr, rate_bits = RATES[rate]
    sig = np.zeros(24, np.uint8)
    sig[:4] = [(rate_bits >> (3 - i)) & 1 for i in range(4)]
    sig[5:17] = [(len(psdu) >> i) & 1 for i in range(12)]
    sig[17] = sig[:17].sum() % 2
    coded = conv_encode(sig)
    inter = np.empty(48, np.uint8)
    inter[_perm(48, 1)] = coded
    sig_pts = _map(inter, "bpsk").reshape(1, 48)

    data = np.concatenate([np.zeros(16, np.uint8), np.unpackbits(
        np.frombuffer(psdu, np.uint8), bitorder="little")])
    n_sym, dbps = n_symbols(rate, len(psdu)), n_dbps(rate)
    padded = np.zeros(n_sym * dbps, np.uint8)
    padded[:len(data)] = data
    scr = scramble(padded, seed)
    scr[len(data):len(data) + 6] = 0                    # the tail
    coded = conv_encode(scr)
    coded = coded[np.resize(_PUNCTURE[cr], len(coded))]
    n_cbps = 48 * n_bpsc
    inter = np.empty_like(coded).reshape(n_sym, n_cbps)
    inter[:, _perm(n_cbps, n_bpsc)] = coded.reshape(n_sym, n_cbps)
    pts = _map(inter.reshape(-1), mod).reshape(n_sym, 48)
    return np.concatenate([PREAMBLE, _ofdm(sig_pts, 0), _ofdm(pts, 1)])


# -- receiver ----------------------------------------------------------------------

def detect(w: np.ndarray, threshold: float = 0.56, min_run: int = 32) -> List[int]:
    """Lag-16 autocorrelation plateau (``ofdm.detect_packets``)."""
    if len(w) < 160:
        return []
    corr = np.cumsum(w[:-16] * np.conj(w[16:]))
    c = np.abs(corr[48:] - corr[:-48])
    power = np.cumsum(np.abs(w) ** 2)
    p = power[48:len(c) + 48] - power[:len(c)]
    above = (c / np.maximum(p, 1e-12) > threshold) & (p > 1e-4 * p.max())
    d = np.diff(np.concatenate([[False], above, [False]]).astype(np.int8))
    starts, skip = [], -1
    for s, e in zip(np.flatnonzero(d == 1), np.flatnonzero(d == -1)):
        s = max(int(s), skip)
        if e - s >= min_run:
            starts.append(s)
            skip = int(e) + 160
    return starts


def sync_long(w: np.ndarray, start: int) -> tuple:
    """LTS cross-correlation, two-peak pairing, CP-ghost guard, CFO
    (``ofdm.sync_long``): ``(lts_start, cfo)``."""
    seg = w[start:start + SEARCH_LEN]
    mag = np.abs(np.correlate(seg, _LTS_SYM, mode="valid"))
    p1 = int(np.argmax(mag))
    rest = mag.copy()
    rest[max(0, p1 - 8):p1 + 8] = 0
    first, second = sorted((p1, int(np.argmax(rest))))
    if second - first != 64:
        first = p1 - 64 if p1 >= 64 and mag[p1 - 64] > 0.5 * mag[p1] else p1
        second = first + 64
    while second + 64 < len(mag) and mag[second + 64] > 0.8 * max(mag[first], 1e-12):
        first, second = second, second + 64
    cfo = np.angle(np.vdot(seg[first:first + 64], seg[second:second + 64])) / 64.0
    return start + first, float(cfo)


def _equalise(spec: np.ndarray, H: np.ndarray, first_symbol: int) -> np.ndarray:
    pol = PILOT_POLARITY[(first_symbol + np.arange(len(spec))) % 127]
    eq = spec / H[None, :]
    cpe = np.angle((eq[:, PILOTS % FFT] * (PILOT_VALUES[None, :] * pol[:, None]))
                   .sum(axis=1))
    return (eq * np.exp(-1j * cpe)[:, None])[:, DATA % FFT]


def _demap(sym: np.ndarray, mod: str) -> np.ndarray:
    """Max-log LLR per bit, positive ⇒ 1, over the whole constellation."""
    table = _TABLES[mod]
    nb = int(math.log2(len(table)))
    d = -np.abs(sym[:, None] - table[None, :]) ** 2
    idx = np.arange(len(table))
    return np.stack([d[:, (idx >> b) & 1 == 1].max(axis=1)
                     - d[:, (idx >> b) & 1 == 0].max(axis=1)
                     for b in range(nb)], axis=1).reshape(-1)


def receive_window(window: np.ndarray, emit_from: int) -> tuple:
    """``(packets, counts)`` of one window; a packet is a dict with the
    fields of a record entry (``lts_start`` relative to ``emit_from``)."""
    w = np.asarray(window, np.complex128)
    n = len(w)
    padded = np.concatenate([w, np.zeros(SEARCH_LEN, np.complex128)])
    counts = {"detected": 0, "aligned": 0, "signal_ok": 0}
    heads = []
    for start in detect(w):
        counts["detected"] += 1
        lts, cfo = sync_long(padded, start)
        if lts + 208 > n:
            continue
        counts["aligned"] += 1
        head = padded[lts:lts + 208] * np.exp(-1j * cfo * np.arange(208))
        H = np.ones(FFT, np.complex128)
        used = _to_grid(LTS_FREQ)
        avg = (np.fft.fft(head[:64]) + np.fft.fft(head[64:128])) / 2
        H[used != 0] = avg[used != 0] / used[used != 0]
        sig = _equalise(np.fft.fft(head[144:208])[None, :], H, 0).reshape(-1)
        heads.append((lts, cfo, H, head, 4.0 * sig.real[_perm(48, 1)]))
    sig_bits = viterbi_batch([h[4] for h in heads], [24] * len(heads))
    todo = []
    for (lts, cfo, H, head, _), b in zip(heads, sig_bits):
        code = int(b[0]) * 8 + int(b[1]) * 4 + int(b[2]) * 2 + int(b[3])
        length = sum(int(b[5 + i]) << i for i in range(12))
        rate = next((i for i, r in enumerate(RATES) if r[4] == code), None)
        if int(b[:18].sum()) % 2 or rate is None or length < 1:
            continue
        counts["signal_ok"] += 1
        n_sym = n_symbols(rate, length)
        end = lts + 208 + SYM * n_sym
        if end > n:
            continue
        _, mod, n_bpsc, cr, _ = RATES[rate]
        body = padded[lts + 208:end] * np.exp(
            -1j * cfo * (np.arange(n_sym * SYM) + 208))
        spec = np.fft.fft(body.reshape(n_sym, SYM)[:, CP:], axis=1)
        llr = _demap(_equalise(spec, H, 1).reshape(-1), mod)
        deint = llr.reshape(n_sym, -1)[:, _perm(48 * n_bpsc, n_bpsc)].reshape(-1)
        mask = np.resize(_PUNCTURE[cr], 2 * n_sym * n_dbps(rate))
        mother = np.zeros(len(mask))
        mother[mask] = deint
        noise = float(np.mean(np.abs(head[:64] - head[64:128]) ** 2)) / 2 + 1e-20
        total = float(np.mean(np.abs(head[:128]) ** 2))
        snr = 10.0 * math.log10(max(total - noise, 1e-20) / noise)
        todo.append((lts, end, rate, length, cfo, snr, mother))
    decoded = viterbi_batch([t[6] for t in todo],
                            [16 + 8 * t[3] + 6 for t in todo])
    packets, claimed_to = [], -1
    for (lts, end, rate, length, cfo, snr, mother), bits in zip(todo, decoded):
        match = np.nonzero((_KEYS[1:, :16] == bits[None, :16]).all(axis=1))[0]
        if lts < claimed_to or not len(match):
            continue
        claimed_to = end
        if end <= emit_from:
            continue
        plain = scramble(bits, int(match[0]) + 1)
        packets.append({
            "lts_start": lts - emit_from, "rate": rate, "length": length,
            "cfo": cfo, "snr_db": snr, "seed_ok": True,
            "llr_mean": float(np.abs(mother[:2 * (16 + 8 * length + 6)]).mean()),
            "psdu": np.packbits(plain[16:16 + 8 * length],
                                bitorder="little").tobytes()})
    counts["emitted"] = len(packets)
    return packets, counts


# -- record blocks (layout: models/wlan/rx_stages.py's docstring) -------------------

MAGIC, HEADER_WORDS, ENTRY_WORDS = 0x574C414E, 16, 8
HEADER = ("magic", "detected", "aligned", "signal_ok", "emitted", "overflow",
          "steps", "lanes_decoded", "symbols", "psdu_words", "lanes", "pieces")


def parse_block(block: np.ndarray) -> tuple:
    """One record block → ``(header dict or None, [packet dict])``."""
    block = np.ascontiguousarray(block, np.int32)
    if len(block) < HEADER_WORDS or int(block[0]) != MAGIC:
        return None, []
    head = {k: int(v) for k, v in zip(HEADER, block)}
    lanes = head["lanes"]
    entries = block[HEADER_WORDS:HEADER_WORDS + ENTRY_WORDS * lanes]
    area = block[HEADER_WORDS + ENTRY_WORDS * lanes:].view(np.uint8)
    packets = []
    for e in entries.reshape(lanes, ENTRY_WORDS)[:max(0, min(head["emitted"], lanes))]:
        off, length = 4 * int(e[6]), int(e[2])
        packets.append({"lts_start": int(e[0]), "rate": int(e[1]), "length": length,
                        "cfo": float(e[3:4].view(np.float32)[0]),
                        "snr_db": float(e[4:5].view(np.float32)[0]),
                        "seed_ok": bool(e[5]),
                        "llr_mean": float(e[7:8].view(np.float32)[0]),
                        "psdu": area[off:off + length].tobytes()})
    return head, packets


def build_block(packets: List[dict], counts: dict, n_words: int,
                lanes: int) -> np.ndarray:
    """The reference's packets in the program's record layout, so that the
    driver can concatenate references as it concatenates outputs."""
    packets = packets[:lanes]
    block = np.zeros(n_words, np.int32)
    block[:11] = [MAGIC, counts["detected"], counts["aligned"], counts["signal_ok"],
                  len(packets), 0,
                  max((16 + 8 * p["length"] + 6 for p in packets), default=0),
                  len(packets), 0, 0, lanes]
    area = block[HEADER_WORDS + ENTRY_WORDS * lanes:].view(np.uint8)
    off = 0
    for i, p in enumerate(packets):
        e = block[HEADER_WORDS + ENTRY_WORDS * i:][:ENTRY_WORDS]
        e[:3] = [p["lts_start"], p["rate"], p["length"]]
        e[3:5] = np.array([p["cfo"], p["snr_db"]], np.float32).view(np.int32)
        e[5:7] = [1, off // 4]
        e[7:8] = np.array([p["llr_mean"]], np.float32).view(np.int32)
        area[off:off + p["length"]] = np.frombuffer(p["psdu"], np.uint8)
        off += -(-p["length"] // 4) * 4
    block[9] = off // 4
    return block
