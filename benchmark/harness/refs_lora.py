"""The benchmark's own LoRa transmitter, float64 gateway receiver and record
reader (LoRaWAN RP002 EU863-870 over the chain gr-lora_sdr publishes:
frame_sync, fft_demod, gray_mapping, deinterleaver, hamming_dec,
header_decoder, dewhitening, crc_verif). numpy float64, one branch
(channel, SF) at a time, frame after frame with explicit state, no jit, no
native library; nothing here imports the program.

The receiver, per frame of ``n`` wideband samples (``n_ch`` slots of 200 kHz):

* **front end**: a half-slot rotation (the band's centre lies between two
  slots, so that every channel lies ``(c - n_ch/2 + 1)`` slots from the
  rotated centre), then per channel the mixed, low-passed stream taken every
  ``n_ch`` samples (what a critically sampled polyphase bank puts out in slot
  ``q``: ``y[t] = e^{j2pi(n_ch-1)q/n_ch} sum_m h[m] x[t n_ch + n_ch - 1 - m]
  e^{-j2pi q (t n_ch + n_ch - 1 - m)/n_ch}``), then a 5/4 rational resampler
  to 250 kHz, two samples a chip;
* **detect**: windows of 2^SF chips (both samples of every chip, so a DFT of
  2 * 2^SF points whose bins k and k + 2^SF add as powers: the two parts of a
  symbol on either side of its wrap, whatever the fraction of a chip in the
  timing) at a hop of 2^SF/4 chips, dechirped by the down-chirp; peak bin and
  the share of the window's energy in the peak and its larger neighbour. A preamble is four
  symbol-spaced windows whose bins agree within 1 and whose shares pass
  ``detect_share(sf)``;
* **sync**: the peak bin ``k`` of the last window (the tone ``cfo + tau``,
  ``tau`` = how late the window starts, in chips) moves the window grid by
  ``-2k`` samples, so that the preamble dechirps to bin 0 within a bin; the
  first window on that grid gives the rest ``nu`` (Jacobsen's three-bin
  estimate; the windows are rotated by it from then on); the grid walks the
  preamble to the two sync-word chirps (24 and 32 for 0x34), measures the
  tone ``g = 2 (cfo - nu)`` of the two whole down-chirps against the up-chirp,
  and has CFO ``nu + g/2`` (its fraction from the phase between two preamble
  windows a symbol apart) and the symbol's edge ``g`` samples on, rounded to
  the sample (half a chip);
* **demodulate**: aligned symbols, dechirped and rotated by CFO plus the
  rest of the timing ``tau``; the two parts of the symbol (bins k and k +
  2^SF) add as amplitudes, the second turned by ``tau`` cycles; argmax; header and LDRO symbols at 2^(SF-2) resolution;
* **decode**: Gray, diagonal de-interleave, Hamming 4/8 (header) and 4/5,
  header checksum, length, de-whitening, CRC-16, hard decisions throughout.

A record leaves in the frame in which the scan reaches the end of the
packet's last symbol: the frame in which the packet's last sample lies, as
delayed by the front end's filters.

Departures from gr-lora_sdr, all shared with the program
(``futuresdr_tpu/models/lora/rx_stages.py``): no sampling-frequency-offset
tracking (the capture's clock is exact); detection on a hop of a quarter
symbol over four windows instead of ``n_up - 3`` symbol-spaced ones; the
fractional timing is taken at half-chip resolution (two samples a chip) and
its rest removed as a rotation, not by a fractional-delay estimate of the
preamble; hard decisions; only CR 4/5 with CRC and explicit header is decoded
(LoRaWAN uplinks); symbols are not offset by one bin and the payload CRC is
CRC-16/CCITT over the whole payload, as ``futuresdr_tpu/models/lora/coding.py``
has them (gr-lora_sdr adds 1 to every symbol and folds the last two payload
bytes into the CRC).
"""

from __future__ import annotations

import math
import struct
from typing import Dict, List

import numpy as np

MAGIC = 0x4C_4F_52_41          # "LORA"
HEADER_WORDS = 16
ENTRY_WORDS = 80               # 16 of fields + 64 of payload (256 bytes)
MAX_ENTRIES = 64
COUNTERS = ("detected", "synced", "header_ok", "emitted", "crc_bad", "in_flight",
            "symbols", "overflow")
SYNC_WORD = 0x34
N_PREAMBLE = 8
BW = 125e3
SLOT = 200e3
OS = 2                         # samples a chip after the resampler (250 kHz)
MAX_PREAMBLE_WALK = 10
IDLE, PRE, SW2, DN1, DN2, DATA = range(6)


def detect_share(sf: int) -> float:
    """The least share of a window's energy in its peak and the larger
    neighbour that counts as a preamble: 0.6 of what a packet at its SF's
    demodulation floor (-7.5 dB at SF7, 2.5 dB lower per SF) shows when its
    tone falls between two bins (0.81 of it in the two); 2 to 3 times what
    noise alone shows."""
    g = 10 ** ((-7.5 - 2.5 * (sf - 7)) / 10)
    return 0.6 * 0.81 * g / (1 + g)


# -- bit-plane coding ---------------------------------------------------------

def whitening(n: int) -> np.ndarray:
    seq, state = np.empty(n, np.uint8), 0xFF
    for i in range(n):
        seq[i] = state
        fb = ((state >> 7) ^ (state >> 5) ^ (state >> 4) ^ (state >> 3)) & 1
        state = ((state << 1) | fb) & 0xFF
    return seq


def crc16(data: bytes) -> int:
    crc = 0
    for byte in data:
        crc ^= byte << 8
        for _ in range(8):
            crc = ((crc << 1) ^ 0x1021) & 0xFFFF if crc & 0x8000 else (crc << 1) & 0xFFFF
    return crc


def hamming_encode(nib: int, cr: int) -> int:
    d = [(nib >> i) & 1 for i in range(4)]
    p0, p1, p2 = d[0] ^ d[1] ^ d[2], d[0] ^ d[1] ^ d[3], d[0] ^ d[2] ^ d[3]
    p3 = d[0] ^ d[1] ^ d[2] ^ d[3] ^ p0 ^ p1 ^ p2
    return nib | sum(p << (4 + i) for i, p in enumerate((p0, p1, p2, p3)[:cr]))


def hamming84_decode(cw: int) -> int:
    d = [(cw >> i) & 1 for i in range(8)]
    syn = (d[4] ^ d[0] ^ d[1] ^ d[2]) | (d[5] ^ d[0] ^ d[1] ^ d[3]) << 1 \
        | (d[6] ^ d[0] ^ d[2] ^ d[3]) << 2
    return (cw & 0xF) ^ {0b111: 1, 0b011: 2, 0b101: 4, 0b110: 8}.get(syn, 0)


def gray(x):
    return x ^ (x >> 1)


def degray(g: int) -> int:
    out, shift = g, 1
    while shift < 32:
        out ^= out >> shift
        shift <<= 1
    return out


def header_nibbles(length: int, cr: int, has_crc: bool) -> List[int]:
    n0, n1, n2 = (length >> 4) & 0xF, length & 0xF, ((cr & 7) << 1) | int(has_crc)
    c4 = ((n0 >> 3) ^ (n0 >> 2) ^ (n0 >> 1) ^ n0) & 1
    c3 = ((n0 >> 3) ^ (n1 >> 3) ^ (n1 >> 2) ^ (n1 >> 1) ^ n2) & 1
    c2 = ((n0 >> 2) ^ (n1 >> 3) ^ n1 ^ (n2 >> 3) ^ (n2 >> 1)) & 1
    c1 = ((n0 >> 1) ^ (n1 >> 2) ^ n1 ^ (n2 >> 2) ^ (n2 >> 1) ^ n2) & 1
    c0 = (n0 ^ (n1 >> 1) ^ (n2 >> 3) ^ (n2 >> 2) ^ (n2 >> 1) ^ n2) & 1
    return [n0, n1, n2, c4, (c3 << 3) | (c2 << 2) | (c1 << 1) | c0]


def interleave(cws: List[int], rows: int, n_sym: int) -> List[int]:
    """``rows`` codewords of ``n_sym`` bits -> ``n_sym`` symbols of ``rows`` bits:
    bit ``i`` of symbol ``j`` is bit ``j`` of codeword ``(i + j) mod rows``."""
    return [sum(((cws[(i + j) % rows] >> j) & 1) << i for i in range(rows))
            for j in range(n_sym)]


def deinterleave(syms: List[int], rows: int) -> List[int]:
    cws = [0] * rows
    for j, s in enumerate(syms):
        for i in range(rows):
            cws[(i + j) % rows] |= ((s >> i) & 1) << j
    return cws


def ldro(sf: int, ldro_from_sf: int = 11) -> bool:
    return sf >= ldro_from_sf


def n_data_symbols(sf: int, length: int, de: bool) -> int:
    """The header block's 8 symbols and the payload's, CR 4/5, CRC on."""
    return 8 + max(-(-(8 * length - 4 * sf + 28 + 16) // (4 * (sf - 2 * de))) * 5, 0)


def packet_chips(sf: int, length: int, de: bool) -> float:
    return (N_PREAMBLE + 4.25 + n_data_symbols(sf, length, de)) * (1 << sf)


def symbols_of(payload: bytes, sf: int, de: bool) -> List[int]:
    """PHYPayload -> the values of its data symbols (header block first)."""
    body = bytes(np.frombuffer(payload, np.uint8) ^ whitening(len(payload)))
    c = crc16(payload)
    body += bytes([c & 0xFF, c >> 8])
    nibbles = [n for b in body for n in (b & 0xF, b >> 4)]
    first = (header_nibbles(len(payload), 1, True) + nibbles)[:sf - 2]
    first += [0] * (sf - 2 - len(first))
    rest = nibbles[max(sf - 7, 0):]
    out = [degray(s) << 2 for s in
           interleave([hamming_encode(n, 4) for n in first], sf - 2, 8)]
    rows = sf - 2 if de else sf
    for i in range(0, len(rest), rows):
        blk = rest[i:i + rows] + [0] * (rows - len(rest[i:i + rows]))
        out += [degray(s) << (2 if de else 0) for s in
                interleave([hamming_encode(n, 1) for n in blk], rows, 5)]
    return [s % (1 << sf) for s in out]


def decode_header(syms, sf: int):
    """The first 8 symbol values -> (length, nibbles after the header's five)
    or None: checksum, CR 4/5 and the CRC flag must hold, length >= 1."""
    q = [gray(((int(s) + 2) >> 2) % (1 << (sf - 2))) for s in syms[:8]]
    nib = [hamming84_decode(c) for c in deinterleave(q, sf - 2)]
    length = (nib[0] << 4) | nib[1]
    if header_nibbles(length, nib[2] >> 1, bool(nib[2] & 1))[3:] != \
            [nib[3] & 1, nib[4]] or nib[2] != 0b0011 or length < 1:
        return None
    return length, nib[5:]


def decode_packet(syms, sf: int, de: bool):
    """All data symbols of a packet -> (payload, crc_ok) or None."""
    head = decode_header(syms, sf)
    if head is None:
        return None
    length, nib = head
    rows, n = (sf - 2 if de else sf), 1 << sf
    for i in range(8, n_data_symbols(sf, length, de), 5):
        blk = [int(s) for s in syms[i:i + 5]]
        if de:
            blk = [((s + 2) >> 2) % (n >> 2) for s in blk]
        nib += [c & 0xF for c in deinterleave([gray(s) for s in blk], rows)]
    body = bytes(nib[2 * i] | (nib[2 * i + 1] << 4) for i in range(length + 2))
    payload = bytes(np.frombuffer(body[:length], np.uint8) ^ whitening(length))
    return payload, crc16(payload) == body[length] | (body[length + 1] << 8)


# -- the transmitter ----------------------------------------------------------

def chirp_phase(u: np.ndarray, value, n: int) -> np.ndarray:
    """Phase in cycles of an up-chirp of ``n`` chips that starts at bin
    ``value``, at chip time ``u`` in [0, n): it wraps by one bandwidth where
    it reaches the band's edge."""
    wrapped = u >= n - value
    return u * u / (2 * n) + u * (value / n - 0.5 - wrapped) + wrapped * (n - value)


def packet_phase(symbols: List[int], sf: int, t_chips: np.ndarray) -> np.ndarray:
    """Phase in cycles of the packet (8 up-chirps, sync word, 2.25 down-chirps,
    data) at the chip times ``t_chips`` (real, 0 = its first sample)."""
    n = 1 << sf
    values = np.array([0] * N_PREAMBLE + [(SYNC_WORD >> 4) * 8, (SYNC_WORD & 0xF) * 8]
                      + [0, 0, 0] + list(symbols), np.float64)
    n_pre = N_PREAMBLE + 2
    # the quarter down-chirp takes a quarter of a symbol: data starts at 12.25
    t = np.asarray(t_chips, np.float64)
    down = (t >= n_pre * n) & (t < (n_pre + 2.25) * n)
    td = np.where(t >= (n_pre + 2.25) * n, t + 0.75 * n, t)   # data on a 3-symbol grid
    k = np.minimum((td // n).astype(np.int64), len(values) - 1)
    ph = chirp_phase(td - k * n, values[k], n)
    return np.where(down, -ph, ph)


def modulate(symbols: List[int], sf: int, t_chips: np.ndarray) -> np.ndarray:
    """The packet at unit amplitude."""
    return np.exp(2j * np.pi * packet_phase(symbols, sf, t_chips))


# -- the front end ------------------------------------------------------------

def kaiser_lowpass(cutoff: float, n_taps: int, beta: float) -> np.ndarray:
    """Windowed-sinc low-pass, ``cutoff`` in cycles a sample, unit DC gain."""
    m = np.arange(n_taps) - (n_taps - 1) / 2
    h = 2 * cutoff * np.sinc(2 * cutoff * m) * np.kaiser(n_taps, beta)
    return h / h.sum()


def channelizer_taps(n_ch: int) -> np.ndarray:
    """12 taps a branch; pass band the 125 kHz of a 200 kHz slot."""
    return kaiser_lowpass(0.5 * 0.82 / n_ch, 12 * n_ch, 7.0) * n_ch


def resampler_taps() -> np.ndarray:
    """5/4: at 1 MHz, pass 62.5 kHz, stop by 100 kHz (the slot's edge)."""
    return kaiser_lowpass(0.081, 120, 7.0) * 5


def slot_of(channel: int, n_ch: int) -> int:
    return (channel - n_ch // 2 + 1) % n_ch


class FrontEnd:
    """One channel of the front end, frame after frame."""

    def __init__(self, channel: int, n_ch: int):
        self.n_ch, self.q = n_ch, slot_of(channel, n_ch)
        self.h, self.g = channelizer_taps(n_ch), resampler_taps()
        self.hist = np.zeros(len(self.h) - n_ch, np.complex128)
        self.rhist = np.zeros(len(self.g) // 5, np.complex128)

    def __call__(self, x: np.ndarray) -> np.ndarray:
        n_ch, q = self.n_ch, self.q
        i = np.arange(len(x))
        z = x * np.exp(2j * np.pi * (i % (2 * n_ch)) / (2 * n_ch))      # half slot
        z = z * np.exp(-2j * np.pi * q * (i % n_ch) / n_ch)             # slot q to 0
        ext = np.concatenate([self.hist, z])
        self.hist = ext[len(ext) - len(self.hist):]
        k_taps = len(self.h) // n_ch
        rows = ext.reshape(-1, n_ch)                 # rows[r] = ext[r n_ch : +n_ch]
        t = len(x) // n_ch
        y = np.zeros(t, np.complex128)
        for k in range(k_taps):                      # sum_m h[m] z[t n_ch + n_ch-1-m]
            y += rows[k_taps - 1 - k:k_taps - 1 - k + t, ::-1] @ self.h[k * n_ch:(k + 1) * n_ch]
        y = y * np.exp(2j * np.pi * (n_ch - 1) * q / n_ch)
        # 5/4: out[j] = sum_t g[(4j mod 5) + 5t] y[floor(4j/5) - t]
        H = len(self.rhist)
        ext = np.concatenate([self.rhist, y])
        self.rhist = ext[len(ext) - H:]
        out = np.zeros((t // 4, 5), np.complex128)
        for r in range(5):
            phase = self.g[(4 * r) % 5::5]
            s0 = (4 * r) // 5
            for tt, c in enumerate(phase):
                out[:, r] += c * ext[H + s0 - tt:H + s0 - tt + 4 * (t // 4):4]
        return out.reshape(-1)


# -- one branch ---------------------------------------------------------------

def _wrap(k, n):
    return (k + n // 2) % n - n // 2


class Branch:
    """The receiver of one (channel, SF): a state machine over windows of the
    channel's 250 kHz stream. ``pos`` counts from the start of ``ext`` =
    the last ``H`` samples of the earlier frames + this frame."""

    def __init__(self, channel: int, sf: int, max_len: int, ldro_from_sf: int = 11):
        self.channel, self.sf, self.n = channel, sf, 1 << sf
        self.S = OS * self.n
        self.hop = self.S // 4
        self.H = 4 * self.S
        self.de = ldro(sf, ldro_from_sf)
        self.max_len = max_len
        self.hist = np.zeros(self.H, np.complex128)
        u = np.arange(self.S) / OS
        self.down = np.exp(-2j * np.pi * (u * u / (2 * self.n) - u / 2))
        self.st, self.pos = IDLE, self.H
        self.cnt = self.nsym = self.need = self.start = 0
        self.nu = self.eps = self.cfo = self.tau = self.share_sum = 0.0
        self.k1 = 0
        self.syms: List[int] = []

    def _window(self, ext, pos, nu, up_ref=False, tau=None):
        """The window of one symbol at ``pos`` (every sample: 2^SF chips, two
        samples a chip), dechirped and rotated by ``nu`` bins -> (bin, X, P):
        a DFT of 2 * 2^SF points, whose bins ``k`` and ``k + 2^SF`` hold the two
        parts of a symbol on either side of its wrap; they add as powers, so no
        fraction of a chip in the timing costs the peak. Where the rest of
        the timing ``tau`` is known (data symbols) the two parts add as
        amplitudes, the second turned by ``tau`` cycles: P is then that sum's
        power in its first 2^SF bins, its second half zero."""
        w = ext[pos:pos + self.S]
        ref = np.conj(self.down) if up_ref else self.down
        i = np.arange(self.S)
        X = np.fft.fft(w * ref * np.exp(-2j * np.pi * nu * i / self.S))
        P = np.abs(X) ** 2
        if tau is not None:
            Z = X[:self.n] + X[self.n:] * np.exp(2j * np.pi * tau)
            return int(np.argmax(np.abs(Z) ** 2)), Z, P
        return int(np.argmax(P[:self.n] + P[self.n:])), X, P

    def _jacobsen(self, X, P, k):
        """The tone's distance from bin ``k`` (its stronger part), three bins."""
        k = k + self.n * bool(P[k + self.n] > P[k])
        xm, x0, xp = X[(k - 1) % self.S], X[k], X[(k + 1) % self.S]
        den = 2 * x0 - xm - xp
        return float(np.real((xm - xp) * np.conj(den)) / max(abs(den) ** 2, 1e-300))

    def frame(self, x: np.ndarray) -> tuple:
        """One frame of the channel's stream -> (records, counts)."""
        n, S, hop, H = self.n, self.S, self.hop, self.H
        L = len(x)
        ext = np.concatenate([self.hist, x])
        self.hist = ext[L:]
        counts = dict.fromkeys(COUNTERS, 0)
        records = []
        # detection features on the hop grid
        nw = (H + L - S) // hop + 1
        idx = np.arange(nw)[:, None] * hop + np.arange(S)[None, :]
        X = np.fft.fft(ext[idx] * self.down[None, :], axis=1)
        P = np.abs(X) ** 2
        Q = P[:, :n] + P[:, n:]
        kb = np.argmax(Q, axis=1)
        ar = np.arange(nw)
        share = (Q[ar, kb] + np.maximum(Q[ar, (kb - 1) % n], Q[ar, (kb + 1) % n])) \
            / np.maximum(Q.sum(axis=1), 1e-300)
        cond = np.zeros(nw, bool)
        j = np.arange(12, nw)
        ok = np.ones(len(j), bool)
        for m in range(4):
            ok &= share[j - 4 * m] > detect_share(self.sf)
            ok &= np.abs(_wrap(kb[j - 4 * m] - kb[j], n)) <= 1
        cond[12:] = ok
        for _ in range(-(-L // S) + 6):
            pos = self.pos
            if pos + S > H + L:
                break
            if self.st == IDLE:
                j0 = pos // hop
                hit = [q for q in range(4) if j0 + q < nw and cond[j0 + q]]
                if not hit:                  # on to the first window not looked at
                    self.pos = pos + hop * min(4, nw - j0)
                    continue
                jt = j0 + hit[0]
                k = int(kb[jt])
                z = X[jt, k] * np.conj(X[jt - 4, k]) + X[jt, k + n] * np.conj(X[jt - 4, k + n])
                self.eps = float(np.angle(z) / (2 * np.pi))
                self.st, self.cnt, self.nu = PRE, 0, 0.0
                self.pos = jt * hop + (-OS * k) % S
                counts["detected"] += 1
                continue
            if self.st in (PRE, SW2):
                k, Xw, Pw = self._window(ext, pos, self.nu)
                kw = _wrap(k, n)
                if self.st == PRE and self.cnt == 0 and abs(kw) <= 1:
                    self.nu, kw = kw + self._jacobsen(Xw, Pw, k), 0
                want = (SYNC_WORD >> 4) * 8 if self.st == PRE else (SYNC_WORD & 0xF) * 8
                if self.st == PRE and abs(kw) <= 1 and self.cnt < MAX_PREAMBLE_WALK:
                    self.cnt += 1
                elif abs(kw - want) <= 1:
                    self.st = SW2 if self.st == PRE else DN1
                else:
                    self._idle(pos + S)
                    continue
                self.pos = pos + S
                continue
            if self.st == DN1:
                self.k1, _, _ = self._window(ext, pos, self.nu, up_ref=True)
                self.st, self.pos = DN2, pos + S
                continue
            if self.st == DN2:
                k, Xw, Pw = self._window(ext, pos, self.nu, up_ref=True)
                if abs(_wrap(k - self.k1, n)) > 1:
                    self._idle(pos + S)
                    continue
                g = _wrap(k, n) + self._jacobsen(Xw, Pw, k)
                cfo = self.nu + g / 2
                self.cfo = self.eps + np.floor(cfo - self.eps + 0.5)
                sh = int(np.floor(g + 0.5))
                self.tau = (sh - g) / OS
                self.st, self.nsym, self.need, self.share_sum = DATA, 0, 8, 0.0
                self.syms = []
                self.pos = pos + S + S // 4 + sh
                self.start = self.pos - (N_PREAMBLE + 4) * S - S // 4 - H
                counts["synced"] += 1
                continue
            # DATA
            k, Z, p = self._window(ext, pos, self.cfo + self.tau, tau=self.tau)
            self.syms.append(k)
            self.share_sum += abs(Z[k]) ** 2 / max(p.sum(), 1e-300)
            self.nsym += 1
            counts["symbols"] += 1
            self.pos = pos + S
            if self.nsym == 8:
                head = decode_header(self.syms, self.sf)
                if head is None or head[0] > self.max_len:
                    self._idle(self.pos)
                    continue
                self.need = n_data_symbols(self.sf, head[0], self.de)
                counts["header_ok"] += 1
            if self.nsym >= 8 and self.nsym == self.need:
                payload, crc_ok = decode_packet(self.syms, self.sf, self.de)
                share = self.share_sum / self.nsym
                records.append({
                    "channel": self.channel, "sf": self.sf, "start": self.start,
                    "end": self.pos - H, "cfo_hz": self.cfo * BW / n, "timing": self.tau,
                    "snr_db": 10 * math.log10(max(share, 1e-9) / max(1 - share, 1e-9)),
                    "share": share, "length": len(payload), "crc_ok": bool(crc_ok),
                    "n_sym": self.nsym, "payload": payload})
                counts["emitted"] += 1
                counts["crc_bad"] += not crc_ok
                self._idle(self.pos)
        self.pos -= L
        self.start -= L
        counts["in_flight"] = int(self.st != IDLE)
        return records, counts

    def _idle(self, pos):
        self.st, self.pos = IDLE, -(-pos // self.hop) * self.hop


class Gateway:
    """All branches of ``n_ch`` channels x ``sfs``, frame after frame."""

    def __init__(self, n_ch: int, sfs, max_len: Dict[int, int], ldro_from_sf: int = 11):
        self.n_ch, self.sfs = n_ch, tuple(sfs)
        self.front = [FrontEnd(c, n_ch) for c in range(n_ch)]
        self.branches = [[Branch(c, sf, max_len[sf], ldro_from_sf) for sf in self.sfs]
                         for c in range(n_ch)]

    def frame(self, x: np.ndarray) -> tuple:
        """One wideband frame -> (records in order of ending, counts)."""
        x, results = np.asarray(x, np.complex128), []
        for front, branches in zip(self.front, self.branches):
            y = front(x)
            results += [b.frame(y) for b in branches]
        return merge_frame(results)


def merge_frame(results) -> tuple:
    """The branches' ``(records, counts)`` of one frame -> the frame's records
    in order of ending and its summed counts."""
    records, counts = [], dict.fromkeys(COUNTERS, 0)
    for r, k in results:
        records += r
        for name in COUNTERS:
            counts[name] += k[name]
    records.sort(key=lambda r: (r["end"], r["sf"], r["channel"]))
    return records, counts


# -- record blocks ------------------------------------------------------------

def _f32(v: float) -> int:
    return struct.unpack("<i", struct.pack("<f", float(v)))[0]


def _to_f32(w: int) -> float:
    return struct.unpack("<f", struct.pack("<i", int(w)))[0]


def build_block(records: List[dict], counts: dict, n_words: int) -> np.ndarray:
    """The program's record block (``models/lora/rx_stages.py`` has the table)."""
    out = np.zeros(n_words, np.int32)
    cap = min(MAX_ENTRIES, (n_words - HEADER_WORDS) // ENTRY_WORDS)
    out[0] = MAGIC
    for i, name in enumerate(COUNTERS):
        out[1 + i] = counts[name]
    out[4] = min(counts["emitted"], cap)
    for i, r in enumerate(records[:cap]):
        e = HEADER_WORDS + i * ENTRY_WORDS
        out[e:e + 11] = [r["channel"], r["sf"], r["start"], r["end"], _f32(r["cfo_hz"]),
                         _f32(r["timing"]), _f32(r["snr_db"]), _f32(r["share"]),
                         r["length"], int(r["crc_ok"]), r["n_sym"]]
        body = r["payload"] + bytes(-len(r["payload"]) % 4)
        out[e + 16:e + 16 + len(body) // 4] = np.frombuffer(body, "<i4")
    return out


def parse_block(block: np.ndarray) -> tuple:
    """One record block -> (header counts or None, records)."""
    block = np.asarray(block).astype(np.int32)
    if len(block) < HEADER_WORDS or int(block[0]) != MAGIC:
        return None, []
    head = {name: int(block[1 + i]) for i, name in enumerate(COUNTERS)}
    records = []
    for i in range(min(head["emitted"], (len(block) - HEADER_WORDS) // ENTRY_WORDS)):
        e = block[HEADER_WORDS + i * ENTRY_WORDS:HEADER_WORDS + (i + 1) * ENTRY_WORDS]
        length = int(e[8])
        records.append({
            "channel": int(e[0]), "sf": int(e[1]), "start": int(e[2]), "end": int(e[3]),
            "cfo_hz": _to_f32(e[4]), "timing": _to_f32(e[5]), "snr_db": _to_f32(e[6]),
            "share": _to_f32(e[7]), "length": length, "crc_ok": bool(e[9]),
            "n_sym": int(e[10]),
            "payload": e[16:].astype("<i4").tobytes()[:max(0, min(length, 256))]})
    return head, records


# -- the air ------------------------------------------------------------------

def channel_offset_hz(channel: int, n_ch: int) -> float:
    """Channel ``c`` of ``n_ch`` at 200 kHz spacing around the band's centre
    (EU868: 867.1 ... 868.5 MHz around 867.8 MHz)."""
    return (channel - (n_ch - 1) / 2) * SLOT


def add_packet(x: np.ndarray, n_ch: int, channel: int, sf: int, payload: bytes,
               t0: float, snr_db: float, n0: float, cfo_hz: float, phase: float,
               de: bool) -> None:
    """Add one packet to the wideband capture ``x`` (``n_ch`` x 200 kHz):
    first sample at the real-valued sample time ``t0``; ``snr_db`` is its power
    over the noise in 125 kHz when the noise per wideband sample is ``n0``."""
    fs = n_ch * SLOT
    n_samp = packet_chips(sf, len(payload), de) * fs / BW
    i0 = int(math.ceil(t0))
    i1 = min(int(math.floor(t0 + n_samp)), len(x) - 1)
    symbols = symbols_of(payload, sf, de)
    gain = math.sqrt(10 ** (snr_db / 10) * n0 * BW / fs)
    f = (channel_offset_hz(channel, n_ch) + cfo_hz) / fs
    # chirps and carrier as ONE phase, reduced to a cycle in float64 and
    # turned into a sample in the capture's own float32
    for a in range(i0, i1 + 1, 1 << 20):
        t = np.arange(a, min(a + (1 << 20), i1 + 1), dtype=np.float64) - t0
        ph = packet_phase(symbols, sf, t * (BW / fs)) + (f * t + phase)
        w = ((ph - np.floor(ph)) * (2 * np.pi)).astype(np.float32)
        seg = x[a:a + len(t)]
        seg.real += np.float32(gain) * np.cos(w)
        seg.imag += np.float32(gain) * np.sin(w)
