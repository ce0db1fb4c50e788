"""A LoRaWAN gateway as ONE fixed-shape device program per frame.

``TpuKernel(lora_gw_stages(), np.complex64)``: a wideband stream of
``n_channels`` slots of 200 kHz in, *records* out. Every (channel, SF) pair is
a **branch**, a receiver of its own (8 x 6 = 48 at the defaults: the EU868
uplink channels, SF7 to SF12 at once), and a packet outlives a frame (an SF12
packet of 64 bytes lasts 2.79 s, seventeen frames of 262144 samples at
1.6 Msps), so the carry holds, per branch, the frame-sync state, timing, CFO
and the symbols collected so far; a record leaves in the frame in which the
scan reaches the end of the packet's last symbol (the frame in which its last
sample lies, as delayed by the front end's filters). Per frame, by scope:

``chan``    a half-slot rotation (the band's centre, 867.8 MHz for EU868, lies
            between two slots; channel ``c`` lands in slot ``(c - n/2 + 1) mod
            n``), then the critically sampled polyphase bank
            (``ops.stages.channelizer_stage``).
``resamp``  5/4 to 250 kHz, two samples a chip (``ops.stages.resample_stage``,
            channels as a batch).
``detect``  per SF, channels as a batch: windows of one symbol at a hop of a
            quarter symbol over the last four symbols of the earlier frames +
            this frame, dechirped (``ops.stages.lora_dechirp_dft``: a DFT of
            2 * 2^SF points through ``ops/mxu_fft``, here thousands of rows a
            call: its many-row forms; bins ``k`` and ``k +
            2^SF`` hold a symbol's two parts on either side of its wrap and add
            as powers, so no fraction of a chip in the timing costs the peak);
            peak bin, the share of the energy in the peak and its larger
            neighbour, the phase against the window a symbol earlier. A
            preamble = four symbol-spaced windows that agree within a bin.
``sync`` / ``demod``  per SF one ``lax.scan`` over the frame's symbol times;
            each step every branch looks at ONE window of its own grid: idle
            branches at four detection windows (a look-up), the others at the
            aligned window their state asks for; the windows of all lanes,
            each starting anywhere, are fetched by ONE kernel a step
            (``_window_fetch``: whole tiles by DMA, then a rotation; a
            ``vmap(dynamic_slice)`` is a loop of a trip a lane inside every
            step), and the peak's neighbours are read for all lanes at once,
            so a step's body holds no loop. The step's DFT has one row a
            channel, 8 rows: ``mxu_fft.form`` gives it the few-row forms (re
            and im as stacked rows through ONE real matmul a stage, four-step
            from 512 points = SF8, tables built outside the scan), because on
            8 rows a DFT costs its tables and its operations, not its
            arithmetic. The grid moves by ``-2k``
            samples so that the preamble dechirps to bin 0, its first window
            gives the rest ``nu`` (Jacobsen), it walks to the sync word (24,
            32 for 0x34), the two whole down-chirps against the up-chirp give
            ``g = 2 (cfo - nu)``: CFO ``nu + g/2`` (its fraction from the
            preamble's phase), symbol edge ``g`` samples on; then aligned data
            symbols, rotated by CFO and the rest of the timing (their two
            parts added as amplitudes, the second turned by that rest), argmax. The
            header block (8 symbols, 2^(SF-2) resolution, Hamming 4/8) is
            decoded in the step that completes it and sets the packet's count
            of symbols. Every branch is a lane of its SF's scan: the scan is
            latency-bound, so an idle lane costs nothing and no pool of
            active slots stands between a preamble and its lane.
``decode``  finished packets (copied out of the scan into ``done_slots`` rows
            per SF): Gray, diagonal de-interleave, Hamming 4/5 (hard
            decisions), nibbles to bytes, de-whitening, CRC-16 as an XOR of
            table rows.
``pack``    entries of all SFs in order of ending into the record block.

The record block of a frame of ``n`` samples is ``n // 8`` int32 words:

================  ==========================================================
words             content
================  ==========================================================
``0 ... 15``      header: magic, detected, synced, header_ok, emitted,
                  crc_bad, in_flight (branches past detection at the frame's
                  end), symbols (aligned data symbols demodulated), overflow,
                  channels, SFs, 0 ...
``16 + 80 i``     entry ``i``, in order of ending: channel, SF, start and end
                  (250 kHz samples from this frame's first; the start is
                  negative for a packet begun in an earlier frame), CFO (Hz),
                  rest of the timing (chips), SNR estimate (dB), mean share of
                  a symbol's energy in its peak (the number every matmul of
                  the program passes through), length, CRC verdict, symbols,
                  0 ..., then from word 16 the payload, little-endian
================  ==========================================================

Decoded: explicit header, CR 4/5, payload CRC on (LoRaWAN uplinks), LDRO from
``ldro_from_sf``. ``benchmark/harness/refs_lora.py`` is the same receiver in
numpy float64, one branch at a time, and lists the departures from
gr-lora_sdr (no SFO tracking among them). Precision: float32; every DFT and
the bank at ``Precision.HIGHEST`` (``benchmark/tools/lora_precision_control.py``
is the control).
"""

from __future__ import annotations

from fractions import Fraction
from typing import Dict, List, Optional, Sequence

import numpy as np

from ...ops.stages import (Stage, channelizer_stage, lora_dechirp_dft,
                           lora_downchirp, resample_stage)
from . import coding

__all__ = ["lora_gw_stages", "parse_records", "record_counters", "front_end_taps",
           "MAGIC", "HEADER_WORDS", "ENTRY_WORDS", "EU868_MAX_PAYLOAD"]

MAGIC = 0x4C_4F_52_41          # "LORA"
HEADER_WORDS = 16
ENTRY_WORDS = 80
MAX_ENTRIES = 64
OS = 2                         # samples a chip after the resampler
MAX_PREAMBLE_WALK = 10
SYNC_WORD = 0x34
_PRECISION = "f32"             # of every DFT and the bank (the control patches it)
_IDLE, _PRE, _SW2, _DN1, _DN2, _DATA = range(6)
_COUNTERS = ("detected", "synced", "header_ok", "emitted", "crc_bad", "in_flight",
             "symbols", "overflow")
#: RP002 EU863-870: the largest PHYPayload of DR0 ... DR5
EU868_MAX_PAYLOAD = {12: 64, 11: 64, 10: 64, 9: 128, 8: 255, 7: 255}


def _interpret() -> bool:
    """Mosaic on a TPU backend, the interpreter elsewhere (the convention of
    ``ops/pallas_kernels.py``; a compile for a described chip patches it)."""
    import jax
    return jax.default_backend() != "tpu"


def _window_fetch(ext, S: int):
    """``fetch(pos)`` -> ``ext[c, p:p + S]`` of every lane ``c``, ``p = clip(pos,
    0, T - S)``, bit for bit, as ONE kernel over all lanes and both planes.

    A gather of slices that start anywhere (``vmap(lax.dynamic_slice)``) is on
    this compiler a loop of one trip a lane and a plane inside whatever calls
    it (``docs/tpu_notes.md``): in a scan step more than half of what the step
    ran. Here the planes are laid out once a frame as rows of 128 samples
    (``[2, lanes, rows, 128]``, at least 1024 samples of zeros behind the
    end); per lane the kernel copies the whole ``(8, 128)`` tiles that hold
    the window from HBM (all lanes' copies in flight at once), rotates its
    rows by ``p % 128`` lanes and takes each output row from two neighbouring
    rows."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, T = ext.shape
    if S % 128:
        raise ValueError(f"a window of {S} samples is not whole rows of 128")
    n = S // 128
    n_copy = 8 * max(2, S // 1024 + 1)          # rows: the tiles S samples can touch
    rows = jnp.pad(jnp.stack([jnp.real(ext), jnp.imag(ext)]),
                   ((0, 0), (0, 0), (0, (-T) % 1024 + 1024))).reshape(2, C, -1, 128)

    def kernel(tile_ref, off_ref, rows_ref, out_ref, buf, sem):
        copies = []
        for c in range(C):
            first = pl.multiple_of(tile_ref[c] * 8, 8)
            copies.append(pltpu.make_async_copy(
                rows_ref.at[:, c, pl.ds(first, n_copy)], buf.at[:, c], sem.at[c]))
            copies[-1].start()
        lane = lax.broadcasted_iota(jnp.int32, (n, 128), 1)
        for c, copy in enumerate(copies):
            copy.wait()
            row, r = off_ref[c] // 128, off_ref[c] % 128
            for plane in range(2):
                a = pltpu.roll(buf[plane, c, pl.ds(row, n), :], 128 - r, axis=1)
                b = pltpu.roll(buf[plane, c, pl.ds(row + 1, n), :], 128 - r, axis=1)
                out_ref[plane, c] = jnp.where(lane < 128 - r, a, b)

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((2, C, n, 128), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((2, C, n, 128), lambda i, *_: (0, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, C, n_copy, 128), jnp.float32),
                            pltpu.SemaphoreType.DMA((C,))]),
        interpret=_interpret(), name="lora_window_fetch")

    def fetch(pos):
        p = jnp.clip(pos, 0, T - S)
        x = call(p // 1024, p % 1024, rows).reshape(2, C, S)
        return lax.complex(x[0], x[1])

    return fetch


def _kaiser_lowpass(cutoff: float, n_taps: int, beta: float) -> np.ndarray:
    m = np.arange(n_taps) - (n_taps - 1) / 2
    h = 2 * cutoff * np.sinc(2 * cutoff * m) * np.kaiser(n_taps, beta)
    return h / h.sum()


def front_end_taps(n_channels: int) -> tuple:
    """(bank prototype: 12 taps a branch, pass band the 125 kHz of a 200 kHz
    slot; 5/4 resampler: 120 taps at 1 MHz, pass 62.5 kHz, stop by 100 kHz)."""
    return (_kaiser_lowpass(0.5 * 0.82 / n_channels, 12 * n_channels, 7.0) * n_channels,
            _kaiser_lowpass(0.081, 120, 7.0) * 5)


def detect_share(sf: int) -> float:
    """The least share of a window's energy in its peak and the larger
    neighbour that counts as a preamble: 0.6 of what a packet at its SF's
    demodulation floor (-7.5 dB at SF7, 2.5 dB lower per SF) shows when its
    tone falls between two bins; 2 to 3 times what noise alone shows."""
    g = 10 ** ((-7.5 - 2.5 * (sf - 7)) / 10)
    return 0.6 * 0.81 * g / (1 + g)


def n_data_symbols(sf: int, length, de: bool):
    """Header block + payload symbols at CR 4/5 with CRC (ints or arrays)."""
    rows = 4 * (sf - 2 * de)
    blocks = (8 * length - 4 * sf + 28 + 16 + rows - 1) // rows
    return 8 + blocks * (blocks > 0) * 5


def record_counters(frame: np.ndarray) -> dict:
    """The eight counts of one landed record block's header (``Stage.counters``:
    what ``TpuKernel`` adds to the ``emit`` span while tracing)."""
    if len(frame) < HEADER_WORDS or int(frame[0]) != MAGIC:
        return {}
    return {f"lora_{name}": int(frame[1 + i]) for i, name in enumerate(_COUNTERS)}


def parse_records(block: np.ndarray) -> tuple:
    """One record block -> ``(header dict, [packet dict])`` on the host."""
    block = np.asarray(block).astype(np.int32)
    head = record_counters(block)
    if not head:
        return {}, []
    n = min(head["lora_emitted"], (len(block) - HEADER_WORDS) // ENTRY_WORDS)
    packets = []
    for e in block[HEADER_WORDS:HEADER_WORDS + n * ENTRY_WORDS].reshape(n, ENTRY_WORDS):
        f = e[4:8].view(np.float32)
        length = int(e[8])
        packets.append({
            "channel": int(e[0]), "sf": int(e[1]), "start": int(e[2]), "end": int(e[3]),
            "cfo_hz": float(f[0]), "timing": float(f[1]), "snr_db": float(f[2]),
            "share": float(f[3]), "length": length, "crc_ok": bool(e[9]),
            "n_sym": int(e[10]),
            "payload": e[16:].astype("<i4").tobytes()[:max(0, min(length, 256))]})
    return head, packets


def _crc_table() -> np.ndarray:
    """``T[d, b]``: CRC-16/CCITT (initial value 0) of byte ``b`` followed by
    ``d`` zero bytes; the CRC of a message is the XOR of its bytes' rows."""
    t = np.zeros((256, 256), np.int32)
    t[0] = [coding.crc16(bytes([b])) for b in range(256)]
    for d in range(1, 256):
        c = t[d - 1]
        for _ in range(8):
            c = np.where(c & 0x8000, ((c << 1) ^ 0x1021) & 0xFFFF, (c << 1) & 0xFFFF)
        t[d] = c
    return t


def lora_gw_stages(n_channels: int = 8, sfs: Sequence[int] = (7, 8, 9, 10, 11, 12),
                   max_payload: Optional[Dict[int, int]] = None,
                   ldro_from_sf: int = 11, done_slots: int = 16, chan_taps=None,
                   resamp_taps=None) -> List[Stage]:
    """The gateway as a one-stage pipeline (see the module docstring).

    ``max_payload``: the largest PHYPayload decoded per SF (default RP002's
    EU868 limits; a header that announces more is let go);
    ``done_slots``: packets of one SF that may end in one frame (more are
    counted as overflow); the taps
    default to :func:`front_end_taps`. A frame must hold a whole number of
    quarter symbols of the largest SF after the front end: ``frame * 5 / (4 *
    n_channels)`` divisible by ``2^sf_max / 2`` (262144 at the defaults,
    16384 for four channels up to SF9)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    C, sfs = int(n_channels), tuple(int(s) for s in sfs)
    max_payload = dict(max_payload or EU868_MAX_PAYLOAD)
    D = int(done_slots)
    h_bank, h_rs = front_end_taps(C)
    chan = channelizer_stage(C, h_bank if chan_taps is None else chan_taps,
                             precision=None if _PRECISION == "f32" else "bf16")
    rs = resample_stage(5, 4, h_rs if resamp_taps is None else resamp_taps)
    slot_of = np.array([(c - C // 2 + 1) % C for c in range(C)])
    rot = np.exp(2j * np.pi * np.arange(2 * C) / (2 * C)).astype(np.complex64)
    S_max = OS << max(sfs)
    H_max = 4 * S_max
    crc_t = _crc_table()
    whitening = np.frombuffer(coding.whiten(bytes(256)), np.uint8).astype(np.int32)
    prec = _PRECISION

    def wrap(k, n):
        return (k + n // 2) % n - n // 2

    def gray(x):
        return x ^ (x >> 1)

    def deinterleave(q, rows):
        """``[..., n_sym]`` symbol values -> ``[..., rows]`` codewords."""
        n_sym = q.shape[-1]
        sh = (np.arange(rows)[:, None] - np.arange(n_sym)[None, :]) % rows
        bits = (q[..., None, :] >> jnp.asarray(sh, jnp.int32)) & 1
        return jnp.sum(bits << jnp.arange(n_sym, dtype=jnp.int32), axis=-1)

    def header(syms8, sf):
        """``[..., 8]`` -> (ok, length, nibbles ``[..., sf - 2]``)."""
        q = gray(((syms8 + 2) >> 2) % (1 << (sf - 2)))
        cw = deinterleave(q, sf - 2)
        d = [(cw >> i) & 1 for i in range(7)]
        syn = (d[4] ^ d[0] ^ d[1] ^ d[2]) | ((d[5] ^ d[0] ^ d[1] ^ d[3]) << 1) \
            | ((d[6] ^ d[0] ^ d[2] ^ d[3]) << 2)
        # the data bit a syndrome points at, as compares: a look-up in a table of
        # eight is a gather, 0.36 ms a frame in the scan steps (PERF.md, PR 34)
        nib = (cw & 0xF) ^ ((syn == 0b111) * 1 | (syn == 0b011) * 2 | (syn == 0b101) * 4
                            | (syn == 0b110) * 8)
        n0, n1, n2 = nib[..., 0], nib[..., 1], nib[..., 2]
        c4 = ((n0 >> 3) ^ (n0 >> 2) ^ (n0 >> 1) ^ n0) & 1
        c3 = ((n0 >> 3) ^ (n1 >> 3) ^ (n1 >> 2) ^ (n1 >> 1) ^ n2) & 1
        c2 = ((n0 >> 2) ^ (n1 >> 3) ^ n1 ^ (n2 >> 3) ^ (n2 >> 1)) & 1
        c1 = ((n0 >> 1) ^ (n1 >> 2) ^ n1 ^ (n2 >> 2) ^ (n2 >> 1) ^ n2) & 1
        c0 = (n0 ^ (n1 >> 1) ^ (n2 >> 3) ^ (n2 >> 2) ^ (n2 >> 1) ^ n2) & 1
        length = (n0 << 4) | n1
        ok = ((nib[..., 3] & 1) == c4) \
            & (nib[..., 4] == ((c3 << 3) | (c2 << 2) | (c1 << 1) | c0)) \
            & (n2 == 0b0011) & (length >= 1) & (length <= max_payload[sf])
        return ok, length, nib

    def spectrum(w, ref, sf):
        """Windows ``[..., S]`` times ``ref`` -> (X, P, folded Q, peak bin)."""
        n = 1 << sf
        X = lora_dechirp_dft(w, sf, OS, ref=ref, precision=prec)
        P = jnp.real(X) ** 2 + jnp.imag(X) ** 2
        Q = P[..., :n] + P[..., n:]
        return X, P, Q, jnp.argmax(Q, axis=-1).astype(jnp.int32)

    def at(a, idx):
        return jnp.take_along_axis(a, idx[..., None], axis=-1)[..., 0]

    def branch_sizes(sf):
        n = 1 << sf
        S = OS * n
        de = sf >= ldro_from_sf
        return n, S, S // 4, 4 * S, de, int(n_data_symbols(sf, max_payload[sf], de))

    def init_branches(sf):
        n, S, hop, H, de, max_sym = branch_sizes(sf)
        zi = lambda: jnp.zeros(C, jnp.int32)
        zf = lambda: jnp.zeros(C, jnp.float32)
        return dict(st=zi(), pos=jnp.full(C, H, jnp.int32), cnt=zi(), nsym=zi(),
                    need=zi(), start=zi(), k1=zi(), nu=zf(), eps=zf(), cfo=zf(),
                    tau=zf(), ssum=zf(), syms=jnp.zeros((C, max_sym), jnp.int32))

    def run_sf(sf, b, ext):
        """One SF over one frame: ``ext`` = ``[C, H + L]`` (history + frame)."""
        n, S, hop, H, de, max_sym = branch_sizes(sf)
        T = ext.shape[1]
        L = T - H
        down = lora_downchirp(sf, OS)
        down_c, up_c = jnp.asarray(down.astype(np.complex64)), \
            jnp.asarray(np.conj(down).astype(np.complex64))
        with jax.named_scope("detect"):
            nw = (T - S) // hop + 1
            blocks = ext[:, :(nw + 3) * hop].reshape(C, nw + 3, hop)
            win = jnp.concatenate([blocks[:, q:q + nw] for q in range(4)], axis=2)
            X, P, Q, kb = spectrum(win, down_c, sf)
            share = (at(Q, kb) + jnp.maximum(at(Q, (kb - 1) % n), at(Q, (kb + 1) % n))) \
                / jnp.maximum(jnp.sum(Q, axis=-1), 1e-30)
            back = lambda a, m: jnp.pad(a, ((0, 0), (4 * m, 0)))[:, :nw]
            cond = jnp.arange(nw)[None, :] >= 12
            for m in range(4):
                cond &= (back(share, m) > detect_share(sf)) \
                    & (jnp.abs(wrap(back(kb, m) - kb, n)) <= 1)
            Xb = jnp.pad(X, ((0, 0), (4, 0), (0, 0)))[:, :nw]
            z = at(X, kb) * jnp.conj(at(Xb, kb)) + at(X, kb + n) * jnp.conj(at(Xb, kb + n))
            eps_all = jnp.arctan2(jnp.imag(z), jnp.real(z)) / (2 * np.pi)
            # what an idle branch at window j reads, worked out here for every j
            # at once, so that a scan step reads two words: is a preamble seen in
            # j ... j + 3 (bit 0), the first such window (bits 1, 2), its bin
            # (from bit 3); and its phase against the window a symbol earlier
            pad4 = lambda a: jnp.pad(a, ((0, 0), (0, 4)))
            cond_p = pad4(cond)
            seen4 = jnp.stack([cond_p[:, i:i + nw + 1] for i in range(4)], axis=2)
            first = jnp.argmax(seen4, axis=2).astype(jnp.int32)
            jt_all = jnp.arange(nw + 1)[None, :] + first
            det_code = jnp.any(seen4, axis=2).astype(jnp.int32) | (first << 1) \
                | (jnp.take_along_axis(pad4(kb), jt_all, axis=1) << 3)
            det_eps = jnp.take_along_axis(pad4(eps_all), jt_all, axis=1)
        i_s = jnp.arange(S, dtype=jnp.int32)
        frac = jnp.asarray((np.arange(S) / S).astype(np.float32))
        fetch = _window_fetch(ext, S)

        def window(pos, up, nu, data, tau):
            """The aligned window of each lane -> (bin, Jacobsen, peak share).
            ``data`` lanes know the rest of their timing ``tau``: the two parts
            of their symbol add as amplitudes, the second turned by ``tau``."""
            w = fetch(pos)
            nu_i = jnp.floor(nu)
            ph = ((nu_i.astype(jnp.int32)[:, None] * i_s[None, :]) % S).astype(jnp.float32) / S \
                + (nu - nu_i)[:, None] * frac[None, :]
            rotn = jnp.exp(-2j * np.pi * ph.astype(jnp.complex64))
            ref = jnp.where(up[:, None], up_c[None, :], down_c[None, :]) * rotn
            X, P, Q, k = spectrum(w, ref, sf)
            Z = X[..., :n] + X[..., n:] * jnp.exp(
                2j * np.pi * tau.astype(jnp.complex64))[:, None]
            Q = jnp.where(data[:, None], jnp.real(Z) ** 2 + jnp.imag(Z) ** 2, Q)
            k = jnp.argmax(Q, axis=-1).astype(jnp.int32)
            # X and P at k - 1, k, k + 1 and the same three a half further on, as
            # three reads; the half with the larger peak is taken
            idx = (k[:, None] + jnp.asarray([-1, 0, 1, n - 1, n, n + 1], jnp.int32)) % S
            re, im, pw = (jnp.take_along_axis(a, idx, axis=1, mode="promise_in_bounds")
                          for a in (jnp.real(X), jnp.imag(X), P))
            x3 = lax.complex(re, im)
            x3 = jnp.where((pw[:, 4] > pw[:, 1])[:, None], x3[:, 3:], x3[:, :3])
            xm, x0, xp = x3[:, 0], x3[:, 1], x3[:, 2]
            den = 2 * x0 - xm - xp
            jac = jnp.real((xm - xp) * jnp.conj(den)) \
                / jnp.maximum(jnp.real(den) ** 2 + jnp.imag(den) ** 2, 1e-30)
            # max(Q) is Q[k]: k is its argmax
            return k, jac, jnp.max(Q, axis=-1) / jnp.maximum(jnp.sum(P, axis=-1), 1e-30)

        lower = jnp.asarray(np.tril(np.ones((C, C), bool)))

        def step(carry, _):
            """What a step carries beside the branches stays in vectors: per-lane
            tallies of the counters and ``n_done`` in every lane. A sum over the
            lanes is a scalar, and a scalar that goes back into a vector (eight
            counters a step were 0.8 ms a frame, the done rows' count 0.25) or a
            small gather (0.2-0.4 ms each) costs a step more than its DFT's
            neighbours do (``PERF.md`` section 6, PR 34)."""
            b, tally, done, n_done = carry
            with jax.named_scope("sync"):
                st, pos = b["st"], b["pos"]
                fits = pos + S <= T
                idle = (st == _IDLE) & fits
                # -- detection: the first of four windows that sees a preamble
                j0 = pos // hop
                j = jnp.clip(j0, 0, nw)
                code, ed = (jnp.take_along_axis(a, j[:, None], axis=1,
                                                mode="promise_in_bounds")[:, 0]
                            for a in (det_code, det_eps))
                trig = idle & ((code & 1) != 0)
                jt, kd = j0 + ((code >> 1) & 3), code >> 3
                # -- the aligned window of every branch past detection
                up = (st == _DN1) | (st == _DN2)
                nu_use = jnp.where(st == _DATA, b["cfo"] + b["tau"], b["nu"])
            with jax.named_scope("demod"):
                k, jac, shr = window(pos, up, nu_use, st == _DATA, b["tau"])
            with jax.named_scope("sync"):
                kw = wrap(k, n)
                in_pre, in_sw2 = (st == _PRE) & fits, (st == _SW2) & fits
                first = in_pre & (b["cnt"] == 0) & (jnp.abs(kw) <= 1)
                nu = jnp.where(first, kw.astype(jnp.float32) + jac, b["nu"])
                kw = jnp.where(first, 0, kw)
                stay = in_pre & (jnp.abs(kw) <= 1) & (b["cnt"] < MAX_PREAMBLE_WALK)
                want = jnp.where(st == _PRE, (SYNC_WORD >> 4) * 8, (SYNC_WORD & 0xF) * 8)
                adv = (in_pre | in_sw2) & ~stay & (jnp.abs(kw - want) <= 1)
                in_dn1, in_dn2 = (st == _DN1) & fits, (st == _DN2) & fits
                synced = in_dn2 & (jnp.abs(wrap(k - b["k1"], n)) <= 1)
                g = kw.astype(jnp.float32) + jac
                cfo_new = b["eps"] + jnp.floor(b["nu"] + g / 2 - b["eps"] + 0.5)
                sh = jnp.floor(g + 0.5).astype(jnp.int32)
                pos_data = pos + S + S // 4 + sh
                in_data = (st == _DATA) & fits
                nsym1 = b["nsym"] + in_data
                syms = jnp.where((jnp.arange(max_sym)[None, :] == b["nsym"][:, None])
                                 & in_data[:, None], k[:, None], b["syms"])
                at8 = in_data & (nsym1 == 8)
                hok, length, _ = header(syms[:, :8], sf)
                need = jnp.where(at8 & hok, n_data_symbols(sf, length, de), b["need"])
                hbad = at8 & ~hok
                complete = in_data & (nsym1 >= 8) & (nsym1 == need) & ~hbad
                ssum = b["ssum"] + jnp.where(in_data, shr, 0.0)
                to_idle = ((in_pre | in_sw2) & ~stay & ~adv) \
                    | (in_dn2 & ~synced) | hbad | complete
                nxt = pos + S
                # an idle branch moves on to the first window it has not looked at
                # (the last of a frame's do not fit and wait for the next frame)
                new_pos = jnp.where(trig, jt * hop + (-OS * kd) % S, jnp.where(
                    synced, pos_data, jnp.where(
                        idle, pos + hop * jnp.minimum(4, nw - j0), jnp.where(
                            to_idle, (nxt + hop - 1) // hop * hop, nxt))))
                new_st = jnp.where(trig, _PRE, jnp.where(
                    to_idle, _IDLE, jnp.where(adv & in_pre, _SW2, jnp.where(
                        adv & in_sw2, _DN1, jnp.where(in_dn1, _DN2, jnp.where(
                            synced, _DATA, st))))))
                # -- a finished packet leaves the scan through a done row
                fin = complete.astype(jnp.int32)
                row = n_done + jnp.sum(jnp.where(lower, fin[None, :], 0), axis=1) - 1
                row = jnp.where(complete & (row < D), row, D)
                end = nxt - H
                bits = lambda v: lax.bitcast_convert_type(v, jnp.int32)
                done = done.at[row].set(jnp.concatenate([jnp.stack(
                    [jnp.arange(C, dtype=jnp.int32), b["start"], end, nsym1, need,
                     bits(b["cfo"]), bits(b["tau"]), bits(ssum / jnp.maximum(nsym1, 1))]).T,
                    syms], axis=1), mode="drop")
                new_b = dict(
                    st=jnp.where(fits, new_st, st).astype(jnp.int32),
                    pos=jnp.where(fits, new_pos, pos).astype(jnp.int32),
                    cnt=jnp.where(trig, 0, b["cnt"] + stay),
                    nsym=jnp.where(synced, 0, nsym1), need=jnp.where(synced, 8, need),
                    start=jnp.where(synced, pos_data - 12 * S - S // 4 - H, b["start"]),
                    k1=jnp.where(in_dn1, k, b["k1"]),
                    nu=jnp.where(trig, 0.0, nu), eps=jnp.where(trig, ed, b["eps"]),
                    cfo=jnp.where(synced, cfo_new, b["cfo"]),
                    tau=jnp.where(synced, (sh.astype(jnp.float32) - g) / OS, b["tau"]),
                    ssum=jnp.where(synced, 0.0, ssum), syms=syms)
                tally = tally + jnp.stack([trig, synced, at8 & hok, in_data]).astype(jnp.int32)
                n_done = n_done + jnp.sum(jnp.broadcast_to(fin[None, :], (C, C)), axis=1)
            return (new_b, tally, done, n_done), None

        # a done row: channel, start, end, symbols, symbols owed, then CFO, rest
        # of the timing and mean peak share as their bits, then the symbols
        done0 = jnp.zeros((D, 8 + max_sym), jnp.int32)
        (b, tally, done, n_done), _ = lax.scan(
            step, (b, jnp.zeros((4, C), jnp.int32), done0, jnp.zeros(C, jnp.int32)), None,
            length=-(-L // S) + 6)
        n_done = n_done[0]
        b = dict(b, pos=b["pos"] - L, start=b["start"] - L)
        done = dict(ints=done[:, :5], syms=done[:, 8:],
                    flts=lax.bitcast_convert_type(done[:, 5:8], jnp.float32))
        with jax.named_scope("decode"):
            entries, crc_ok = decode(sf, de, max_sym, done, jnp.minimum(n_done, D))
        valid = jnp.arange(D) < jnp.minimum(n_done, D)
        detected, synced, header_ok, symbols = jnp.sum(tally, axis=1)
        cnts = jnp.stack([
            detected, synced, header_ok, jnp.minimum(n_done, D), jnp.sum(valid & ~crc_ok),
            jnp.sum(b["st"] != _IDLE), symbols, jnp.maximum(n_done - D, 0)]).astype(jnp.int32)
        key = jnp.where(valid, (done["ints"][:, 2] + H_max) * 128
                        + sfs.index(sf) * 16 + done["ints"][:, 0], jnp.int32(2 ** 31 - 1))
        return b, cnts, entries, key

    def decode(sf, de, max_sym, done, n_done):
        """Done rows -> (entries ``[D, 80]`` int32, CRC verdicts)."""
        n, rows = 1 << sf, (sf - 2 if de else sf)
        syms, ints, flts = done["syms"], done["ints"], done["flts"]
        _, length, nib0 = header(syms[:, :8], sf)
        length = jnp.clip(length, 1, max_payload[sf])
        body = syms[:, 8:].reshape(D, -1, 5)
        if de:
            body = ((body + 2) >> 2) % (n >> 2)
        cw = deinterleave(gray(body), rows)                       # [D, blocks, rows]
        nib = jnp.concatenate([nib0[:, 5:], (cw & 0xF).reshape(D, -1)], axis=1)
        nib = jnp.pad(nib, ((0, 0), (0, max(0, 2 * 258 - nib.shape[1]))))[:, :2 * 258]
        byt = nib[:, 0::2] | (nib[:, 1::2] << 4)                  # [D, 258]
        i = jnp.arange(256)[None, :]
        inside = i < length[:, None]
        pay = jnp.where(inside, byt[:, :256] ^ jnp.asarray(whitening)[None, :], 0)
        dist = jnp.clip(length[:, None] - 1 - i, 0, 255)
        crc = lax.reduce(jnp.where(inside, jnp.asarray(crc_t)[dist, pay], 0),
                         jnp.int32(0), lax.bitwise_xor, (1,))
        sent = at(byt, length) | (at(byt, length + 1) << 8)
        crc_ok = crc == sent
        words = pay.reshape(D, 64, 4)
        words = words[..., 0] | (words[..., 1] << 8) | (words[..., 2] << 16) \
            | (words[..., 3] << 24)
        share = flts[:, 2]
        snr = 10 * jnp.log10(jnp.maximum(share, 1e-9) / jnp.maximum(1 - share, 1e-9))
        f32 = lambda v: lax.bitcast_convert_type(v.astype(jnp.float32), jnp.int32)
        fields = jnp.stack([
            ints[:, 0], jnp.full(D, sf, jnp.int32), ints[:, 1], ints[:, 2],
            f32(flts[:, 0] * (125e3 / n)), f32(flts[:, 1]), f32(snr), f32(share),
            length, crc_ok.astype(jnp.int32), ints[:, 3]], axis=1)
        return jnp.concatenate([fields, jnp.zeros((D, 5), jnp.int32), words], axis=1), crc_ok

    def fn(carry, x):
        c_chan, c_rs, hist, branches = carry
        n_words = x.shape[0] // 8
        assert (x.shape[0] * 5) % (4 * C * (S_max // 4)) == 0, \
            f"frame {x.shape[0]} does not hold whole quarter symbols of SF{max(sfs)}"
        with jax.named_scope("chan"):
            xr = (x.reshape(-1, 2 * C) * jnp.asarray(rot)[None, :]).reshape(-1)
            c_chan, y = chan.fn(c_chan, xr)
            y = y.reshape(-1, C).T[jnp.asarray(slot_of)]                 # [C, t]
        with jax.named_scope("resamp"):
            c_rs, y = jax.vmap(rs.fn)(c_rs, y)                           # [C, L]
        ext = jnp.concatenate([hist, y], axis=1)
        new_branches, cnts, entries, keys = [], jnp.zeros(8, jnp.int32), [], []
        for sf, b in zip(sfs, branches):
            S = OS << sf
            b, c, e, k = run_sf(sf, b, ext[:, H_max - 4 * S:])
            new_branches.append(b)
            cnts = cnts + c
            entries.append(e)
            keys.append(k)
        with jax.named_scope("pack"):
            cap = min(MAX_ENTRIES, (n_words - HEADER_WORDS) // ENTRY_WORDS)
            entries, keys = jnp.concatenate(entries), jnp.concatenate(keys)
            order = jnp.argsort(keys)[:cap]
            n_emit = jnp.minimum(cnts[3], cap)
            picked = jnp.where((jnp.arange(len(order)) < n_emit)[:, None],
                               entries[order], 0)
            head = jnp.zeros(HEADER_WORDS, jnp.int32).at[:11].set(jnp.concatenate([
                jnp.array([MAGIC], jnp.int32), cnts.at[3].set(n_emit)
                .at[7].add(cnts[3] - n_emit), jnp.array([C, len(sfs)], jnp.int32)]))
            out = jnp.concatenate([head, picked.reshape(-1)])
            out = jnp.pad(out, (0, n_words - out.shape[0]))
        return (c_chan, c_rs, ext[:, ext.shape[1] - H_max:], tuple(new_branches)), out

    def init_carry(dtype):
        c_rs = rs.init_carry(np.complex64)
        return (chan.init_carry(np.complex64),
                jnp.zeros((C,) + tuple(c_rs.shape), jnp.complex64),
                jnp.zeros((C, H_max), jnp.complex64),
                tuple(init_branches(sf) for sf in sfs))

    mult = int(np.lcm(4 * C, 8))
    return [Stage(fn, init_carry, ratio=Fraction(1, 8), out_dtype=np.int32,
                  frame_multiple=mult, name="lora_gw", counters=record_counters)]
