"""Jitted OFDM demodulation: CFO → batched FFT → equalize → CPE → max-log demap.

XLA residency of the WLAN RX hot path (only packet detection stays host-side;
Viterbi already runs as a lax.scan): the frame HEAD (LTS channel estimate +
SIGNAL demap) is one jit call, all data symbols of a frame demap in another,
bucketed by symbol count and cached per modulation. Constant tables
(constellation, carrier indices, LTS reference) are passed as device arguments
rather than embedded constants: uploaded once, shared by every bucket's program.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np

from .consts import (CP_LEN, DATA_CARRIERS, FFT_SIZE, LTS_FREQ, MODULATION_TABLES,
                     PILOT_CARRIERS, PILOT_VALUES, SYM_LEN)

__all__ = ["demod_body_jax", "demod_head_jax"]

_DATA_IDX = (DATA_CARRIERS % FFT_SIZE).astype(np.int32)
_PIL_IDX = (PILOT_CARRIERS % FFT_SIZE).astype(np.int32)


@lru_cache(maxsize=None)
def _compiled(modulation: str, bucket: int):
    import jax
    import jax.numpy as jnp

    table = MODULATION_TABLES[modulation].astype(np.complex64)
    n_bpsc = int(np.log2(len(table)))
    # Per-axis max-log decomposition: every 802.11 constellation is a product
    # of two gray PAMs with the LOW idx-bit group selecting the I level and
    # the HIGH group the Q level (consts.py `_qam16`/`_qam64`), so
    # d(z, a+jb) = dI(re, a) + dQ(im, b) and the axis-orthogonal term cancels
    # in l1−l0: LLR_b = max_{lvl: bit set} −(re−lvl)² − max_{clear} −(re−lvl)²
    # (resp. imag). √M REAL point distances per axis instead of M complex
    # ones — 4× (qam16) to 8× (qam64) less demap work, identical LLRs up to
    # float rounding.
    n_i = (n_bpsc + 1) // 2                    # I-group bit count (bpsk: 1)
    n_q = n_bpsc - n_i
    lvl_i = table[np.arange(1 << n_i)].real.astype(np.float32)
    lvl_q = table[(np.arange(1 << n_q)) << n_i].imag.astype(np.float32)
    mask_i = np.stack([(((np.arange(1 << n_i) >> b) & 1)).astype(np.float32)
                       for b in range(n_i)])                  # [n_i, Li]
    mask_q = np.stack([(((np.arange(1 << n_q) >> b) & 1)).astype(np.float32)
                       for b in range(n_q)]) if n_q else \
        np.zeros((0, 1), np.float32)                          # [n_q, Lq]

    @jax.jit
    def run(body, H, pol, sym_mask, cfo, phase0, li, lq, data_idx, pil_idx,
            mi, mq):
        k = jnp.arange(bucket * SYM_LEN)
        x = body * jnp.exp(-1j * cfo * (k + phase0))
        sym = x.reshape(bucket, SYM_LEN)[:, CP_LEN:]
        spec = jnp.fft.fft(sym, axis=1)
        eq = spec / H[None, :]
        pilots = eq[:, pil_idx]
        expected = jnp.asarray(PILOT_VALUES)[None, :] * pol[:, None]
        cpe = jnp.angle((pilots * jnp.conj(expected)).sum(axis=1))
        eq = eq * jnp.exp(-1j * cpe)[:, None]
        data = eq[:, data_idx]                                # [bucket, 48]
        big = jnp.float32(1e30)
        d_i = -(data.real[..., None] - li[None, None, :]) ** 2  # [bucket,48,Li]
        llrs = [jnp.max(jnp.where(mi[b] > 0, d_i, -big), axis=2)
                - jnp.max(jnp.where(mi[b] > 0, -big, d_i), axis=2)
                for b in range(n_i)]
        if n_q:
            d_q = -(data.imag[..., None] - lq[None, None, :]) ** 2
            llrs += [jnp.max(jnp.where(mq[b] > 0, d_q, -big), axis=2)
                     - jnp.max(jnp.where(mq[b] > 0, -big, d_q), axis=2)
                     for b in range(n_q)]
        out = jnp.stack(llrs, axis=2).reshape(bucket, -1)     # [bucket, 48*n_bpsc]
        return (out * sym_mask[:, None]).reshape(-1)

    consts = (lvl_i, lvl_q, _DATA_IDX, _PIL_IDX, mask_i, mask_q)
    return run, consts


@lru_cache(maxsize=None)
def _compiled_head():
    import jax
    import jax.numpy as jnp

    # LTS reference spectrum on the fft grid + the used-carrier mask, host-built
    from .consts import carriers_to_grid
    ref = carriers_to_grid(LTS_FREQ).astype(np.complex64)
    used = (ref != 0)
    ref_safe = np.where(used, ref, 1.0).astype(np.complex64)

    @jax.jit
    def run(head, cfo, ref_c, used_c, pil_idx, data_idx):
        # head = [208] raw samples from lts_start (2x LTS, then SIGNAL with CP),
        # CFO applied in-trace with phase reference 0 at lts_start — the same
        # convention demod_body_jax uses via its phase0 argument
        k = jnp.arange(head.shape[0])
        x = head * jnp.exp(-1j * cfo * k)
        s1 = jnp.fft.fft(x[0:64])
        s2 = jnp.fft.fft(x[64:128])
        avg = (s1 + s2) * 0.5
        H = jnp.where(used_c, avg / ref_c, 1.0 + 0j)
        spec = jnp.fft.fft(x[128 + CP_LEN:128 + SYM_LEN])
        eq = spec / H
        pilots = eq[pil_idx]
        # SIGNAL symbol: pilot polarity index 0 => +1 on all four pilots
        expected = jnp.asarray(PILOT_VALUES.astype(np.complex64))
        cpe = jnp.angle((pilots * jnp.conj(expected)).sum())
        eq = eq * jnp.exp(-1j * cpe)
        llrs = 4.0 * eq[data_idx].real          # BPSK max-log, closed form
        return H, llrs.astype(jnp.float32)

    # ship the complex constant to the device ONCE here (lru-cached with the
    # jit): a per-call to_device would pay a transfer and a dispatch for an
    # unchanging table
    from ...ops.xfer import to_device
    return run, (to_device(ref_safe), used, _PIL_IDX, _DATA_IDX)


def demod_head_jax(head: np.ndarray, cfo: float):
    """LTS channel estimate + SIGNAL-symbol LLRs in ONE jit call.

    ``head``: the 208 raw samples from ``lts_start`` (two LTS symbols + the
    SIGNAL symbol with CP), WITHOUT host-side CFO correction. Returns
    ``(H[64] complex64 ndarray, llrs[48] float32 ndarray)`` matching the host
    path (``ofdm.estimate_channel`` + ``ofdm.equalize`` + BPSK demap).

    Every complex host↔device crossing rides the xfer pair shim (one fused
    kernel per crossing, ``ops/xfer.py``)."""
    from ...ops.xfer import to_device, to_host

    run, consts = _compiled_head()       # consts already device-resident
    H, llrs = run(to_device(np.asarray(head[:208], dtype=np.complex64)),
                  np.float32(cfo), *consts)
    return to_host(H), np.asarray(llrs)


def demod_body_jax(body: np.ndarray, H: np.ndarray, n_sym: int, symbol_offset: int,
                   cfo: float, phase0: float, modulation: str) -> np.ndarray:
    """Returns raw LLRs for ``n_sym`` symbols ([n_sym·n_cbps]); ``body`` holds exactly
    n_sym·80 samples (un-CFO-corrected); bucket padding handled internally."""
    from .consts import PILOT_POLARITY

    bucket = max(4, 1 << int(np.ceil(np.log2(max(n_sym, 1)))))
    run, consts = _compiled(modulation, bucket)
    padded = np.zeros(bucket * SYM_LEN, dtype=np.complex64)
    padded[:n_sym * SYM_LEN] = body
    pol = PILOT_POLARITY[(symbol_offset + np.arange(bucket)) % len(PILOT_POLARITY)]
    mask = (np.arange(bucket) < n_sym).astype(np.float32)
    # complex jit args through the xfer pair shim
    from ...ops.xfer import to_device
    out = np.asarray(run(to_device(padded), to_device(H.astype(np.complex64)),
                         pol.astype(np.float32),
                         mask, np.float32(cfo), np.float32(phase0), *consts))
    n_bpsc = int(np.log2(len(MODULATION_TABLES[modulation])))
    return out[:n_sym * 48 * n_bpsc]
