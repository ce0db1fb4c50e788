"""Wire-format codecs for every host↔device crossing of the streamed path.

The streamed flowgraph path is bounded by min(compute, link): complex64 ships
as 8 B/sample float32 pairs both ways (`ops/xfer.py`), so halving the wire
bytes doubles a link-bound rate. Real SDR links quantize IQ on the wire — sc16/sc8
interleaved formats are what the reference's seify streams and every
USRP/SoapySDR transport speak — because RF data carries 50-80 dB of SNR at
best, far below 16-bit quantization noise. The same trick (cheap host-side
cast, dequantize on the accelerator) is how TPU input pipelines feed
(arXiv:1810.09868 §4).

A :class:`Wire` turns a logical frame (complex64/float32 stream samples) into
**wire parts** — a tuple of small-dtype numpy/jax arrays that cross the link —
and back, on both ends:

    host:   encode_host(frame) -> parts          (cheap views/casts, one pass)
    device: decode_jax(parts)  -> frame          (jitted PROLOG, fused into the
    device: encode_jax(frame)  -> parts           kernel program — dequantized
    host:   decode_host(parts) -> frame           frames never round-trip)

Part layouts are SYMMETRIC in both directions, so a host-side
``encode_host → decode_host`` round trip measures exactly the quantization the
link applies (see :func:`measure_snr_db`: ``chip_smoke.py`` holds the sc16
streamed phase to the measured, not nominal, SNR).

Formats:

========  ==============  ==========================  =====================
name      c64 B/sample    layout                      SNR (measured, c64)
========  ==============  ==========================  =====================
``f32``   8               float32 IQ pairs            exact
``bf16``  4               bfloat16 IQ pairs           ~40 dB (8-bit mantissa)
``sc16``  4               int16 IQ + per-frame scale  ~85-90 dB
``sc8``   2               int8 IQ + per-frame scale   ~45-50 dB
========  ==============  ==========================  =====================

``sc16``/``sc8`` use per-frame block-floating-point: one float32 scale =
max(|I|,|Q|) over the frame rides with the int payload, so the full int range
is always used regardless of the stream's absolute level (the AGC-free
convention of UHD's sc16 mode). Complex arrays are never materialised on the
wire — every format ships reals and forms the complex frame device-side in the
jitted prolog (the pair layout of `ops/xfer.py`).

Non-float payloads (e.g. a lora demod's int32 symbols) pass through every
format unchanged: quantizing indices would corrupt them, and they are already
compact.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

__all__ = ["Wire", "WIRE_FORMATS", "get_wire", "resolve_wire", "wire_names",
           "measure_snr_db", "streamed_ceiling_msps"]


def _is_float(dt) -> bool:
    dt = np.dtype(dt)
    return (np.issubdtype(dt, np.floating)
            or np.issubdtype(dt, np.complexfloating))


def _pairs_view(a: np.ndarray) -> np.ndarray:
    """complex (…) → float re/im pairs (…, 2) — a zero-copy view after the
    contiguity normalization (the regression-locked trick of ops/xfer.py)."""
    f = np.float64 if a.dtype == np.complex128 else np.float32
    return np.ascontiguousarray(a).view(f).reshape(a.shape + (2,))


def _join_pairs_np(p: np.ndarray, dt: np.dtype) -> np.ndarray:
    """float32 pairs (…, 2) → complex (…) host-side (zero-copy when contiguous)."""
    p = np.ascontiguousarray(np.asarray(p, dtype=np.float32))
    return p.view(np.complex64).reshape(p.shape[:-1]).astype(dt, copy=False)


class Wire:
    """One wire format. Stateless; instances are shared via :data:`WIRE_FORMATS`."""

    name = "?"
    #: nominal quantization SNR in dB for a full-scale c64 stream (None = exact)
    nominal_snr_db: Optional[float] = None

    def __init__(self):
        self._jit_dec: dict = {}          # np.dtype -> jitted decode prolog
        self._jit_enc = None              # jitted encode epilog
        self._part_counts: dict = {}      # np.dtype -> parts per frame

    def bytes_per_sample(self, dtype) -> int:
        """Bytes ONE logical sample of ``dtype`` occupies on the wire (the
        per-frame scale scalar is amortized away)."""
        raise NotImplementedError

    def part_count(self, dtype) -> int:
        """How many wire parts one frame of ``dtype`` ships as (quantizing
        formats ride a scale scalar beside the int payload; f32/bf16 ship one
        part). Probed once per dtype with a 1-item host encode and cached —
        the re-nesting key for multi-output (fan-out) programs whose flat
        part tuple concatenates per-branch parts
        (:meth:`futuresdr_tpu.ops.stages.FanoutPipeline.part_counts`)."""
        dt = np.dtype(dtype)
        n = self._part_counts.get(dt)
        if n is None:
            n = self._part_counts[dt] = len(self.encode_host(np.zeros(1, dt)))
        return n

    def encode_may_alias(self, dtype) -> bool:
        """Can :meth:`encode_host` return views ALIASING its input's memory?
        Decides whether a caller handing in a live ring-buffer slice must copy
        it out first (the async H2D would read the ring after the writer
        reclaims it — ``ops/xfer.h2d_needs_staging``). Quantizing/casting
        formats materialize fresh arrays for float payloads, so the staging
        copy is pure waste there — one fewer frame-sized memcpy per crossing
        on the hot path."""
        return True

    def encode_host(self, a: np.ndarray) -> Tuple[np.ndarray, ...]:
        raise NotImplementedError

    def encode_into(self, a: np.ndarray, alloc) -> Tuple[np.ndarray, ...]:
        """:meth:`encode_host` with output buffers drawn from ``alloc``
        (an ``ops/arena.GroupAlloc``): quantizing formats land their int
        payload in recycled arena pages instead of fresh allocations —
        bit-identical parts, no per-frame allocator tax. The base
        implementation falls back to :meth:`encode_host` (exact formats'
        parts are views of the caller's staging buffer, which the caller
        already pins; formats without an arena path stay allocation-fresh,
        which is always recycle-safe)."""
        return self.encode_host(a)

    def decode_jax(self, parts: Sequence, dtype):
        raise NotImplementedError

    def pair_words(self, shape, part_dtype, dtype) -> bool:
        """Does a part of ``shape``/``part_dtype`` hold ONE logical sample of
        ``dtype`` per 32-bit word, so that the coalesced uplink's prolog can
        hand it to :meth:`decode_words_jax` as the words it crossed the link
        in (``ops/xfer.PackedLayout.unpack_jax``)? A function of the slot
        alone; no format but sc16 under a complex dtype has such a part."""
        return False

    def encode_jax(self, y) -> tuple:
        raise NotImplementedError

    def decode_host(self, parts: Sequence[np.ndarray], dtype) -> np.ndarray:
        raise NotImplementedError

    def jit_decode(self, dtype):
        """Cached ``jax.jit`` of :meth:`decode_jax` for one logical dtype —
        the standalone wire PROLOG for blocks that decode onto the frame
        plane without a fused pipeline (``tpu/frames.py``). One cache per
        shared Wire instance keeps the jit function identity stable."""
        import jax
        dt = np.dtype(dtype)
        fn = self._jit_dec.get(dt)
        if fn is None:
            w = self
            fn = self._jit_dec[dt] = jax.jit(lambda *p: w.decode_jax(p, dt))
        return fn

    def jit_encode(self):
        """Cached ``jax.jit`` of :meth:`encode_jax` — the standalone wire
        EPILOG (symmetric of :meth:`jit_decode`)."""
        import jax
        if self._jit_enc is None:
            w = self
            self._jit_enc = jax.jit(lambda y: w.encode_jax(y))
        return self._jit_enc

    def __repr__(self):
        return f"Wire({self.name})"


class F32Wire(Wire):
    """Today's pair shim as a codec: float32 IQ pairs, bit-exact."""

    name = "f32"
    nominal_snr_db = None

    def bytes_per_sample(self, dtype) -> int:
        return np.dtype(dtype).itemsize

    def encode_host(self, a):
        a = np.asarray(a)
        if np.issubdtype(a.dtype, np.complexfloating):
            return (_pairs_view(a),)
        return (np.ascontiguousarray(a),)

    def decode_jax(self, parts, dtype):
        import jax
        (p,) = parts
        if np.issubdtype(np.dtype(dtype), np.complexfloating):
            return jax.lax.complex(p[..., 0], p[..., 1])
        return p

    def encode_jax(self, y):
        import jax.numpy as jnp
        if jnp.iscomplexobj(y):
            return (jnp.stack([y.real, y.imag], axis=-1),)
        return (y,)

    def decode_host(self, parts, dtype):
        dt = np.dtype(dtype)
        (p,) = parts
        if np.issubdtype(dt, np.complexfloating):
            return _join_pairs_np(np.asarray(p), dt)
        return np.asarray(p).astype(dt, copy=False)


class Bf16Wire(Wire):
    """bfloat16 IQ pairs: truncated-mantissa float32 — 2× fewer bytes, no scale
    bookkeeping, graceful over any dynamic range (~40 dB SNR: display-grade)."""

    name = "bf16"
    nominal_snr_db = 54.0    # 8-bit mantissa: ~2^-9 relative error per sample

    def encode_may_alias(self, dtype) -> bool:
        return not _is_float(dtype)      # astype(bf16) materializes floats

    def _bf16(self):
        import ml_dtypes
        return ml_dtypes.bfloat16

    def bytes_per_sample(self, dtype) -> int:
        dt = np.dtype(dtype)
        if not _is_float(dt):
            return dt.itemsize
        return 4 if np.issubdtype(dt, np.complexfloating) else 2

    def encode_host(self, a):
        a = np.asarray(a)
        if np.issubdtype(a.dtype, np.complexfloating):
            return (_pairs_view(a.astype(np.complex64, copy=False))
                    .astype(self._bf16()),)
        if np.issubdtype(a.dtype, np.floating):
            return (a.astype(self._bf16()),)
        return (np.ascontiguousarray(a),)

    def decode_jax(self, parts, dtype):
        import jax
        import jax.numpy as jnp
        dt = np.dtype(dtype)
        (p,) = parts
        if np.issubdtype(dt, np.complexfloating):
            f = p.astype(jnp.float32)
            return jax.lax.complex(f[..., 0], f[..., 1])
        if np.issubdtype(dt, np.floating):
            return p.astype(jnp.float32)
        return p

    def encode_jax(self, y):
        import jax.numpy as jnp
        if jnp.iscomplexobj(y):
            return (jnp.stack([y.real, y.imag], axis=-1).astype(jnp.bfloat16),)
        if np.issubdtype(y.dtype, np.floating):
            return (y.astype(jnp.bfloat16),)
        return (y,)

    def decode_host(self, parts, dtype):
        dt = np.dtype(dtype)
        (p,) = parts
        p = np.asarray(p)
        if np.issubdtype(dt, np.complexfloating):
            return _join_pairs_np(p.astype(np.float32), dt)
        if np.issubdtype(dt, np.floating):
            return p.astype(np.float32).astype(dt, copy=False)
        return p


class _QuantWire(Wire):
    """Block-floating-point int IQ: ``q = round(x * qmax / scale)`` with
    ``scale = max(|I|,|Q|)`` over the frame (one float32 riding beside the
    payload). Quantization error is uniform in ±scale/(2·qmax) →
    SNR ≈ 6.02·bits + 1.76 − PAPR dB relative to the frame peak.

    Non-finite samples are ZEROED on encode, both host- and device-side: an
    int wire cannot carry inf/NaN, and letting one bad sample poison the
    frame scale would overflow/wrap every finite neighbour — zeroing loses
    only the already-meaningless sample."""

    itype: np.dtype
    qmax: float

    def encode_may_alias(self, dtype) -> bool:
        return not _is_float(dtype)      # quantization materializes floats

    def bytes_per_sample(self, dtype) -> int:
        dt = np.dtype(dtype)
        if not _is_float(dt):
            return dt.itemsize
        unit = np.dtype(self.itype).itemsize
        return 2 * unit if np.issubdtype(dt, np.complexfloating) else unit

    def _flat_host(self, a: np.ndarray):
        if np.issubdtype(a.dtype, np.complexfloating):
            return _pairs_view(a.astype(np.complex64, copy=False))
        return a.astype(np.float32, copy=False)

    def encode_host(self, a):
        a = np.asarray(a)
        if not _is_float(a.dtype):
            return (np.ascontiguousarray(a),)
        flat = self._flat_host(a)
        peak = float(np.max(np.abs(flat))) if flat.size else 0.0
        if not np.isfinite(peak):
            # non-finite samples (upstream divide-by-zero, AGC transients)
            # cannot ride an int wire; ZERO them so the rest of the frame
            # survives — without this the scale fallback would let every
            # finite sample overflow/wrap the int payload
            flat = np.where(np.isfinite(flat), flat, np.float32(0.0))
            peak = float(np.max(np.abs(flat))) if flat.size else 0.0
        if peak <= 0.0:
            peak = 1.0
        q = np.round(flat * (self.qmax / peak)).astype(self.itype)
        return (q, np.float32(peak))

    def encode_into(self, a, alloc):
        """Arena path: the int payload lands in a recycled buffer; the
        float scratch is a pool temp released before returning. The math is
        exactly :meth:`encode_host`'s (multiply → round → cast), so the
        parts are bit-identical to the allocating path."""
        a = np.asarray(a)
        if not _is_float(a.dtype):
            return (np.ascontiguousarray(a),)
        flat = self._flat_host(a)
        peak = float(np.max(np.abs(flat))) if flat.size else 0.0
        if not np.isfinite(peak):
            flat = np.where(np.isfinite(flat), flat, np.float32(0.0))
            peak = float(np.max(np.abs(flat))) if flat.size else 0.0
        if peak <= 0.0:
            peak = 1.0
        scratch = alloc.temp(flat.shape, np.float32)
        np.multiply(flat, np.float32(self.qmax / peak), out=scratch)
        np.round(scratch, out=scratch)
        q = alloc(flat.shape, self.itype)
        np.copyto(q, scratch, casting="unsafe")
        alloc.drop_temps()
        return (q, np.float32(peak))

    def decode_jax(self, parts, dtype):
        import jax
        import jax.numpy as jnp
        dt = np.dtype(dtype)
        if not _is_float(dt):
            return parts[0]
        q, scale = parts
        x = q.astype(jnp.float32) * (scale.astype(jnp.float32) / self.qmax)
        if np.issubdtype(dt, np.complexfloating):
            return jax.lax.complex(x[..., 0], x[..., 1])
        return x

    def pair_words(self, shape, part_dtype, dtype) -> bool:
        # a little-endian int16 I/Q pair IS one 32-bit word: I the low half,
        # Q the high half (sc8 packs two samples a word, a real dtype two
        # int16 samples: neither has a word-a-sample form)
        return (np.dtype(part_dtype) == np.dtype(self.itype) == np.int16
                and len(shape) >= 2 and shape[-1] == 2
                and np.issubdtype(np.dtype(dtype), np.complexfloating))

    def decode_words_jax(self, parts: Sequence, dtype):
        """:meth:`decode_jax` for a payload still in the 32-bit words it
        crossed the link in (:meth:`pair_words`): ``parts`` is ``(int32
        words [...], scale)``, one complex sample a word, split by two
        shifts. Same convert and same one multiply per component as
        :meth:`decode_jax`, so every sample is bit-identical to it, and no
        array with a minor dimension of 2 exists on the way (such an array is
        padded to 128 lanes on the TPU: ``docs/tpu_notes.md``)."""
        import jax
        import jax.numpy as jnp
        w, scale = parts
        s = scale.astype(jnp.float32) / self.qmax
        sh = jnp.int32(16)
        i = jax.lax.shift_right_arithmetic(jax.lax.shift_left(w, sh), sh)
        q = jax.lax.shift_right_arithmetic(w, sh)
        return jax.lax.complex(i.astype(jnp.float32) * s,
                               q.astype(jnp.float32) * s)

    def encode_jax(self, y):
        import jax.numpy as jnp
        if jnp.iscomplexobj(y):
            flat = jnp.stack([y.real, y.imag], axis=-1)
        elif np.issubdtype(y.dtype, np.floating):
            flat = y.astype(jnp.float32)
        else:
            return (y,)
        flat = flat.astype(jnp.float32)
        # zero non-finite samples (host-side encode contract): the scale must
        # stay finite and finite neighbours must not overflow the int payload
        flat = jnp.where(jnp.isfinite(flat), flat, jnp.float32(0.0))
        if flat.size:
            peak = jnp.max(jnp.abs(flat)).astype(jnp.float32)
            scale = jnp.where(peak > 0, peak, jnp.float32(1.0))
        else:
            scale = jnp.float32(1.0)
        q = jnp.round(flat * (self.qmax / scale)).astype(self.itype)
        return (q, scale)

    def decode_host(self, parts, dtype):
        dt = np.dtype(dtype)
        if not _is_float(dt):
            return np.asarray(parts[0])
        q, scale = parts
        x = np.asarray(q).astype(np.float32) * \
            (np.float32(np.asarray(scale)) / np.float32(self.qmax))
        if np.issubdtype(dt, np.complexfloating):
            return _join_pairs_np(x, dt)
        return x.astype(dt, copy=False)


class Sc16Wire(_QuantWire):
    name = "sc16"
    itype = np.int16
    qmax = 32767.0
    nominal_snr_db = 90.0


class Sc8Wire(_QuantWire):
    name = "sc8"
    itype = np.int8
    qmax = 127.0
    nominal_snr_db = 41.0    # 6.02·7 + 1.76 − Gaussian PAPR


WIRE_FORMATS = {w.name: w for w in (F32Wire(), Bf16Wire(), Sc16Wire(), Sc8Wire())}


def wire_names() -> tuple:
    return tuple(WIRE_FORMATS)


def get_wire(w) -> Wire:
    """``"sc16"`` / Wire instance → Wire instance; raises on unknown names."""
    if isinstance(w, Wire):
        return w
    try:
        return WIRE_FORMATS[str(w)]
    except KeyError:
        raise KeyError(f"unknown wire format {w!r}; "
                       f"known: {sorted(WIRE_FORMATS)}") from None


def resolve_wire(w, platform: str) -> Wire:
    """Resolve a user/config wire choice for a backend platform.

    ``None`` reads ``config().tpu_wire_format`` (env override:
    ``FUTURESDR_TPU_WIRE_FORMAT``). ``"auto"`` picks ``f32`` on the CPU backend
    (the "link" is a memcpy — quantization would only add an encode pass and
    noise) and ``sc16`` elsewhere (half the bytes at ~-90 dB, far below any RF
    noise floor; :func:`futuresdr_tpu.tpu.autotune.autotune_streamed` refines
    the choice against the measured link envelope)."""
    if w is None:
        from ..config import config
        w = config().tpu_wire_format
    if isinstance(w, str) and w == "auto":
        w = "f32" if platform == "cpu" else "sc16"
    wire = get_wire(w)
    _note_wire_gauges(wire)
    return wire


_noted_wires: set = set()


def _note_wire_gauges(wire: Wire) -> None:
    """Stamp the telemetry gauges for a wire format the first time a block
    resolves it: measured codec SNR (one host round trip, ~ms) and the
    per-sample byte widths — so ``GET /metrics`` carries the rate/fidelity
    tradeoff of every codec actually in use."""
    if wire.name in _noted_wires:
        return
    _noted_wires.add(wire.name)
    try:
        from ..telemetry import prom
        prom.gauge("fsdr_wire_snr_db",
                   "measured codec SNR of one link crossing (c64 payload)",
                   ("wire",)).set(measure_snr_db(wire), wire=wire.name)
        prom.gauge("fsdr_wire_bytes_per_sample",
                   "wire bytes per complex64 sample",
                   ("wire",)).set(wire.bytes_per_sample(np.complex64),
                                  wire=wire.name)
    except Exception:                    # pragma: no cover — never block a
        _noted_wires.discard(wire.name)  # kernel build on telemetry



def measure_snr_db(wire, dtype=np.complex64, n: int = 8192,
                   seed: int = 0) -> float:
    """MEASURED codec SNR in dB: a host encode→decode round trip over a
    unit-power Gaussian frame (part layouts are direction-symmetric, so this is
    exactly the quantization one link crossing applies). ``inf`` for exact
    formats. ``pick_wire`` refuses a format whose measured SNR is under its
    floor; the ``fsdr_wire_snr_db`` gauge exports it."""
    wire = get_wire(wire)
    rng = np.random.default_rng(seed)
    dt = np.dtype(dtype)
    if not _is_float(dt):
        return float("inf")       # int payloads pass through every wire losslessly
    if np.issubdtype(dt, np.complexfloating):
        x = ((rng.standard_normal(n) + 1j * rng.standard_normal(n))
             / np.sqrt(2)).astype(np.complex64)
    else:
        x = rng.standard_normal(n).astype(np.float32)
    y = wire.decode_host(wire.encode_host(x), dt)
    err = float(np.mean(np.abs(y - x) ** 2))
    if err == 0.0:
        return float("inf")
    sig = float(np.mean(np.abs(x) ** 2))
    return 10.0 * np.log10(sig / err)


def streamed_ceiling_msps(wire, h2d_Bps: float, d2h_Bps: float,
                          in_dtype=np.complex64, out_dtype=np.float32,
                          out_per_in: float = 1.0) -> float:
    """Link-bounded streamed ceiling for one wire format, in Msamples/s:
    ``min(h2d / up_bytes, d2h / (down_bytes · out_per_in))``. The duplex
    directions overlap when frames are in flight, so the binding one is the
    slower, not the sum."""
    w = get_wire(wire)
    up = w.bytes_per_sample(in_dtype)
    down = w.bytes_per_sample(out_dtype) * max(out_per_in, 1e-12)
    return min(h2d_Bps / up, d2h_Bps / down) / 1e6
