"""Pallas TPU kernels for the streaming hot ops.

Hand-written kernels for cases XLA's fusion doesn't cover well (the dataflow-shaped
kernel argument of Flex-TPU, arXiv:2407.08700):

* the short-tap streaming FIR (direct form beats FFT overlap-save below ~32 taps) as an
  unrolled shifted multiply-accumulate on the VPU, with the inter-block overlap handled
  by passing each grid step both its own input block and its left neighbour (no
  overlapping BlockSpecs needed);
* the fused PFB channelizer (:func:`pallas_pfb`): polyphase partition MAC + the
  twiddle-feed IDFT across branches as one kernel — the intermediate ``v[t, c]`` bank
  never round-trips HBM between the branch filters and the branch transform, which is
  exactly the HBM-bound half of the ``blocks/pfb.py`` / ``ops/stages.channelizer_stage``
  matmul path;
* the fused FIR→decimate kernel (:func:`pallas_poly_fir`): the shifted-row polyphase
  factorization of ``ops/stages._poly_decim_fir_stage`` computed at the DECIMATED rate
  inside one kernel (ntaps/D MACs per input sample, no full-rate intermediate) — a 3-D
  weight tensor runs the same kernel per interpolation phase, which is the resampler's
  polyphase inner loop;
* the fused FIR→FFT kernel (:func:`pallas_fir_fft`): filter + windowed DFT in one
  kernel — the filtered frame never round-trips HBM between the FIR and the transform,
  which is the resident fir64+fft2048 chain's whole interior edge;
* the rotator / quadrature-demod inner loops (:func:`pallas_rotator`,
  :func:`pallas_quad_demod`): phase-ramp multiply and ``angle(x·conj(x₋₁))`` over 2-D
  lane tiles, the remaining elementwise hot loops of the FM chain.

Every kernel takes ``precision="bf16"`` for the interior-precision policy
(``ops/precision.py``): operands are cast to bfloat16 and accumulated in float32 —
on the MXU this is the native-speed pass; on CPU/interpret it applies exactly the same
quantization, so SNR calibration measures the real thing. (The int8 rung does NOT run
through these kernels — it lowers to quantized XLA matmuls in ``ops/stages``.)

Block shapes: every kernel's ``block`` parameter defaults to ``None`` = "resolve
through the autotuned table" (:func:`set_tuned_blocks`, installed at kernel init from
the ``pallas_blocks`` autotune-cache axis swept by ``tpu/pallas_tune.py``), falling
back to the hand-picked :data:`DEFAULT_BLOCKS`. Stage-level callers pass no block, so
a measured sweep reaches every ``impl="pallas"`` stage without re-plumbing.

``interpret=None`` (every stage-level call) resolves from the backend: Mosaic on a TPU
— never the interpreter implicitly, and no caller catches a compile error to fall back
to XLA or to it — and interpret mode elsewhere, where numerics are identical, so CI
validates the kernels on CPU. All six compile and match their XLA routes on a v5e at
the repo's shapes (``chip_smoke.py`` ``pallas`` phase; ``tests/test_on_chip.py``).
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pallas_fir", "pallas_fir_continue", "pallas_fir_stage",
           "pallas_pfb", "pallas_poly_fir", "pallas_fir_fft",
           "pallas_rotator", "pallas_quad_demod",
           "DEFAULT_BLOCKS", "set_tuned_blocks", "tuned_blocks"]

# ---------------------------------------------------------------------------
# tuned block shapes (the Pallas autotune plane, tpu/pallas_tune.py)
# ---------------------------------------------------------------------------

#: hand-picked fallback block shapes per kernel — the pre-autotune defaults
#: (``fir``/``poly_fir`` in samples / decimated rows, ``pfb`` in commutated
#: time rows, ``fir_fft`` in transform rows, ``rotator``/``quad_demod`` in
#: 128-lane rows). Always part of the sweep's candidate set, so a recorded
#: winner is never a regression against them.
DEFAULT_BLOCKS: Dict[str, int] = {
    "fir": 4096, "pfb": 256, "poly_fir": 1024, "fir_fft": 8,
    "rotator": 256, "quad_demod": 256,
}

_tuned_lock = threading.Lock()
_tuned: Dict[str, int] = {}


def set_tuned_blocks(blocks: Optional[Dict[str, int]]) -> None:
    """Install measured block shapes process-wide (``None``/``{}`` clears).
    Unknown kernel keys and non-positive values are IGNORED, not raised —
    a stale cache entry from an older repo revision must never wedge kernel
    init (mirrors the autotune cache's per-axis guarded-parse contract)."""
    with _tuned_lock:
        _tuned.clear()
        for k, v in (blocks or {}).items():
            try:
                v = int(v)
            except (TypeError, ValueError):
                continue
            if k in DEFAULT_BLOCKS and v > 0:
                _tuned[k] = v


def tuned_blocks() -> Dict[str, int]:
    """The active block table: measured winners over the defaults."""
    with _tuned_lock:
        return {**DEFAULT_BLOCKS, **_tuned}


def _resolve_block(kernel: str, block: Optional[int]) -> int:
    """``block=None`` (the stage-level calling convention) → the tuned table;
    an explicit block always wins (tests pin odd shapes through it)."""
    if block is not None:
        return int(block)
    with _tuned_lock:
        return int(_tuned.get(kernel, DEFAULT_BLOCKS[kernel]))


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` → compiled by Mosaic on a TPU backend, interpreted elsewhere."""
    return jax.default_backend() != "tpu" if interpret is None else bool(interpret)


def _maybe_bf16(*arrays, bf16: bool):
    if not bf16:
        return arrays if len(arrays) > 1 else arrays[0]
    out = tuple(a.astype(jnp.bfloat16) for a in arrays)
    return out if len(out) > 1 else out[0]


def _fir_kernel(prev_ref, cur_ref, taps_ref, o_ref, *, n_taps: int, block: int,
                bf16: bool = False):
    """One grid step: y[i] = Σ_k taps[k] · x[i − k] over this block, using the previous
    block's tail for the first n_taps−1 outputs."""
    full = jnp.concatenate([prev_ref[...], cur_ref[...]])       # [2·block]
    taps = taps_ref[...]
    full, taps = _maybe_bf16(full, taps, bf16=bf16)
    acc = jnp.zeros((block,), jnp.float32)
    base = block - (n_taps - 1)
    for k in range(n_taps):                                     # static unroll
        # static slice offsets (k is a Python int) — dynamic_slice has no Mosaic
        # TC lowering; static lax.slice does
        acc = acc + (taps[n_taps - 1 - k]
                     * full[base + k:base + k + block]).astype(jnp.float32)
    o_ref[...] = acc


def pallas_fir(x: jnp.ndarray, taps, block: Optional[int] = None,
               interpret: Optional[bool] = None,
               precision: Optional[str] = None) -> jnp.ndarray:
    """Causal FIR of a float32 frame (zero initial state): len(x) must divide ``block``
    (default: the tuned table's ``"fir"`` shape).

    Complex frames are filtered as two real passes at the wrapper level
    (:func:`pallas_fir_stage`). ``precision="bf16"`` runs the MAC with bfloat16
    operands and float32 accumulation (module docstring).
    """
    block = _resolve_block("fir", block)
    taps = jnp.asarray(taps)
    if not jnp.issubdtype(taps.dtype, jnp.bfloat16):
        taps = taps.astype(jnp.float32)
    n_taps = taps.shape[0]
    assert block >= n_taps, "block must exceed the tap count"
    n = x.shape[0]
    assert n % block == 0, f"frame ({n}) must be a multiple of block ({block})"
    grid = n // block
    interpret = _resolve_interpret(interpret)

    # block i sees: prev = x[(i-1)·block : i·block] (block 0 → block of zeros via the
    # leading pad), cur = x[i·block : (i+1)·block]
    xp = jnp.concatenate([jnp.zeros(block, x.dtype), x])
    kernel = partial(_fir_kernel, n_taps=n_taps, block=block,
                     bf16=(precision == "bf16"))
    return pl.pallas_call(
        kernel,
        name="pallas_fir",
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),        # prev (offset by the pad)
            pl.BlockSpec((block,), lambda i: (i + 1,)),    # cur
            pl.BlockSpec((n_taps,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=interpret,
    )(xp, xp, taps)


def pallas_fir_continue(hist: jnp.ndarray, x: jnp.ndarray, taps: np.ndarray,
                        block: Optional[int] = None,
                        precision: Optional[str] = None) -> jnp.ndarray:
    """Streaming continuation: filter frame ``x`` given the previous ``n_taps-1``
    input samples in ``hist``. Pads to the kernel's block granularity, runs complex
    frames as two real passes, and returns exactly ``len(x)`` aligned outputs.
    Shared by :func:`pallas_fir_stage` and ``stages.fir_stage(impl="pallas")``.
    ``taps`` may be a traced device array (carry-resident, for runtime tap swap) —
    only its static shape is read here."""
    block = _resolve_block("fir", block)
    taps = jnp.asarray(taps)
    if not jnp.issubdtype(taps.dtype, jnp.bfloat16):
        taps = taps.astype(jnp.float32)
    nt = taps.shape[0]
    ext = jnp.concatenate([hist, x])               # [(nt-1) + n]
    pad = (-ext.shape[0]) % block
    if pad:
        ext = jnp.concatenate([ext, jnp.zeros(pad, ext.dtype)])
    if jnp.iscomplexobj(x):
        yr = pallas_fir(ext.real, taps, block, precision=precision)
        yi = pallas_fir(ext.imag, taps, block, precision=precision)
        y = (yr + 1j * yi).astype(x.dtype)
    else:
        y = pallas_fir(ext, taps, block, precision=precision).astype(x.dtype)
    return y[nt - 1:nt - 1 + x.shape[0]]


def pallas_fir_stage(taps, block: Optional[int] = None):
    """Streaming Stage (carry = tail samples) running the pallas kernel per frame; the
    drop-in alternative to :func:`futuresdr_tpu.ops.stages.fir_stage` for short taps."""
    from fractions import Fraction

    from .stages import Stage

    taps = np.asarray(taps, dtype=np.float32)
    nt = len(taps)

    def fn(carry, x):
        y = pallas_fir_continue(carry, x, taps, block)
        ext = jnp.concatenate([carry, x])
        return ext[ext.shape[0] - (nt - 1):], y

    def init_carry(dtype):
        return jnp.zeros(nt - 1, dtype=dtype)

    return Stage(fn, init_carry, Fraction(1, 1), None, 1, "pallas_fir")


# ---------------------------------------------------------------------------
# fused PFB channelizer: polyphase MAC + twiddle-feed IDFT in one kernel
# ---------------------------------------------------------------------------

def _pfb_kernel(prev_r, prev_i, cur_r, cur_i, taps_ref, er_ref, ei_ref,
                out_r, out_i, *, n_taps: int, block: int, bf16: bool):
    """One grid step over ``block`` commutated time rows: the branch-filter MAC
    ``v[s, c] = Σ_k taps[k, c] · rows[s + K−1 − k, c]`` (history rows ride in
    from the previous block, exactly the FIR kernel's neighbour trick), then
    the IDFT across branches as two real matmuls per output plane — the
    intermediate ``v`` bank lives only in VMEM."""
    fr = jnp.concatenate([prev_r[...], cur_r[...]])          # [2·block, N]
    fi = jnp.concatenate([prev_i[...], cur_i[...]])
    taps = taps_ref[...]                                     # [K, N]
    fr, fi, taps = _maybe_bf16(fr, fi, taps, bf16=bf16)
    acc_r = jnp.zeros(cur_r.shape, jnp.float32)
    acc_i = jnp.zeros(cur_i.shape, jnp.float32)
    for k in range(n_taps):                                  # static unroll
        t = taps[k]
        acc_r = acc_r + (t * fr[block - k:2 * block - k]).astype(jnp.float32)
        acc_i = acc_i + (t * fi[block - k:2 * block - k]).astype(jnp.float32)
    er, ei = er_ref[...], ei_ref[...]
    prec = (jax.lax.Precision.DEFAULT if bf16
            else jax.lax.Precision.HIGHEST)
    if bf16:
        acc_r, acc_i, er, ei = _maybe_bf16(acc_r, acc_i, er, ei, bf16=True)
    dot = partial(jnp.dot, preferred_element_type=jnp.float32,
                  precision=prec)
    # y = v @ E with E = exp(+2πi·cc'/N): 4 real matmuls (er=cos, ei=sin)
    out_r[...] = dot(acc_r, er) - dot(acc_i, ei)
    out_i[...] = dot(acc_r, ei) + dot(acc_i, er)


def pallas_pfb(rows: jnp.ndarray, taps_kn, block: Optional[int] = None,
               interpret: Optional[bool] = None,
               precision: Optional[str] = None) -> jnp.ndarray:
    """Fused critically-sampled PFB analysis bank over commutated rows.

    ``rows``: ``[t + K−1, N]`` complex64 — the channelizer's commutated block
    matrix WITH its K−1 history rows in front (``ops/stages.channelizer_stage``
    builds exactly this from its carry). ``taps_kn``: ``[K, N]`` branch taps at
    depth k (``branchᵀ`` — may be a carry-resident traced array, f32 or bf16).
    Returns ``[t, N]`` complex64 — bit-comparable to the matmul path's
    ``ifft(v) * N`` (same math, fused op order; tolerance-pinned in
    tests/test_pallas.py). ``precision="bf16"`` casts MAC/matmul operands to
    bfloat16 with float32 accumulation.
    """
    block = _resolve_block("pfb", block)
    K, N = taps_kn.shape
    R = rows.shape[0]
    t = R - (K - 1)
    bt = max(int(block), K)             # alignment needs bt ≥ K−1; K is safe
    assert t >= 1, "need at least one output row"
    interpret = _resolve_interpret(interpret)
    bf16 = precision == "bf16"
    rr = jnp.real(rows).astype(jnp.float32)
    ri = jnp.imag(rows).astype(jnp.float32)
    # pad t up to a block multiple with zero rows (their outputs are trimmed)
    t_pad = -(-t // bt) * bt
    tail = t_pad - t
    if tail:
        z = jnp.zeros((tail, N), jnp.float32)
        rr = jnp.concatenate([rr, z])
        ri = jnp.concatenate([ri, z])
    # causal alignment: front-pad so output row s reads full[bt + s − k]
    z0 = jnp.zeros((bt - (K - 1), N), jnp.float32)
    xr = jnp.concatenate([z0, rr])
    xi = jnp.concatenate([z0, ri])
    # twiddle-feed IDFT matrix built IN TRACE (a device constant, no host
    # transfer). The phase index reduces mod N BEFORE the float multiply:
    # cc' grows to ~N² and f32 rounding of 2π·cc'/N at large N costs ~10 dB
    # per octave of N (88 dB @ N=512 without the reduction vs near-exact
    # with it)
    c = jnp.arange(N)
    ang = 2 * jnp.pi * (jnp.outer(c, c) % N) / N
    er = jnp.cos(ang).astype(jnp.float32)
    ei = jnp.sin(ang).astype(jnp.float32)
    grid = t_pad // bt
    kern = partial(_pfb_kernel, n_taps=K, block=bt, bf16=bf16)
    out_r, out_i = pl.pallas_call(
        kern,
        name="pallas_pfb",
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((bt, N), lambda i: (i, 0)),       # prev rows (re)
            pl.BlockSpec((bt, N), lambda i: (i, 0)),       # prev rows (im)
            pl.BlockSpec((bt, N), lambda i: (i + 1, 0)),   # cur rows (re)
            pl.BlockSpec((bt, N), lambda i: (i + 1, 0)),   # cur rows (im)
            pl.BlockSpec((K, N), lambda i: (0, 0)),
            pl.BlockSpec((N, N), lambda i: (0, 0)),
            pl.BlockSpec((N, N), lambda i: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((bt, N), lambda i: (i, 0)),
                   pl.BlockSpec((bt, N), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((t_pad, N), jnp.float32),
                   jax.ShapeDtypeStruct((t_pad, N), jnp.float32)],
        interpret=interpret,
    )(xr, xi, xr, xi, taps_kn, er, ei)
    return jax.lax.complex(out_r[:t], out_i[:t])


# ---------------------------------------------------------------------------
# fused FIR→decimate: shifted-row polyphase MACs at the decimated rate
# ---------------------------------------------------------------------------

def _poly_fir_kernel(prev, cur, w_ref, o_ref, *, m: int, block: int,
                     bf16: bool):
    """One grid step of ``block`` decimated outputs: ``y[q] = Σ_a
    rows[q + m − a] · W[a]`` over the stride-D row matrix — m+1 [block, D]·[D]
    matvecs, the in-kernel form of ``ops/stages._shifted_matvec``. A 3-D
    weight tensor (``W[a]``: [D, I] — the resampler's phase-tap matrix) runs
    the same accumulation as m+1 [block, D]·[D, I] matmuls."""
    full = jnp.concatenate([prev[...], cur[...]])            # [2·block, D]
    W = w_ref[...]                                           # [m+1, D]
    full, W = _maybe_bf16(full, W, bf16=bf16)
    prec = (jax.lax.Precision.DEFAULT if bf16
            else jax.lax.Precision.HIGHEST)
    dot = partial(jnp.dot, preferred_element_type=jnp.float32,
                  precision=prec)
    acc = dot(full[block:2 * block], W[0])
    for a in range(1, m + 1):                                # static unroll
        acc = acc + dot(full[block - a:2 * block - a], W[a])
    o_ref[...] = acc


def pallas_poly_fir(rows: jnp.ndarray, W, block: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    precision: Optional[str] = None) -> jnp.ndarray:
    """Fused decimating FIR over the stride-D row matrix.

    ``rows``: ``[m + nq, D]`` float32 — the reshape of the history-extended
    input (``ext.reshape(-1, D)``, no copy); ``W``: ``[m+1, D]`` the shifted-row
    weight matrix (``ops/stages._poly_decim_weights`` — may be carry-resident,
    f32 or bf16, REAL taps only). Returns ``[nq]`` float32 decimated outputs —
    ntaps/D MACs per input sample with no full-rate intermediate (the fused
    FIR→decimate kernel). A 3-D ``W`` (``[m+1, D, I]`` — the resampler's
    phase-tap tensor, :func:`ops.stages.resample_stage`) returns ``[nq, I]``
    interpolated rows instead, same kernel. Complex frames run as two real
    passes at the stage level. ``precision="bf16"`` casts operands to
    bfloat16, accumulates f32.
    """
    block = _resolve_block("poly_fir", block)
    m1, D = W.shape[0], W.shape[1]
    m = m1 - 1
    nq = rows.shape[0] - m
    assert nq >= 1, "need at least one output row"
    bq = max(int(block), m)             # slice starts need bq ≥ m
    interpret = _resolve_interpret(interpret)
    rows = rows.astype(jnp.float32)
    nq_pad = -(-nq // bq) * bq
    tail = nq_pad - nq
    if tail:
        rows = jnp.concatenate([rows, jnp.zeros((tail, D), jnp.float32)])
    # causal alignment: front-pad so output q reads full[bq + q − a]
    xp = jnp.concatenate([jnp.zeros((bq - m, D), jnp.float32), rows])
    grid = nq_pad // bq
    kern = partial(_poly_fir_kernel, m=m, block=bq,
                   bf16=(precision == "bf16"))
    if W.ndim == 3:
        I = W.shape[2]
        w_spec = pl.BlockSpec((m + 1, D, I), lambda i: (0, 0, 0))
        out_specs = pl.BlockSpec((bq, I), lambda i: (i, 0))
        out_shape = jax.ShapeDtypeStruct((nq_pad, I), jnp.float32)
    else:
        w_spec = pl.BlockSpec((m + 1, D), lambda i: (0, 0))
        out_specs = pl.BlockSpec((bq,), lambda i: (i,))
        out_shape = jax.ShapeDtypeStruct((nq_pad,), jnp.float32)
    y = pl.pallas_call(
        kern,
        name="pallas_poly_fir",
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((bq, D), lambda i: (i, 0)),       # prev rows
            pl.BlockSpec((bq, D), lambda i: (i + 1, 0)),   # cur rows
            w_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(xp, xp, W)
    return y[:nq]


# ---------------------------------------------------------------------------
# fused FIR→FFT: filter + windowed DFT with no HBM round-trip between them
# ---------------------------------------------------------------------------

def _fir_fft_kernel(prev_r, prev_i, cur_r, cur_i, taps_ref, er_ref, ei_ref,
                    out_r, out_i, acc_r, acc_i, *, n_taps: int, block: int,
                    n_fft: int, bf16: bool):
    """One grid step ``(i, j)``: ``block`` transform rows of ``n_fft`` samples
    against twiddle column tile ``j``. At ``j == 0`` the FIR MAC over the
    row-major stream (sample shifts that cross a row boundary read the tail of
    the row above — the 1-D neighbour trick lifted to 2-D row tiles) lands in
    the VMEM scratch ``acc``; every ``j`` then runs the forward DFT of those
    rows against its ``[n_fft, tile]`` twiddle columns as four real matmuls.
    The filtered rows live only in VMEM between the two halves — that
    intermediate is exactly the resident chain's fir→fft HBM edge. The column
    tiling is what keeps the twiddles inside VMEM: a whole ``[2048, 2048]``
    f32 pair is 32 MiB against v5e's 16 MiB scoped default."""

    @pl.when(pl.program_id(1) == 0)
    def _filter():
        taps = _maybe_bf16(taps_ref[...], bf16=bf16)
        col = jax.lax.broadcasted_iota(jnp.int32, cur_r.shape, 1)

        def _mac(prev, cur):
            # S_k[r, c] = stream[r·n_fft + c − k] for the rows of the CUR tile:
            # a lane rotation by k, whose first k columns come from the row
            # above instead of wrapping around
            cur = cur[...]
            above = jnp.concatenate([prev[block - 1:block, :], cur])[:block]
            v = jnp.zeros(cur.shape, jnp.float32)
            for k in range(n_taps):                          # static unroll
                s_k = cur if k == 0 else jnp.where(
                    col < k, pltpu.roll(above, k, 1), pltpu.roll(cur, k, 1))
                v = v + (taps[k] * _maybe_bf16(s_k, bf16=bf16)
                         ).astype(jnp.float32)
            return v

        acc_r[...] = _mac(prev_r, cur_r)
        acc_i[...] = _mac(prev_i, cur_i)

    vr, vi = acc_r[...], acc_i[...]
    er, ei = er_ref[...], ei_ref[...]
    prec = (jax.lax.Precision.DEFAULT if bf16
            else jax.lax.Precision.HIGHEST)
    if bf16:
        vr, vi, er, ei = _maybe_bf16(vr, vi, er, ei, bf16=True)
    dot = partial(jnp.dot, preferred_element_type=jnp.float32,
                  precision=prec)
    # Y = v @ E with E = exp(−2πi·cj/N) = er − i·ei (forward DFT sign)
    out_r[...] = dot(vr, er) + dot(vi, ei)
    out_i[...] = dot(vi, er) - dot(vr, ei)


#: twiddle column tile of the fused FIR→FFT kernel: [n_fft, 256] f32 is 2 MiB
#: at n_fft=2048, so the cos/sin pair double-buffered is 8 MiB of VMEM
_FIR_FFT_COLS = 256
#: scoped-VMEM request of that kernel. Mosaic's own account at the headline
#: shape (64 taps, n_fft=2048, 8 rows, f32 HIGHEST matmuls) is 24.6 MiB —
#: over the 16 MiB scoped default, well inside v5e's 128 MiB of VMEM
_FIR_FFT_VMEM = 64 << 20


def pallas_fir_fft(hist: jnp.ndarray, x: jnp.ndarray, taps, n_fft: int,
                   block: Optional[int] = None,
                   interpret: Optional[bool] = None,
                   precision: Optional[str] = None) -> jnp.ndarray:
    """Fused FIR → windowed forward FFT: ``fft(filtered.reshape(-1, n_fft))``
    flattened, without materializing the filtered stream in HBM.

    ``hist``: the previous ``n_taps−1`` input samples (carry-resident);
    ``x``: the frame, ``len(x) % n_fft == 0``; ``taps``: REAL taps (may be a
    traced carry array), ``n_taps ≤ n_fft`` (a shift never reaches past the
    row directly above). ``block`` counts transform ROWS per grid step
    (default: the tuned table's ``"fir_fft"`` shape — ragged row counts are
    zero-padded and trimmed). Complex frames filter both planes with the real
    taps and transform once. ``precision="bf16"`` casts the MAC and DFT
    matmul operands to bfloat16 with float32 accumulation.
    """
    block = _resolve_block("fir_fft", block)
    taps = jnp.asarray(taps)
    if not jnp.issubdtype(taps.dtype, jnp.bfloat16):
        taps = taps.astype(jnp.float32)
    nt = taps.shape[0]
    n = x.shape[0]
    assert n % n_fft == 0, f"frame ({n}) must be a multiple of n_fft ({n_fft})"
    assert nt <= n_fft, "fused FIR→FFT requires n_taps <= n_fft"
    interpret = _resolve_interpret(interpret)
    B = max(1, int(block))
    R = n // n_fft
    R_pad = -(-R // B) * B

    def _plane(p):
        # history row: the nt−1 carry samples land at the END of the row
        # directly above the frame's first row, zeros elsewhere
        pad_row = jnp.concatenate(
            [jnp.zeros(n_fft - (nt - 1), jnp.float32), p[:nt - 1]])
        rows = jnp.concatenate([pad_row[None, :],
                                p[nt - 1:].reshape(R, n_fft)])
        z0 = jnp.zeros((B - 1, n_fft), jnp.float32)
        ztail = jnp.zeros((R_pad - R, n_fft), jnp.float32)
        return jnp.concatenate([z0, rows, ztail])        # [B + R_pad, n_fft]

    if jnp.iscomplexobj(x):
        full = jnp.concatenate([hist, x])
        pr = _plane(full.real.astype(jnp.float32))
        pi = _plane(full.imag.astype(jnp.float32))
    else:
        full = jnp.concatenate([hist, x]).astype(jnp.float32)
        pr = _plane(full)
        pi = jnp.zeros_like(pr)
    # forward-DFT twiddles built IN TRACE, phase index reduced mod N before
    # the float multiply (same reasoning as pallas_pfb's IDFT matrix)
    c = jnp.arange(n_fft)
    ang = 2 * jnp.pi * (jnp.outer(c, c) % n_fft) / n_fft
    er = jnp.cos(ang).astype(jnp.float32)
    ei = jnp.sin(ang).astype(jnp.float32)
    tn = min(n_fft, _FIR_FFT_COLS)
    assert n_fft % tn == 0, f"n_fft ({n_fft}) must be a multiple of {tn}"
    kern = partial(_fir_fft_kernel, n_taps=nt, block=B, n_fft=n_fft,
                   bf16=(precision == "bf16"))
    out_r, out_i = pl.pallas_call(
        kern,
        name="pallas_fir_fft",
        grid=(R_pad // B, n_fft // tn),          # column tiles innermost
        in_specs=[
            pl.BlockSpec((B, n_fft), lambda i, j: (i, 0)),      # prev rows (re)
            pl.BlockSpec((B, n_fft), lambda i, j: (i, 0)),      # prev rows (im)
            pl.BlockSpec((B, n_fft), lambda i, j: (i + 1, 0)),  # cur rows (re)
            pl.BlockSpec((B, n_fft), lambda i, j: (i + 1, 0)),  # cur rows (im)
            pl.BlockSpec((nt,), lambda i, j: (0,)),
            pl.BlockSpec((n_fft, tn), lambda i, j: (0, j)),
            pl.BlockSpec((n_fft, tn), lambda i, j: (0, j)),
        ],
        out_specs=[pl.BlockSpec((B, tn), lambda i, j: (i, j)),
                   pl.BlockSpec((B, tn), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((R_pad, n_fft), jnp.float32),
                   jax.ShapeDtypeStruct((R_pad, n_fft), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((B, n_fft), jnp.float32),
                        pltpu.VMEM((B, n_fft), jnp.float32)],
        # the scratch carries the filtered rows across the column axis
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_FIR_FFT_VMEM),
        interpret=interpret,
    )(pr, pi, pr, pi, taps, er, ei)
    return jax.lax.complex(out_r[:R], out_i[:R]).reshape(-1)


# ---------------------------------------------------------------------------
# rotator / quadrature-demod inner loops over 2-D lane tiles
# ---------------------------------------------------------------------------

_LANES = 128      # TPU vector lane width — the tile minor dimension


def _rotator_kernel(xr, xi, p_ref, or_, oi_, *, block: int):
    """One grid step of ``block`` 128-lane rows: y = x · exp(i·(ph0 + inc·t))
    with the absolute sample index rebuilt from the grid position (2-D int32
    iota — Mosaic's iota has no 1-D and no float form)."""
    ph0 = p_ref[0:1, :]                                  # [1, 128] broadcast rows
    inc = p_ref[1:2, :]
    g = pl.program_id(0)
    r = jax.lax.broadcasted_iota(jnp.int32, (block, _LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (block, _LANES), 1)
    t = ((g * block + r) * _LANES + c).astype(jnp.float32)
    ph = ph0 + inc * t
    cr = jnp.cos(ph)
    si = jnp.sin(ph)
    or_[...] = xr[...] * cr - xi[...] * si
    oi_[...] = xr[...] * si + xi[...] * cr


def pallas_rotator(x: jnp.ndarray, ph0, inc,
                   block: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Phase-ramp rotator ``y[t] = x[t] · exp(i·(ph0 + inc·t))`` over 2-D
    lane tiles — the in-kernel form of ``ops/stages.rotator_stage``'s inner
    loop. ``ph0``/``inc`` may be traced carry scalars; ragged frames are
    zero-padded to the tile grid and trimmed."""
    block = max(1, _resolve_block("rotator", block))
    interpret = _resolve_interpret(interpret)
    n = x.shape[0]
    tile = block * _LANES
    n_pad = -(-n // tile) * tile
    xr = jnp.real(x).astype(jnp.float32)
    xi = jnp.imag(x).astype(jnp.float32)
    if n_pad != n:
        z = jnp.zeros(n_pad - n, jnp.float32)
        xr = jnp.concatenate([xr, z])
        xi = jnp.concatenate([xi, z])
    rows = n_pad // _LANES
    xr = xr.reshape(rows, _LANES)
    xi = xi.reshape(rows, _LANES)
    # carry scalars ride a broadcast VMEM row (no SMEM plumbing needed):
    # row 0 = ph0, row 1 = inc
    params = jnp.stack([jnp.broadcast_to(jnp.float32(ph0), (_LANES,)),
                        jnp.broadcast_to(jnp.float32(inc), (_LANES,))])
    kern = partial(_rotator_kernel, block=block)
    out_r, out_i = pl.pallas_call(
        kern,
        name="pallas_rotator",
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((2, _LANES), lambda i: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
                   pl.BlockSpec((block, _LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)],
        interpret=interpret,
    )(xr, xi, params)
    y = jax.lax.complex(out_r.reshape(-1), out_i.reshape(-1))
    return y[:n].astype(jnp.complex64)


def _atan2(y, x):
    """float32 ``atan2`` from VPU primitives — Mosaic lowers no ``atan2``/``atan``
    (jax 0.9.0). Octant reduction to ``t = min/max ∈ [0, 1]``, one more fold at
    tan(π/8), then the Cephes ``atanf`` odd polynomial (|err| ≲ 2e-7 rad).
    ``atan2(0, 0) = 0`` like ``jnp.angle``; a −0.0 imaginary part counts as +0."""
    ax, ay = jnp.abs(x), jnp.abs(y)
    mx, mn = jnp.maximum(ax, ay), jnp.minimum(ax, ay)
    t = mn / jnp.where(mx == 0.0, 1.0, mx)
    fold = t > 0.4142135623730950                        # tan(π/8)
    u = jnp.where(fold, (t - 1.0) / (t + 1.0), t)
    z = u * u
    a = ((((8.05374449538e-2 * z - 1.38776856032e-1) * z
           + 1.99777106478e-1) * z - 3.33329491539e-1) * z * u + u)
    a = jnp.where(fold, a + np.float32(np.pi / 4), a)
    a = jnp.where(ay > ax, np.float32(np.pi / 2) - a, a)
    a = jnp.where(x < 0.0, np.float32(np.pi) - a, a)
    return jnp.where(y < 0.0, -a, a)


def _quad_demod_kernel(prev_r, prev_i, cur_r, cur_i, g_ref, o_ref, *,
                       block: int):
    """One grid step: y[t] = gain · atan2(im, re) of x[t]·conj(x[t−1]) — the
    one-sample shift reads the previous tile's last lane row (the FIR
    neighbour trick at shift 1, lifted to 2-D tiles)."""
    gain = g_ref[...]                                    # [1, 128] broadcast row
    ar = jnp.concatenate([prev_r[...], cur_r[...]])      # [2·block, 128]
    ai = jnp.concatenate([prev_i[...], cur_i[...]])

    def _shift1(a):
        left = a[block - 1:2 * block - 1, _LANES - 1:]
        right = a[block:2 * block, :_LANES - 1]
        return jnp.concatenate([left, right], axis=1)

    xr, xi = ar[block:2 * block], ai[block:2 * block]
    pr, pi = _shift1(ar), _shift1(ai)
    zr = xr * pr + xi * pi
    zi = xi * pr - xr * pi
    o_ref[...] = gain * _atan2(zi, zr)


def pallas_quad_demod(prev, x: jnp.ndarray, gain,
                      block: Optional[int] = None,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """Quadrature (FM) demod ``y[t] = gain · angle(x[t] · conj(x[t−1]))``
    over 2-D lane tiles — the in-kernel form of
    ``ops/stages.quad_demod_stage``'s inner loop. ``prev`` is the carry's
    last sample of the previous frame (a traced scalar); ragged frames are
    zero-padded and trimmed."""
    block = max(1, _resolve_block("quad_demod", block))
    interpret = _resolve_interpret(interpret)
    n = x.shape[0]
    tile = block * _LANES
    n_pad = -(-n // tile) * tile
    # the stream with its one-sample history in front; the pad keeps tile
    # rows aligned so sample t sits at flat index t + tile
    ext = jnp.concatenate([jnp.zeros(tile - 1, x.dtype),
                           jnp.reshape(prev, (1,)).astype(x.dtype), x])
    if n_pad != n:
        ext = jnp.concatenate([ext, jnp.zeros(n_pad - n, x.dtype)])
    xr = jnp.real(ext).astype(jnp.float32).reshape(-1, _LANES)
    xi = jnp.imag(ext).astype(jnp.float32).reshape(-1, _LANES)
    g = jnp.broadcast_to(jnp.float32(gain), (1, _LANES))
    kern = partial(_quad_demod_kernel, block=block)
    y = pl.pallas_call(
        kern,
        name="pallas_quad_demod",
        grid=(n_pad // tile,),
        in_specs=[
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),      # prev tile
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((block, _LANES), lambda i: (i + 1, 0)),  # cur tile
            pl.BlockSpec((block, _LANES), lambda i: (i + 1, 0)),
            pl.BlockSpec((1, _LANES), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad // _LANES, _LANES),
                                       jnp.float32),
        interpret=interpret,
    )(xr, xi, xr, xi, g)
    return y.reshape(-1)[:n]
