"""MXU-mapped FFT: the DFT as batched matrix products (four-step algorithm).

XLA's TPU FFT lowers to a vector-unit kernel that measures ~3 Gsamples/s for batched
2048-point complex64 FFTs on a v5e chip, leaving the MXU (where the chip's FLOPs live)
idle. This module runs the same transform as two matmul passes — the classic four-step
decomposition N = N1·N2:

    X[k1 + N1·k2] = Σ_b W_N^{b·k1} · ( Σ_a x[a·N2 + b] · W_N1^{a·k1} ) · W_N2^{b·k2}

i.e. ``DFT_N1 @ A`` (columns), a twiddle multiply, and ``C @ DFT_N2ᵀ`` (rows) — both
matmuls batched over frames and mapped onto the systolic array. Measured on-chip
(docs/tpu_notes.md): ~5.5 Gsps at float32 matmul precision (rel err ~1e-5, same order
as the FFT itself) and ~19 Gsps at bfloat16 precision (rel err ~4e-3 ≈ -47 dB — fine
for spectrum display, not for decoding chains).

The DFT/twiddle matrices are built *in trace* (``jnp.exp`` of ``jnp.outer``), never as
embedded host constants: device constants cost no host transfer and no program bytes.

**What holds for which rows** (``form(n, rows)``, the one place the form is chosen, from
static shapes). The figures above are for MANY rows (thousands of frames a call): the
rows fill the MXU, the arithmetic is the cost, and a direct ``[n, n]`` matmul wins up to
512 points. With FEW rows (the LoRa gateway's scan steps hand 8, one a channel, 351
times a frame) the arithmetic is nothing: a DFT costs its tables and its count of
operations (``docs/tpu_notes.md``, "A DFT on a few rows"). There re and im go through
ONE real matmul a stage as stacked rows against ``[F_re | F_im]`` (``2 n^2`` weights
where Gauss's three products read ``3 n^2``), the four-step starts at 512 points
(``~2 n`` weights), and the tables are built through a cumulative sum so that they stay
OUTSIDE a caller's loop: XLA sinks what is elementwise from an ``iota`` into a ``while``
body, where the many-row forms' matrices are built again every step (``_roots``).

Reference role: the reference delegates FFTs to rustfft (``src/blocks/fft.rs``); this
module is the TPU-first equivalent of "use the fastest FFT the hardware has".
"""
from __future__ import annotations

import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# Module policy: implementation ("auto" | "mxu" | "xla") and matmul precision
# ("f32" | "bf16"). Env overrides let a deployment flip the policy without code.
#
# TRACE-TIME BINDING: the policy is read when a function is *traced*, and jit
# caches keep whichever path was bound at first trace. Flipping set_impl /
# set_precision after a stage or Pipeline has compiled has no effect on the
# cached executable — rebuild the stage, or pass impl=/precision= explicitly
# to bind per call site: fft/ifft(..., impl=..., precision=...) here,
# fft_stage(impl=..., precision=...) and fir_stage(fft_impl=...,
# precision=...) at the stage layer (regression-pinned in
# tests/test_precision.py) — two chains in one process can hold different
# routes without fighting over the module policy.
_impl = os.environ.get("FUTURESDR_TPU_FFT_IMPL", "auto")
_precision = os.environ.get("FUTURESDR_TPU_FFT_PRECISION", "f32")

_MIN_MXU_N = 256          # below this the four-step matmuls are too skinny...
_MAX_DIRECT_N = 512       # ...but with MANY rows a DIRECT [n,n] DFT matmul wins for
                          # small n (any factorization): one dense MXU pass. With few
                          # rows it does not: its n^2 table is the cost (see ``form``)
_MIN_FEW_FOUR_STEP_N = 512    # few rows: four-step from here (see ``form``)
# the most rows that count as few. Stacked as planes, 2 * rows rows pass the MXU's
# 128-row array at once up to 64; the line lies under that because 64 rows is a
# shipped many-row caller (the spectrum chain's FIR: 64 blocks of 8192 points a
# frame; its fft2048 runs 128 rows) whose measured program stays as it is. The
# gateway's scan steps hand 8 (docs/tpu_notes.md)
_MAX_FEW_ROWS = 32
_MAX_FORCED_DIRECT_N = 4096   # forced-mxu safety cap: above this a dense [n,n]
                              # DFT is O(n^2) HBM (4096^2 c64 = 134 MB); fall
                              # back to jnp.fft rather than OOM/crawl


def set_impl(impl: str) -> None:
    """Set the FFT implementation policy: "auto" (MXU on TPU), "mxu", or "xla".

    Trace-time binding: affects only functions traced *after* this call; already
    jit-compiled stages keep their old path (see module docstring)."""
    global _impl
    assert impl in ("auto", "mxu", "xla"), impl
    _impl = impl


def set_precision(precision: str) -> None:
    """Set MXU matmul precision: "f32" (accurate) or "bf16" (~2-4x faster, -47 dB).

    Trace-time binding: affects only functions traced *after* this call; already
    jit-compiled stages keep their old path (see module docstring)."""
    global _precision
    assert precision in ("f32", "bf16"), precision
    _precision = precision


def _use_mxu(n: int, impl: Optional[str] = None) -> bool:
    """Trace-time dispatch decision (backend is static under jit)."""
    eff = impl or _impl
    if eff == "xla":
        return False
    if eff == "mxu":
        if n > _MAX_FORCED_DIRECT_N and (n & (n - 1)) != 0:
            # forced policy would route this through a dense [n,n] DFT matmul —
            # O(n^2) HBM with no upside at this size; refuse and use jnp.fft
            import logging
            logging.getLogger("futuresdr_tpu").warning(
                "fft: impl='mxu' forced but n=%d is a non-power-of-two above the "
                "direct-DFT cap (%d); falling back to jnp.fft for this size",
                n, _MAX_FORCED_DIRECT_N)
            return False
        return True
    if jax.default_backend() != "tpu":
        return False
    return (8 <= n <= _MAX_DIRECT_N) or (n >= _MIN_MXU_N and (n & (n - 1)) == 0)


def _factor(n: int) -> tuple:
    """Split n = N1 * N2 with N1 >= N2, both powers of two, near sqrt(n)."""
    assert n >= 4 and (n & (n - 1)) == 0, f"four-step FFT needs power-of-two n, got {n}"
    log = n.bit_length() - 1
    n1 = 1 << ((log + 1) // 2)
    return n1, n // n1


def _lax_precision(precision: Optional[str]):
    p = precision or _precision
    return jax.lax.Precision.HIGHEST if p == "f32" else jax.lax.Precision.DEFAULT


def _mxu_fft(x: jnp.ndarray, n: int, precision: Optional[str]) -> jnp.ndarray:
    if n <= _MAX_DIRECT_N or (n & (n - 1)) != 0:
        # direct DFT matmul: one dense [n, n] MXU pass, any n
        k = jnp.arange(n)
        F = jnp.exp(-2j * jnp.pi * jnp.outer(k, k) / n).astype(jnp.complex64)
        return jnp.einsum("kn,...n->...k", F, x, precision=_lax_precision(precision))
    n1, n2 = _factor(n)
    prec = _lax_precision(precision)
    # DFT + twiddle factors computed in trace (device constants, not host transfers)
    a = jnp.arange(n1)
    b = jnp.arange(n2)
    f1 = jnp.exp(-2j * jnp.pi * jnp.outer(a, a) / n1).astype(jnp.complex64)  # [k1, a]
    f2 = jnp.exp(-2j * jnp.pi * jnp.outer(b, b) / n2).astype(jnp.complex64)  # [k2, b]
    tw = jnp.exp(-2j * jnp.pi * jnp.outer(a, b) / n).astype(jnp.complex64)   # [k1, b]
    shape = x.shape
    A = x.reshape(shape[:-1] + (n1, n2))
    B = jnp.einsum("ka,...ab->...kb", f1, A, precision=prec)
    D = jnp.einsum("...kb,cb->...kc", B * tw, f2, precision=prec)            # (k1, k2)
    return jnp.swapaxes(D, -1, -2).reshape(shape)


def form(n: int, rows: int) -> str:
    """The form an ``n``-point matmul DFT of ``rows`` rows takes (``rows`` = the
    product of the leading dimensions; both static at trace time). The one place
    the choice is made.

    Many rows: ``"direct"`` (dense ``[n, n]`` complex einsum) up to
    ``_MAX_DIRECT_N`` points and for any non-power-of-two, ``"four_step"`` above:
    ``_mxu_fft``, where the rows fill the MXU and the arithmetic is the cost.
    Few rows (``<= _MAX_FEW_ROWS``): the arithmetic is nothing and a stage costs
    its weights and its count of operations, so re and im go through ONE real
    matmul a stage as stacked rows (``"planes_direct"``), and the weights shrink
    from ``n^2`` to ``~2 n`` as soon as the four-step pays
    (``"planes_four_step"``, power-of-two ``n >= _MIN_FEW_FOUR_STEP_N``)."""
    pow2 = (n & (n - 1)) == 0
    if rows > _MAX_FEW_ROWS:
        return "direct" if n <= _MAX_DIRECT_N or not pow2 else "four_step"
    return "planes_four_step" if pow2 and n >= _MIN_FEW_FOUR_STEP_N else "planes_direct"


def _roots(n1: int, n2: int, n: int) -> jnp.ndarray:
    """``exp(-2j pi a b / n)`` for ``a < n1``, ``b < n2`` as planes side by side:
    ``[cos | -sin]``, ``[n1, 2 n2]`` float32. The index ``a b`` is reduced modulo
    ``n`` as an integer first: exact, where ``2 pi a b / n`` in float32 is off by
    ``1e-4`` rad at 512 points.

    ``a b`` is a cumulative sum of ``a`` and not a product of two ``iota``: XLA
    sinks whatever is elementwise from an ``iota`` into a ``while`` body and fuses
    it into its consumer, so a table made that way inside a scan step is built
    again every step, ``n^2`` cosines and sines inside the matmul's fusion (the
    gateway's 9 us a matmul at 512 points, PR 34's trace); a cumulative sum is no
    such operation, and the table stays outside the loop as an operand of it
    (held by ``tests/test_lora_gw_stages.py``)."""
    a = jnp.broadcast_to(jnp.arange(n1, dtype=jnp.int32)[:, None], (n1, n2))
    ang = ((jnp.cumsum(a, axis=1) - a) % n).astype(jnp.float32) * np.float32(2 * np.pi / n)
    return jnp.concatenate([jnp.cos(ang), -jnp.sin(ang)], axis=-1)


def _plane_dft(planes: jnp.ndarray, axis: int, prec) -> jnp.ndarray:
    """A DFT along ``axis`` of ``planes`` = ``[2, ...]`` (re, im stacked in
    front): ONE real matmul against ``[F_re | F_im]`` gives the four real
    products of the complex one, which the combine adds up; the transform's
    axis comes out last."""
    n = planes.shape[axis]
    y = jnp.tensordot(planes, _roots(n, n, n), axes=((axis,), (0,)), precision=prec)
    return jnp.stack([y[0, ..., :n] - y[1, ..., n:], y[0, ..., n:] + y[1, ..., :n]])


def _planes_fft(x: jnp.ndarray, n: int, precision: Optional[str],
                four_step: bool) -> jnp.ndarray:
    """The few-row forms: ``planes_direct``, or ``planes_four_step`` over
    ``_factor(n)`` (the four-step of the module's header, a matmul a stage)."""
    prec = _lax_precision(precision)
    shape = x.shape
    planes = jnp.stack([jnp.real(x), jnp.imag(x)])
    if not four_step:
        y = _plane_dft(planes, -1, prec)
        return jax.lax.complex(y[0], y[1])
    n1, n2 = _factor(n)
    # x[a n2 + b] as [a, b]; columns first: [..., b, k1]
    B = _plane_dft(planes.reshape((2,) + shape[:-1] + (n1, n2)), -2, prec)
    tw = _roots(n2, n1, n)                                            # [b, 2 k1]
    C = jnp.stack([B[0] * tw[:, :n1] - B[1] * tw[:, n1:],
                   B[0] * tw[:, n1:] + B[1] * tw[:, :n1]])
    D = _plane_dft(C, -2, prec)                                       # [..., k1, k2]
    D = jnp.swapaxes(D, -1, -2).reshape((2,) + shape)                 # k1 + n1 k2
    return jax.lax.complex(D[0], D[1])


def _matmul_dft(x: jnp.ndarray, n: int, precision: Optional[str]) -> jnp.ndarray:
    f = form(n, math.prod(x.shape[:-1]))
    if f in ("direct", "four_step"):
        return _mxu_fft(x, n, precision)
    return _planes_fft(x, n, precision, f == "planes_four_step")


def fft(x: jnp.ndarray, precision: Optional[str] = None,
        impl: Optional[str] = None) -> jnp.ndarray:
    """Forward DFT along the last axis. Dispatches MXU four-step vs jnp.fft per the
    module policy; always safe to call on any backend.

    ``impl``/``precision`` override the module policy for this call site, binding
    the choice at trace time regardless of later set_impl/set_precision calls."""
    n = x.shape[-1]
    x = x.astype(jnp.complex64)
    if _use_mxu(n, impl):
        return _matmul_dft(x, n, precision)
    return jnp.fft.fft(x, axis=-1)


def ifft(x: jnp.ndarray, precision: Optional[str] = None,
         impl: Optional[str] = None) -> jnp.ndarray:
    """Inverse DFT along the last axis (conjugation trick over the forward path)."""
    n = x.shape[-1]
    x = x.astype(jnp.complex64)
    if _use_mxu(n, impl):
        return jnp.conj(_matmul_dft(jnp.conj(x), n, precision)) / n
    return jnp.fft.ifft(x, axis=-1)
