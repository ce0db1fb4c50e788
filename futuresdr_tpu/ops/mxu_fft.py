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
_MAX_DIRECT_N = 512       # ...but a DIRECT [n,n] DFT matmul wins for small n (any
                          # factorization, huge batch): one dense MXU pass
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


def fft(x: jnp.ndarray, precision: Optional[str] = None,
        impl: Optional[str] = None) -> jnp.ndarray:
    """Forward DFT along the last axis. Dispatches MXU four-step vs jnp.fft per the
    module policy; always safe to call on any backend.

    ``impl``/``precision`` override the module policy for this call site, binding
    the choice at trace time regardless of later set_impl/set_precision calls."""
    n = x.shape[-1]
    x = x.astype(jnp.complex64)
    if _use_mxu(n, impl):
        return _mxu_fft(x, n, precision)
    return jnp.fft.fft(x, axis=-1)


def ifft(x: jnp.ndarray, precision: Optional[str] = None,
         impl: Optional[str] = None) -> jnp.ndarray:
    """Inverse DFT along the last axis (conjugation trick over the forward path)."""
    n = x.shape[-1]
    x = x.astype(jnp.complex64)
    if _use_mxu(n, impl):
        return jnp.conj(_mxu_fft(jnp.conj(x), n, precision)) / n
    return jnp.fft.ifft(x, axis=-1)
