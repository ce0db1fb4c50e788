"""Block-shape sweep harness for the Pallas kernels (the Pallas autotune
plane — Flex-TPU's runtime-reconfigurable dataflow shapes, arXiv:2407.08700).

The hand-picked ``DEFAULT_BLOCKS`` in ``ops/pallas_kernels.py`` were tuned
once on one chip; the VMEM/compute balance that makes a block shape win moves
with the chip generation (v5e's 128 MB/s-per-FLOP HBM ratio vs v5p's). This
module measures each kernel over a small per-kernel candidate grid on a
representative workload and returns the winners, which
:func:`~futuresdr_tpu.tpu.autotune.autotune_pallas_blocks` persists in the
streamed-pick cache (the guarded ``pallas_blocks`` axis, keyed by
:func:`device_key`) and installs via
:func:`~futuresdr_tpu.ops.pallas_kernels.set_tuned_blocks`.

Sweep contract (docs/tpu_notes.md "Pallas autotune plane"):

- the defaults are ALWAYS in the candidate set, and win ties within timer
  noise — a recorded winner is never a regression against the hand-picked
  shapes;
- a NON-default candidate that fails to compile or run is skipped with a
  warning (an odd shape may exceed VMEM); the DEFAULT block failing is an
  error — it is the shape every untuned launch uses, and a sweep that
  skipped it would hide a kernel Mosaic refuses;
- on CPU the kernels run in interpret mode, so the measured ranking is a
  functional smoke of the sweep loop, not a performance statement — the cache
  key (:func:`device_key` → ``"cpu"``) keeps those picks away from real chips.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..log import logger
from ..ops import pallas_kernels as pk

log = logger(__name__)

__all__ = ["CANDIDATE_BLOCKS", "device_key", "sweep_blocks"]

#: per-kernel candidate grids — every grid contains its kernel's
#: :data:`~futuresdr_tpu.ops.pallas_kernels.DEFAULT_BLOCKS` entry (asserted
#: in tests) so the sweep can always fall back to "default wins".
CANDIDATE_BLOCKS: Dict[str, Tuple[int, ...]] = {
    "fir":        (1024, 2048, 4096, 8192),
    "pfb":        (64, 128, 256, 512),
    "poly_fir":   (256, 512, 1024, 2048),
    "fir_fft":    (4, 8, 16, 32),
    "rotator":    (64, 128, 256, 512),
    "quad_demod": (64, 128, 256, 512),
}

#: winners within this factor of the default's time count as a TIE and keep
#: the default — timer noise on a sub-millisecond kernel must not churn the
#: recorded axis between runs
_TIE_MARGIN = 0.98


def device_key(backend: Optional[str] = None) -> str:
    """The cache key for this process's accelerator: the chip generation
    (``"v5e"``, ``"v5p"``, …) via the same ``device_kind`` mapping
    ``detect_peaks`` uses, or the backend platform name (``"cpu"``) when the
    kind is unknown — CPU-interpret sweeps must never shadow real-chip
    picks."""
    from ..utils.roofline import _kind_to_chip
    try:
        devs = jax.devices(backend) if backend else jax.devices()
    except RuntimeError:
        return "cpu"
    if not devs:
        return "cpu"
    chip = _kind_to_chip(getattr(devs[0], "device_kind", "") or "")
    return chip or str(getattr(devs[0], "platform", "") or "cpu")


def _workload(frame: int) -> Dict[str, jnp.ndarray]:
    """Representative operands, sized so every candidate divides evenly
    where the kernel requires it (``pallas_fir`` asserts
    ``frame % block == 0``; the rest pad ragged tails)."""
    big = max(c for c in CANDIDATE_BLOCKS["fir"])
    frame = max(big, (int(frame) // big) * big)
    rng = np.random.default_rng(20)
    x = jnp.asarray(rng.standard_normal(frame).astype(np.float32))
    xc = jnp.asarray((rng.standard_normal(frame)
                      + 1j * rng.standard_normal(frame))
                     .astype(np.complex64))
    taps = jnp.asarray(rng.standard_normal(64).astype(np.float32))
    K, N = 8, 64
    rows_pfb = jnp.asarray(
        (rng.standard_normal((1024 + K - 1, N))
         + 1j * rng.standard_normal((1024 + K - 1, N))).astype(np.complex64))
    taps_kn = jnp.asarray(rng.standard_normal((K, N)).astype(np.float32))
    D, m = 4, 15
    rows_poly = jnp.asarray(
        rng.standard_normal((m + frame // D, D)).astype(np.float32))
    W_poly = jnp.asarray(rng.standard_normal((m + 1, D)).astype(np.float32))
    return {"x": x, "xc": xc, "taps": taps, "taps33": taps[:33],
            "hist": jnp.zeros(32, jnp.complex64),
            "rows_pfb": rows_pfb, "taps_kn": taps_kn,
            "rows_poly": rows_poly, "W_poly": W_poly}


def _runner(kernel: str, block: int, d: Dict[str, jnp.ndarray]) -> Callable:
    """A zero-arg timed unit: the jitted kernel at this block shape over the
    shared workload, synchronized on completion."""
    if kernel == "fir":
        f = jax.jit(lambda x, t: pk.pallas_fir(x, t, block=block))
        args = (d["x"], d["taps"])
    elif kernel == "pfb":
        f = jax.jit(lambda r, t: pk.pallas_pfb(r, t, block=block))
        args = (d["rows_pfb"], d["taps_kn"])
    elif kernel == "poly_fir":
        f = jax.jit(lambda r, w: pk.pallas_poly_fir(r, w, block=block))
        args = (d["rows_poly"], d["W_poly"])
    elif kernel == "fir_fft":
        f = jax.jit(lambda h, x, t: pk.pallas_fir_fft(h, x, t, 256,
                                                      block=block))
        args = (d["hist"], d["xc"], d["taps33"])
    elif kernel == "rotator":
        f = jax.jit(lambda x: pk.pallas_rotator(x, 0.1, 0.013, block=block))
        args = (d["xc"],)
    elif kernel == "quad_demod":
        f = jax.jit(lambda p, x: pk.pallas_quad_demod(p, x, 0.7,
                                                      block=block))
        args = (d["xc"][0], d["xc"])
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    return lambda: jax.block_until_ready(f(*args))


def sweep_blocks(kernels: Optional[Sequence[str]] = None,
                 frame: int = 1 << 16, reps: int = 3,
                 candidates: Optional[Dict[str, Sequence[int]]] = None,
                 ) -> Tuple[Dict[str, int], Dict[str, Dict[int, float]]]:
    """Measure every kernel × candidate block and pick per-kernel winners.

    Returns ``(winners, matrix)``: ``winners[kernel] = block`` and
    ``matrix[kernel][block] = best-of-reps seconds`` (the full sweep, for
    the artifact tables). Timing is min-of-``reps`` after a warm-up call
    that also pays compilation; a candidate that raises is dropped with a
    warning — unless it is the kernel's default block, whose failure
    propagates. The default block wins any tie within :data:`_TIE_MARGIN`."""
    names = tuple(kernels) if kernels else tuple(CANDIDATE_BLOCKS)
    data = _workload(frame)
    winners: Dict[str, int] = {}
    matrix: Dict[str, Dict[int, float]] = {}
    for kn in names:
        if kn not in pk.DEFAULT_BLOCKS:
            log.warning("pallas sweep: unknown kernel %r skipped", kn)
            continue
        default = pk.DEFAULT_BLOCKS[kn]
        grid = sorted({int(b) for b in
                       ((candidates or {}).get(kn) or CANDIDATE_BLOCKS[kn])
                       if int(b) > 0} | {default})
        times: Dict[int, float] = {}
        for b in grid:
            try:
                fn = _runner(kn, b, data)
                fn()                           # compile + warm
                best = min(_timed(fn) for _ in range(max(1, int(reps))))
                times[b] = best
            except Exception as e:             # Mosaic reject, OOM, …
                if b == default:
                    raise
                log.warning("pallas sweep %s block=%d failed: %r", kn, b, e)
        best_b = min(times, key=times.get)
        if (default in times and best_b != default
                and times[default] * _TIE_MARGIN <= times[best_b]):
            best_b = default                   # tie → never churn the axis
        winners[kn] = best_b
        matrix[kn] = times
    return winners, matrix


def _timed(fn: Callable) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0
