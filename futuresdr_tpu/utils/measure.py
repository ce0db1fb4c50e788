"""Honest device-throughput measurement for streaming stages.

Async-dispatch timing loops mislead on any async backend: jax returns before the
device finishes, and per-dispatch latency swamps sub-second kernels. See
docs/tpu_notes.md "Measuring device-resident rates".

:func:`run_marginal` implements the corrected methodology the ``perf/`` harnesses
use (``perf/fir.py``, ``perf/fm.py``, ``perf/lora.py``, ``perf/wlan.py``):

- the frame loop rides INSIDE the jitted program via ``lax.scan`` — one dispatch runs
  K frames with the stage carry chained;
- a checksum accumulates in the scan carry and is fed back into each iteration's input,
  creating a sequential data dependence so XLA cannot hoist the (otherwise
  loop-invariant) body out of the loop;
- the checksum readback happens inside the timed region and is validated finite;
- the reported rate is the **marginal** rate between the two K values, cancelling the
  constant dispatch latency.
"""
from __future__ import annotations

import time
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.xfer import to_host

__all__ = ["run_marginal", "run_marginal_retry", "default_k_pair",
           "scaled_k_pair"]


def run_marginal(step: Callable, carry0, x, k_pair: Tuple[int, int] = (512, 1024),
                 reps: int = 4) -> float:
    """Measure sustained samples/s of ``step(carry, x) -> (carry, y)`` on x's device.

    ``x`` may be any shape; the rate is ``x.size`` samples per step invocation.
    Returns samples/second (marginal between the two scan lengths). Raises
    RuntimeError if timing noise makes the marginal ill-conditioned (k_hi run not
    measurably longer than k_lo run) — callers should retry rather than report it.
    (Real raises, not asserts: under ``python -O`` an assert-based rail would
    silently report garbage — the exact failure mode this module exists to prevent.)
    """
    k_lo, k_hi = k_pair
    if k_hi <= k_lo:
        raise ValueError(f"k_pair must be increasing, got {k_pair}")

    def make(k):
        @jax.jit
        def run_k(carry, xin):
            def body(c, _):
                stage_c, acc = c
                xi = xin * (1 + 1e-20 * acc.astype(xin.dtype))
                stage_c, y = step(stage_c, xi)
                return (stage_c, acc + jnp.sum(y).real.astype(jnp.float32)), None
            (carry, acc), _ = jax.lax.scan(body, (carry, jnp.float32(0)), None,
                                           length=k)
            return carry, acc
        return run_k

    times = {}
    for k in (k_lo, k_hi):
        run_k = make(k)
        _, acc = run_k(carry0, x)
        warm = float(to_host(acc))                    # compile + warm + validate
        if not np.isfinite(warm):
            raise RuntimeError(f"non-finite warmup checksum {warm} at K={k}")
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            _, acc = run_k(carry0, x)
            checksum = float(to_host(acc))            # sync inside the timed region
            best = min(best, time.perf_counter() - t0)
        if not np.isfinite(checksum):
            raise RuntimeError(f"non-finite checksum {checksum} at K={k}")
        times[k] = best
    if times[k_hi] <= times[k_lo]:
        raise RuntimeError(
            f"marginal ill-conditioned: K={k_hi} ran in {times[k_hi]:.3f}s vs "
            f"K={k_lo} in {times[k_lo]:.3f}s — timing noise exceeds the workload; "
            f"increase k_pair or frame size")
    return (k_hi - k_lo) * int(np.prod(np.shape(x))) / (times[k_hi] - times[k_lo])


def default_k_pair(platform: str) -> Tuple[int, int]:
    """Scan-length pair for the marginal methodology: hundreds of frames per scan
    make each timed window long against per-dispatch latency on an accelerator;
    the CPU backend is far slower per frame, so short scans keep its runs short.
    THE single source of these constants — every perf/ harness routes through
    here."""
    return (512, 1024) if platform == "tpu" else (8, 16)


def scaled_k_pair(k_pair: Tuple[int, int], frame_items: int, platform: str,
                  min_lo_items: int = None) -> Tuple[int, int]:
    """Grow a scan pair so ONE ``k_lo`` scan covers a worthwhile timed window.

    Small frames make sub-ms scans where scheduler noise dominates the
    marginal (lora_msps 58–182 across rounds on the CPU backend); behind an
    accelerator dispatch path, per-dispatch jitter swamps a tens-of-ms scan
    delta the same way. Scale the pair so the k_lo scan covers ≥2M samples on
    the CPU backend and ≥512M on accelerators (a few tenths of a second at
    Gsps-class chain rates — the k_hi−k_lo delta then dwarfs per-dispatch
    jitter). THE shared window
    discipline of perf/lora.py / perf/wlan.py."""
    if min_lo_items is None:
        min_lo_items = 2_000_000 if platform == "cpu" else 512_000_000
    scale = max(1, -(-min_lo_items // (k_pair[0] * max(1, frame_items))))
    return (k_pair[0] * scale, k_pair[1] * scale)


def run_marginal_retry(step: Callable, carry0, x,
                       k_pair: Tuple[int, int] = (512, 1024),
                       attempts: int = 3, grow: int = 2) -> float:
    """:func:`run_marginal` with the retry its error contract asks callers for:
    on an ill-conditioned marginal, double the scan lengths (more work per timing
    window conditions the difference) and try again, up to ``attempts`` total."""
    last = None
    for _ in range(attempts):
        try:
            return run_marginal(step, carry0, x, k_pair)
        except RuntimeError as e:
            last = e
            k_pair = (k_pair[0] * grow, k_pair[1] * grow)
    raise last
