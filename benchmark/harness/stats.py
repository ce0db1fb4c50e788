"""Window, percentile and spread arithmetic. Pure numpy, no clock reads."""

from __future__ import annotations

from typing import Iterable, List, Sequence, Tuple

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between the
    two nearest order statistics. Raises on an empty input: a metric with no
    reading is left out, never reported as 0."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        raise ValueError("percentile of no readings")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (v.size - 1) * q / 100.0
    lo = int(np.floor(pos))
    hi = min(lo + 1, v.size - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def spread(values: Sequence[float]) -> float:
    """Distance between the quartiles over the median: the driver's spread."""
    m = median(values)
    if m == 0.0:
        raise ValueError("spread of a metric whose median is 0")
    return (percentile(values, 75.0) - percentile(values, 25.0)) / abs(m)


def count_in_window(stamps: Iterable[float], t0: float, t1: float) -> int:
    """Events stamped inside the half-open window ``[t0, t1)``."""
    s = np.asarray(stamps)
    return int(np.count_nonzero((s >= t0) & (s < t1)))


def rate_per_s(count: int, t0_ns: int, t1_ns: int) -> float:
    """``count`` events over a window given in nanoseconds."""
    if t1_ns <= t0_ns:
        raise ValueError("window has no length")
    return count / ((t1_ns - t0_ns) * 1e-9)


def merge_intervals(iv: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of closed intervals as a sorted list of disjoint intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted((a, b) for a, b in iv if b > a):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip_intervals(iv: Iterable[Tuple[float, float]], t0: float,
                   t1: float) -> List[Tuple[float, float]]:
    """Each interval cut to ``[t0, t1]``; those outside it dropped."""
    return [(max(a, t0), min(b, t1)) for a, b in iv
            if min(b, t1) > max(a, t0)]


def union_length(iv: Iterable[Tuple[float, float]]) -> float:
    return float(sum(b - a for a, b in merge_intervals(iv)))


def block_rates_per_s(stamps_ns: Sequence[int], block: int) -> List[float]:
    """Completions per second over consecutive blocks of ``block`` completions:
    ``block`` over the time from one block's last stamp to the next block's
    last stamp. No slice of the clock cuts a step in two, so the rates are not
    quantized to whole steps. Fewer than ``block + 1`` stamps give no rate."""
    s = np.sort(np.asarray(stamps_ns, dtype=np.int64))
    if block < 1:
        raise ValueError("a block holds at least one completion")
    edges = s[::block]
    dt = np.diff(edges).astype(np.float64) * 1e-9
    if np.any(dt <= 0.0):
        raise ValueError("two blocks end at the same instant")
    return (block / dt).tolist()


def slice_percentiles(times_ns: Sequence[int], values: Sequence[float],
                      t0_ns: int, t1_ns: int, slice_ns: int,
                      q: float, min_readings: int = 1) -> List[float]:
    """The ``q``-th percentile of ``values`` inside each whole slice of
    ``slice_ns`` of the window ``[t0, t1)``, by each value's time; slices with
    fewer than ``min_readings`` readings are left out."""
    t = np.asarray(times_ns, dtype=np.int64)
    v = np.asarray(values, dtype=np.float64)
    if t.shape != v.shape:
        raise ValueError("one time per value")
    if slice_ns <= 0:
        raise ValueError("a slice has a length")
    out = []
    n = int((t1_ns - t0_ns) // slice_ns)
    for i in range(n):
        a = t0_ns + i * slice_ns
        sel = v[(t >= a) & (t < a + slice_ns)]
        if sel.size >= min_readings:
            out.append(percentile(sel, q))
    return out


def quantiles_ms(durations_ns: Sequence[int]) -> dict:
    """A run's notes: a set of durations in ns as ms at fixed quantiles."""
    v = np.asarray(durations_ns, dtype=np.float64) * 1e-6
    return {f"p{q}": percentile(v, q) for q in (5, 25, 50, 75, 95, 100)}
