"""Fixed-bucket log2 histogram: the data type behind latency percentiles.

Design constraints (why not a reservoir or a t-digest):

* **O(1) observe on the work() hot path.** The bucket index is one
  ``math.frexp`` call — a value in ``(2^(e-1), 2^e]`` lands in the bucket
  whose upper bound is ``2^e`` — plus three integer adds under a private
  lock. No allocation, no sort, no bisect; the ≤3% telemetry overhead gate
  (``tests/test_telemetry.py``) bills this path per work call.
* **Fixed buckets, bounded memory.** Powers of two from ``2^lo_exp`` to
  ``2^hi_exp`` seconds (default ~1 µs … 128 s) plus an overflow bucket:
  29 ints per (metric, label) pair, mergeable across label children and
  across processes by plain addition — the property Prometheus histograms
  are built on.
* **Quantiles with bounded error.** :meth:`quantile` linearly interpolates
  inside the winning bucket, so the estimate is exact to within one log2
  bucket (a factor-of-2 envelope) — the right fidelity for "is p99 1 ms or
  1 s", which is the doctor's question. Exact-percentile needs
  (``utils/trace.py::latency_stats``) keep their raw-sample numpy path.

``telemetry/prom.py`` wraps this into the :class:`~.prom.Histogram` metric
type (Prometheus ``_bucket``/``_sum``/``_count`` exposition); the doctor
(``telemetry/doctor.py``) reads quantiles for its reports.
"""

from __future__ import annotations

import math
import threading
import time
from typing import List, Optional, Sequence, Tuple

__all__ = ["Log2Hist", "log2_bounds", "DEFAULT_LO_EXP", "DEFAULT_HI_EXP"]

#: default bucket range: 2^-20 s (~0.95 µs) … 2^7 s (128 s) — the span from a
#: single jitted dispatch to a wedged transfer, in factor-of-2 steps
DEFAULT_LO_EXP = -20
DEFAULT_HI_EXP = 7

_frexp = math.frexp
_time = time.time


def log2_bounds(lo_exp: int = DEFAULT_LO_EXP,
                hi_exp: int = DEFAULT_HI_EXP) -> Tuple[float, ...]:
    """Inclusive bucket upper bounds ``2^lo_exp … 2^hi_exp`` (no +Inf entry)."""
    if hi_exp <= lo_exp:
        raise ValueError(f"need hi_exp > lo_exp, got [{lo_exp}, {hi_exp}]")
    return tuple(2.0 ** e for e in range(lo_exp, hi_exp + 1))


class Log2Hist:
    """One fixed-bucket log2 histogram (one label child of a prom Histogram)."""

    __slots__ = ("lo_exp", "hi_exp", "bounds", "_lo", "_n", "_counts", "_sum",
                 "_count", "_lock", "_stride_tick", "_stride_mask",
                 "_exemplars")

    #: stride of :meth:`observe_sampled` (must stay a power of two)
    SAMPLE_STRIDE = 8

    def __init__(self, lo_exp: int = DEFAULT_LO_EXP,
                 hi_exp: int = DEFAULT_HI_EXP):
        self.lo_exp = lo_exp
        self.hi_exp = hi_exp
        self.bounds = log2_bounds(lo_exp, hi_exp)
        self._lo = self.bounds[0]
        self._n = len(self.bounds)
        # bounds buckets + one overflow (+Inf) bucket
        self._counts = [0] * (self._n + 1)
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()
        self._stride_tick = 0
        # observe_sampled hot path: one attribute load instead of a class
        # attribute lookup + subtraction per call
        self._stride_mask = self.SAMPLE_STRIDE - 1
        # bucket index -> (value, trace_id, wall_ts): latest lineage-sampled
        # observation per bucket, for OpenMetrics exemplar exposition; lazy —
        # only histograms fed by the lineage tracer ever allocate it
        self._exemplars: Optional[dict] = None

    def _index(self, v: float) -> int:
        # v in (2^(e-1), 2^e] belongs to the bucket bounded above by 2^e;
        # frexp(v) = (m, e) with m in [0.5, 1), so v == 2^(e-1) exactly when
        # m == 0.5 — one bucket down from the open-interval case
        if v <= self._lo:
            return 0
        m, e = _frexp(v)
        i = e - self.lo_exp - (m == 0.5)
        return i if i < self._n else self._n   # overflow bucket

    def observe(self, v: float) -> None:
        # hot path (one per work() call / frame / transfer): stay lean —
        # `not (v >= 0)` rejects negatives AND NaN (clock skew) in one compare
        if not (v >= 0.0):
            return
        if v <= self._lo:
            i = 0
        else:
            m, e = _frexp(v)
            i = e - self.lo_exp - (m == 0.5)
            if i >= self._n:
                i = self._n
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    def observe_sampled(self, v: float) -> None:
        """1-in-:attr:`SAMPLE_STRIDE` systematic sample of :meth:`observe`.

        For call-rate-bound sites (one candidate observation per ``work()``
        call, ``runtime/block.py``): the full observe costs ~0.4 µs of
        interpreter time, which a 60k-calls/s chain cannot afford inside the
        ≤3% telemetry budget — the stride check costs ~0.1 µs and a
        systematic 1-in-8 sample estimates the duration distribution
        unbiasedly (call durations carry no phase-mod-8 structure; exact
        TOTALS stay on the ``work_calls``/``work_time_s`` counters). The
        tick update is intentionally unlocked: each per-block child has a
        single writer (the block's own event loop), and a lost tick under a
        cross-flowgraph label collision only shifts the sampling phase.
        """
        t = self._stride_tick = self._stride_tick + 1
        if t & self._stride_mask:
            return
        self.observe(v)

    def exemplar(self, v: float, trace_id: str) -> None:
        """Attach a lineage exemplar to ``v``'s bucket (keeps the latest).

        Called only on lineage-sampled frames (default 1-in-64), so it can
        afford the lock and a ``time.time()`` call — the hot ``observe`` path
        stays untouched. Does NOT bump counts: the caller observes the value
        through the normal path; this just remembers which trace id landed in
        the bucket most recently (the OpenMetrics exemplar contract).
        """
        if not (v >= 0.0) or not trace_id:
            return
        i = self._index(v)
        wall = _time()
        with self._lock:
            if self._exemplars is None:
                self._exemplars = {}
            self._exemplars[i] = (v, trace_id, wall)

    def exemplars(self) -> dict:
        """``{bucket_index: (value, trace_id, wall_ts)}`` snapshot (may be
        empty); bucket_index matches :meth:`snapshot` count positions."""
        with self._lock:
            return dict(self._exemplars) if self._exemplars else {}

    # -- reads -----------------------------------------------------------------
    def snapshot(self) -> Tuple[List[int], float, int]:
        """``(bucket_counts, sum, count)`` — counts per bucket (last entry is
        the +Inf overflow), consistent under the lock."""
        with self._lock:
            return list(self._counts), self._sum, self._count

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def quantile(self, q: float) -> Optional[float]:
        """Estimated ``q``-quantile (``0 ≤ q ≤ 1``); ``None`` when empty.

        Linear interpolation inside the winning bucket (lower bound 0 for the
        first bucket); the overflow bucket clamps to the highest finite bound
        — a log2 histogram cannot claim precision past its range.
        """
        counts, _s, total = self.snapshot()
        return quantile_from_buckets(counts, self.bounds, total, q)


def quantile_from_buckets(counts: Sequence[int], bounds: Sequence[float],
                          total: int, q: float) -> Optional[float]:
    """Shared bucket→quantile math (also used on merged label children)."""
    if total <= 0:
        return None
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile out of range: {q}")
    target = q * total
    cum = 0
    for i, c in enumerate(counts):
        if c == 0:
            continue
        if cum + c >= target:
            lo = bounds[i - 1] if 0 < i <= len(bounds) else 0.0
            if i >= len(bounds):          # overflow: clamp to the top bound
                return bounds[-1]
            hi = bounds[i]
            frac = (target - cum) / c
            return lo + max(0.0, min(1.0, frac)) * (hi - lo)
        cum += c
    # rounding fell off the end: the last non-empty bucket's bound
    for i in range(len(counts) - 1, -1, -1):
        if counts[i]:
            return bounds[min(i, len(bounds) - 1)]
    return None
