"""From a ``jax.profiler`` trace (``*.xplane.pb``) to device busy/idle time,
per-operation time and idle gaps named by what the host was doing.

Two halves. ``load`` reads the file with ``jax.profiler.ProfileData`` (nothing
but JAX) into plain tuples. ``reduce_events`` and ``name_gaps`` are pure
arithmetic on those tuples, checked in ``benchmark/tests`` on hand-made
intervals and on the recorded trace under ``benchmark/recorded``.

Times are nanoseconds on the profiler's clock. The program's spans are on
``time.perf_counter_ns``; the harness writes a ``TraceAnnotation`` named
``bench_sync`` at both ends of the traced window and reads
``perf_counter_ns`` at the same instants (``Trace.sync_ns``), which gives the
offset between the clocks.
"""

from __future__ import annotations

import bisect
import gzip
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

from .stats import merge_intervals

Event = Tuple[str, float, float]            # name, start_ns, duration_ns
Span = Tuple[str, float, float]             # name, start_ns, end_ns

SYNC_NAME = "bench_sync"
#: the device plane's line that holds one event per executed HLO operation;
#: the other lines ("Steps", "XLA Modules", "Framework Ops", name scopes)
#: re-state the same time and would count it twice
OP_LINES = ("XLA Ops",)
FALLBACK_LINES = ("XLA Modules",)


@dataclass
class Trace:
    devices: Dict[str, List[Event]]         # device plane -> its op events
    op_line: Dict[str, str]                 # device plane -> line used
    sync_ns: List[float]                    # starts of the bench_sync marks
    layout: Dict[str, Dict[str, int]] = field(default_factory=dict)


@dataclass
class Reduced:
    window_ns: Tuple[float, float]
    busy_ns: float                          # averaged over the devices
    per_device_busy_ns: Dict[str, float]
    op_self_ns: Dict[str, float]            # summed over devices
    op_calls: Dict[str, int]
    gaps: List[Tuple[float, float]]         # idle intervals of the first device

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / (self.window_ns[1] - self.window_ns[0])

    def top_ops(self, n: int = 10) -> List[List]:
        top = sorted(self.op_self_ns.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9] for name, ns in top]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "host" not in name.lower()


#: the CPU backend has no device plane; a rehearsal reads the PjRt CPU
#: client's executor threads instead, so that the whole reduction is rehearsed
CPU_REHEARSAL_LINES = "tf_XLAPjRtCpuClient"


def short_op_name(name: str) -> str:
    """XLA's own name of an operation: ``%fusion.37 = (f32[...]) fusion(...)``
    is ``fusion.37``. Per-kernel names need ``named_scope`` in the program."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str, cpu_rehearsal: bool = False) -> Trace:
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(str(path))
    devices: Dict[str, List[Event]] = {}
    op_line: Dict[str, str] = {}
    sync: List[float] = []
    layout: Dict[str, Dict[str, int]] = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            evs = [(short_op_name(e.name), float(e.start_ns),
                    float(e.duration_ns)) for e in line.events]
            lines.setdefault(line.name, []).extend(evs)
        layout[plane.name] = {k: len(v) for k, v in lines.items()}
        if is_device_plane(plane.name):
            for cand in OP_LINES + FALLBACK_LINES:
                if lines.get(cand):
                    devices[plane.name] = lines[cand]
                    op_line[plane.name] = cand
                    break
        else:
            for evs in lines.values():
                sync.extend(s for n, s, _ in evs if n == SYNC_NAME)
            if cpu_rehearsal and plane.name == "/host:CPU":
                ops = [e for k, v in lines.items()
                       if k.startswith(CPU_REHEARSAL_LINES) for e in v
                       if e[2] > 0 and not e[0].startswith(
                           ("ThreadpoolListener", "end:", "ThunkExecutor"))]
                if ops:
                    devices["/host:CPU(rehearsal)"] = ops
                    op_line["/host:CPU(rehearsal)"] = CPU_REHEARSAL_LINES
    return Trace(devices, op_line, sorted(sync), layout)


def self_times(events: Sequence[Event]) -> List[float]:
    """Each event's duration minus what the events nested inside it cover
    (a ``while`` holds its body's ops; a fusion holds none). Events are taken
    in start order, the longer first; the result is in the given order."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    out = [e[2] for e in events]
    stack: List[Tuple[float, int]] = []     # (end, index)
    for i in order:
        _, start, dur = events[i]
        end = start + dur
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            p_end, p = stack[-1]
            out[p] -= max(0.0, min(end, p_end) - start)
        stack.append((end, i))
    return [max(0.0, v) for v in out]


def _clip_events(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def reduce_events(devices: Dict[str, Sequence[Event]], t0: float,
                  t1: float) -> Reduced:
    """Busy union, per-op self time and idle gaps inside ``[t0, t1]``."""
    if t1 <= t0:
        raise ValueError("traced window has no length")
    per_dev: Dict[str, float] = {}
    op_ns: Dict[str, float] = defaultdict(float)
    calls: Dict[str, int] = defaultdict(int)
    gaps: List[Tuple[float, float]] = []
    for k, (dev, events) in enumerate(sorted(devices.items())):
        evs = _clip_events(events, t0, t1)
        busy = merge_intervals((s, s + d) for _, s, d in evs)
        per_dev[dev] = float(sum(b - a for a, b in busy))
        for (name, _, _), ns in zip(evs, self_times(evs)):
            op_ns[name] += ns
            calls[name] += 1
        if k == 0:
            edge = t0
            for a, b in busy:
                if a > edge:
                    gaps.append((edge, a))
                edge = b
            if t1 > edge:
                gaps.append((edge, t1))
    n = max(1, len(per_dev))
    return Reduced((t0, t1), sum(per_dev.values()) / n, per_dev,
                   dict(op_ns), dict(calls), gaps)


#: spans that bracket other spans: they name a gap only when no narrower span
#: covers at least half of it
BROAD_SPANS = ("serve_step",)


class _Coverage:
    """Length of a set of intervals inside any ``[a, b]``, in O(log n)."""

    def __init__(self, iv: Iterable[Tuple[float, float]]):
        merged = merge_intervals(iv)
        self.starts = [a for a, _ in merged]
        self.ends = [b for _, b in merged]
        self.before = [0.0]                 # covered length left of interval i
        for a, b in merged:
            self.before.append(self.before[-1] + (b - a))

    def _upto(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.before[i - 1] + min(t, self.ends[i - 1]) - self.starts[i - 1]

    def inside(self, a: float, b: float) -> float:
        return self._upto(b) - self._upto(a)


def name_gaps(gaps: Sequence[Tuple[float, float]],
              host_spans: Sequence[Span],
              broad: Sequence[str] = BROAD_SPANS) -> List[Tuple[str, float]]:
    """Each idle gap with the name of the host span that covers most of it
    (``unattributed`` when none touches it), as ``(name, gap_ns)``."""
    by_name: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for name, a, b in host_spans:
        by_name[name].append((a, b))
    cover = {n: _Coverage(iv) for n, iv in by_name.items()}
    out = []
    for a, b in gaps:
        best, best_c, wide, wide_c = "unattributed", 0.0, None, 0.0
        for n, cov in cover.items():
            c = cov.inside(a, b)
            if n in broad:
                if c > wide_c:
                    wide, wide_c = n, c
            elif c > best_c:
                best, best_c = n, c
        if wide is not None and best_c < 0.5 * (b - a) and wide_c > best_c:
            best = wide
        out.append((best, b - a))
    return out


def breakdown(red: Reduced, host_spans: Sequence[Span],
              n_ops: int = 10, n_gaps: int = 5) -> dict:
    """The last line's ``breakdown``: the operations with most device time,
    then the ``n_gaps`` longest idle gaps by name and, as ``sum:<name>``, the
    idle seconds under each kind of host span over the whole traced window."""
    named = name_gaps(red.gaps, host_spans)
    longest = sorted(named, key=lambda g: -g[1])[:n_gaps]
    sums: Dict[str, float] = defaultdict(float)
    for name, ns in named:
        sums[name] += ns
    total = sorted(sums.items(), key=lambda kv: -kv[1])[:10 - len(longest)]
    return {"device_ops": red.top_ops(n_ops),
            "idle_gaps": [[n, ns * 1e-9] for n, ns in longest]
            + [[f"sum:{n}", ns * 1e-9] for n, ns in total]}


def to_profile_clock(spans_perf_ns: Sequence[Span], sync_profile_ns: float,
                     sync_perf_ns: float) -> List[Span]:
    off = sync_profile_ns - sync_perf_ns
    return [(n, a + off, b + off) for n, a, b in spans_perf_ns]
