"""What a driver hands back and what a per-layer reader receives."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import stats


@dataclass
class HostSpan:
    """One span on ``time.perf_counter_ns``: the program's own (drained from
    ``telemetry.spans``) or the load generator's."""
    cat: str
    name: str
    t0_ns: int
    dur_ns: int
    args: Optional[dict] = None
    thread: str = ""

    @property
    def t1_ns(self) -> int:
        return self.t0_ns + self.dur_ns


@dataclass
class Reading:
    """Everything a per-layer reader may read. A reader that finds nothing
    returns None and its metric is left out of the line.

    ``unit_stamps_ns`` are the instants at which one unit of work completed
    at the client: a frame at the sink (stream) or a dispatch collected
    (serve). "per frame" / "per dispatch" metrics divide by the units inside
    the window they look at."""
    driver: str
    window_ns: Tuple[int, int]
    unit: str                                   # "frame" | "dispatch"
    unit_stamps_ns: np.ndarray
    spans: List[HostSpan] = field(default_factory=list)
    counters: Dict[str, float] = field(default_factory=dict)
    latencies_ms: List[float] = field(default_factory=list)
    gen_late_ms: List[float] = field(default_factory=list)
    compiles_in_window: int = 0
    cost_per_unit: Optional[Dict[str, float]] = None    # flops, bytes
    peaks: Optional[dict] = None
    traced_ns: Optional[Tuple[int, int]] = None         # perf_counter clock
    trace: Any = None                                   # xplane.Reduced

    def units_in(self, t0_ns: int, t1_ns: int) -> int:
        return stats.count_in_window(self.unit_stamps_ns, t0_ns, t1_ns)

    def device_ns_per_unit(self) -> Optional[float]:
        """Device busy time in the traced window over the units completed in
        it; None without a trace, without units or without device time."""
        if self.trace is None or self.traced_ns is None:
            return None
        units = self.units_in(*self.traced_ns)
        if not units or self.trace.busy_ns <= 0:
            return None
        return self.trace.busy_ns / units

    def spans_in_window(self, cat: Optional[str] = None,
                        names: Optional[tuple] = None,
                        name_prefix: Optional[str] = None) -> List[HostSpan]:
        t0, t1 = self.window_ns
        out = []
        for s in self.spans:
            if cat is not None and s.cat != cat:
                continue
            if names is not None and s.name not in names:
                continue
            if name_prefix is not None and not s.name.startswith(name_prefix):
                continue
            if s.t1_ns > t0 and s.t0_ns < t1:
                out.append(s)
        return out


@dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    window_start_ns: int                        # setup_s ends here
    end_to_end: Dict[str, float]
    reading: Reading
    host_spans_named: List[Tuple[str, int, int]] = field(default_factory=list)
    notes: Dict[str, Any] = field(default_factory=dict)


@dataclass
class Run:
    """What run.py hands a driver."""
    cell: Any                                   # harness.cells.Cell
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    meter: Any                                  # refs.CompileMeter
    device: Any                                 # jax device
    peaks: Optional[dict]
    trace_window: Any = None                    # profiler.TraceWindow when trace
