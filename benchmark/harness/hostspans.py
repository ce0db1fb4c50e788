"""The program's span recorder, read from outside: drained into
``HostSpan``s, and renamed to the words ``breakdown`` uses. ``compute`` is
read only under the name ``dispatch_call`` (on a TPU it brackets the enqueue
of the program call, never device time); ``H2D`` is not read at all (it ends
when ``finish()`` is called, not when the transfer does)."""

from __future__ import annotations

import gc
import time
from typing import List, Tuple

from .reading import HostSpan


def drain_program_spans() -> List[HostSpan]:
    from futuresdr_tpu.telemetry import spans
    return [HostSpan(e.cat, e.name, e.t0_ns, e.dur_ns, e.args, e.thread)
            for e in spans.drain() if e.dur_ns is not None]


def named_for_breakdown(spans: List[HostSpan],
                        kernel_prefix: str = "TpuKernel") -> List[Tuple[str, int, int]]:
    out = []
    for s in spans:
        if s.cat == "park" and s.name.startswith(kernel_prefix):
            a = s.args or {}
            kind = "park_stalled" if a.get("stalled") else \
                "park_starved" if a.get("starved") else "park_other"
            out.append((kind, s.t0_ns, s.t1_ns))
        elif s.cat == "tpu" and s.name in ("encode", "decode"):
            out.append((s.name, s.t0_ns, s.t1_ns))
        elif s.cat == "tpu" and s.name == "compute":
            out.append(("dispatch_call", s.t0_ns, s.t1_ns))
        elif s.cat == "serve" and s.name == "serve_step":
            out.append(("serve_step", s.t0_ns, s.t1_ns))
        elif s.cat == "bench":
            out.append((s.name, s.t0_ns, s.t1_ns))
    return out


class GcWatch:
    """Observes the interpreter's garbage collector without changing it: one
    ``(generation, start_ns, dur_ns)`` per collection, from ``gc.callbacks``.
    A full collection stops every thread of the process, the generator's
    included, so it shows as a stall in both paced cells."""

    def __init__(self):
        self.events: List[Tuple[int, int, int]] = []
        self._t0 = 0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter_ns()
        else:
            self.events.append((int(info["generation"]), self._t0,
                                time.perf_counter_ns() - self._t0))

    def close(self) -> None:
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)

    def summary(self, t0_ns: int, t1_ns: int) -> dict:
        ev = [e for e in self.events if t0_ns <= e[1] < t1_ns]
        full = [e for e in ev if e[0] == 2]
        return {"collections": len(ev), "full": len(full),
                "full_at_s": [round((e[1] - t0_ns) * 1e-9, 3) for e in full][:20],
                "full_ms": [round(e[2] * 1e-6, 1) for e in full][:20],
                "max_ms": round(max((e[2] for e in ev), default=0) * 1e-6, 1)}


def longest(spans: List[HostSpan], t0_ns: int, n: int = 10) -> list:
    """The ``n`` longest spans, for the run's notes on stderr."""
    top = sorted(spans, key=lambda s: -s.dur_ns)[:n]
    return [[s.cat, s.name, s.thread, round(s.dur_ns * 1e-6, 1),
             round((s.t0_ns - t0_ns) * 1e-9, 3)] for s in top]
