"""The traced window: ``jax.profiler`` around a few seconds of the run, with a
``bench_sync`` mark at both ends tying the profiler's clock to
``time.perf_counter_ns``."""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time
from typing import List, Optional, Tuple

from .xplane import SYNC_NAME


class TraceWindow:
    """``start()`` … ``stop()`` once; the trace lands under ``TMPDIR`` and is
    removed by ``close()`` (``BENCH_KEEP_TRACE=<dir>`` keeps a copy there: the
    tool that records the test fixture sets it)."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.sync_perf_ns: List[int] = []
        self.path: Optional[str] = None

    def _mark(self) -> None:
        import jax
        with jax.profiler.TraceAnnotation(SYNC_NAME):
            self.sync_perf_ns.append(time.perf_counter_ns())

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # the Python tracer slows the host
        opts.host_tracer_level = 1          # the marks are host annotations
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._mark()

    def stop(self) -> str:
        import jax
        self._mark()
        jax.profiler.stop_trace()
        found = sorted(glob.glob(
            os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"the profiler wrote no xplane.pb under {self.dir}")
        self.path = found[-1]
        keep = os.environ.get("BENCH_KEEP_TRACE")
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(self.path, os.path.join(keep, "trace.xplane.pb"))
        return self.path

    @property
    def traced_perf_ns(self) -> Tuple[int, int]:
        return self.sync_perf_ns[0], self.sync_perf_ns[-1]

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
