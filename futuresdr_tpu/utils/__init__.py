"""Utilities: latency tracepoints, checkpoint/resume, misc helpers."""

from .trace import LatencyProbeSource, LatencyProbeSink, latency_stats
from .checkpoint import (save_pytree, load_pytree, save_flowgraph_state,
                         load_flowgraph_state)

__all__ = ["LatencyProbeSource", "LatencyProbeSink", "latency_stats",
           "save_pytree", "load_pytree", "save_flowgraph_state",
           "load_flowgraph_state"]
