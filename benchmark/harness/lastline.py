"""The one JSON object a run prints last."""

from __future__ import annotations

import json
from typing import Dict, Optional

REQUIRED = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


def build(correct: bool, attempted: int, failed: int,
          metrics: Dict[str, Dict[str, object]], device: dict,
          breakdown: Optional[dict] = None, rehearse: bool = False) -> dict:
    """``metrics`` maps a metric's name to ``{"value": number, "unit": str}``.
    A rehearsal is never correct and says so."""
    for k in DEVICE_KEYS:
        if k not in device:
            raise ValueError(f"device lacks {k!r}")
    for name, m in metrics.items():
        v = m.get("value")
        if isinstance(v, bool) or not isinstance(v, (int, float)) or v != v:
            raise ValueError(f"metric {name!r} has no number: {v!r}")
        if not m.get("unit"):
            raise ValueError(f"metric {name!r} has no unit")
    line = {"correct": bool(correct) and not rehearse,
            "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = {
            "device_ops": [list(x) for x in breakdown["device_ops"]][:10],
            "idle_gaps": [list(x) for x in breakdown["idle_gaps"]][:10]}
    if rehearse:
        line["rehearse"] = True
    return line


def dumps(line: dict) -> str:
    return json.dumps(line, allow_nan=False)
