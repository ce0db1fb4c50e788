"""The table of peaks, keyed by ``device_kind`` exactly as JAX reports it."""

from __future__ import annotations

import json
from pathlib import Path

_PATH = Path(__file__).with_name("peaks.json")


class UnknownDevice(KeyError):
    """The device is not in ``peaks.json``: no roofline can be stated."""


def peaks_for(device_kind: str) -> dict:
    table = json.loads(_PATH.read_text())
    row = table.get(device_kind)
    if not isinstance(row, dict) or device_kind.startswith("_"):
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in {_PATH.name}; add its "
            f"published peaks with their source")
    return row
