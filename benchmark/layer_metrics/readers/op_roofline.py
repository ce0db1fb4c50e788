"""A kernel's share of its roofline, in percent: the least time the chip
could take for what one unit needs of it (``cost_per_unit["kernels"]
[params["cost"]]``, from shapes by ``harness/costs.py``) over the device time
of its operations a unit (``op_ms_per_unit``). None where either is absent."""

from pathlib import Path

from harness import cells, costs

_ops = cells.load_module(Path(__file__).with_name("op_ms_per_unit.py"))


def read(reading, params):
    ns = _ops.op_ns_per_unit(reading, set(params["ops"]))
    cost = ((reading.cost_per_unit or {}).get("kernels") or {}).get(params["cost"])
    if ns is None or cost is None or not reading.peaks:
        return None
    least_s = costs.roofline(cost, reading.peaks)["min_s"]
    return 100.0 * least_s / (ns * 1e-9) if least_s > 0 else None
