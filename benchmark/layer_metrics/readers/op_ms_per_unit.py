"""Device time of the named operations in the traced window over the units
completed in it, in ms: the divisor of ``Reading.device_ns_per_unit``, so a
kernel and the whole program are read on one base. An operation matches when
its name in the trace, less a trailing ``.<digits>``, is one of
``params["ops"]`` (``pallas_pfb.1`` is ``pallas_pfb``); every call of it
counts, at its self time, averaged over the devices as the busy time is."""

import re

_SUFFIX = re.compile(r"\.\d+$")


def op_ns_per_unit(reading, ops):
    """Nanoseconds of the matching ops a unit; None without a trace, without
    units or where no such op ran."""
    tr = reading.trace
    if tr is None or reading.traced_ns is None:
        return None
    units = reading.units_in(*reading.traced_ns)
    ns = sum(v for name, v in tr.op_self_ns.items()
             if _SUFFIX.sub("", name) in ops)
    if not units or ns <= 0:
        return None
    return ns / max(1, len(tr.per_device_busy_ns)) / units


def read(reading, params):
    ns = op_ns_per_unit(reading, set(params["ops"]))
    return None if ns is None else ns * 1e-6
