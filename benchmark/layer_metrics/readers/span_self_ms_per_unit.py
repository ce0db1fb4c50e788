"""A parent span's own time: its length inside the window minus the union of
the child spans recorded on the SAME thread inside it, summed over the parents
and divided by the units completed in the window. Threads are told apart by
name, as the span recorder hands them over."""

from harness import stats


def _select(reading, sel):
    return reading.spans_in_window(
        cat=sel.get("cat"), name_prefix=sel.get("name_prefix"),
        names=tuple(sel["names"]) if "names" in sel else None)


def read(reading, params):
    t0, t1 = reading.window_ns
    parents = _select(reading, params["parent"])
    units = reading.units_in(t0, t1)
    if not parents or not units:
        return None
    kids = {}
    for s in _select(reading, params["children"]):
        kids.setdefault(s.thread, []).append((s.t0_ns, s.t1_ns))
    kids = {th: stats.merge_intervals(iv) for th, iv in kids.items()}
    self_ns = 0.0
    for p in parents:
        a, b = max(p.t0_ns, t0), min(p.t1_ns, t1)
        inside = stats.clip_intervals(kids.get(p.thread, ()), a, b)
        self_ns += (b - a) - sum(y - x for x, y in inside)
    return self_ns * 1e-6 / units
