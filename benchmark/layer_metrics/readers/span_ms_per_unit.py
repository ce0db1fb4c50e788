def read(reading, params):
    t0, t1 = reading.window_ns
    names = tuple(params["names"]) if "names" in params else None
    spans = reading.spans_in_window(cat=params.get("cat"), names=names,
                                    name_prefix=params.get("name_prefix"))
    units = reading.units_in(t0, t1)
    if not spans or not units:
        return None
    total_ns = sum(min(s.t1_ns, t1) - max(s.t0_ns, t0) for s in spans)
    return total_ns * 1e-6 / units
