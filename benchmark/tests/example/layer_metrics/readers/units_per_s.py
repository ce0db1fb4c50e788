def read(reading, params):
    t0, t1 = reading.window_ns
    n = reading.units_in(t0, t1)
    return n / ((t1 - t0) * 1e-9) if n else None
