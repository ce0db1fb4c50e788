def read(reading, params):
    v = reading.counters.get(params["key"])
    return None if v is None else float(v)
