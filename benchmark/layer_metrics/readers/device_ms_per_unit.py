def read(reading, params):
    ns = reading.device_ns_per_unit()
    return None if ns is None else ns * 1e-6
