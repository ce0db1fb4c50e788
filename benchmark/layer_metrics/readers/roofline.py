from harness import costs


def read(reading, params):
    ns = reading.device_ns_per_unit()
    if ns is None or not reading.cost_per_unit or not reading.peaks:
        return None
    least_s = costs.roofline(reading.cost_per_unit, reading.peaks)["min_s"]
    return 100.0 * least_s / (ns * 1e-9)
