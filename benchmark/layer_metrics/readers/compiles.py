def read(reading, params):
    return float(reading.compiles_in_window)
