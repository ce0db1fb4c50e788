def read(reading, params):
    return None if reading.trace is None else reading.trace.idle_share
