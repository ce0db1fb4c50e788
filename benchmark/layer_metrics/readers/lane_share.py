def read(reading, params):
    c = reading.counters
    slots = c.get("dispatches", 0) * c.get("capacity", 0)
    if not slots:
        return None
    return c["session_frames"] / slots
