from harness import stats


def read(reading, params):
    if not reading.latencies_ms:
        return None
    return stats.percentile(reading.latencies_ms, params["q"])
