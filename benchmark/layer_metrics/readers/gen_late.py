from harness import stats


def read(reading, params):
    if not reading.gen_late_ms:
        return None
    return stats.percentile(reading.gen_late_ms, params.get("q", 95))
