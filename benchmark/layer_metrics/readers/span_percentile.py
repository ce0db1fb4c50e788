from harness import stats


def read(reading, params):
    spans = reading.spans_in_window(cat=params.get("cat"),
                                    names=(params["name"],))
    if not spans:
        return None
    return stats.percentile([s.dur_ns * 1e-6 for s in spans], params["q"])
