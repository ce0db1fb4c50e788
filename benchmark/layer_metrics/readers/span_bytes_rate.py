"""Median, over the named spans in the window that carry ``args["bytes"]`` and
have a length, of bytes over duration, in GB/s (bytes per nanosecond)."""

from harness import stats


def read(reading, params):
    spans = reading.spans_in_window(cat=params.get("cat"),
                                    names=(params["name"],))
    rates = [s.args["bytes"] / s.dur_ns for s in spans
             if s.dur_ns > 0 and s.args and s.args.get("bytes")]
    if not rates:
        return None
    return stats.percentile(rates, params.get("q", 50))
