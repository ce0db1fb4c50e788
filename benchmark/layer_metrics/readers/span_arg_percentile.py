"""A percentile of one numeric argument of the named spans in the window."""

from harness import stats


def read(reading, params):
    spans = reading.spans_in_window(cat=params.get("cat"),
                                    names=(params["name"],))
    vals = [s.args[params["arg"]] for s in spans
            if s.args and s.args.get(params["arg"]) is not None]
    if not vals:
        return None
    return stats.percentile(vals, params["q"])
