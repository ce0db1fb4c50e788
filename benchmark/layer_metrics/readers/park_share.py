from harness import stats


def read(reading, params):
    if not reading.spans:               # an untraced run recorded none
        return None
    t0, t1 = reading.window_ns
    parks = reading.spans_in_window(cat="park",
                                    name_prefix=params["block_prefix"])
    iv = stats.clip_intervals([(s.t0_ns, s.t1_ns) for s in parks], t0, t1)
    return stats.union_length(iv) / (t1 - t0)
