"""The share of the device's idle time in the traced window during which one
of the named host spans was open. The reduced trace's gaps are on the
profiler's clock and the spans on ``time.perf_counter_ns``: both windows start
at the same ``bench_sync`` mark, so ``traced_ns[0] - trace.window_ns[0]`` moves
a gap to the host clock."""

from harness import stats


def read(reading, params):
    if reading.trace is None or reading.traced_ns is None:
        return None
    t0, t1 = reading.traced_ns
    off = t0 - reading.trace.window_ns[0]
    gaps = stats.clip_intervals(
        [(a + off, b + off) for a, b in reading.trace.gaps], t0, t1)
    idle = sum(b - a for a, b in gaps)
    names = tuple(params["names"])
    spans = [(s.t0_ns, s.t1_ns) for s in reading.spans
             if s.name in names and params.get("cat") in (None, s.cat)]
    if not idle or not spans:
        return None
    open_iv = stats.merge_intervals(stats.clip_intervals(spans, t0, t1))
    gaps = stats.merge_intervals(gaps)
    covered, i, j = 0.0, 0, 0           # both lists sorted and disjoint
    while i < len(gaps) and j < len(open_iv):
        (a, b), (c, d) = gaps[i], open_iv[j]
        covered += max(0.0, min(b, d) - max(a, c))
        if b <= d:
            i += 1
        else:
            j += 1
    return covered / idle
