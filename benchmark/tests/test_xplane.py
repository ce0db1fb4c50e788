"""xplane.py on hand-made intervals and on the recorded trace (half a second
of ``spectrum_sat`` on the v5e, taken in PR 22's first traced chip call)."""

from pathlib import Path

import pytest

from harness import xplane

RECORDED = Path(__file__).resolve().parents[1] / "recorded" \
    / "spectrum_sat_half_second.xplane.pb.gz"


def test_busy_idle_and_self_time_by_hand():
    # a `while` from 10 to 50 holds two body ops; then a lone op 70..80
    dev = {"/device:TPU:0": [("while", 10, 40), ("body.a", 12, 10),
                             ("body.b", 30, 15), ("lone", 70, 10)]}
    red = xplane.reduce_events(dev, 0, 100)
    assert red.busy_ns == 50                      # [10,50] + [70,80]
    assert red.idle_share == pytest.approx(0.5)
    assert red.op_self_ns == {"while": 15, "body.a": 10, "body.b": 15,
                              "lone": 10}
    assert sum(red.op_self_ns.values()) == red.busy_ns
    assert red.gaps == [(0, 10), (50, 70), (80, 100)]
    # clipped to a window that cuts events
    red = xplane.reduce_events(dev, 20, 75)
    assert red.busy_ns == 30 + 5                  # [20,50] + [70,75]
    assert red.gaps == [(50, 70)]


def test_busy_is_averaged_over_devices():
    dev = {"/device:TPU:0": [("a", 0, 10)], "/device:TPU:1": [("a", 0, 30)]}
    red = xplane.reduce_events(dev, 0, 100)
    assert red.busy_ns == 20
    assert red.per_device_busy_ns == {"/device:TPU:0": 10, "/device:TPU:1": 30}
    assert red.op_self_ns["a"] == 40


def test_gaps_are_named_by_the_host_span_that_covers_most():
    gaps = [(0, 100), (200, 300), (400, 500), (600, 700)]
    spans = [("encode", 0, 60), ("decode", 60, 90),           # encode wins
             ("serve_step", 190, 310), ("encode", 200, 220),  # broad wins
             ("serve_step", 390, 510), ("decode", 400, 480)]  # narrow >= half
    named = xplane.name_gaps(gaps, spans)
    assert named == [("encode", 100), ("serve_step", 100), ("decode", 100),
                     ("unattributed", 100)]


def test_breakdown_has_at_most_ten_of_each():
    dev = {"/device:TPU:0": [(f"op{i}", 10 * i, 5) for i in range(30)]}
    red = xplane.reduce_events(dev, 0, 300)
    b = xplane.breakdown(red, [("generator", 0, 300)])
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] == "generator"
    assert b["idle_gaps"][-1][0] == "sum:generator"
    assert b["idle_gaps"][-1][1] == pytest.approx(150e-9)


def test_clock_offset():
    assert xplane.to_profile_clock([("x", 1000, 1100)], 50, 900) == \
        [("x", 150, 250)]


def test_short_op_name():
    assert xplane.short_op_name(
        "%reshape.123 = u16[524288,2]{1,0} reshape(u16[1048576] %x)") == "reshape.123"
    assert xplane.short_op_name("fusion.7") == "fusion.7"


def _brute_union(events, t0, t1):
    """Independent of xplane.py: mark every covered nanosecond boundary."""
    edges = sorted({t0, t1, *[max(t0, min(t1, s)) for _, s, _ in events],
                    *[max(t0, min(t1, s + d)) for _, s, d in events]})
    covered = 0.0
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        if any(s <= mid < s + d for _, s, d in events):
            covered += b - a
    return covered


def test_recorded_trace_from_the_chip():
    tr = xplane.load(str(RECORDED))
    assert tr.op_line == {"/device:TPU:0": "XLA Ops"}
    assert tr.layout["/device:TPU:0"]["XLA Ops"] == 9921
    assert tr.layout["/device:TPU:0"]["XLA Modules"] == 149
    assert len(tr.sync_ns) == 2
    t0, t1 = tr.sync_ns
    assert t1 - t0 == pytest.approx(500188511.0)          # the 0.5 s window
    red = xplane.reduce_events(tr.devices, t0, t1)
    # values read off the trace by hand when it was recorded
    assert red.busy_ns == pytest.approx(193506489.0)
    assert red.idle_share == pytest.approx(0.61313288, abs=1e-7)
    top = red.top_ops(4)
    assert [n for n, _ in top] == ["reshape.123", "slice_reduce_fusion",
                                   "reshape.121", "shift-left_reduce_fusion"]
    assert top[0][1] == pytest.approx(0.059362981)
    # no op nests in another here, so self times add up to the busy time
    assert sum(red.op_self_ns.values()) == pytest.approx(red.busy_ns)
    # an independent union over the first 400 events agrees
    evs = tr.devices["/device:TPU:0"][:400]
    a = min(s for _, s, _ in evs)
    b = max(s + d for _, s, d in evs)
    part = xplane.reduce_events({"d": evs}, a, b)
    assert part.busy_ns == pytest.approx(_brute_union(evs, a, b))
    # the program ran 149 times at ~1.308 ms: the modules line, which the
    # reduction does not read, says the same within the gaps between ops
    assert 149 * 1.25e6 < red.busy_ns * (149 / 148) < 149 * 1.35e6
