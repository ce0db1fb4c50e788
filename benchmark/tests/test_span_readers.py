"""The readers PR 24 added, each on a hand-made ``Reading``."""

from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from harness import cells
from harness.reading import HostSpan, Reading

READERS = Path(__file__).resolve().parents[1] / "layer_metrics" / "readers"


def _reader(name):
    return cells.load_module(READERS / f"{name}.py")


def _reading(spans, window=(0, 1000), units=(100, 500, 900), **kw):
    return Reading(driver="stream", window_ns=window, unit="frame",
                   unit_stamps_ns=np.asarray(units, np.int64), spans=spans,
                   **kw)


def _sp(cat, name, t0, t1, thread="drain", **args):
    return HostSpan(cat, name, t0, t1 - t0, args or None, thread)


SELF = {"parent": {"cat": "block", "name_prefix": "TpuKernel"},
        "children": {"cat": "tpu"}}


def test_self_time_with_nested_and_overlapping_children():
    spans = [
        _sp("block", "TpuKernel_0", 0, 400),
        _sp("tpu", "h2d_wait", 50, 150),            # overlaps the next one
        _sp("tpu", "compute", 100, 200),
        _sp("tpu", "d2h_wait", 250, 350),
        _sp("tpu", "inner", 260, 300),              # nested in d2h_wait
        _sp("tpu", "decode", 0, 400, thread="codec-0"),    # another thread
        _sp("block", "StampSink_0", 0, 400),        # not the parent asked for
        _sp("block", "TpuKernel_0", 600, 700),      # a parent with no child
        _sp("tpu", "emit", 690, 720),               # reaches out of its parent
    ]
    got = _reader("span_self_ms_per_unit").read(_reading(spans), SELF)
    # parent 1: 400 - ([50,200] + [250,350]) = 150; parent 2: 100 - 10 = 90
    assert got == pytest.approx((150 + 90) * 1e-6 / 3)


def test_self_time_clips_parent_to_the_window_and_needs_units():
    spans = [_sp("block", "TpuKernel_0", -100, 100),
             _sp("tpu", "compute", -50, 50)]
    r = _reader("span_self_ms_per_unit")
    assert r.read(_reading(spans, units=(10,)), SELF) == pytest.approx(50e-6)
    assert r.read(_reading(spans, units=()), SELF) is None
    assert r.read(_reading([], units=(10,)), SELF) is None


def test_bytes_rate_is_a_median_and_skips_spans_without_bytes():
    spans = [_sp("tpu", "H2D", 0, 100, bytes=1000),        # 10 B/ns
             _sp("tpu", "H2D", 100, 200, bytes=2000),      # 20
             _sp("tpu", "H2D", 200, 300, bytes=6000),      # 60
             _sp("tpu", "H2D", 300, 400),                  # no args at all
             _sp("tpu", "H2D", 400, 500, seq=4),           # args, no bytes
             _sp("tpu", "H2D", 500, 500, bytes=64),        # no length
             _sp("tpu", "D2H", 0, 10, bytes=10 ** 9)]      # another span
    r = _reader("span_bytes_rate")
    assert r.read(_reading(spans), {"cat": "tpu", "name": "H2D"}) == 20.0
    assert r.read(_reading(spans[3:5]), {"cat": "tpu", "name": "H2D"}) is None


def test_arg_percentile_reads_one_argument():
    spans = [_sp("serve", "queue_wait", 10 * i, 10 * i + 5, mean_ms=float(v))
             for i, v in enumerate((4, 12, 8))] + \
            [_sp("serve", "queue_wait", 90, 95, frames=3)]     # no mean_ms
    r = _reader("span_arg_percentile")
    p = {"cat": "serve", "name": "queue_wait", "arg": "mean_ms", "q": 50}
    assert r.read(_reading(spans), p) == 8.0
    assert r.read(_reading(spans[3:]), p) is None


def test_idle_under_spans_moves_the_gaps_between_the_clocks():
    # profiler clock: window [5000, 6000], idle in [5000,5100] and [5400,5800];
    # host clock: the same window is [1000, 2000], so the offset is -4000
    trace = SimpleNamespace(window_ns=(5000.0, 6000.0),
                            gaps=[(5000.0, 5100.0), (5400.0, 5800.0)])
    spans = [_sp("tpu", "h2d_wait", 1050, 1150),    # 50 of gap 1
             _sp("tpu", "h2d_wait", 1300, 1500),    # 100 of gap 2
             _sp("tpu", "h2d_wait", 1450, 1600),    # overlaps: union to 1600
             _sp("tpu", "d2h_wait", 1000, 2000),    # not asked for
             _sp("tpu", "h2d_wait", 1900, 2500)]    # idle nowhere
    r = _reader("idle_under_spans")
    p = {"cat": "tpu", "names": ["h2d_wait"]}
    got = r.read(_reading(spans, window=(0, 3000), trace=trace,
                          traced_ns=(1000, 2000)), p)
    assert got == pytest.approx((50 + 200) / 500)
    assert r.read(_reading(spans), p) is None                  # no trace
    assert r.read(_reading([], trace=trace, traced_ns=(1000, 2000)), p) is None


def test_spans_dropped_reads_the_programs_recorder(monkeypatch):
    from futuresdr_tpu.telemetry import spans as prog

    rec = prog.recorder()
    monkeypatch.setattr(rec, "dropped", 3)
    monkeypatch.setattr(rec, "unwatched", 2, raising=False)
    r = _reader("spans_dropped")
    assert r.read(_reading([_sp("tpu", "frame", 0, 1)]), {}) == 5.0
    assert r.read(_reading([]), {}) is None         # an untraced run


def test_every_new_metric_file_names_a_reader_that_exists():
    names = [p.stem for p in READERS.parent.glob("*.json")]
    assert len(names) == 31
    for n in names:
        spec = cells.load_json(READERS.parent / f"{n}.json")
        assert spec["name"] == n
        assert (READERS / f"{spec['reader']}.py").is_file(), n
