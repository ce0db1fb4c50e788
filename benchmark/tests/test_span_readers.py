"""The per-layer readers, each on a hand-made ``Reading``, and the metric
files against ``BENCHMARK.json``."""

import re
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from harness import cells, xplane
from harness.reading import HostSpan, Reading

READERS = Path(__file__).resolve().parents[1] / "layer_metrics" / "readers"
RECORDED = Path(__file__).resolve().parents[1] / "recorded" \
    / "spectrum_sat_half_second.xplane.pb.gz"


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
    names = {p.stem for p in READERS.parent.glob("*.json")}
    listed = {m["name"] for m in cells.load_json(
        READERS.parents[2] / "BENCHMARK.json")["per_layer"]}
    assert not names - listed, "metric files without a per_layer entry"
    assert not listed - names, "per_layer entries without a metric file"
    for n in names:
        spec = cells.load_json(READERS.parent / f"{n}.json")
        assert spec["name"] == n
        assert (READERS / f"{spec['reader']}.py").is_file(), n


def test_a_listed_metric_that_reads_nothing_is_named():
    manifest = {"per_layer": [
        {"name": "pallas.pfb_ms_per_frame", "workloads": ["lora_gw_sat"]},
        {"name": "pallas.viterbi_ms_per_frame", "workloads": ["wlan_rx_sat"]},
        {"name": "device.idle_share"},              # no list: not this check's
        {"name": "lora.symbols_per_frame", "workloads": ["lora_gw_sat"]}]}
    read = {"lora.symbols_per_frame": {"value": 403.0, "unit": "count"}}
    assert cells.unread(manifest, "lora_gw_sat", read) == \
        ["pallas.pfb_ms_per_frame"]
    assert cells.unread(manifest, "spectrum_sat", {}) == []
    # every metric BENCHMARK.json lists for a cell is one it resolves, so a
    # cell's traced run can read each of them
    spec = cells.load_json(READERS.parents[2] / "BENCHMARK.json")
    for w in spec["workloads"]:
        got = {m.name: 1 for m in cells.resolve(w["name"]).layer_metrics}
        assert cells.unread(spec, w["name"], got) == [], w["name"]


def test_arg_ratio_sums_both_arguments():
    spans = [_sp("tpu", "encode", 0, 10, lanes_shipped=16, capacity=64),
             _sp("tpu", "encode", 20, 30, lanes_shipped=32, capacity=64),
             _sp("tpu", "encode", 40, 50, capacity=64),           # no numerator
             _sp("tpu", "encode", 60, 70, lanes_shipped=8, capacity=0),
             _sp("tpu", "H2D", 80, 90, lanes_shipped=64, capacity=64)]
    r = _reader("span_arg_ratio")
    p = {"cat": "tpu", "name": "encode", "num": "lanes_shipped",
         "den": "capacity"}
    assert r.read(_reading(spans), p) == pytest.approx(48 / 128)
    assert r.read(_reading(spans[2:4]), p) is None


def _traced(op_self_ns, devices=1, units=(1100, 1500, 1900, 2500)):
    """A reading whose traced window [1000, 2000] holds three units."""
    red = xplane.Reduced((0.0, 1000.0), 900.0,
                         {f"/device:TPU:{i}": 900.0 for i in range(devices)},
                         op_self_ns, {k: 1 for k in op_self_ns}, [])
    return _reading([], window=(0, 3000), units=units, trace=red,
                    traced_ns=(1000, 2000), peaks={"flops_per_s": 1e12,
                                                   "hbm_bytes_per_s": 1e9})


def test_op_time_strips_the_suffix_and_sums_ops_and_calls():
    r = _reader("op_ms_per_unit")
    rd = _traced({"pallas_pfb.1": 120.0, "pallas_pfb.7": 30.0,
                  "lora_window_fetch": 60.0, "lora_window_fetch.72": 90.0,
                  "pallas_pfb_tail.2": 999.0, "fusion.3": 500.0})
    assert r.read(rd, {"ops": ["pallas_pfb"]}) == pytest.approx(150e-6 / 3)
    assert r.read(rd, {"ops": ["pallas_pfb", "lora_window_fetch"]}) == \
        pytest.approx(300e-6 / 3)
    assert r.read(rd, {"ops": ["viterbi_acs"]}) is None          # absent
    assert r.read(_reading([]), {"ops": ["pallas_pfb"]}) is None  # untraced
    # every call of one op: three events of one name in a reduced trace,
    # one of them inside a ``while``, count at their self time
    dev = {"/device:TPU:0": [("while.5", 0, 100), ("lora_window_fetch.72", 10, 20),
                             ("lora_window_fetch.72", 40, 20),
                             ("lora_window_fetch.72", 150, 30)]}
    red = xplane.reduce_events(dev, 0, 200)
    rd = _reading([], units=(50, 150), trace=red, traced_ns=(0, 200))
    assert r.read(rd, {"ops": ["lora_window_fetch"]}) == pytest.approx(70e-6 / 2)
    # several devices: the sum is averaged over them, as the busy time is
    rd = _traced({"traceback.4": 300.0, "viterbi_acs.3": 300.0}, devices=2)
    assert r.read(rd, {"ops": ["viterbi_acs", "traceback"]}) == \
        pytest.approx(300e-6 / 3)


def test_op_roofline_reads_the_kernels_cost():
    r = _reader("op_roofline")
    rd = _traced({"pallas_pfb.1": 150.0, "fusion": 50.0})
    # 20 bytes at 1e9 B/s = 20 ns against 50 ns a unit of the kernel's time
    rd.cost_per_unit = {"flops": 1.0, "bytes": 1.0,
                        "kernels": {"pfb": {"flops": 1000.0, "bytes": 20.0}}}
    p = {"ops": ["pallas_pfb"], "cost": "pfb"}
    assert r.read(rd, p) == pytest.approx(40.0)
    assert r.read(rd, {"ops": ["pallas_pfb"], "cost": "viterbi"}) is None
    assert r.read(rd, {"ops": ["traceback"], "cost": "pfb"}) is None
    rd.cost_per_unit = {"flops": 1.0, "bytes": 1.0}              # no kernels
    assert r.read(rd, p) is None
    rd.cost_per_unit = {"kernels": {"pfb": {"flops": 0.0, "bytes": 0.0}}}
    assert r.read(rd, p) is None                    # never a share of 0


def test_op_readers_on_the_recorded_trace():
    tr = xplane.load(str(RECORDED))
    t0, t1 = tr.sync_ns
    red = xplane.reduce_events(tr.devices, t0, t1)
    # 149 programs ran in the half second: one unit each, on the host clock
    rd = _reading([], window=(0, 10 ** 9), units=np.linspace(1e6, 4.99e8, 149),
                  trace=red, traced_ns=(0, 5 * 10 ** 8),
                  peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    ms = _reader("op_ms_per_unit")
    # four ops of that name, 148 calls each: the FFT-based FIR's matmuls
    names = ("convolution_subtract_fusion", "convolution_subtract_fusion.1",
             "convolution_subtract_fusion.3", "convolution_subtract_fusion.5")
    assert {n for n in red.op_self_ns
            if n.startswith("convolution_subtract")} == set(names)
    assert all(red.op_calls[n] == 148 for n in names)
    want = sum(red.op_self_ns[n] for n in names) * 1e-6 / 149
    got = ms.read(rd, {"ops": ["convolution_subtract_fusion"]})
    assert got == pytest.approx(want) and 0.026 < got < 0.027
    assert ms.read(rd, {"ops": ["slice_reduce_fusion"]}) == \
        pytest.approx(red.op_self_ns["slice_reduce_fusion"] * 1e-6 / 149)
    every = ms.read(rd, {"ops": sorted({re.sub(r"\.\d+$", "", n)
                                        for n in red.op_self_ns})})
    assert every == pytest.approx(rd.device_ns_per_unit() * 1e-6)
    rd.cost_per_unit = {"kernels": {"k": {"flops": 0.0, "bytes": 819e3}}}
    share = _reader("op_roofline").read(
        rd, {"ops": ["convolution_subtract_fusion"], "cost": "k"})
    assert share == pytest.approx(100 * 1e-6 / (want * 1e-3))
