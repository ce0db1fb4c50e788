"""Profile plane (telemetry/profile.py): compile registry reason labels,
recompile-storm detection, the doctor's "compiling" verdict, live-gauge math,
peak autodetection/overrides, and the REST round trip."""

import json
import time
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

from futuresdr_tpu.telemetry import doctor as doc
from futuresdr_tpu.telemetry import profile


# ---------------------------------------------------------------------------
# compile registry: reasons, histogram, active window
# ---------------------------------------------------------------------------

def test_record_compile_reasons_and_histogram():
    pl = profile.ProfilePlane()
    before = profile.COMPILES.get(program="t-reasons", reason="warmup")
    with pl.compiling("t-reasons", "warmup", "frame=1024"):
        time.sleep(0.01)
    assert profile.COMPILES.get(program="t-reasons",
                                reason="warmup") == before + 1
    pl.record_compile("t-reasons", "recover", "frame=1024", seconds=0.5)
    assert profile.COMPILES.get(program="t-reasons", reason="recover") == 1
    assert pl.compiles_total == 2
    assert pl.compile_seconds_total > 0.5       # ctx-manager secs + 0.5
    # the histogram family carries the observation
    h = profile.COMPILE_SECONDS.labels(program="t-reasons")
    assert h.count >= 2


def test_active_compile_window_visible():
    pl = profile.ProfilePlane()
    assert pl.compiling_or_recent(10.0) is None
    with pl.compiling("t-active", "warmup", "sig"):
        act = pl.active_compiles()
        assert len(act) == 1 and act[0]["program"] == "t-active"
        comp = pl.compiling_or_recent(0.001)
        assert comp["in_progress"] and comp["program"] == "t-active"
    assert pl.active_compiles() == []
    # finished inside the window still reports (not in progress)
    comp = pl.compiling_or_recent(10.0)
    assert comp is not None and not comp["in_progress"]
    assert comp["program"] == "t-active" and comp["reason"] == "warmup"
    # ... and ages out of a short window
    time.sleep(0.02)
    assert pl.compiling_or_recent(0.001) is None


def test_storm_detection_names_signatures_and_skips_autotune():
    pl = profile.ProfilePlane()
    # autotune sweeps never read as storms
    for i in range(5):
        pl.record_compile("t-sweep", "autotune", f"frame={i}")
    assert pl.storm_report() == []
    # shape churn on one program: storm naming the signatures
    for sig in ("frame=1024", "frame=2048", "frame=4096"):
        pl.record_compile("t-churn", "warmup", sig)
    (storm,) = pl.storm_report()
    assert storm["program"] == "t-churn" and storm["compiles"] == 3
    assert storm["signatures"] == ["frame=1024", "frame=2048", "frame=4096"]
    assert storm["signature_churn"] is True
    # below threshold: quiet
    pl2 = profile.ProfilePlane()
    pl2.record_compile("t-two", "warmup", "a")
    pl2.record_compile("t-two", "warmup", "b")
    assert pl2.storm_report() == []
    # cost-analysis compiles are one-per-signature by construction: like
    # autotune they never read as a storm (several kernels registering
    # compile many signatures back to back)
    for i in range(5):
        pl2.record_compile("cost_analysis", "cost", f"sig{i}")
    assert pl2.storm_report() == []


def test_finished_benign_reasons_do_not_downgrade_verdicts():
    """A FINISHED autotune/cost compile is invisible to the doctor's
    compiling-verdict lookback (a background sweep must not mask a real
    deadlock); an IN-PROGRESS one still counts."""
    pl = profile.ProfilePlane()
    pl.record_compile("t-sweep", "autotune", "frame=1", seconds=0.2)
    pl.record_compile("cost_analysis", "cost", "sig", seconds=0.2)
    assert pl.compiling_or_recent(60.0) is None
    pl.record_compile("t-real", "warmup", "frame=2", seconds=0.2)
    comp = pl.compiling_or_recent(60.0)
    assert comp is not None and comp["program"] == "t-real"
    with pl.compiling("t-sweep", "autotune", "frame=3"):
        comp = pl.compiling_or_recent(0.001)
        assert comp is not None and comp["in_progress"]


def test_reregistration_replaces_cost_source():
    """register() with a new cost_thunk REPLACES an already-materialized
    cost (a re-init can change the program); dispatch counters survive."""
    pl = profile.ProfilePlane()
    p = pl.register("t-rereg", cost={"flops": 1.0, "bytes": 1.0})
    p.dispatch(3)
    pl.register("t-rereg", cost_thunk=lambda: {"flops": 9.0, "bytes": 2.0})
    assert p.cost is None                 # stale cost dropped
    assert p.units == 3                   # counters kept
    assert p.ensure_cost() == {"flops": 9.0, "bytes": 2.0}


# ---------------------------------------------------------------------------
# doctor "compiling" verdict
# ---------------------------------------------------------------------------

def _fake_wk(name="fake_0"):
    wk = types.SimpleNamespace()
    wk.instance_name = name
    wk.kernel = types.SimpleNamespace(stream_inputs=(), stream_outputs=())
    wk.counters = {"work_calls": 0}
    wk.metrics = lambda: dict(wk.counters)
    return wk


def test_watchdog_compiling_verdict_rearms():
    """An in-progress compile inside the no-progress window classifies
    `compiling` (no flight record, window re-arms); once the compile ages
    out, the same silence gets its real diagnosis."""
    d = doc.Doctor()
    d.interval, d.window = 0.01, 3
    token = d.attach([_fake_wk()], [])
    with profile.plane().compiling("t-doctor-prog", "warmup", "frame=2M"):
        for _ in range(5):
            d.tick()
        assert d.last_trip is not None
        assert d.last_trip["state"] == "compiling"
        assert d.last_trip["suspect_block"] == "t-doctor-prog"
        assert "warmup" in d.last_trip["detail"]
        assert d.last_report is None          # benign: no flight record
        att = d._fgs[token]
        assert not att.tripped                # window re-armed
    # compile done and aged out of the (strikes x interval) window: the
    # quiet message-plane flowgraph now reports its genuine verdict
    time.sleep(0.1)
    att = d._fgs[token]
    att.strikes = 0
    for _ in range(4):
        d.tick()
    assert d.last_trip["state"] == "idle"
    d.detach(token)


# ---------------------------------------------------------------------------
# live-gauge math + roofline report
# ---------------------------------------------------------------------------

def test_live_gauge_math(monkeypatch):
    from futuresdr_tpu.config import config
    monkeypatch.setattr(config(), "peak_flops", 1e12)
    monkeypatch.setattr(config(), "peak_hbm_gbps", 100.0)   # 1e11 B/s
    pl = profile.ProfilePlane()
    p = pl.register("t-gauge-math", cost={"flops": 2e9, "bytes": 1e8})
    pl.update_live_gauges(min_interval=0.0)   # seed the window
    p.dispatch(4, t=time.monotonic())     # dispatch SITES own the group
    time.sleep(0.05)                      # stamp (kernel drive loop/serve
    p.dispatch(4, t=time.monotonic())     # step); the hook stays bare
    pl.update_live_gauges(min_interval=0.0)
    assert p.mfu is not None and p.mfu > 0
    # the config peak_flops is the BF16 matmul peak; an unlowered program
    # defaults to compute_dtype="f32" whose peak is half (per-dtype chip
    # peaks, utils/roofline.dtype_peak_flops): flops/(peak/2) = 2e9/5e11 =
    # 4e-3 per unit-rate; bytes/peak_bw = 1e8/1e11 = 1e-3 — mfu must be
    # exactly 4x hbm_util (same window)
    assert p.compute_dtype == "f32"
    assert p.mfu == pytest.approx(4 * p.hbm_util, rel=1e-6)
    assert profile.MFU.get(program="t-gauge-math") == pytest.approx(p.mfu)
    # run-average lands in the roofline report with bound classification
    rep = pl.roofline_report()
    entry = rep["programs"]["t-gauge-math"]
    assert entry["units"] == 8
    assert entry["compute_dtype"] == "f32"
    assert entry["mfu_avg"] > 0
    # the run average spans first..last dispatch and the FIRST call's units
    # mark the left edge: rate = (8 - 4) / (t_last - t_first), not 8/dt —
    # units/(units-1) inflation on short runs is the bug this pins
    dt = p.t_last - p.t_first
    want = (4 / dt) * 2e9 / (1e12 / 2)
    assert entry["mfu_avg"] == pytest.approx(want, rel=1e-3)
    # arith intensity 2e9/1e8 = 20 flop/B vs the f32 ridge 5e11/1e11 = 5
    # → compute
    assert entry["bound"] == "compute"
    # a bf16-lowered program re-registered with dtype="bf16" grades against
    # the FULL tabled peak: same dispatch record, half the mfu
    pl.register("t-gauge-math", cost={"flops": 2e9, "bytes": 1e8},
                dtype="bf16")
    rep2 = pl.roofline_report()
    e2 = rep2["programs"]["t-gauge-math"]
    assert e2["compute_dtype"] == "bf16"
    assert e2["mfu_avg"] == pytest.approx(want / 2, rel=1e-3)


def test_int8_program_grades_against_int8_peak(monkeypatch):
    """An int8-lowered program's MFU denominator is the int8 peak where the
    chip tables one (2x the bf16 MXU figure), the bf16 peak where it does
    not — NEVER the f32 half (the pre-round-20 fallback this pins out)."""
    from futuresdr_tpu.config import config
    from futuresdr_tpu.utils.roofline import (CHIP_PEAKS, dominant_dtype,
                                              dtype_peak_flops)
    v5e = CHIP_PEAKS["v5e"]
    assert dtype_peak_flops(v5e, "int8") == v5e["int8_flops"]
    assert dtype_peak_flops(v5e, "int8") == 2 * dtype_peak_flops(v5e, "bf16")
    # v2-v4 MXUs have no int8 mode: fall back to the bf16 figure
    v4 = CHIP_PEAKS["v4"]
    assert "int8_flops" not in v4
    assert dtype_peak_flops(v4, "int8") == dtype_peak_flops(v4, "bf16")
    assert dtype_peak_flops(v4, "int8") == 2 * dtype_peak_flops(v4, "f32")

    # the registration path TpuKernel drives: a mode="int8"-lowered chain's
    # dominant dtype is "int8", so fsdr_mfu{program} keys the peak above
    from futuresdr_tpu.ops import precision as P
    from futuresdr_tpu.ops.stages import (Pipeline, fft_stage, fir_stage,
                                          mag2_stage)
    taps = np.hanning(33).astype(np.float32)
    pipe = Pipeline([fir_stage(taps), fft_stage(256), mag2_stage()],
                    np.complex64)
    low, plan = P.plan_interior_precision(pipe, mode="int8")
    assert plan.lowered >= 1
    assert dominant_dtype(low.stages) == "int8"

    # gauge math end-to-end: config peaks carry no int8 figure, so an
    # int8-registered program grades against the FULL bf16 peak
    monkeypatch.setattr(config(), "peak_flops", 1e12)
    monkeypatch.setattr(config(), "peak_hbm_gbps", 100.0)
    pl = profile.ProfilePlane()
    p = pl.register("t-int8-peak", cost={"flops": 2e9, "bytes": 1e8},
                    dtype="int8")
    p.dispatch(4, t=time.monotonic())
    time.sleep(0.05)
    p.dispatch(4, t=time.monotonic())
    rep = pl.roofline_report()
    e = rep["programs"]["t-int8-peak"]
    assert e["compute_dtype"] == "int8"
    dt = p.t_last - p.t_first
    want = (4 / dt) * 2e9 / 1e12
    assert e["mfu_avg"] == pytest.approx(want, rel=1e-3)


def test_dispatch_hook_bound_before_first_call_advances_window(monkeypatch):
    """A dispatch hook reference captured at init (before any dispatch —
    the hot-path pattern _Program's docstring encourages) must keep
    advancing t_last on later stamped calls: the bound method still points
    at _dispatch_first after the slot swap, and a frozen right edge would
    silently zero mfu_avg for that program."""
    from futuresdr_tpu.config import config
    monkeypatch.setattr(config(), "peak_flops", 1e12)
    monkeypatch.setattr(config(), "peak_hbm_gbps", 100.0)
    pl = profile.ProfilePlane()
    p = pl.register("t-stale-hook", cost={"flops": 1e6, "bytes": 1e6})
    hook = p.dispatch                     # bound BEFORE the first call
    t0 = time.monotonic()
    hook(2, t=t0)
    hook(2, t=t0 + 1.0)                   # same stale reference
    assert p.units == 4
    assert p.t_first == pytest.approx(t0)
    assert p.t_last == pytest.approx(t0 + 1.0)
    rep = pl.roofline_report()
    assert rep["programs"]["t-stale-hook"]["mfu_avg"] is not None


def test_live_gauge_bound_classification(monkeypatch):
    from futuresdr_tpu.config import config
    monkeypatch.setattr(config(), "peak_flops", 1e12)
    monkeypatch.setattr(config(), "peak_hbm_gbps", 100.0)   # ridge = 10 f/B
    pl = profile.ProfilePlane()
    pl.register("t-bound-hbm", cost={"flops": 1e6, "bytes": 1e6})   # ai 1
    pl.register("t-bound-mxu", cost={"flops": 1e8, "bytes": 1e6})   # ai 100
    rep = pl.roofline_report()
    assert rep["programs"]["t-bound-hbm"]["bound"] == "hbm"
    assert rep["programs"]["t-bound-mxu"]["bound"] == "compute"
    assert rep["ridge_flop_per_byte"] == pytest.approx(10.0)


def test_unmaterialized_cost_publishes_nothing(monkeypatch):
    """A lazily-registered program with no materialized cost degrades to
    dispatch counting — no gauge, no wrong denominator; ensure_costs
    swallows a failing thunk."""
    from futuresdr_tpu.config import config
    monkeypatch.setattr(config(), "peak_flops", 1e12)
    monkeypatch.setattr(config(), "peak_hbm_gbps", 100.0)

    def boom():
        raise RuntimeError("no cost for you")

    pl = profile.ProfilePlane()
    p = pl.register("t-no-cost", cost_thunk=boom)
    p.dispatch(3)
    pl.ensure_costs()
    pl.update_live_gauges(min_interval=0.0)
    assert p.cost is None and p.mfu is None
    assert profile.MFU.get(program="t-no-cost") == 0.0
    entry = pl.roofline_report()["programs"]["t-no-cost"]
    assert entry == {"units": 3}


# ---------------------------------------------------------------------------
# peak autodetection (utils/roofline.detect_peaks)
# ---------------------------------------------------------------------------

def test_detect_peaks_config_override(monkeypatch):
    from futuresdr_tpu.config import config
    from futuresdr_tpu.utils.roofline import detect_peaks
    monkeypatch.setattr(config(), "peak_flops", 5e12)
    monkeypatch.setattr(config(), "peak_hbm_gbps", 123.0)
    p = detect_peaks()
    assert p == {"flops": 5e12, "hbm_bytes": 123e9, "chip": "config"}


def test_detect_peaks_device_kind(monkeypatch):
    import jax

    from futuresdr_tpu.utils import roofline

    class _Dev:
        def __init__(self, platform, kind):
            self.platform = platform
            self.device_kind = kind

    # known chip kinds map to the public table
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_Dev("tpu", "TPU v5 lite")])
    p = roofline.detect_peaks()
    assert p["chip"] == "v5e" and p["flops"] == 197e12
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("tpu", "TPU v4")])
    assert roofline.detect_peaks()["chip"] == "v4"
    # UNKNOWN accelerator: degrade to flops/bytes-only, never a wrong
    # denominator
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("tpu", "TPU v99")])
    assert roofline.detect_peaks() is None
    # a cpu host has no peak: a label never stands in for a live device
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("cpu", "cpu")])
    assert roofline.detect_peaks() is None


def test_kind_to_chip_mapping():
    from futuresdr_tpu.utils.roofline import _kind_to_chip
    assert _kind_to_chip("TPU v5 lite") == "v5e"
    assert _kind_to_chip("tpu_v5_lite") == "v5e"
    assert _kind_to_chip("TPU v5p") == "v5p"
    assert _kind_to_chip("TPU v6e") == "v6e"
    assert _kind_to_chip("TPU v4") == "v4"
    assert _kind_to_chip("TPU v3") == "v3"
    assert _kind_to_chip("TPU v2") == "v2"
    assert _kind_to_chip("Quantum Accelerator Mk1") is None


# ---------------------------------------------------------------------------
# kernel integration: warmup billed once, dispatches billed as units
# ---------------------------------------------------------------------------

def test_tpu_kernel_bills_warmup_and_dispatches():
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import Head, NullSink, NullSource
    from futuresdr_tpu.config import config
    from futuresdr_tpu.ops import mag2_stage
    from futuresdr_tpu.tpu import TpuKernel

    frame = 1 << 12
    config().buffer_size = max(config().buffer_size, 4 * frame * 8)
    fg = Flowgraph()
    # frames_per_dispatch pinned to an EXPLICIT 1: a streamed pick recorded
    # by an earlier test could otherwise resolve K>1 from the in-memory
    # autotune cache and halve the dispatch count this test asserts on
    tk = TpuKernel([mag2_stage()], np.complex64, frame_size=frame,
                   frames_in_flight=2, frames_per_dispatch=1)
    fg.connect(NullSource(np.complex64), Head(np.complex64, 8 * frame),
               tk, NullSink(np.float32))
    # DELTA assertions: instance names are per-flowgraph, so an earlier
    # test's TpuKernel_2 shares this program label (and its plane entry —
    # register() keeps counters across re-registration by design)
    prog = tk.meta.instance_name
    warm0 = profile.COMPILES.get(program=prog, reason="warmup")
    reinit0 = profile.COMPILES.get(program=prog, reason="reinit")
    prev = profile.plane().program(prog)
    units0 = prev.units if prev is not None else 0
    Runtime().run(fg)
    assert profile.COMPILES.get(program=prog, reason="warmup") == warm0 + 1
    assert profile.COMPILES.get(program=prog, reason="reinit") == reinit0
    assert tk._prof is not None
    assert tk._prof.units - units0 == tk._dispatches >= 8
    # the registered cost materializes on demand (cached cost analysis)
    cost = tk._prof.ensure_cost()
    assert cost is not None and cost["bytes"] > 0


def test_doctor_report_roofline_and_resource(monkeypatch):
    """doctor.report() carries the roofline table and the binding-resource
    verdict: a compute-lane bottleneck names the dominant program's bound
    resource, not just the lane."""
    from futuresdr_tpu.config import config
    from futuresdr_tpu.telemetry.spans import SpanEvent
    monkeypatch.setattr(config(), "peak_flops", 1e12)
    monkeypatch.setattr(config(), "peak_hbm_gbps", 100.0)
    p = profile.plane().register("t-resource",
                                 cost={"flops": 1e6, "bytes": 1e6})  # hbm
    p.dispatch(2)
    mk = lambda name, s, e: SpanEvent(1, "t", s, e - s, "tpu", name, None)
    rep = doc.Doctor().report(events=[mk("compute", 0, 10_000_000),
                                      mk("H2D", 0, 1_000_000)])
    assert rep["bottleneck_lane"] == "compute"
    assert rep["bottleneck_resource"] == "hbm"
    assert "t-resource" in rep["roofline"]["programs"]
    # link-bound run names the link
    rep2 = doc.Doctor().report(events=[mk("compute", 0, 1_000_000),
                                       mk("H2D", 0, 10_000_000)])
    assert rep2["bottleneck_resource"] == "link"


# ---------------------------------------------------------------------------
# REST round trip
# ---------------------------------------------------------------------------

def test_profile_endpoint_round_trip():
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import NullSink, NullSource
    from futuresdr_tpu.runtime.ctrl_port import ControlPort

    profile.plane().register("t-rest-prog",
                             cost={"flops": 1e6, "bytes": 1e6})
    profile.record_compile("t-rest-prog", "warmup", "frame=4096", 0.1)
    fg = Flowgraph()
    fg.connect(NullSource(np.float32), NullSink(np.float32))
    rt = Runtime()
    running = rt.start(fg)
    cp = ControlPort(rt.handle, bind="127.0.0.1:29473")
    cp.start()
    base = "http://127.0.0.1:29473"
    try:
        snap = json.load(urllib.request.urlopen(base + "/api/fg/0/profile/"))
        assert snap["compiles"]["t-rest-prog"]["warmup"] >= 1
        assert snap["compiles_total"] >= 1
        assert "t-rest-prog" in snap["roofline"]["programs"]
        assert "storms" in snap and "active_compiles" in snap
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/api/fg/99/profile/")
        assert ei.value.code == 404
        # the gauges live on GET /metrics (acceptance: fsdr_mfu /
        # fsdr_compiles_total on the scrape endpoint)
        text = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "fsdr_compiles_total" in text
        assert 'program="t-rest-prog"' in text
        assert "# TYPE fsdr_mfu gauge" in text
        assert "# TYPE fsdr_compile_seconds histogram" in text
    finally:
        running.stop_sync()
        cp.stop()
