"""One frame's timeline from inside the program (docs/observability.md "Span
categories"): every boundary of both launch paths is a span, the spans of one
dispatch group share ``args["seq"]``, ``H2D``/``D2H``/``program`` end when the
device says so (the readiness watcher of ``ops/xfer.py``), and the device
program carries its stages' names."""

import re
import threading
import time

import numpy as np
import pytest

from futuresdr_tpu.ops import xfer
from futuresdr_tpu.telemetry import spans

WATCHER = "fsdr-xfer-watch-"         # one per lane: -h2d, -out


@pytest.fixture
def tracing():
    rec = spans.recorder()
    was = rec.enabled
    rec.enabled = True
    rec.drain()
    yield rec
    rec.enabled = was
    rec.drain()


def _watcher_threads():
    return [t for t in threading.enumerate() if t.name.startswith(WATCHER)]


def _wait_watcher_gone(timeout=3.0):
    deadline = time.monotonic() + timeout
    while _watcher_threads() and time.monotonic() < deadline:
        time.sleep(0.02)
    return not _watcher_threads()


def _drain_settled(rec, settle=0.3):
    """Everything recorded so far, the watcher's late stamps included."""
    evs = rec.drain()
    deadline = time.monotonic() + settle
    while time.monotonic() < deadline:
        time.sleep(0.02)
        evs += rec.drain()
    return sorted(evs, key=lambda e: e.t0_ns)


def _by_seq(evs, cat="tpu"):
    out = {}
    for e in evs:
        if e.dur_ns is not None and e.args and e.args.get("seq") is not None \
                and (cat is None or e.cat == cat):
            out.setdefault(e.args["seq"], {}).setdefault(e.name, []).append(e)
    return out


def _end(e):
    return e.t0_ns + e.dur_ns


# ---------------------------------------------------------------------------
# streamed path
# ---------------------------------------------------------------------------

FRAME = 1 << 12
N_FRAMES = 6


def _run_streamed(data):
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.config import config
    from futuresdr_tpu.ops import fir_stage, mag2_stage
    from futuresdr_tpu.tpu import TpuKernel

    config().buffer_size = max(config().buffer_size, 4 * FRAME * 8)
    taps = np.hanning(16).astype(np.float32)
    fg = Flowgraph()
    src = VectorSource(data)
    k = TpuKernel([fir_stage(taps), mag2_stage()], np.complex64,
                  frame_size=FRAME, frames_per_dispatch=1)
    snk = VectorSink(np.float32)
    fg.connect(src, k, snk)
    Runtime().run(fg)
    return np.asarray(snk.items()), k


def _stream_input():
    rng = np.random.default_rng(7)
    return (rng.standard_normal(N_FRAMES * FRAME)
            + 1j * rng.standard_normal(N_FRAMES * FRAME)).astype(np.complex64)


FRAME_SPANS = ("frame", "stage", "H2D", "compute", "program", "D2H",
               "decode", "emit")


def test_streamed_every_frame_has_its_chain_of_spans(tracing):
    unwatched = tracing.unwatched
    _run_streamed(_stream_input())
    groups = _by_seq(_drain_settled(tracing))
    assert sorted(groups) == list(range(N_FRAMES))
    for seq, g in groups.items():
        for name in FRAME_SPANS:
            assert name in g, f"frame {seq} has no {name!r}: {sorted(g)}"
        frame = g["frame"][0]
        # ordered in time: each boundary starts no earlier than the one before
        firsts = [min(e.t0_ns for e in g[n]) for n in
                  ("stage", "H2D", "compute", "D2H", "decode", "emit")]
        assert firsts == sorted(firsts), (seq, firsts)
        assert g["program"][0].t0_ns == g["compute"][0].t0_ns
        assert _end(g["program"][0]) >= _end(g["compute"][0])
        assert g["D2H"][0].t0_ns >= g["program"][0].t0_ns
        # and inside the frame's own span, from ring exit to the last item
        for name in FRAME_SPANS[1:]:
            for e in g[name]:
                assert e.t0_ns >= frame.t0_ns, (seq, name)
                assert _end(e) <= _end(frame) + 50_000_000, (seq, name)
        for name in ("stage", "H2D", "D2H", "emit"):
            assert all(e.args["bytes"] > 0 for e in g[name]), (seq, name)
        assert _end(g["emit"][-1]) <= _end(frame)
        assert "h2d_put" in g and "d2h_wait" in g and "h2d_wait" in g
    assert tracing.unwatched == unwatched


def test_recorder_off_no_watcher_no_event_same_output(tracing):
    data = _stream_input()
    on, _ = _run_streamed(data)
    assert _watcher_threads(), "the recorder is on and transfers ran"
    _drain_settled(tracing)
    tracing.enabled = False
    assert _wait_watcher_gone(), "the watcher outlived the recorder"
    assert xfer._watchers == {}                 # no queue, no reference
    off, _ = _run_streamed(data)
    assert not _watcher_threads()
    assert tracing.drain() == []
    assert on.dtype == off.dtype and np.array_equal(on, off)


# ---------------------------------------------------------------------------
# the watcher
# ---------------------------------------------------------------------------

class _SlowArray:
    """Stands for an upload that takes ``delay`` seconds to land."""

    nbytes = 4096

    def __init__(self, delay):
        self.delay = delay

    def block_until_ready(self):
        time.sleep(self.delay)
        return self


@pytest.mark.parametrize("finish_late_s", [0.0, 0.1])
def test_h2d_ends_when_the_transfer_does(tracing, monkeypatch, finish_late_s):
    import jax

    monkeypatch.setattr(jax, "device_put", lambda p, d=None: _SlowArray(0.02))
    fin = xfer.start_device_transfer_parts((np.zeros(1024, np.float32),),
                                           seq=5)
    time.sleep(finish_late_s)
    fin()
    evs = _drain_settled(tracing)
    (h2d,) = [e for e in evs if e.name == "H2D"]
    (put,) = [e for e in evs if e.name == "h2d_put"]
    assert h2d.args == {"bytes": 4096, "seq": 5} and put.args == h2d.args
    assert h2d.t0_ns == put.t0_ns               # from the first device_put
    assert 18e6 <= h2d.dur_ns <= 60e6, h2d.dur_ns / 1e6
    assert h2d.thread == WATCHER + "h2d" and put.thread != h2d.thread


def test_deleted_watched_array_counts_as_unwatched(tracing):
    import jax.numpy as jnp

    before = tracing.unwatched
    a = jnp.ones(16) + 1
    a.delete()
    xfer.watch((a,), "program", tracing.now(), {"seq": 0})
    b = jnp.ones(16) + 1
    xfer.watch((b,), "program", tracing.now(), {"seq": 1})
    evs = [e for e in _drain_settled(tracing) if e.name == "program"]
    assert [e.args["seq"] for e in evs] == [1]  # no span, nothing raised
    assert tracing.unwatched == before + 1


# ---------------------------------------------------------------------------
# serving path
# ---------------------------------------------------------------------------

SERVE_FRAME = 1 << 17
STEP_SPANS = ("encode", "h2d_put", "H2D", "compute", "program", "d2h_wait",
              "D2H", "decode")


def _engine(capacity=4):
    from futuresdr_tpu.ops import fir_stage, mag2_stage
    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.serve.engine import ServeEngine

    taps = np.hanning(64).astype(np.float32)
    return ServeEngine(Pipeline([fir_stage(taps), mag2_stage()], np.complex64),
                       frame_size=SERVE_FRAME, app="timeline",
                       buckets=(capacity,))


def _frame(seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(SERVE_FRAME)
            + 1j * rng.standard_normal(SERVE_FRAME)).astype(np.complex64)


def test_one_serving_step_is_a_chain_of_spans(tracing):
    eng, unwatched = _engine(), tracing.unwatched
    try:
        sids = [eng.admit("t").sid for _ in range(3)]
        for rnd in range(2):                    # warm-up steps: compiles
            for i, sid in enumerate(sids):
                assert eng.submit(sid, _frame(i))
            assert eng.step() == 3
        _drain_settled(tracing)
        for i, sid in enumerate(sids):
            assert eng.submit(sid, _frame(10 + i))
        assert eng.step() == 3
        evs = _drain_settled(tracing)
    finally:
        eng.shutdown()
    (step,) = [e for e in evs if e.name == "serve_step"]
    seq = step.args["seq"]
    assert seq == eng.steps
    tpu = _by_seq(evs)[seq]
    serve = _by_seq(evs, cat="serve")[seq]
    for name in STEP_SPANS:
        assert len(tpu.get(name, ())) == 1, (name, sorted(tpu))
    assert {e.args["lock"] for e in serve["lock_wait"]} == {"step", "state"}
    assert len(serve["lock_wait"]) == 3         # step, _assemble, _commit
    (qw,) = serve["queue_wait"]
    assert qw.args["frames"] == 3
    assert tpu["H2D"][0].args["bytes"] == tpu["h2d_put"][0].args["bytes"] \
        >= 4 * SERVE_FRAME * 8
    assert tpu["H2D"][0].t0_ns == tpu["h2d_put"][0].t0_ns
    slack = 5_000_000           # the watcher's stamps are late by its wake-up
    assert _end(tpu["program"][0]) <= _end(tpu["D2H"][0]) + slack
    assert _end(tpu["D2H"][0]) <= _end(tpu["d2h_wait"][0]) + slack
    # the step's children cover it
    kids = [e for g in (tpu, serve) for evs_ in g.values() for e in evs_
            if e is not step]
    a, b = step.t0_ns, _end(step)
    covered = spans.union_ns([(max(e.t0_ns, a), min(_end(e), b)) for e in kids
                              if _end(e) > a and e.t0_ns < b])
    assert covered >= 0.9 * step.dur_ns, covered / step.dur_ns
    assert tracing.unwatched == unwatched


def test_queue_wait_is_the_submit_to_step_interval(tracing):
    eng = _engine()
    try:
        sid = eng.admit("t").sid
        eng.submit(sid, _frame(0))
        eng.step()                              # compiles
        _drain_settled(tracing)
        frame = _frame(1)
        t_sub = time.perf_counter_ns()
        assert eng.submit(sid, frame)
        time.sleep(0.05)
        t_step = time.perf_counter_ns()
        eng.step()
        evs = _drain_settled(tracing)
    finally:
        eng.shutdown()
    (qw,) = [e for e in evs if e.name == "queue_wait"]
    hand_ms = (t_step - t_sub) * 1e-6
    assert qw.cat == "serve" and qw.args["frames"] == 1
    assert qw.args["mean_ms"] == pytest.approx(hand_ms, abs=2.0)
    assert qw.dur_ns * 1e-6 == pytest.approx(qw.args["mean_ms"], abs=0.01)
    assert hand_ms >= 50.0


# ---------------------------------------------------------------------------
# names inside the device program
# ---------------------------------------------------------------------------

def test_wired_program_hlo_carries_stage_and_wire_names():
    """``compile_wired`` sc16 at the benchmark's frame: every stage's name and
    the wire prolog/epilog are in the optimized HLO's ``op_name`` metadata."""
    import jax

    from futuresdr_tpu.ops import fft_stage, fir_stage, mag2_stage
    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.ops.wire import get_wire
    from futuresdr_tpu.ops.xfer import PackedLayout

    frame = 262144
    taps = np.hanning(64).astype(np.float32)
    pipe = Pipeline([fir_stage(taps), fft_stage(2048), mag2_stage()],
                    np.complex64)
    wire = get_wire("sc16")
    lay = PackedLayout.probe(wire, frame, np.complex64)
    fn, carry = pipe.compile_wired(frame, wire, packed=lay)
    text = fn.lower(carry, jax.ShapeDtypeStruct((lay.nbytes // 4,),
                                                np.uint32)) \
        .compile().as_text()
    for name in [s.name for s in pipe.stages] + \
            ["wire_decode", "wire_encode", "unpack"]:
        assert re.search(rf'op_name="[^"]*[/(]{name}[/)"]', text), name


def test_paged_fm_step_hlo_carries_stage_and_page_names():
    """The paged FM serving step at capacity 64 (the benchmark's program)."""
    import jax

    from futuresdr_tpu.apps.fm_receiver import front_end_stages
    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.serve.engine import build_slot_program

    cap = 64
    pipe = Pipeline(front_end_stages(), np.complex64)
    frame = 65536 - 65536 % pipe.frame_multiple     # as ServeEngine rounds it
    assert frame == 65500
    prog = build_slot_program(pipe, cap)
    pages = jax.tree_util.tree_map(
        lambda t: jax.ShapeDtypeStruct((cap,) + np.shape(t),
                                       np.asarray(t).dtype),
        pipe.init_carry())
    text = prog.lower(
        pages, jax.ShapeDtypeStruct((cap,), np.int32),
        jax.ShapeDtypeStruct((cap,), np.bool_),
        jax.ShapeDtypeStruct((cap, frame), np.complex64),
        jax.ShapeDtypeStruct((cap,), np.bool_)).compile().as_text()
    for name in [s.name for s in pipe.stages] + \
            ["serve_gather", "serve_scatter"]:
        assert re.search(rf'op_name="[^"]*[/(]{name}[/)"]', text), name


# ---------------------------------------------------------------------------
# the doctor's compute lane
# ---------------------------------------------------------------------------

def test_doctor_compute_lane_reads_program_spans_where_there_are_any():
    """On an accelerator ``compute`` brackets the enqueue call; the lane that
    picks ``bottleneck_lane`` must mean device occupancy."""
    from futuresdr_tpu.telemetry import doctor
    from futuresdr_tpu.telemetry.spans import SpanEvent

    def ev(name, t0, t1):
        return SpanEvent(1, "t", t0, t1 - t0, "tpu", name, None)

    evs = [ev("H2D", 0, 300), ev("compute", 300, 310), ev("D2H", 900, 1000)]
    rep = doctor.report(events=evs)
    assert rep["bottleneck_lane"] == "H2D"      # the enqueue looks idle
    rep = doctor.report(events=evs + [ev("program", 300, 900)])
    assert rep["bottleneck_lane"] == "compute"
    assert rep["lanes"]["compute"]["busy_s"] == pytest.approx(600e-9)
