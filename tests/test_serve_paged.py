"""Paged carries + the overlapped serve step (docs/serving.md).

The serving-engine-2.0 contract: the per-bucket stacked carry became a
PAGE POOL indexed by the slot table's lane→page permutation, and ``step()``
became an overlapped launch/commit pipeline governed by the streamed
path's CreditController. These tests pin the acceptance surface:

* bit-identity per session survives the paging AND the overlap (N=1 at
  in-flight depth > 1 ≡ the bare fused pipeline);
* a join lands MID-megabatch at its own frame cursor (K>1 ragged mask +
  fresh-page substitution), a leave frees the page without touching a
  sibling's bits, and neither ever recompiles the resident capacity;
* evict→readmit rides the same snapshot leaf surface under overlap;
* the overlap is PROVEN by trace interval-union (the test_wire.py
  discipline: serialized ratio ≈ 1, pipelined ≤ 0.75);
* lane-addressed retunes touch exactly one session's page, journaled;
* the step lock is narrow: /metrics, ``health()`` and ``describe()``
  answer while a compile-bearing step is in flight.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from futuresdr_tpu.ops.stages import (Pipeline, fir_stage, rotator_stage)
from futuresdr_tpu.serve import ServeEngine
from futuresdr_tpu.serve.api import register_app, unregister_app

from _serve_ref import SoloSlot, assert_bit_equal, assert_within_ulps

FRAME = 1024


def _pipe():
    taps = np.hanning(31).astype(np.float32)
    return Pipeline([fir_stage(taps, fft_len=256), rotator_stage(0.03)],
                    np.complex64)


def _frames(n, seed=0, frame=FRAME):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal(frame) + 1j * rng.standard_normal(frame))
            .astype(np.complex64) for _ in range(n)]


def _solo(pipe, frames):
    fn, carry = pipe.compile(FRAME, donate=False)
    out = []
    for f in frames:
        carry, y = fn(carry, f)
        out.append(np.asarray(y))
    return out


def _pump(eng, feeds):
    """Feed ``{sid: [frames]}`` through the engine (submit as credits
    allow, step until everything drained)."""
    cursors = {sid: 0 for sid in feeds}
    while True:
        moved = False
        for sid, frames in feeds.items():
            while cursors[sid] < len(frames) and \
                    eng.submit(sid, frames[cursors[sid]]):
                cursors[sid] += 1
                moved = True
        if not eng.step() and not moved and \
                all(cursors[s] >= len(feeds[s]) for s in feeds):
            break


# ---------------------------------------------------------------------------
# bit-identity through paging + overlap
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [1, 3])
def test_paged_n1_bit_equals_bare_pipeline(depth):
    """N=1 through the paged pool at in-flight depth 1 AND >1 ≡ the bare
    fused pipeline, bit for bit — the overlapped step's speculative
    head/commit chain must not perturb a single carry bit."""
    pipe = _pipe()
    data = _frames(8)
    expected = _solo(pipe, data)
    eng = ServeEngine(_pipe(), frame_size=FRAME, app=f"paged{depth}",
                      buckets=(1,), queue_frames=8, inflight=depth)
    s = eng.admit(tenant="t0")
    _pump(eng, {s.sid: data})
    got = eng.results(s.sid)
    assert len(got) == len(expected)
    for g, e in zip(got, expected):
        np.testing.assert_array_equal(g, e)
    assert eng.compiles == 1


def test_mid_megabatch_join_lands_at_own_cursor():
    """K=4 megabatch serving: a session that joins while a sibling is
    mid-stream rides the NEXT dispatch with its own frames — no waiting
    for a group boundary, no recompile — and its outputs are bit-identical
    to the same session served alone AT THE SAME K (K>1 scan programs
    round differently from K=1 by repo contract, so the pin is
    interference-freedom at matched K; the fresh-page substitution starts
    the joiner from the init-carry template at its own frame 0)."""
    da, db = _frames(8, seed=3), _frames(6, seed=4)

    def solo_k4(app, frames):
        e = ServeEngine(_pipe(), frame_size=FRAME, app=app, buckets=(2,),
                        queue_frames=16, frames_per_dispatch=4)
        s = e.admit(tenant="solo")
        _pump(e, {s.sid: frames})
        out = e.results(s.sid)
        assert len(out) == len(frames)
        return out

    ref_a, ref_b = solo_k4("mjsa", da), solo_k4("mjsb", db)
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="midjoin",
                      buckets=(2,), queue_frames=8, frames_per_dispatch=4)
    a = eng.admit(tenant="ta")
    for f in da[:4]:
        assert eng.submit(a.sid, f)
    assert eng.step() == 4            # full group for A alone
    # A mid-stream with a PARTIAL group queued; B joins mid-megabatch
    for f in da[4:7]:
        assert eng.submit(a.sid, f)
    b = eng.admit(tenant="tb")
    for f in db[:2]:
        assert eng.submit(b.sid, f)
    # ONE ragged dispatch carries A's 3-frame tailgroup and B's first 2
    # frames from B's own cursor (frame 0)
    assert eng.step() == 5
    assert eng.dispatches == 2
    _pump(eng, {a.sid: da[7:], b.sid: db[2:]})
    got_a, got_b = eng.results(a.sid), eng.results(b.sid)
    assert len(got_a) == 8 and len(got_b) == 6
    for g, e in zip(got_a, ref_a):
        np.testing.assert_array_equal(g, e)
    for g, e in zip(got_b, ref_b):
        np.testing.assert_array_equal(g, e)
    assert eng.compiles == 1          # churn never recompiled capacity 2


def test_leave_mid_group_frees_page_without_disturbing_siblings():
    """A session leaving mid-stream is a page-map edit: its page returns
    to the free list, every sibling's stream stays bit-identical to the
    same slot program run with that sibling alone (and within 1 ulp of the
    bare pipeline: batched and unbatched XLA:CPU programs reassociate,
    ``_serve_ref``), and the resident capacity never recompiles."""
    pipe = _pipe()
    data = [_frames(6, seed=10 + i) for i in range(3)]
    bare = [_solo(pipe, d) for d in data]
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="leave",
                      buckets=(4,), queue_frames=8)
    ss = [eng.admit(tenant=f"t{i}") for i in range(3)]
    refs = [SoloSlot(_pipe(), FRAME, s.slot).run(4, d)
            for s, d in zip(ss, data)]
    for i, s in enumerate(ss):
        for f in data[i][:3]:
            assert eng.submit(s.sid, f)
    while eng.step():
        pass
    free_before = eng.table.free_slots()
    eng.close(ss[1].sid)              # leave mid-stream
    assert eng.table.free_slots() == free_before + 1
    _pump(eng, {ss[0].sid: data[0][3:], ss[2].sid: data[2][3:]})
    for i in (0, 2):
        got = eng.results(ss[i].sid)
        assert len(got) == 6
        assert_bit_equal(got, refs[i])
        assert_within_ulps(got, bare[i])
    assert eng.compiles == 1


def test_page_map_stays_permutation_under_churn():
    """The page_of_lane map must remain a permutation of [0, capacity)
    through arbitrary admit/close churn — the in-program scatter's
    determinism rests on never seeing a duplicate page index."""
    eng = ServeEngine(Pipeline([rotator_stage(0.05)], np.complex64),
                      frame_size=256, app="perm", buckets=(8,))
    rng = np.random.default_rng(7)
    live = []
    for _ in range(200):
        if live and rng.random() < 0.45:
            sid = live.pop(rng.integers(len(live)))
            eng.close(sid)
        elif len(live) < 8:
            live.append(eng.admit(tenant="t").sid)
        t = eng.table
        assert sorted(t.page_of_lane) == list(range(t.capacity))
        assert all(t.lane_of_page[t.page_of_lane[i]] == i
                   for i in range(t.capacity))
        assert all(t.sessions[sid].page == t.page_of_lane[
            t.sessions[sid].slot] for sid in live)


def test_evict_readmit_round_trip_under_overlap():
    """Evict→readmit with in-flight groups pending: the surgery quiesces
    the window first and the round trip stays bit-identical (the
    snapshot_carry leaf surface reads the COMMITTED page)."""
    pipe = _pipe()
    data = _frames(9, seed=21)
    expected = _solo(pipe, data)
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="evro",
                      buckets=(2,), queue_frames=4, inflight=3)
    s = eng.admit(tenant="t0")
    for f in data[:4]:
        assert eng.submit(s.sid, f)
    eng.step()                        # launch; groups may still be in flight
    eng.evict(s.sid)                  # quiesces, snapshots the page
    assert s.state == "evicted" and s.carry_leaves is not None
    eng.readmit(s.sid)
    _pump(eng, {s.sid: data[4:]})
    got = eng.results(s.sid)
    assert len(got) == len(expected)
    for g, e in zip(got, expected):
        np.testing.assert_array_equal(g, e)


# ---------------------------------------------------------------------------
# overlap evidence: trace interval-union (the test_wire.py discipline)
# ---------------------------------------------------------------------------

def test_serve_step_overlap_interval_union():
    """H2D(t+1) ∥ compute(t) ∥ D2H(t−1) on the SERVING path: under a
    deterministic fake link, the span recorder's lane intervals show
    union < sum at in-flight depth 4 (ratio ≤ 0.75) while depth 1 reads
    serialized (≥ 0.9) — the same bound discipline as the streamed wire
    test."""
    from futuresdr_tpu.ops import xfer
    from futuresdr_tpu.telemetry import spans

    frame = 8192
    pipe_of = lambda: Pipeline([rotator_stage(0.011)], np.complex64)  # noqa: E731
    rng = np.random.default_rng(5)
    data = [(rng.standard_normal(frame) + 1j * rng.standard_normal(frame))
            .astype(np.complex64) for _ in range(14)]

    def run(depth):
        eng = ServeEngine(pipe_of(), frame_size=frame, app=f"ovl{depth}",
                          buckets=(2,), queue_frames=4, inflight=depth)
        a = eng.admit(tenant="t0")
        b = eng.admit(tenant="t1")
        # warmup compile outside the span sample
        eng.submit(a.sid, data[0])
        eng.submit(b.sid, data[0])
        while eng.step():
            pass
        eng.results(a.sid), eng.results(b.sid)
        spans.drain()                          # fresh ring for this run
        for f in data[1:]:
            eng.submit(a.sid, f)
            eng.submit(b.sid, f)
            eng.step()
        while eng.step():
            pass
        return spans.overlap_report(spans.drain())

    was = spans.enabled()
    spans.enable(True)
    try:
        # [2, 8192] c64 = 128 KiB per crossing: 8 ms up at 16 MB/s, 16 ms
        # down at 8 MB/s — modeled wire time dominates the tiny rotator
        xfer.set_fake_link(16e6, 8e6)
        serial = run(1)
        xfer.set_fake_link(16e6, 8e6)          # fresh link timeline
        pipe4 = run(4)
    finally:
        xfer.set_fake_link()
        spans.enable(was)
    for rep in (serial, pipe4):
        for lane in ("H2D", "compute", "D2H"):
            assert rep["lanes"][lane]["spans"] > 0, (lane, rep)
    assert pipe4["sum_s"] >= 0.2, pipe4
    assert serial["ratio"] >= 0.9, f"serialized lanes overlapped: {serial}"
    assert pipe4["ratio"] <= 0.75, \
        f"no overlap: pipelined union/sum {pipe4['ratio']:.2f} ({pipe4})"


# ---------------------------------------------------------------------------
# lane-addressed retunes
# ---------------------------------------------------------------------------

def test_lane_retune_isolated_to_one_session():
    """Retuning one session's rotator mid-stream matches the bare pipeline
    with the same update applied at the same cursor — and the sibling's
    stream stays bit-identical to an untouched solo run."""
    from futuresdr_tpu.telemetry import journal
    pipe = _pipe()
    da, db = _frames(8, seed=31), _frames(8, seed=32)
    ref_b = _solo(pipe, db)
    # reference for A: 4 frames, retune, 4 more
    fn, carry = pipe.compile(FRAME, donate=False)
    ref_a = []
    for f in da[:4]:
        carry, y = fn(carry, f)
        ref_a.append(np.asarray(y))
    carry = pipe.update_stage(carry, "rotator", phase_inc=0.11)
    for f in da[4:]:
        carry, y = fn(carry, f)
        ref_a.append(np.asarray(y))

    eng = ServeEngine(_pipe(), frame_size=FRAME, app="retune",
                      buckets=(2,), queue_frames=8)
    a, b = eng.admit(tenant="ta"), eng.admit(tenant="tb")
    _pump(eng, {a.sid: da[:4], b.sid: db[:4]})
    since = journal.journal().seq
    eng.retune(a.sid, "rotator", phase_inc=0.11)
    evs = journal.events(since=since, cat="serve")["events"]
    assert any(e["event"] == "lane-retune" and e["session"] == a.sid
               for e in evs)
    _pump(eng, {a.sid: da[4:], b.sid: db[4:]})
    got_a, got_b = eng.results(a.sid), eng.results(b.sid)
    for g, e in zip(got_a, ref_a):
        np.testing.assert_array_equal(g, e)
    for g, e in zip(got_b, ref_b):     # sibling bit-frozen through it
        np.testing.assert_array_equal(g, e)
    assert eng.compiles == 1           # surgery never recompiles


def test_retune_fresh_lane_and_error_contract():
    """Retune of a never-dispatched (fresh) lane retunes the template it
    will start from; unknown sessions raise KeyError, bad stage addresses
    ValueError (the REST plane's 404 vs 409 split)."""
    pipe = _pipe()
    data = _frames(4, seed=33)
    fn, carry = pipe.compile(FRAME, donate=False)
    carry = pipe.update_stage(carry, "rotator", phase_inc=0.2)
    ref = []
    for f in data:
        carry, y = fn(carry, f)
        ref.append(np.asarray(y))
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="freshtune",
                      buckets=(2,), queue_frames=8)
    s = eng.admit(tenant="t0")        # fresh: never dispatched
    eng.retune(s.sid, "rotator", phase_inc=0.2)
    _pump(eng, {s.sid: data})
    got = eng.results(s.sid)
    for g, e in zip(got, ref):
        np.testing.assert_array_equal(g, e)
    with pytest.raises(KeyError):
        eng.retune("nosuch", "rotator", phase_inc=0.1)
    with pytest.raises(ValueError):
        eng.retune(s.sid, "nosuchstage", phase_inc=0.1)


def test_rest_session_ctrl_endpoint():
    """POST /api/serve/{app}/session/{sid}/ctrl/ applies a lane retune;
    unknown sid → 404, bad stage → 409, malformed body → 400."""
    from futuresdr_tpu import Runtime
    from futuresdr_tpu.runtime.ctrl_port import ControlPort
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="ctrlapp",
                      buckets=(2,), queue_frames=8)
    register_app(eng)
    rt = Runtime()
    cp = ControlPort(rt.handle, bind="127.0.0.1:29654")
    cp.start()
    base = "http://127.0.0.1:29654"

    def post(path, body):
        req = urllib.request.Request(
            f"{base}{path}", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        return json.load(urllib.request.urlopen(req))

    try:
        s = post("/api/serve/ctrlapp/session/", {"tenant": "gold"})
        sid = s["sid"]
        view = post(f"/api/serve/ctrlapp/session/{sid}/ctrl/",
                    {"stage": "rotator", "params": {"phase_inc": 0.09}})
        assert view["sid"] == sid and view["state"] == "active"
        with pytest.raises(urllib.error.HTTPError) as e404:
            post(f"/api/serve/ctrlapp/session/{sid}x/ctrl/",
                 {"stage": "rotator", "params": {}})
        assert e404.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as e409:
            post(f"/api/serve/ctrlapp/session/{sid}/ctrl/",
                 {"stage": "nosuch", "params": {}})
        assert e409.value.code == 409
        with pytest.raises(urllib.error.HTTPError) as e400:
            post(f"/api/serve/ctrlapp/session/{sid}/ctrl/",
                 {"params": {}})
        assert e400.value.code == 400
    finally:
        cp.stop()
        unregister_app("ctrlapp")


# ---------------------------------------------------------------------------
# page-admit journal + narrow step lock
# ---------------------------------------------------------------------------

def test_admission_journals_page_admit():
    from futuresdr_tpu.telemetry import journal
    eng = ServeEngine(Pipeline([rotator_stage(0.02)], np.complex64),
                      frame_size=256, app="jadmit", buckets=(2,))
    since = journal.journal().seq
    s = eng.admit(tenant="t0")
    evs = [e for e in journal.events(since=since, cat="serve")["events"]
           if e["event"] == "page-admit"]
    assert len(evs) == 1
    assert evs[0]["session"] == s.sid
    assert evs[0]["slot"] == s.slot and evs[0]["page"] == s.page


def test_observability_answers_during_compile_bearing_step():
    """The small-fix pin: a long (compile-bearing) step must not block
    /metrics, health() or describe() — the state lock is held for
    assembly/commit bookkeeping only, never across the program call."""
    import futuresdr_tpu.serve.engine as engine_mod
    from futuresdr_tpu.telemetry import prom

    real_build = engine_mod.build_slot_program
    entered = threading.Event()
    release = threading.Event()

    def slow_build(pipeline, capacity, k=1, **kw):
        prog = real_build(pipeline, capacity, k, **kw)

        def slow(*args):
            entered.set()
            assert release.wait(10.0), "test hung"
            return prog(*args)
        return slow

    engine_mod.build_slot_program = slow_build
    try:
        eng = ServeEngine(Pipeline([rotator_stage(0.02)], np.complex64),
                          frame_size=256, app="locknarrow", buckets=(1,))
        s = eng.admit(tenant="t0")
        eng.submit(s.sid, np.zeros(256, np.complex64))
        t = threading.Thread(target=eng.step, daemon=True)
        t.start()
        assert entered.wait(10.0), "step never reached the program call"
        # the step thread is parked inside the "program" — every
        # observability surface must answer NOW, without waiting it out
        t0 = time.perf_counter()
        h = eng.health()
        d = eng.describe()
        v = eng.session_view(s.sid)
        text = prom.render_all()
        elapsed = time.perf_counter() - t0
        assert t.is_alive(), "step finished early — probe proved nothing"
        assert elapsed < 2.0, f"observability blocked {elapsed:.1f}s"
        assert h["active"] == 1 and d["app"] == "locknarrow"
        assert v["sid"] == s.sid and "fsdr_serve_sessions" in text
    finally:
        release.set()
        t.join(10.0)
        engine_mod.build_slot_program = real_build


# ---------------------------------------------------------------------------
# pool growth
# ---------------------------------------------------------------------------

def test_page_pool_growth_preserves_resident_streams():
    """Growing to the next bucket is page-pool growth: residents keep
    their pages (each stream bit-identical to the same slot programs run
    with that session alone, its page carried from the capacity-2 to the
    capacity-4 pool; within 1 ulp of the bare pipeline: batched and
    unbatched XLA:CPU programs reassociate, ``_serve_ref``) and only the NEW
    capacity compiles."""
    pipe = _pipe()
    data = [_frames(6, seed=40 + i) for i in range(3)]
    bare = [_solo(pipe, d) for d in data]
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="pgrow",
                      buckets=(2, 4), queue_frames=8)
    s0 = eng.admit(tenant="t0")
    s1 = eng.admit(tenant="t1")
    _pump(eng, {s0.sid: data[0][:3], s1.sid: data[1][:3]})
    assert eng.compiles == 1 and eng.capacity == 2
    s2 = eng.admit(tenant="t2")       # forces growth 2 -> 4
    assert eng.capacity == 4
    _pump(eng, {s0.sid: data[0][3:], s1.sid: data[1][3:],
                s2.sid: data[2]})
    assert eng.compiles == 2          # exactly one new-capacity compile
    for i, s in enumerate((s0, s1, s2)):
        solo = SoloSlot(_pipe(), FRAME, s.slot)
        ref = (solo.run(2, data[i][:3]) + solo.run(4, data[i][3:])
               if i < 2 else solo.run(4, data[i]))
        got = eng.results(s.sid)
        assert_bit_equal(got, ref)
        assert_within_ulps(got, bare[i])
