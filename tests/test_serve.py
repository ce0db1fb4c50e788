"""Multi-tenant flowgraph serving (ISSUE 11 tentpole, docs/serving.md):
slot-table ragged admission over the vmapped serving engine, per-session
carry evict/re-admit riding the checkpoint leaf contract, per-tenant fair
credits, per-session fault isolation, slot-bucket autotune axis, and the
REST session plane."""

import json
import urllib.request

import numpy as np
import pytest

from futuresdr_tpu.ops.stages import (FanoutPipeline, Pipeline, fir_stage,
                                      rotator_stage)
from futuresdr_tpu.serve import (ServeEngine, ServeFull,
                                 TenantCreditController, register_app,
                                 unregister_app)

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
    """The bare fused pipeline, frame by frame — the bit-equality
    reference."""
    fn, carry = pipe.compile(FRAME, donate=False)
    out = []
    for f in frames:
        carry, y = fn(carry, f)
        out.append(np.asarray(y))
    return out


def _drain(eng, *sessions):
    while eng.step():
        pass
    return [eng.results(s.sid) for s in sessions]


# ---------------------------------------------------------------------------
# bit-equality: the serving program IS the pipeline, per lane
# ---------------------------------------------------------------------------

def test_n1_serving_bit_equals_bare_pipeline():
    """Acceptance: N=1 serving ≡ the served slot program run solo, bit for
    bit — in the capacity-1 bucket AND in a capacity-4 bucket with three
    masked pad lanes (the masked-lane merge must not perturb the active
    lane) — and ≡ the bare fused pipeline to 1 ulp (a batched and an
    unbatched XLA:CPU program reassociate: ``_serve_ref``)."""
    pipe = _pipe()
    data = _frames(6)
    bare = _solo(pipe, data)
    for buckets in ((1,), (4,)):
        eng = ServeEngine(_pipe(), frame_size=FRAME, app=f"n1b{buckets[0]}",
                          buckets=buckets, queue_frames=8)
        s = eng.admit(tenant="a")
        exp = SoloSlot(_pipe(), FRAME, s.slot).run(buckets[0], data)
        for f in data:
            assert eng.submit(s.sid, f)
        (out,) = _drain(eng, s)
        assert_bit_equal(out, exp)
        assert_within_ulps(out, bare)


def test_join_leave_mid_stream_bit_equality():
    """Sessions joining and leaving mid-stream never perturb a resident
    session's stream: every session's outputs equal its own solo run."""
    pipe = _pipe()
    d0, d1, d2 = _frames(6, 1), _frames(4, 2), _frames(3, 3)
    exp = [_solo(pipe, d) for d in (d0, d1, d2)]
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="joinleave",
                      buckets=(1, 2, 4), queue_frames=8)
    s0 = eng.admit(tenant="a")
    for f in d0[:2]:
        assert eng.submit(s0.sid, f)
    eng.step()
    s1 = eng.admit(tenant="b")        # join mid-flight (bucket growth)
    for f in d0[2:]:
        assert eng.submit(s0.sid, f)
    for f in d1:
        assert eng.submit(s1.sid, f)
    out0, out1 = _drain(eng, s0, s1)
    eng.close(s1.sid)                 # leave mid-stream
    s2 = eng.admit(tenant="c")        # reuses the freed lane, fresh carry
    for f in d2:
        assert eng.submit(s2.sid, f)
    (out2,) = _drain(eng, s2)
    out0 += eng.results(s0.sid)
    for got, want in zip((out0, out1, out2), exp):
        assert len(got) == len(want)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)


def test_stalled_lane_carry_is_bit_frozen():
    """A session with no input simply masks its lane: its carry is frozen
    bit-exactly while siblings dispatch, and its stream resumes as if
    nothing happened."""
    pipe = _pipe()
    d0, d1 = _frames(6, 4), _frames(9, 5)
    exp0 = _solo(pipe, d0)
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="stall",
                      buckets=(2,), queue_frames=16)
    s0 = eng.admit(tenant="a")
    s1 = eng.admit(tenant="b")
    for f in d0[:3]:
        assert eng.submit(s0.sid, f)
    for f in d1:
        assert eng.submit(s1.sid, f)
    (head,) = _drain(eng, s0)         # s0 stalls after 3 frames; s1 keeps going
    assert eng.table.get(s0.sid).stall_steps > 0
    for f in d0[3:]:
        assert eng.submit(s0.sid, f)
    (tail,) = _drain(eng, s0)
    out0 = head + tail
    assert len(out0) == 6
    for a, b in zip(out0, exp0):
        np.testing.assert_array_equal(a, b)


def test_megabatch_k4_join_leave_at_boundaries():
    """K>1 megabatch serving: joins/leaves land at megabatch boundaries via
    the ragged per-lane-frame mask — a resident session's outputs under
    churn are BIT-IDENTICAL to the same session served alone at the same K
    (K>1 scan programs round differently from K=1 by repo contract, so the
    pin is interference-freedom at matched K, exactly like the devchain
    megabatch tests pin K=4 against K=4)."""
    d0, d1 = _frames(7, 6), _frames(3, 7)
    solo_eng = ServeEngine(_pipe(), frame_size=FRAME, app="k4solo",
                           buckets=(2,), queue_frames=16,
                           frames_per_dispatch=4)
    sA = solo_eng.admit(tenant="a")
    for f in d0[:4]:
        assert solo_eng.submit(sA.sid, f)
    assert solo_eng.step() == 4       # one full megabatch group
    for f in d0[4:]:
        assert solo_eng.submit(sA.sid, f)
    assert solo_eng.step() == 3       # ragged tail masked in-program
    solo = solo_eng.results(sA.sid)
    assert len(solo) == 7

    churn = ServeEngine(_pipe(), frame_size=FRAME, app="k4churn",
                        buckets=(2,), queue_frames=16,
                        frames_per_dispatch=4)
    sX = churn.admit(tenant="a")
    for f in d0[:4]:
        assert churn.submit(sX.sid, f)
    assert churn.step() == 4
    sY = churn.admit(tenant="b")      # join at the megabatch boundary
    for f in d0[4:]:
        assert churn.submit(sX.sid, f)
    for f in d1:
        assert churn.submit(sY.sid, f)
    assert churn.step() == 6          # both lanes ragged inside one dispatch
    churn.close(sY.sid)               # leave at the boundary
    outX = churn.results(sX.sid)
    assert len(outX) == 7
    for a, b in zip(outX, solo):
        np.testing.assert_array_equal(a, b)
    assert churn.dispatches == 2      # still one dispatch per step


def test_stall_evict_readmit_round_trip():
    """Acceptance: stall → evict (carry to host) → re-admit restores the
    session BIT-IDENTICALLY — the serving-plane analog of the kernel
    checkpoint restore, on the same leaf contract."""
    pipe = _pipe()
    data = _frames(10, 8)
    exp = _solo(pipe, data)
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="evict",
                      buckets=(1, 2), queue_frames=16)
    s = eng.admit(tenant="a")
    for f in data[:5]:
        assert eng.submit(s.sid, f)
    (head,) = _drain(eng, s)
    eng.evict(s.sid)
    assert s.state == "evicted" and s.slot is None
    assert s.carry_leaves is not None
    # queued input survives eviction, but an evicted session never
    # dispatches
    for f in data[5:]:
        assert eng.submit(s.sid, f)
    eng.step()
    assert len(eng.results(s.sid)) == 0
    # a sibling may take the lane meanwhile
    other = eng.admit(tenant="b")
    eng.readmit(s.sid)
    (tail,) = _drain(eng, s)
    got = head + tail
    assert len(got) == 10
    for a, b in zip(got, exp):
        np.testing.assert_array_equal(a, b)
    assert other.state == "active"


def test_readmit_validates_carry_contract():
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="badcarry",
                      buckets=(1, 2), queue_frames=4)
    s = eng.admit(tenant="a")
    assert eng.submit(s.sid, _frames(1, 9)[0])
    eng.step()
    eng.evict(s.sid)
    s.carry_leaves = [np.zeros(3, np.uint8) for _ in s.carry_leaves]
    with pytest.raises(ValueError, match="contract"):
        eng.readmit(s.sid)


# ---------------------------------------------------------------------------
# slot buckets: growth without recompiles
# ---------------------------------------------------------------------------

def test_bucket_growth_without_recompile_of_resident_buckets():
    """Acceptance pin: session churn inside resident buckets causes ZERO
    recompiles; crossing a bucket boundary compiles exactly the new bucket
    once (and restacks carries without disturbing resident sessions)."""
    pipe = _pipe()
    data = _frames(4, 10)
    exp = _solo(pipe, data)
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="grow",
                      buckets=(1, 2, 4), queue_frames=32)
    s0 = eng.admit(tenant="a")
    assert eng.submit(s0.sid, data[0])
    eng.step()
    assert eng.compiles == 1 and eng.capacity == 1
    s1 = eng.admit(tenant="b")        # 1 -> 2 growth
    assert eng.capacity == 2
    assert eng.submit(s0.sid, data[1])
    eng.step()
    assert eng.compiles == 2
    # churn INSIDE the resident bucket: close + admit repeatedly
    for i in range(5):
        eng.close(s1.sid)
        s1 = eng.admit(tenant="b")
        assert eng.submit(s1.sid, _frames(1, 20 + i)[0])
        eng.step()
    assert eng.compiles == 2, "churn recompiled a resident bucket"
    # the resident session's stream was never perturbed
    for f in data[2:]:
        assert eng.submit(s0.sid, f)
    (out0,) = _drain(eng, s0)
    assert len(out0) == 4
    for a, b in zip(out0, exp):
        np.testing.assert_array_equal(a, b)
    # growth to 4, then refusal past the largest bucket
    eng.admit(tenant="c")
    eng.admit(tenant="c")
    assert eng.capacity == 4 and eng.compiles == 2   # compile is lazy (next step)
    with pytest.raises(ServeFull):
        for _ in range(8):
            eng.admit(tenant="d")


def test_configured_bucket_ladder(monkeypatch):
    from futuresdr_tpu.config import config
    monkeypatch.setattr(config(), "serve_buckets", "2, 8")
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="ladder")
    assert eng.buckets == (2, 8)


# ---------------------------------------------------------------------------
# per-tenant fairness
# ---------------------------------------------------------------------------

def test_tenant_credit_fairness_unit():
    c = TenantCreditController(8)
    c.register("a")
    c.register("b")
    assert c.fair_share() == 4
    # a may borrow past its fair share only out of unreserved headroom
    grants = sum(c.try_acquire("a") for _ in range(8))
    assert grants == 4, "borrowing ate into b's guaranteed share"
    # b's fair share is grantable no matter how wedged a is
    assert all(c.try_acquire("b") for _ in range(4))
    assert not c.try_acquire("b")
    # released credits go back to their OWNER's guarantee first: b still
    # cannot borrow past its share while a's reserve is unexhausted, but a
    # can always reclaim up to its fair share
    c.release("a", 2)
    assert not c.try_acquire("b")
    assert c.try_acquire("a") and c.try_acquire("a")
    # lone tenant uses the whole budget
    solo = TenantCreditController(8)
    solo.register("x")
    assert sum(solo.try_acquire("x") for _ in range(10)) == 8


def test_stalled_tenant_cannot_starve_siblings():
    """Engine-level starvation guard: a tenant whose session stalls with a
    full queue cannot deny a sibling tenant its fair share of submit
    credits."""
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="fair",
                      buckets=(2,), queue_frames=2)     # total = 4 credits
    hog = eng.admit(tenant="hog")
    vip = eng.admit(tenant="vip")
    data = _frames(6, 11)
    # hog fills its queue and never dispatches (we never step) — its fair
    # share is 2 of 4, and borrowing must stop before vip's guarantee
    got = sum(eng.submit(hog.sid, f) for f in data[:4])
    assert got == 2
    assert eng.submit(vip.sid, data[4])
    assert eng.submit(vip.sid, data[5])


# ---------------------------------------------------------------------------
# per-session fault isolation
# ---------------------------------------------------------------------------

def test_session_fault_retires_only_its_slot():
    from futuresdr_tpu.runtime import faults
    pipe = _pipe()
    da, db = _frames(4, 12), _frames(4, 13)
    expa = _solo(pipe, da)
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="faulty",
                      buckets=(2,), queue_frames=16)
    sa = eng.admit(tenant="a", sid="iso_a")
    sb = eng.admit(tenant="b", sid="iso_b")
    plan = faults.reset()
    plan.arm("dispatch:iso_b", rate=1.0, max_faults=1, seed=1)
    try:
        for fa, fb in zip(da, db):
            assert eng.submit(sa.sid, fa)
            if sb.state == "active":
                eng.submit(sb.sid, fb)
            eng.step()
    finally:
        faults.reset()
    assert sb.state == "retired" and sb.error
    assert eng.session_view("iso_b")["state"] == "retired"
    outa = eng.results(sa.sid)
    assert len(outa) == 4
    for a, b in zip(outa, expa):
        np.testing.assert_array_equal(a, b)
    # the retired session refuses new input
    with pytest.raises(ValueError, match="retired"):
        eng.submit(sb.sid, db[0])


def test_retired_tenant_releases_its_fair_share_reservation():
    """A tenant whose sessions all faulted must not keep its fair-share
    credits reserved forever: retirement unregisters the tenant once it has
    no live (active/evicted) session left, so a lone surviving tenant can
    use the whole budget again."""
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="fairret",
                      buckets=(2,), queue_frames=4)      # total = 8 credits
    a = eng.admit(tenant="dead")
    b = eng.admit(tenant="live")
    eng._retire(eng.table.get(a.sid), RuntimeError("injected"))
    # the retired session stays viewable, but its tenant no longer divides
    # the budget — "live" gets all 8 credits, not total - fair = 4
    assert eng.session_view(a.sid)["state"] == "retired"
    assert all(eng.submit(b.sid, f) for f in _frames(8, 17))
    # and closing the last live session of a tenant with only retired
    # siblings left unregisters it too
    eng.close(b.sid)
    assert eng.credits.snapshot() == {}


def test_retired_sessions_are_pruned_beyond_retention():
    """Bounded retired-session retention (config ``serve_retired_keep``):
    fault churn in a long-running process must not grow the session
    registry without bound — only the newest N retired views survive."""
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="retkeep", buckets=(2,))
    eng._retired_keep = 2
    sids = []
    for _ in range(4):
        s = eng.admit(tenant="t")
        eng._retire(eng.table.get(s.sid), RuntimeError("injected"))
        sids.append(s.sid)
    assert eng.table.get(sids[0]) is None and eng.table.get(sids[1]) is None
    assert eng.table.get(sids[2]).state == "retired"
    assert eng.table.get(sids[3]).state == "retired"


def test_step_dispatch_failure_requeues_frames(monkeypatch):
    """A real (non-injected) transfer/dispatch error inside step() must not
    silently lose the popped frames: they go back to the front of their
    queues with their credits re-taken, the carries stay untouched, and a
    retry dispatches the exact same frames — output bit-identical to a
    fault-free run."""
    from futuresdr_tpu.ops import xfer
    pipe = _pipe()
    data = _frames(3, 19)
    expected = _solo(pipe, data)
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="rollback",
                      buckets=(2,), queue_frames=4)
    s = eng.admit(tenant="t0")
    for f in data:
        assert eng.submit(s.sid, f)
    assert eng.credits.used("t0") == 3

    real = xfer.to_device
    state = {"boom": True}

    def flaky(*args, **kw):
        if state["boom"]:
            state["boom"] = False
            raise RuntimeError("transient transfer error")
        return real(*args, **kw)

    monkeypatch.setattr(xfer, "to_device", flaky)
    with pytest.raises(RuntimeError, match="transient transfer error"):
        eng.step()
    # rolled back: frames re-queued in order, credits re-taken, nothing out
    sess = eng.table.get(s.sid)
    assert len(sess.pending) == 3 and sess.frames_out == 0
    assert eng.credits.used("t0") == 3
    assert eng.dispatches == 0
    # the retry re-dispatches the same frames bit-identically
    while eng.step():
        pass
    got = eng.results(s.sid)
    assert len(got) == 3
    for g, e in zip(got, expected):
        np.testing.assert_array_equal(g, e)


# ---------------------------------------------------------------------------
# fan-out pipelines serve too (multi-sink delivery)
# ---------------------------------------------------------------------------

def test_fanout_pipeline_serving_multi_sink():
    import jax
    taps = np.hanning(17).astype(np.float32)

    def mk():
        return FanoutPipeline(
            [rotator_stage(0.01)],
            [[fir_stage(taps, fft_len=128)], [rotator_stage(0.2)]],
            np.complex64)

    fan = mk()
    data = _frames(3, 14)
    fn = jax.jit(fan.fn())
    carry = fan.init_carry()
    exp = []
    for f in data:
        carry, ys = fn(carry, f)
        exp.append(tuple(np.asarray(y) for y in ys))
    eng = ServeEngine(mk(), frame_size=FRAME, app="fanout",
                      buckets=(2,), queue_frames=8)
    s = eng.admit(tenant="a")
    for f in data:
        assert eng.submit(s.sid, f)
    (out,) = _drain(eng, s)
    assert len(out) == 3
    assert all(isinstance(got, tuple) and len(got) == 2 for got in out)
    # bit for bit against the same two-sink slot program run solo; against
    # the bare fan-out pipeline to 1 ulp for each of the two stages a branch
    # chains (batched and unbatched XLA:CPU programs reassociate:
    # ``_serve_ref``)
    assert_bit_equal(out, SoloSlot(mk(), FRAME, s.slot).run(2, data))
    assert_within_ulps(out, exp, ulps=2)


# ---------------------------------------------------------------------------
# autotuned slot buckets (tpu/autotune.py serve axis)
# ---------------------------------------------------------------------------

def test_autotune_serve_buckets_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("FUTURESDR_TPU_AUTOTUNE_CACHE_DIR", str(tmp_path))
    from futuresdr_tpu.config import reload_config
    reload_config()
    try:
        import importlib
        at = importlib.import_module("futuresdr_tpu.tpu.autotune")
        pipe = Pipeline([rotator_stage(0.07)], np.complex64)
        ladder, results = at.autotune_serve(pipe, frame_size=256,
                                            capacities=(1, 2, 4), reps=2)
        assert ladder and ladder[0] == 1
        assert set(results) >= set(ladder)
        got = at.cached_serve_buckets(pipe, np.complex64, "cpu")
        assert got == ladder
        # the serving-plane axis must survive a streamed re-record
        at.record_streamed_pick(pipe.stages, np.complex64, "cpu", 2,
                                inflight=3)
        entry = at.cached_streamed_pick(pipe.stages, np.complex64, "cpu")
        assert entry["k"] == 2 and entry["serve_buckets"] == ladder
        # and the engine consumes the cached ladder
        eng = ServeEngine(Pipeline([rotator_stage(0.07)], np.complex64),
                          frame_size=256, app="tuned")
        assert list(eng.buckets) == ladder
    finally:
        monkeypatch.delenv("FUTURESDR_TPU_AUTOTUNE_CACHE_DIR")
        reload_config()


# ---------------------------------------------------------------------------
# REST session plane + per-tenant exposition
# ---------------------------------------------------------------------------

def test_serve_rest_session_api():
    from futuresdr_tpu import Runtime
    from futuresdr_tpu.runtime.ctrl_port import ControlPort
    eng = ServeEngine(_pipe(), frame_size=FRAME, app="restapp",
                      buckets=(1, 2), queue_frames=8)
    register_app(eng)
    rt = Runtime()
    cp = ControlPort(rt.handle, bind="127.0.0.1:29644")
    cp.start()
    base = "http://127.0.0.1:29644"
    try:
        apps = json.load(urllib.request.urlopen(f"{base}/api/serve/"))
        assert "restapp" in apps

        def post(path, body=None):
            req = urllib.request.Request(
                f"{base}{path}", data=json.dumps(body or {}).encode(),
                headers={"Content-Type": "application/json"}, method="POST")
            return json.load(urllib.request.urlopen(req))

        s = post("/api/serve/restapp/session/", {"tenant": "gold"})
        sid = s["sid"]
        assert s["state"] == "active" and s["tenant"] == "gold"
        # drive a frame through so the view carries real numbers
        assert eng.submit(sid, _frames(1, 15)[0])
        eng.step()
        view = json.load(urllib.request.urlopen(
            f"{base}/api/serve/restapp/session/{sid}/"))
        assert view["frames_out"] == 1 and view["tenant"] == "gold"
        desc = json.load(urllib.request.urlopen(f"{base}/api/serve/restapp/"))
        assert desc["dispatches"] == 1
        assert "gold" in desc["tenants"]
        # per-tenant Prometheus labels on /metrics
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert 'fsdr_serve_frames_total{app="restapp",tenant="gold"} 1' \
            in text
        # evict → readmit → delete over REST
        assert post(f"/api/serve/restapp/session/{sid}/evict/")["state"] \
            == "evicted"
        assert post(f"/api/serve/restapp/session/{sid}/readmit/")["state"] \
            == "active"
        req = urllib.request.Request(
            f"{base}/api/serve/restapp/session/{sid}/", method="DELETE")
        assert json.load(urllib.request.urlopen(req)) == {"ok": True}
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"{base}/api/serve/restapp/session/{sid}x/")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/api/serve/nosuchapp/")
    finally:
        cp.stop()
        unregister_app("restapp")


def test_prometheus_stable_label_ordering():
    """Satellite: /metrics exposition emits samples of a family in a stable
    order regardless of label-set CREATION order — scrape diffing and the
    regress harness see deterministic text."""
    from futuresdr_tpu.telemetry import prom
    c1 = prom.Counter("order_probe_total", "t", ("app", "tenant"))
    c1.inc(app="z", tenant="t9")
    c1.inc(app="a", tenant="t1")
    c1.inc(app="m", tenant="t5")
    first = "\n".join(c1.render())
    c2 = prom.Counter("order_probe_total", "t", ("app", "tenant"))
    c2.inc(app="m", tenant="t5")
    c2.inc(app="z", tenant="t9")
    c2.inc(app="a", tenant="t1")
    assert "\n".join(c2.render()) == first
    lines = [l for l in first.splitlines() if not l.startswith("#")]
    assert lines == sorted(lines)
    # histogram children follow the same contract
    h1 = prom.Histogram("order_probe_seconds", "t", ("tenant",))
    h1.observe(0.1, tenant="zz")
    h1.observe(0.2, tenant="aa")
    h2 = prom.Histogram("order_probe_seconds", "t", ("tenant",))
    h2.observe(0.2, tenant="aa")
    h2.observe(0.1, tenant="zz")
    assert "\n".join(h1.render()) == "\n".join(h2.render())
