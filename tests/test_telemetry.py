"""Telemetry subsystem: span recorder, Prometheus registry, control-port
endpoints, the supervisor post-close MetricsMsg drain, and the disabled-path
overhead gate (tier-1 acceptance: ≤ ~3% on a null_rand actor chain)."""

import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from futuresdr_tpu.telemetry import prom, spans
from futuresdr_tpu.telemetry.spans import SpanEvent, SpanRecorder


@pytest.fixture
def tracing():
    """Enable span recording for the test; drain + restore after."""
    rec = spans.recorder()
    was = rec.enabled
    rec.enabled = True
    rec.drain()
    yield rec
    rec.enabled = was
    rec.drain()


# ---------------------------------------------------------------------------
# span recorder units
# ---------------------------------------------------------------------------

def test_disabled_recorder_records_nothing():
    rec = SpanRecorder(capacity=64, enabled=False)
    rec.complete("cat", "a", rec.now())
    rec.instant("cat", "b")
    with rec.span("cat", "c"):
        pass
    assert rec.drain() == []


def test_complete_and_instant_events():
    rec = SpanRecorder(capacity=64, enabled=True)
    t0 = rec.now()
    rec.complete("tpu", "H2D", t0, args={"bytes": 7})
    rec.instant("runtime", "terminate_cascade")
    evs = rec.drain()
    assert [e.name for e in evs] == ["H2D", "terminate_cascade"]
    h2d, inst = evs
    assert h2d.cat == "tpu" and h2d.dur_ns >= 0 and h2d.args == {"bytes": 7}
    assert inst.dur_ns is None
    assert rec.drain() == []            # drain cleared the ring


def test_span_context_manager_measures():
    rec = SpanRecorder(capacity=64, enabled=True)
    with rec.span("cat", "sleepy", tag=1):
        time.sleep(0.01)
    (e,) = rec.drain()
    assert e.name == "sleepy" and e.args == {"tag": 1}
    assert e.dur_ns >= 8e6              # ≥ 8 ms recorded for a 10 ms sleep


def test_ring_bounds_and_drop_accounting():
    rec = SpanRecorder(capacity=16, enabled=True)
    for i in range(50):
        rec.complete("c", f"e{i}", rec.now())
    evs = rec.drain()
    assert len(evs) == 16
    # ring keeps the newest events, oldest-first on drain
    assert [e.name for e in evs] == [f"e{i}" for i in range(34, 50)]
    assert rec.dropped == 34


def test_thread_aware_rings():
    rec = SpanRecorder(capacity=64, enabled=True)

    def record():
        rec.complete("c", "worker", rec.now())

    t = threading.Thread(target=record, name="span-worker")
    t.start()
    t.join()
    rec.complete("c", "main", rec.now())
    evs = rec.drain()
    by_name = {e.name: e for e in evs}
    assert by_name["worker"].tid != by_name["main"].tid
    assert by_name["worker"].thread == "span-worker"


def test_chrome_trace_export_shape(tmp_path):
    rec = SpanRecorder(capacity=64, enabled=True)
    t0 = rec.now()
    rec.complete("tpu", "compute", t0, args={"frame": 8})
    rec.instant("jit", "sp_trace")
    doc = json.loads(json.dumps(rec.chrome_trace()))   # JSON-serializable
    evs = doc["traceEvents"]
    x = next(e for e in evs if e["ph"] == "X")
    assert x["name"] == "compute" and x["dur"] >= 0 and "ts" in x
    assert any(e["ph"] == "i" for e in evs)
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta and meta[0]["name"] == "thread_name"
    # export writes the same document
    rec.complete("tpu", "compute", rec.now())
    path = rec.export(str(tmp_path / "t.json"))
    assert json.load(open(path))["traceEvents"]


def test_snapshot_is_non_destructive():
    rec = SpanRecorder(capacity=64, enabled=True)
    rec.complete("c", "a", rec.now())
    snap = rec.snapshot()
    assert [e.name for e in snap] == ["a"]
    assert [e.name for e in rec.snapshot()] == ["a"]   # still there
    assert [e.name for e in rec.drain()] == ["a"]      # drain still sees it
    assert rec.snapshot() == []


def test_dead_thread_rings_pruned_after_drain():
    rec = SpanRecorder(capacity=64, enabled=True)

    def record():
        rec.complete("c", "from_dead_thread", rec.now())

    t = threading.Thread(target=record)
    t.start()
    t.join()
    assert len(rec._rings) == 1
    evs = rec.drain()                   # events survive the thread's death...
    assert [e.name for e in evs] == ["from_dead_thread"]
    assert rec._rings == []             # ...then the dead ring is unregistered


def test_d2h_parts_billed_as_one_transfer(tracing):
    """A multi-part frame (complex f32-pair wire, quantized formats' scale+
    payload) must count as ONE D2H transfer and one lane span — symmetric with
    the H2D side — or counters and per-lane span counts would scale with the
    wire's part count instead of the frame count."""
    import jax.numpy as jnp

    from futuresdr_tpu.ops import xfer
    before = xfer._XFER_TRANSFERS.get(direction="d2h")
    parts = (jnp.zeros(64, jnp.float32), jnp.zeros(64, jnp.float32))
    out = xfer.start_host_transfer_parts(parts)()
    assert len(out) == 2
    assert xfer._XFER_TRANSFERS.get(direction="d2h") == before + 1
    # the span's start (parts ready) is stamped by ops/xfer.py's watcher
    # thread: give it a moment
    d2h, deadline = [], time.monotonic() + 2.0
    while not d2h and time.monotonic() < deadline:
        d2h += [e for e in tracing.drain() if e.name == "D2H"]
        time.sleep(0.005)
    assert len(d2h) == 1 and d2h[0].args["bytes"] == 512


def test_union_and_overlap_arithmetic():
    assert spans.union_ns([]) == 0
    assert spans.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    mk = lambda name, s, e: SpanEvent(1, "t", s, e - s, "tpu", name, None)
    serial = [mk("H2D", 0, 10), mk("compute", 10, 20), mk("D2H", 20, 30)]
    rep = spans.overlap_report(serial)
    assert rep["ratio"] == pytest.approx(1.0)
    overlapped = [mk("H2D", 0, 10), mk("compute", 0, 10), mk("D2H", 0, 10)]
    rep = spans.overlap_report(overlapped)
    assert rep["ratio"] == pytest.approx(1 / 3)
    assert rep["lanes"]["H2D"]["spans"] == 1


# ---------------------------------------------------------------------------
# prometheus registry + exposition
# ---------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^(#.*|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [0-9eE.+-]+|"
    r"[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [+-]?(Inf|NaN))$")


def _assert_valid_exposition(text: str):
    for line in text.strip().splitlines():
        assert _PROM_LINE.match(line), f"invalid exposition line: {line!r}"


def test_registry_counter_gauge_render():
    reg = prom.Registry()
    c = reg.counter("t_bytes_total", "bytes", ("direction",))
    c.inc(10, direction="h2d")
    c.inc(5, direction="h2d")
    c.inc(3, direction="d2h")
    g = reg.gauge("t_snr_db", "snr", ("wire",))
    g.set(float("inf"), wire="f32")
    g.set(-90.5, wire="sc16")
    text = reg.render()
    _assert_valid_exposition(text)
    assert '# TYPE t_bytes_total counter' in text
    assert 't_bytes_total{direction="h2d"} 15' in text
    assert 't_snr_db{wire="f32"} +Inf' in text
    assert 't_snr_db{wire="sc16"} -90.5' in text
    assert c.get(direction="h2d") == 15


def test_registry_rejects_redefinition_and_bad_labels():
    reg = prom.Registry()
    reg.counter("x_total", "", ("a",))
    with pytest.raises(ValueError, match="re-registered"):
        reg.gauge("x_total", "", ("a",))
    with pytest.raises(ValueError, match="expected labels"):
        reg.counter("x_total", "", ("a",)).inc(b=1)
    with pytest.raises(ValueError, match="only go up"):
        reg.counter("x_total", "", ("a",)).inc(-1, a="v")


def test_render_block_metrics_families():
    fg_metrics = {0: {
        "TpuKernel_1": {
            "work_calls": 3, "work_time_s": 0.25, "messages_handled": 0,
            "items_in": {"in": 100}, "items_out": {"out": 50},
            "buffer_fill": {"in": 0.5}, "stalls": {"out": 2},
            "starved": {"in": 1},
            "frames_in_flight": 4,          # numeric extra → _extra gauge
            "wire": "sc16",                 # string extra  → _attr sample
        },
    }}
    text = prom.render_block_metrics(fg_metrics)
    _assert_valid_exposition(text)
    assert 'fsdr_block_work_calls_total{block="TpuKernel_1",fg="0"} 3' in text
    assert 'fsdr_block_items_in_total{block="TpuKernel_1",fg="0",port="in"} 100' in text
    assert 'fsdr_block_buffer_fill_ratio{block="TpuKernel_1",fg="0",port="in"} 0.5' in text
    assert 'fsdr_block_buffer_stalls_total{block="TpuKernel_1",fg="0",port="out"} 2' in text
    assert 'fsdr_block_starved_total' in text or \
        'fsdr_block_buffer_starved_total' in text
    assert 'key="frames_in_flight"' in text
    assert 'value="sc16"' in text


def test_label_escaping():
    reg = prom.Registry()
    g = reg.gauge("esc", "", ("k",))
    g.set(1, k='a"b\\c\nd')
    text = reg.render()
    assert r'k="a\"b\\c\nd"' in text


# ---------------------------------------------------------------------------
# instrumentation end-to-end: spans from a flowgraph run
# ---------------------------------------------------------------------------

def test_flowgraph_run_records_runtime_and_block_spans(tracing):
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import Copy, VectorSink, VectorSource
    fg = Flowgraph()
    src = VectorSource(np.zeros(65536, np.float32))
    cp = Copy(np.float32)
    snk = VectorSink(np.float32)
    fg.connect(src, cp, snk)
    Runtime().run(fg)
    evs = tracing.drain()
    cats = {(e.cat, e.name) for e in evs}
    assert ("runtime", "init_barrier") in cats
    assert ("runtime", "flowgraph") in cats
    # block spans for actor-run blocks OR one fastchain span when fused
    assert any(c == "block" for c, _ in cats) or \
        any(c == "fastchain" for c, _ in cats)
    barrier = next(e for e in evs if e.name == "init_barrier")
    total = next(e for e in evs if e.name == "flowgraph")
    assert barrier.args["blocks"] == 3 and total.args["errors"] == 0
    assert total.dur_ns >= barrier.dur_ns


def test_buffer_stall_and_starve_counters(monkeypatch):
    """A throttled consumer backpressures the producer (stalls on its output),
    and a starved consumer counts starved parks on its input."""
    monkeypatch.setenv("FSDR_NO_FASTCHAIN", "1")   # the counters live in the
    from futuresdr_tpu import Flowgraph, Runtime   # Python actor event loop
    from futuresdr_tpu.blocks import Head, NullSink, NullSource, Throttle
    fg = Flowgraph()
    src = NullSource(np.float32)
    head = Head(np.float32, 2_000_000)
    thr = Throttle(np.float32, rate=4e6)
    snk = NullSink(np.float32)
    fg.connect(src, head, thr, snk)
    fg_done = Runtime().run(fg)
    m = {b.kernel.meta.instance_name: b.metrics()
         for b in map(fg_done.wrapped, (src, head, thr, snk))}
    stalls = sum(sum(v["stalls"].values()) for v in m.values())
    starved = sum(sum(v["starved"].values()) for v in m.values())
    assert stalls > 0, m        # the throttle backpressured someone upstream
    assert starved > 0, m       # and starved someone downstream
    assert all("buffer_fill" in v for v in m.values())


# ---------------------------------------------------------------------------
# control port: /metrics, /api/fg/{fg}/trace/, CORS on raised errors
# ---------------------------------------------------------------------------

def _start_live_fg():
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import NullSink, NullSource
    fg = Flowgraph()
    fg.connect(NullSource(np.float32), NullSink(np.float32))
    rt = Runtime()
    running = rt.start(fg)
    return rt, running


def test_ctrl_port_prometheus_and_trace_endpoints(tracing):
    from aiohttp import web

    from futuresdr_tpu.ops import xfer                    # noqa: F401 —
    # importing registers the link-plane counters in the global registry
    from futuresdr_tpu.runtime.ctrl_port import ControlPort

    async def failing_route(request):
        raise web.HTTPNotFound(text="nope")

    rt, running = _start_live_fg()
    cp = ControlPort(rt.handle, bind="127.0.0.1:29471",
                     extra_routes=[("GET", "/fail/", failing_route)])
    cp.start()
    base = "http://127.0.0.1:29471"
    try:
        # ---- /metrics: valid exposition with the per-block families -------
        deadline = time.perf_counter() + 10.0
        text = ""
        while time.perf_counter() < deadline:
            text = urllib.request.urlopen(base + "/metrics").read().decode()
            if "fsdr_block_work_calls_total" in text and \
                    re.search(r'fsdr_block_work_calls_total{[^}]*} [1-9]', text):
                break
            time.sleep(0.02)
        _assert_valid_exposition(text)
        assert re.search(r'fsdr_block_work_calls_total{[^}]*} [1-9]', text)
        assert "fsdr_block_buffer_fill_ratio" in text     # occupancy gauge
        assert "fsdr_block_buffer_stalls_total" in text   # stall counters
        assert "fsdr_block_items_out_total" in text
        assert "fsdr_xfer_bytes_total" in text            # registry counters

        # ---- /api/fg/{fg}/trace/: drains the ring as Chrome trace JSON ----
        tracing.complete("tpu", "H2D", tracing.now(), args={"bytes": 1})
        # ?keep=1 peeks without stealing events from other trace consumers
        peek = json.load(urllib.request.urlopen(
            base + "/api/fg/0/trace/?keep=1"))
        assert any(e.get("name") == "H2D" for e in peek["traceEvents"])
        doc = json.load(urllib.request.urlopen(base + "/api/fg/0/trace/"))
        assert any(e.get("name") == "H2D" for e in doc["traceEvents"])
        # drained: a second scrape no longer carries it
        doc2 = json.load(urllib.request.urlopen(base + "/api/fg/0/trace/"))
        assert not any(e.get("name") == "H2D" for e in doc2["traceEvents"])
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/api/fg/99/trace/")
        assert ei.value.code == 404

        # ---- CORS adorns RAISED error responses too (middleware fix) -----
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/fail/")
        assert ei.value.code == 404
        assert ei.value.headers["Access-Control-Allow-Origin"] == "*"
        # and non-error responses keep it
        r = urllib.request.urlopen(base + "/api/fg/")
        assert r.headers["Access-Control-Allow-Origin"] == "*"
    finally:
        running.stop_sync()
        cp.stop()


# ---------------------------------------------------------------------------
# supervisor post-close drain: MetricsMsg must be answered (satellite fix)
# ---------------------------------------------------------------------------

def test_metrics_racing_completion_gets_final_snapshot():
    """A MetricsMsg queued just before the supervisor closes its inbox (the
    metrics()-vs-completion race) must be answered with the final per-block
    snapshot — pre-fix it was silently dropped and the caller awaited forever."""
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import Copy, VectorSink, VectorSource
    from futuresdr_tpu.runtime.inbox import ReplySlot
    from futuresdr_tpu.runtime.runtime import MetricsMsg

    rt = Runtime()
    # the monkeypatch itself races flowgraph completion: on a loaded box the
    # supervisor can reach fg_inbox.close() before the patch below lands, and
    # the racer message is never sent at all (`armed` stays clear).  That run
    # did not exercise the race window — rebuild and try again
    for _ in range(20):
        fg = Flowgraph()
        src = VectorSource(np.zeros(10_000, np.float32))
        cp = Copy(np.float32)
        snk = VectorSink(np.float32)
        fg.connect(src, cp, snk)
        running = rt.start(fg)
        inbox = running.handle._inbox
        reply = ReplySlot()
        orig_close = inbox.close
        armed = threading.Event()

        def close_with_racer():
            # enqueue while the inbox is still open — exactly the race window:
            # sent before close, drained after the main loop already exited
            inbox.send(MetricsMsg(reply))
            armed.set()
            orig_close()

        inbox.close = close_with_racer
        running.wait_sync()
        if armed.is_set():
            break
    else:
        pytest.fail("patched close never won the race against completion")

    async def get():
        import asyncio
        return await asyncio.wait_for(reply.get(), timeout=10.0)

    snapshot = rt.scheduler.run_coro_sync(get())
    assert isinstance(snapshot, dict) and len(snapshot) == 3
    assert any(v.get("work_calls", 0) > 0 for v in snapshot.values())


# ---------------------------------------------------------------------------
# overhead gate (tier-1 acceptance): telemetry disabled ≤ ~3% on null_rand
# ---------------------------------------------------------------------------

def _null_rand_chain(samples=1_000_000, stages=3, max_copy=2048):
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import CopyRand, Head, NullSink, NullSource
    fg = Flowgraph()
    blocks = [NullSource(np.float32), Head(np.float32, samples)]
    fg.connect(blocks[0], blocks[1])
    last = blocks[1]
    for s in range(stages):
        c = CopyRand(np.float32, max_copy=max_copy, seed=1 + s)
        fg.connect(last, c)
        blocks.append(c)
        last = c
    snk = NullSink(np.float32)
    fg.connect(last, snk)
    blocks.append(snk)
    t0 = time.perf_counter()
    done = Runtime().run(fg)
    elapsed = time.perf_counter() - t0
    calls = sum(done.wrapped(b).work_calls for b in blocks)
    return elapsed, calls


def test_telemetry_disabled_overhead_null_rand(monkeypatch):
    """The ≤ ~3% gate, measured on the REAL null_rand actor chain — with the
    doctor watchdog armed at its default interval (the flowgraph-doctor PR
    extends the gate: always-on diagnosis must ride inside the same budget),
    the device-plane recovery PR's disabled checkpoint hook billed as a
    third per-call cost (checkpoint_every=0 must be free), and the profile
    plane's dispatch-unit counter billed as a fourth (live MFU attribution
    must ride inside the same budget too), the lineage plane's per-frame
    sample draw billed as a fifth (frame-lineage tracing at the default
    stride must ride inside the same budget as well), and the fleet plane's
    per-step tick billed as a sixth (the cross-host plane off by default
    must be one falsy check), and the span-timeline sites of both launch
    paths billed as a seventh (ten guard pairs per frame or serving step).

    The per-work-call cost of the disabled telemetry path (the `if
    rec.enabled:` guard, the ns-clock reads the loop already paid
    pre-telemetry, AND the doctor's per-call work-duration histogram observe)
    is micro-measured directly, then multiplied by the chain's actual
    work-call rate: `hook_cost × calls / elapsed` IS the fraction of the
    no-telemetry baseline the instrumentation costs. An interleaved
    wall-clock A/B at 3% precision would gate on CI noise instead
    (VERDICT item 3's instability bar exists for exactly that reason); the
    analytic bound is deterministic and measures the same thing. The
    watchdog itself samples at 1 Hz off the hot path — its cost shows up (if
    at all) in the measured chain elapsed, not in the per-call hook.
    """
    monkeypatch.setenv("FSDR_NO_FASTCHAIN", "1")  # the hooks live in the
    rec = spans.recorder()                        # Python actor event loop
    assert not rec.enabled, "gate must measure the DISABLED path"
    from futuresdr_tpu.telemetry import doctor as doc
    hist = doc.WORK_DURATION.labels(block="overhead-gate-probe")

    # per-call disabled-path cost, billed separately per site: a WORK call
    # pays guard + end-clock read + the work-duration histogram observe; a
    # PARK pays only the guard (runtime/block.py) — parks ≈ work calls at
    # worst, so the chain pays one of each per call
    n = 200_000

    def best_of(loop):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter_ns()
            loop()
            best = min(best, (time.perf_counter_ns() - t0) / n)
        return best

    def work_hook():
        for _ in range(n):
            if rec.enabled:                       # pragma: no cover
                rec.complete("block", "x", 0)
            hist.observe_sampled(1.5e-6)          # the work-duration observe
            time.perf_counter_ns()                # the end-timestamp read

    def park_hook():
        for _ in range(n):
            if rec.enabled:                       # pragma: no cover
                rec.complete("park", "x", 0)

    # checkpoint hook (device-plane recovery, tpu/kernel_block.py): with
    # checkpoint_every=0 the per-dispatch _checkpoint_tick must be one falsy
    # check — billed here as a THIRD per-call hook even though the host chain
    # never dispatches (a conservative over-count: the real rate is one tick
    # per device dispatch group, far below the work-call rate)
    from futuresdr_tpu.ops import mag2_stage
    from futuresdr_tpu.tpu import TpuKernel
    tk = TpuKernel([mag2_stage()], np.complex64, frame_size=1 << 12,
                   checkpoint_every=0)
    assert tk._ckpt_every == 0
    tick = tk._checkpoint_tick

    def ckpt_hook():
        for _ in range(n):
            tick(0)

    # profile-plane dispatch hook (telemetry/profile.py): the live-roofline
    # unit counter every kernel dispatch bills — a FOURTH per-call hook
    # class, again a conservative over-count (the real rate is one call per
    # dispatch GROUP, far below the work-call rate). One priming call first:
    # the first dispatch seeds the run-average window and swaps in the
    # steady-state hook — a bare counter add; the t_last group stamp is the
    # dispatch SITE's own clock, passed as t=, and real sites run at group
    # rate — which is what every later call pays
    from futuresdr_tpu.telemetry import profile as prof_mod
    entry = prof_mod.register("overhead-gate-probe")
    entry.dispatch()
    dispatch = entry.dispatch

    def prof_hook():
        for _ in range(n):
            dispatch()

    # lineage sample hook (telemetry/lineage.py): the per-frame trace-id
    # draw at the DEFAULT 1-in-64 stride — a FIFTH per-call hook class,
    # again a conservative over-count (the real rate is one sample per
    # FRAME, far below the work-call rate). Like the checkpoint and
    # profile classes, the bill is the steady-state per-call guard — the
    # unlocked countdown the contract promises — with the heavy-but-rare
    # companion (the 1-in-64 record build + stamps, a few µs at 1/64 the
    # frame rate) landing at group rate like checkpoint commits and
    # profile window swaps. The loop still drains each sampled id through
    # finish() so the open-table bound rides inside the measurement.
    # Journal emits live at lifecycle decision sites, not on the
    # per-frame path, so they bill into `elapsed`, not per call.
    from futuresdr_tpu.telemetry import lineage as lin_mod
    ltr = lin_mod.reset_tracer()
    assert ltr.stride >= 2, "gate must measure the default sampled stride"
    sample = ltr.sample

    def lineage_hook():
        for _ in range(n):
            tid = sample()
            if tid:
                ltr.finish(tid)

    # fleet tick (telemetry/fleet.py): the serve engine's step() guards the
    # tick INLINE (`if _fleet._tick_state is not None:` — a module-global
    # read, no call frame) — a SIXTH per-call hook class, again a
    # conservative over-count (the real rate is one tick per serve
    # DISPATCH, far below the work-call rate). With fleet_peers unset the
    # guard is one falsy check, like the park guard; the enabled-path
    # summary build runs at poll cadence off this bill.
    from futuresdr_tpu.telemetry import fleet as fleet_mod
    assert fleet_mod._tick_state is None, \
        "gate must measure the fleet-disabled path"

    def fleet_hook():
        for _ in range(n):
            if fleet_mod._tick_state is not None:  # pragma: no cover
                fleet_mod.tick()

    # span-timeline sites (tpu/kernel_block.py, serve/engine.py, ops/xfer.py):
    # one frame's way through either launch path passes about ten
    # `t0 = now() if enabled else 0` … `if t0:` guard pairs (stage, h2d_put,
    # h2d_wait, compute/program, d2h_wait, emit, frame; lock_wait ×3,
    # queue_wait, h2d group, d2h_wait in serving) — a SEVENTH per-call hook
    # class. Unlike the work/park hooks, which every block pays on every
    # call, only the device kernel's own calls pass these: billed at TEN
    # pairs per work call of ONE of the chain's six blocks — still a
    # conservative over-count (the real rate is per FRAME or serving step,
    # a millisecond or more, against this chain's ~20 µs per call)
    chain_blocks = 6                    # source, head, 3 × CopyRand, sink
    def timeline_hook():
        for _ in range(n):
            t0 = rec.now() if rec.enabled else 0
            if t0:                               # pragma: no cover
                rec.complete("tpu", "x", t0)
            t1 = rec.now() if rec.enabled else 0
            if t1:                               # pragma: no cover
                rec.complete("tpu", "x", t1)
            t2 = rec.now() if rec.enabled else 0
            if t2:                               # pragma: no cover
                rec.complete("tpu", "x", t2)
            t3 = rec.now() if rec.enabled else 0
            if t3:                               # pragma: no cover
                rec.complete("tpu", "x", t3)
            t4 = rec.now() if rec.enabled else 0
            if t4:                               # pragma: no cover
                rec.complete("tpu", "x", t4)
            t5 = rec.now() if rec.enabled else 0
            if t5:                               # pragma: no cover
                rec.complete("tpu", "x", t5)
            t6 = rec.now() if rec.enabled else 0
            if t6:                               # pragma: no cover
                rec.complete("tpu", "x", t6)
            t7 = rec.now() if rec.enabled else 0
            if t7:                               # pragma: no cover
                rec.complete("tpu", "x", t7)
            t8 = rec.now() if rec.enabled else 0
            if t8:                               # pragma: no cover
                rec.complete("tpu", "x", t8)
            t9 = rec.now() if rec.enabled else 0
            if t9:                               # pragma: no cover
                rec.complete("tpu", "x", t9)

    # paired trials: hook micro-costs and the chain rate are measured back to
    # back INSIDE each trial, and the gate takes the best trial — a transient
    # load spike that inflates only one side of one trial (the structural
    # flake mode: hooks and chain are necessarily sampled at different
    # instants) cannot flip the verdict as long as one trial runs clean.
    # Up to 12 trials, breaking on the first clean one: contention bursts
    # on a shared box last seconds, and the pure-CPU micro-loops inflate
    # more than the chain elapsed (which includes parks) — a settle sleep
    # after each dirty trial stretches the escape window past burst length,
    # and the healthy path never sleeps
    trials = []
    for _ in range(12):
        if trials:
            time.sleep(1.0)
        work_ns, park_ns, ckpt_ns, prof_ns, lin_ns, fleet_ns = \
            best_of(work_hook), best_of(park_hook), best_of(ckpt_hook), \
            best_of(prof_hook), best_of(lineage_hook), best_of(fleet_hook)
        tl_ns = best_of(timeline_hook) / chain_blocks
        # the chain's real call rate, measured with the watchdog running at
        # its DEFAULT interval (1 Hz sampling lands in `elapsed`, not per
        # call)
        doc.enable()
        assert doc.enabled()
        try:
            elapsed, calls = _null_rand_chain()
        finally:
            doc.disable()
        overhead = calls * (work_ns + park_ns + ckpt_ns + prof_ns
                            + lin_ns + fleet_ns + tl_ns) * 1e-9 / elapsed
        trials.append((overhead, work_ns, park_ns, ckpt_ns, prof_ns,
                       lin_ns, fleet_ns, tl_ns, calls, elapsed))
        if overhead <= 0.03:
            break
    (overhead, work_ns, park_ns, ckpt_ns, prof_ns, lin_ns, fleet_ns,
     tl_ns, calls, elapsed) = min(trials)
    ltr.clear()
    assert overhead <= 0.03, (
        f"telemetry-disabled hooks cost {overhead * 100:.2f}% of the "
        f"null_rand chain ({calls} work calls, {work_ns:.0f}+{park_ns:.0f}"
        f"+{ckpt_ns:.0f}+{prof_ns:.0f}+{lin_ns:.0f}+{fleet_ns:.0f}"
        f"+{tl_ns:.0f} ns/hook, "
        f"{elapsed:.3f}s elapsed; best of {len(trials)} paired trials)")


def test_telemetry_enabled_stays_cheap(tracing, monkeypatch):
    """Coarse guard, not the 3% gate: recording spans for every work call must
    not blow up the chain (ring pushes are O(100ns)); generous 1.5× bound so
    CI noise cannot flake it."""
    monkeypatch.setenv("FSDR_NO_FASTCHAIN", "1")
    tracing.enabled = False
    t_off, _ = _null_rand_chain(samples=500_000)
    tracing.enabled = True
    t_on, _ = _null_rand_chain(samples=500_000)
    tracing.drain()
    assert t_on <= 1.5 * t_off + 0.05, (t_on, t_off)
