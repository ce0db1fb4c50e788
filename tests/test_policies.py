"""Fault-tolerant runtime (ISSUE 6 tentpole): per-block failure policies
(restart / isolate / fail_fast), structured multi-error FlowgraphError,
``Runtime.run(timeout=)`` graceful deadlines, and the doctor's
``doctor_action: cancel`` escalation."""

import os
import time

import numpy as np
import pytest

from futuresdr_tpu import (BlockPolicy, Flowgraph, FlowgraphCancelled,
                           FlowgraphError, Kernel, Runtime)
from futuresdr_tpu.blocks import Copy, NullSource, VectorSink, VectorSource
from futuresdr_tpu.config import config
from futuresdr_tpu.telemetry import doctor as doc


class FlakyCopy(Kernel):
    """Copies input, raising on chosen work calls BEFORE touching any port —
    the same fault point as the ``work:<block>`` injection site, so a restart
    loses no consumed input."""

    def __init__(self, dtype, fail_on=(), always=False):
        super().__init__()
        self.input = self.add_stream_input("in", dtype)
        self.output = self.add_stream_output("out", dtype)
        self.fail_on = set(fail_on)
        self.always = always
        self.calls = 0
        self.init_calls = 0

    async def init(self, mio, meta):
        self.init_calls += 1

    async def work(self, io, mio, meta):
        self.calls += 1
        if self.always or self.calls in self.fail_on:
            raise RuntimeError(f"flaky boom #{self.calls}")
        inp = self.input.slice()
        out = self.output.slice()
        n = min(len(inp), len(out))
        if n:
            out[:n] = inp[:n]
            self.input.consume(n)
            self.output.produce(n)
        if self.input.finished() and n == len(inp):
            io.finished = True


class FlakyInit(Kernel):
    """Init fails ``fail_times`` times, then comes up and copies."""

    def __init__(self, dtype, fail_times: int):
        super().__init__()
        self.input = self.add_stream_input("in", dtype)
        self.output = self.add_stream_output("out", dtype)
        self.fail_times = fail_times
        self.init_calls = 0

    async def init(self, mio, meta):
        self.init_calls += 1
        if self.init_calls <= self.fail_times:
            raise RuntimeError(f"init boom #{self.init_calls}")

    async def work(self, io, mio, meta):
        inp = self.input.slice()
        out = self.output.slice()
        n = min(len(inp), len(out))
        if n:
            out[:n] = inp[:n]
            self.input.consume(n)
            self.output.produce(n)
        if self.input.finished() and n == len(inp):
            io.finished = True


class WedgeSink(Kernel):
    """Never consumes, never finishes — the canonical wedged flowgraph."""

    def __init__(self, dtype):
        super().__init__()
        self.input = self.add_stream_input("in", dtype)

    async def work(self, io, mio, meta):
        pass


def _restarts(block_name: str) -> float:
    from futuresdr_tpu.runtime.block import _RESTARTS
    return _RESTARTS.get(block=block_name)


# ---------------------------------------------------------------------------
# restart policy
# ---------------------------------------------------------------------------

def test_restart_recovers_bit_correct():
    """Acceptance: `restart` recovers to bit-correct output for a transient
    single-fault run — fresh init, billed restart counter, no graph teardown."""
    data = np.arange(200_000, dtype=np.float32)
    fg = Flowgraph()
    src = VectorSource(data)
    fc = FlakyCopy(np.float32, fail_on=(2,))
    fc.policy = BlockPolicy(on_error="restart", max_restarts=3, backoff=0.002)
    snk = VectorSink(np.float32)
    fg.connect(src, fc, snk)
    before = _restarts(f"FlakyCopy_{fg.block_id(fc)}")
    Runtime().run(fg)
    np.testing.assert_array_equal(np.asarray(snk.items()), data)
    wk = fg.wrapped(fc)
    assert wk.restarts == 1
    assert fc.init_calls == 2             # original init + one restart re-init
    assert _restarts(wk.instance_name) - before == 1
    assert wk.metrics()["restarts"] == 1


def test_restart_exhausted_escalates_to_failure():
    fg = Flowgraph()
    src = VectorSource(np.zeros(10_000, np.float32))
    fc = FlakyCopy(np.float32, always=True)
    fc.policy = BlockPolicy(on_error="restart", max_restarts=2, backoff=0.002)
    snk = VectorSink(np.float32)
    fg.connect(src, fc, snk)
    with pytest.raises(FlowgraphError) as ei:
        Runtime().run(fg)
    e = ei.value
    wk = fg.wrapped(fc)
    assert wk.restarts == 2
    assert e.blocks == [wk.instance_name]
    actions = [d["action"] for d in e.policy_decisions]
    assert actions.count("restart") == 2
    assert actions[-1] == "restarts_exhausted"


def test_restart_covers_init_failures():
    data = np.arange(50_000, dtype=np.float32)
    fg = Flowgraph()
    src = VectorSource(data)
    fi = FlakyInit(np.float32, fail_times=2)
    fi.policy = BlockPolicy(on_error="restart", max_restarts=3, backoff=0.002)
    snk = VectorSink(np.float32)
    fg.connect(src, fi, snk)
    Runtime().run(fg)
    np.testing.assert_array_equal(np.asarray(snk.items()), data)
    assert fi.init_calls == 3
    assert fg.wrapped(fi).restarts == 2


def test_policy_validation():
    with pytest.raises(ValueError):
        BlockPolicy(on_error="explode")
    assert BlockPolicy.from_config().on_error == "fail_fast"


# ---------------------------------------------------------------------------
# isolate policy
# ---------------------------------------------------------------------------

def test_isolate_lets_independent_branches_finish():
    """Acceptance: `isolate` retires the failed block (EOS downstream,
    upstream detach) while an independent branch completes bit-correct; the
    run still raises a structured FlowgraphError naming the faulted block."""
    data = np.arange(100_000, dtype=np.float32)
    fg = Flowgraph()
    src_a = VectorSource(data)
    cp = Copy(np.float32)
    snk_a = VectorSink(np.float32)
    fg.connect(src_a, cp, snk_a)
    src_b = VectorSource(np.zeros(50_000, np.float32))
    bad = FlakyCopy(np.float32, always=True)
    bad.policy = BlockPolicy(on_error="isolate")
    snk_b = VectorSink(np.float32)
    fg.connect(src_b, bad, snk_b)
    with pytest.raises(FlowgraphError) as ei:
        Runtime().run(fg)
    e = ei.value
    # the healthy branch finished ALL its data despite the peer failure
    np.testing.assert_array_equal(np.asarray(snk_a.items()), data)
    assert e.blocks == [fg.wrapped(bad).instance_name]
    assert [d["action"] for d in e.policy_decisions] == ["isolate"]
    assert isinstance(e.errors[0], RuntimeError)


def test_isolate_covers_init_failures():
    data = np.arange(60_000, dtype=np.float32)
    fg = Flowgraph()
    src_a = VectorSource(data)
    snk_a = VectorSink(np.float32)
    fg.connect(src_a, Copy(np.float32), snk_a)
    src_b = VectorSource(np.zeros(1000, np.float32))
    bad = FlakyInit(np.float32, fail_times=99)
    bad.policy = BlockPolicy(on_error="isolate")
    snk_b = VectorSink(np.float32)
    fg.connect(src_b, bad, snk_b)
    with pytest.raises(FlowgraphError) as ei:
        Runtime().run(fg)
    np.testing.assert_array_equal(np.asarray(snk_a.items()), data)
    dec = ei.value.policy_decisions
    assert dec and dec[0]["action"] == "isolate" and dec[0]["phase"] == "init"


# ---------------------------------------------------------------------------
# fail_fast default + multi-error aggregation (satellite: errors[0]-only bug)
# ---------------------------------------------------------------------------

def test_fail_fast_default_structured_error():
    fg = Flowgraph()
    src = VectorSource(np.zeros(10_000, np.float32))
    bad = FlakyCopy(np.float32, always=True)     # no policy set anywhere
    snk = VectorSink(np.float32)
    fg.connect(src, bad, snk)
    with pytest.raises(FlowgraphError) as ei:
        Runtime().run(fg)
    e = ei.value
    assert str(e) == str(e.errors[0])            # single-error message contract
    assert e.blocks == [fg.wrapped(bad).instance_name]
    assert [d["action"] for d in e.policy_decisions] == ["fail_fast"]
    assert e.flight_record is None
    assert len(fg) == 3                          # blocks restored


def test_multi_block_failures_are_aggregated():
    """Satellite: FlowgraphError used to stringify only errors[0] — concurrent
    failures must all surface, with the count in the message."""
    fg = Flowgraph()
    src = NullSource(np.float32)
    bad1 = FlakyInit(np.float32, fail_times=99)
    bad2 = FlakyInit(np.float32, fail_times=99)
    snk = VectorSink(np.float32)
    fg.connect(src, bad1, bad2, snk)
    with pytest.raises(FlowgraphError) as ei:
        Runtime().run(fg)
    e = ei.value
    assert len(e.errors) == 2
    assert "2 blocks failed" in str(e)
    names = {fg.wrapped(bad1).instance_name, fg.wrapped(bad2).instance_name}
    assert set(e.blocks) == names
    for n in names:
        assert n in str(e)


# ---------------------------------------------------------------------------
# run deadlines (Runtime.run(timeout=) / run_timeout config)
# ---------------------------------------------------------------------------

def _wedged_fg():
    fg = Flowgraph()
    fg.connect(NullSource(np.float32), Copy(np.float32),
               WedgeSink(np.float32))
    return fg


def test_run_timeout_converts_hang_to_error(monkeypatch):
    monkeypatch.setattr(config(), "run_timeout_grace", 3.0)
    t0 = time.perf_counter()
    with pytest.raises(FlowgraphError) as ei:
        Runtime().run(_wedged_fg(), timeout=0.6)
    elapsed = time.perf_counter() - t0
    assert elapsed < 8.0, f"deadline did not bound the run ({elapsed:.1f}s)"
    e = ei.value
    assert any(isinstance(x, FlowgraphCancelled) for x in e.errors)
    assert any(d["action"] == "cancel" for d in e.policy_decisions)
    assert "deadline" in str(e)


def test_run_timeout_config_knob(monkeypatch):
    monkeypatch.setattr(config(), "run_timeout", 0.6)
    monkeypatch.setattr(config(), "run_timeout_grace", 3.0)
    with pytest.raises(FlowgraphError):
        Runtime().run(_wedged_fg())


def test_run_timeout_bounds_wedged_init():
    """The deadline is a TOTAL budget: a kernel.init wedged on a dead link
    must not hang run() any more than a wedged work() may."""
    import asyncio

    class WedgedInit(Kernel):
        def __init__(self, dtype):
            super().__init__()
            self.input = self.add_stream_input("in", dtype)

        async def init(self, mio, meta):
            await asyncio.sleep(3600)

    fg = Flowgraph()
    fg.connect(NullSource(np.float32), WedgedInit(np.float32))
    t0 = time.perf_counter()
    with pytest.raises(FlowgraphError, match="init barrier"):
        Runtime().run(fg, timeout=0.5)
    assert time.perf_counter() - t0 < 4.0
    e_ok = False
    try:
        Runtime().run(fg, timeout=0.5)
    except FlowgraphError as e:
        e_ok = any(isinstance(x, FlowgraphCancelled) for x in e.errors)
    except RuntimeError:
        e_ok = True        # second launch of a taken flowgraph also raises
    assert e_ok


def test_run_timeout_not_triggered_on_healthy_run():
    data = np.arange(10_000, dtype=np.float32)
    fg = Flowgraph()
    src = VectorSource(data)
    snk = VectorSink(np.float32)
    fg.connect(src, Copy(np.float32), snk)
    Runtime().run(fg, timeout=30.0)
    np.testing.assert_array_equal(np.asarray(snk.items()), data)


# ---------------------------------------------------------------------------
# doctor escalation (doctor_action: cancel) — acceptance
# ---------------------------------------------------------------------------

def test_doctor_cancel_converts_wedge_to_error(tmp_path, monkeypatch):
    """Acceptance: with `doctor_action: cancel` a wedged-sink flowgraph turns
    from an indefinite hang into a FlowgraphError with an attached flight
    record."""
    monkeypatch.setenv("FSDR_NO_FASTCHAIN", "1")
    monkeypatch.setattr(config(), "doctor_action", "cancel")
    monkeypatch.setattr(config(), "doctor_dir", str(tmp_path))
    d = doc.doctor()
    d.enable(interval=0.05, window=3)
    try:
        with pytest.raises(FlowgraphError) as ei:
            Runtime().run(_wedged_fg())
        e = ei.value
        assert any(isinstance(x, FlowgraphCancelled) for x in e.errors)
        assert "doctor watchdog: backpressured" in str(e)
        assert e.flight_record is not None and os.path.exists(e.flight_record)
    finally:
        d.disable()
        d.last_trip = None


def test_doctor_cancel_unwedges_init_barrier(monkeypatch):
    """A block wedged inside init() never answers the barrier — the doctor's
    cancel must still convert the run into a FlowgraphError (the supervisor
    abandons the barrier) instead of queueing the cancel forever."""
    import asyncio

    class WedgedInit(Kernel):
        def __init__(self, dtype):
            super().__init__()
            self.input = self.add_stream_input("in", dtype)

        async def init(self, mio, meta):
            await asyncio.sleep(3600)

    monkeypatch.setenv("FSDR_NO_FASTCHAIN", "1")
    monkeypatch.setattr(config(), "doctor_action", "cancel")
    d = doc.doctor()
    d.enable(interval=0.05, window=3)
    try:
        fg = Flowgraph()
        fg.connect(NullSource(np.float32), WedgedInit(np.float32))
        t0 = time.perf_counter()
        with pytest.raises(FlowgraphError) as ei:
            Runtime().run(fg)
        assert time.perf_counter() - t0 < 15.0
        assert any(isinstance(x, FlowgraphCancelled) for x in ei.value.errors)
    finally:
        d.disable()
        d.last_trip = None


def test_supervisor_flight_record_carries_error_count():
    """Satellite: the supervisor's on-error flight record surfaces how many
    blocks failed and which policy decisions were taken."""
    d = doc.doctor()
    d.enable(interval=30.0, window=5)     # enabled → supervisor errors dump
    try:
        fg = Flowgraph()
        src = VectorSource(np.zeros(1000, np.float32))
        bad = FlakyCopy(np.float32, always=True)
        snk = VectorSink(np.float32)
        fg.connect(src, bad, snk)
        with pytest.raises(FlowgraphError):
            Runtime().run(fg)
        sup = (d.last_report or {}).get("supervisor")
        assert sup is not None
        assert sup["block_errors"] == 1
        assert sup["blocks"] == [fg.wrapped(bad).instance_name]
        assert sup["policy_decisions"][0]["action"] == "fail_fast"
    finally:
        d.disable()
        d.last_trip = None


# ---------------------------------------------------------------------------
# fusion × policy: isolate refuses, restart fuses (device-plane recovery)
# ---------------------------------------------------------------------------

def test_devchain_refuses_isolate_members():
    from futuresdr_tpu.ops import mag2_stage
    from futuresdr_tpu.tpu import TpuD2H, TpuH2D, TpuStage
    frame = 4096
    n = 4 * frame
    tone = np.exp(2j * np.pi * 0.05 * np.arange(n)).astype(np.complex64)
    fg = Flowgraph()
    src = VectorSource(tone)
    h2d = TpuH2D(np.complex64, frame_size=frame)
    st = TpuStage([mag2_stage()], np.complex64)
    st.policy = BlockPolicy(on_error="isolate")
    d2h = TpuD2H(np.float32)
    snk = VectorSink(np.float32)
    fg.connect(src, h2d, st, d2h, snk)
    done = Runtime().run(fg)
    m = done.wrapped(st).metrics()
    assert not m.get("fused_devchain"), \
        "an isolate-policy member must refuse device-graph fusion"
    np.testing.assert_allclose(
        np.asarray(snk.items()),
        (tone.real ** 2 + tone.imag ** 2).astype(np.float32), rtol=1e-5)


def test_devchain_fuses_restart_members():
    """Device-plane recovery acceptance: a restart-policy member NO LONGER
    declines fusion — the fused kernel carries the recovery contract
    (checkpoint/replay) itself."""
    from futuresdr_tpu.ops import mag2_stage
    from futuresdr_tpu.tpu import TpuD2H, TpuH2D, TpuStage
    frame = 4096
    n = 4 * frame
    tone = np.exp(2j * np.pi * 0.05 * np.arange(n)).astype(np.complex64)
    fg = Flowgraph()
    src = VectorSource(tone)
    h2d = TpuH2D(np.complex64, frame_size=frame)
    st = TpuStage([mag2_stage()], np.complex64)
    st.policy = BlockPolicy(on_error="restart")
    d2h = TpuD2H(np.float32)
    snk = VectorSink(np.float32)
    fg.connect(src, h2d, st, d2h, snk)
    done = Runtime().run(fg)
    m = done.wrapped(st).metrics()
    assert m.get("fused_devchain"), \
        "a restart-policy member should fuse (recovery AND fusion)"
    np.testing.assert_allclose(
        np.asarray(snk.items()),
        (tone.real ** 2 + tone.imag ** 2).astype(np.float32), rtol=1e-5)


def test_devchain_degrades_under_global_policy(monkeypatch):
    from futuresdr_tpu.runtime.devchain import devchain_enabled
    assert devchain_enabled()
    # a global restart default no longer degrades (fused kernels restart in
    # place from their composed-carry checkpoint); isolate still does
    monkeypatch.setattr(config(), "block_policy", "restart")
    assert devchain_enabled()
    monkeypatch.setattr(config(), "block_policy", "isolate")
    assert not devchain_enabled()


def test_devchain_degrades_under_work_faults():
    from futuresdr_tpu.runtime import faults
    from futuresdr_tpu.runtime.devchain import devchain_enabled
    faults.reset().arm("work:some_block", rate=0.5)
    try:
        assert not devchain_enabled()
    finally:
        faults.reset()
    assert devchain_enabled()


def test_devchain_dispatch_fault_gating():
    """A bare `dispatch` site keeps fusion on (the fused kernel polls it);
    a block-ADDRESSED dispatch:<name> site degrades — fused mode would
    silently un-arm it."""
    from futuresdr_tpu.runtime import faults
    from futuresdr_tpu.runtime.devchain import devchain_enabled
    faults.reset().arm("dispatch", rate=0.5)
    try:
        assert devchain_enabled()
    finally:
        faults.reset()
    faults.reset().arm("dispatch:TpuKernel_1", rate=0.5)
    try:
        assert not devchain_enabled()
    finally:
        faults.reset()
    assert devchain_enabled()


# ---------------------------------------------------------------------------
# injected work faults drive the same machinery end to end
# ---------------------------------------------------------------------------

def test_injected_work_fault_with_restart_policy(monkeypatch):
    """The chaos harness's core recovery path as a unit test: a seeded
    single-shot work fault + restart policy → bit-correct output."""
    from futuresdr_tpu.runtime import faults
    monkeypatch.setenv("FSDR_NO_FASTCHAIN", "1")
    data = np.arange(120_000, dtype=np.float32)
    fg = Flowgraph()
    src = VectorSource(data)
    cp = Copy(np.float32)
    cp.policy = BlockPolicy(on_error="restart", max_restarts=2, backoff=0.002)
    snk = VectorSink(np.float32)
    fg.connect(src, cp, snk)
    name = fg.wrapped(cp).instance_name
    faults.reset().arm(f"work:{name}", rate=1.0, max_faults=1, seed=3)
    try:
        Runtime().run(fg)
    finally:
        faults.reset()
    np.testing.assert_array_equal(np.asarray(snk.items()), data)
    assert fg.wrapped(cp).restarts == 1


# ---------------------------------------------------------------------------
# policy surface on the control plane (REST describe, ISSUE 7 satellite)
# ---------------------------------------------------------------------------

def test_describe_carries_policy_decisions_and_restarts(monkeypatch):
    """A run that RECOVERED via restart leaves its policy story readable:
    block descriptions carry the resolved policy + restart count and the
    flowgraph description the supervisor's decision log — the surface
    ``GET /api/fg/{fg}/`` serves (FlowgraphError only exists for failed
    runs; recovered runs report here)."""
    monkeypatch.setenv("FSDR_NO_FASTCHAIN", "1")
    data = np.arange(50_000, dtype=np.float32)
    fg = Flowgraph()
    src = VectorSource(data)
    cp = FlakyCopy(np.float32, fail_on=(1,))
    cp.policy = BlockPolicy(on_error="restart", max_restarts=3, backoff=0.0)
    snk = VectorSink(np.float32)
    fg.connect(src, cp, snk)
    Runtime().run(fg)
    np.testing.assert_array_equal(np.asarray(snk.items()), data)
    desc = fg.describe().to_json()
    blk = next(b for b in desc["blocks"] if b["type_name"] == "FlakyCopy")
    assert blk["policy"] == "restart"
    assert blk["restarts"] == 1
    others = [b for b in desc["blocks"] if b["type_name"] != "FlakyCopy"]
    assert all(b["policy"] == "fail_fast" and b["restarts"] == 0
               for b in others)
    acts = [d for d in desc["policy_decisions"] if d["action"] == "restart"]
    assert len(acts) == 1 and acts[0]["block"] == blk["instance_name"]
    assert acts[0]["attempt"] == 1 and acts[0]["phase"] == "work"


def test_describe_policy_decisions_empty_on_clean_run():
    fg = Flowgraph()
    src = VectorSource(np.arange(1000, dtype=np.float32))
    snk = VectorSink(np.float32)
    fg.connect(src, snk)
    Runtime().run(fg)
    desc = fg.describe().to_json()
    assert desc["policy_decisions"] == []
    assert all(b["restarts"] == 0 for b in desc["blocks"])


# ---------------------------------------------------------------------------
# device-plane recovery: carry checkpoint/replay (ISSUE 8 tentpole)
# ---------------------------------------------------------------------------

_FRAME = 1 << 11
_N = _FRAME * 21 + 517        # partial tail frame + partial K-batch at EOS


def _stateful_data():
    rng = np.random.default_rng(7)
    return (rng.standard_normal(_N) + 1j * rng.standard_normal(_N)) \
        .astype(np.complex64)


def _stateful_stages():
    """FIR history + rotator phase: both carries must survive a restart for
    bit-equality to hold — exactly the state a fresh re-init forfeits."""
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage, rotator_stage
    taps = firdes.lowpass(0.2, 31).astype(np.float32)
    return [fir_stage(taps, fft_len=256), rotator_stage(0.05)]


def _run_stateful(data, fault=None, restart=False, k=1, ck=None,
                  max_faults=1, wire=None):
    """One VectorSource → TpuKernel(FIR→rotator) → VectorSink run; ``fault``
    = (site, rate, seed) armed NON-transient (h2d/d2h included — the fatal
    class is what exercises restart, the transient class only the retry
    plane). Returns (output, restarts)."""
    from futuresdr_tpu.runtime import faults
    from futuresdr_tpu.tpu import TpuKernel
    fg = Flowgraph()
    tk = TpuKernel(_stateful_stages(), np.complex64, frame_size=_FRAME,
                   frames_in_flight=2, frames_per_dispatch=k,
                   checkpoint_every=ck, wire=wire)
    if restart:
        tk.policy = BlockPolicy(on_error="restart", max_restarts=4,
                                backoff=0.002)
    snk = VectorSink(np.complex64)
    fg.connect(VectorSource(data), tk, snk)
    name = fg.wrapped(tk).instance_name
    plan = faults.reset()
    if fault:
        site, rate, seed = fault
        plan.arm(f"{site}:{name}" if site == "dispatch" else site,
                 rate=rate, max_faults=max_faults, seed=seed,
                 transient=False)
    try:
        Runtime().run(fg, timeout=60.0)
    finally:
        faults.reset()
    return np.asarray(snk.items()), fg.wrapped(tk).restarts


def _replayed() -> float:
    from futuresdr_tpu.tpu.kernel_block import _REPLAYED
    return sum(v for _, v in _REPLAYED.samples())


def _forfeited() -> float:
    from futuresdr_tpu.tpu.kernel_block import _FORFEITED
    return sum(v for _, v in _FORFEITED.samples())


def test_stateful_restart_replay_dispatch_fault():
    """Acceptance: a carry-bearing device chain with `restart` policy and a
    seeded dispatch fault injected MID-STREAM produces output bit-identical
    to the fault-free run — the checkpoint restore + replay path, billed on
    fsdr_frames_replayed_total."""
    data = _stateful_data()
    exp, r0 = _run_stateful(data)
    assert r0 == 0
    before = _replayed()
    got, r = _run_stateful(data, fault=("dispatch", 0.12, 9), restart=True)
    assert r == 1
    assert _replayed() - before > 0
    np.testing.assert_array_equal(got, exp)


def test_stateful_restart_replay_transfer_faults():
    """Fatal (non-transient) h2d/d2h failures mid-stream recover bit-correct
    too — including a second fault landing DURING recovery (it consumes
    another restart attempt and the retried recovery completes)."""
    data = _stateful_data()
    exp, _ = _run_stateful(data)
    for site, rate, seed, mf in (("h2d", 0.08, 4, 1), ("h2d", 0.05, 11, 2),
                                 ("d2h", 0.03, 2, 2)):
        got, r = _run_stateful(data, fault=(site, rate, seed), restart=True,
                               max_faults=mf)
        assert r >= 1, (site, seed)
        np.testing.assert_array_equal(got, exp, err_msg=f"{site}@{seed}")


def test_stateful_restart_replay_megabatch():
    """Megabatch K=4 replay respects partial-batch semantics: the log
    retains the exact zero-padded scan payload, so the partial EOS group
    replays bit-identical (compared against the fault-free K=4 run — the
    scan program's own rounding differs from K=1's by contract)."""
    data = _stateful_data()
    exp, _ = _run_stateful(data, k=4)
    got, r = _run_stateful(data, fault=("dispatch", 0.3, 5), restart=True,
                           k=4)
    assert r == 1
    np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("k", [1, 4])
def test_restart_replay_reships_the_packed_words(k, monkeypatch):
    """The coalesced sc16 uplink under a `restart` policy: a group the
    recovery re-stages from the replay log crosses as the SAME uint32 words,
    dtype and bytes, as its first attempt (the program's one input is words,
    ISSUE 32), and the output is bit-identical to the fault-free run."""
    from _ship_log import ShipLog
    data = _stateful_data()
    exp, _ = _run_stateful(data, k=k, wire="sc16")
    log = ShipLog(monkeypatch)
    before = _replayed()
    got, r = _run_stateful(data, fault=("dispatch", 0.3 if k > 1 else 0.12,
                                        5 if k > 1 else 9),
                           restart=True, k=k, wire="sc16")
    assert r == 1
    assert _replayed() - before > 0
    np.testing.assert_array_equal(got, exp)
    assert log.assert_reships_identical(np.uint32) >= 1


def test_sparse_checkpoint_cadence_replays_bit_correct():
    """checkpoint_every=3: longer replay window, same bit-equality."""
    data = _stateful_data()
    exp, _ = _run_stateful(data)
    got, r = _run_stateful(data, fault=("dispatch", 0.12, 9), restart=True,
                           ck=3)
    assert r == 1
    np.testing.assert_array_equal(got, exp)


def test_checkpoint_off_forfeits_and_bills():
    """checkpoint_every=0: recover() declines, the fresh re-init forfeits the
    in-flight window (billed on fsdr_frames_forfeited_total) and the run
    completes with the gap — the pre-recovery behavior, now accounted."""
    data = _stateful_data()
    exp, _ = _run_stateful(data)
    before = _forfeited()
    got, r = _run_stateful(data, fault=("dispatch", 0.12, 9), restart=True,
                           ck=0)
    assert r == 1
    assert _forfeited() - before > 0
    assert len(got) < len(exp)            # frames really were dropped


def test_carry_fault_falls_back_to_previous_checkpoint():
    """Satellite: the `carry` site corrupts checkpoint candidates; the
    restore path's integrity check (tree/shape/dtype) must reject them and
    fall back — output stays bit-identical."""
    from futuresdr_tpu.runtime import faults
    from futuresdr_tpu.tpu import TpuKernel
    data = _stateful_data()
    exp, _ = _run_stateful(data)
    fg = Flowgraph()
    tk = TpuKernel(_stateful_stages(), np.complex64, frame_size=_FRAME,
                   frames_in_flight=2)
    tk.policy = BlockPolicy(on_error="restart", max_restarts=4,
                            backoff=0.002)
    snk = VectorSink(np.complex64)
    fg.connect(VectorSource(data), tk, snk)
    name = fg.wrapped(tk).instance_name
    plan = faults.reset()
    carry_inj = plan.arm("carry", rate=0.3, max_faults=2, seed=3)
    plan.arm(f"dispatch:{name}", rate=0.10, max_faults=1, seed=9,
             transient=False)
    try:
        Runtime().run(fg, timeout=60.0)
    finally:
        faults.reset()
    assert carry_inj.fired >= 1, "the carry corruption never fired"
    assert fg.wrapped(tk).restarts == 1
    np.testing.assert_array_equal(np.asarray(snk.items()), exp)


def test_fused_devchain_restart_replay():
    """Acceptance: the FUSED devchain path recovers bit-identically too —
    a restart-policy member fuses, the drive loop restarts the fused kernel
    from its composed-carry checkpoint, and the supervisor records the
    restart decision under the member's name."""
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage, rotator_stage
    from futuresdr_tpu.runtime import faults
    from futuresdr_tpu.tpu import TpuKernel
    data = _stateful_data()
    taps = firdes.lowpass(0.2, 31).astype(np.float32)

    def run(fault):
        fg = Flowgraph()
        k1 = TpuKernel([fir_stage(taps, fft_len=256)], np.complex64,
                       frame_size=_FRAME, frames_in_flight=2)
        k2 = TpuKernel([rotator_stage(0.05)], np.complex64,
                       frame_size=_FRAME, frames_in_flight=2)
        k2.policy = BlockPolicy(on_error="restart", max_restarts=4,
                                backoff=0.002)
        snk = VectorSink(np.complex64)
        fg.connect(VectorSource(data), k1, k2, snk)
        plan = faults.reset()
        if fault:
            plan.arm("dispatch", rate=0.12, max_faults=1, seed=5,
                     transient=False)
        try:
            Runtime().run(fg, timeout=60.0)
        finally:
            faults.reset()
        wk2 = fg.wrapped(k2)
        return (np.asarray(snk.items()), wk2.restarts,
                bool(wk2.metrics().get("fused_devchain")),
                fg.describe().to_json())

    exp, _, fused0, _ = run(fault=False)
    assert fused0, "restart-policy member should fuse"
    got, restarts, fused1, desc = run(fault=True)
    assert fused1 and restarts == 1
    np.testing.assert_array_equal(got, exp)
    acts = [d for d in desc["policy_decisions"] if d["action"] == "restart"]
    assert len(acts) == 1 and acts[0]["phase"] == "work"


def test_fanout_fused_restart_replay():
    """Acceptance: a fused fan-out region (TpuFanoutKernel, FLAT composed
    carry) recovers bit-identically on EVERY branch."""
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage, mag2_stage, rotator_stage
    from futuresdr_tpu.runtime import faults
    from futuresdr_tpu.tpu import TpuKernel
    taps = firdes.lowpass(0.2, 31).astype(np.float32)
    n = _FRAME * 13 + 300
    rng = np.random.default_rng(3)
    data = (rng.standard_normal(n) + 1j * rng.standard_normal(n)) \
        .astype(np.complex64)

    def run(fault):
        fg = Flowgraph()
        prod = TpuKernel([fir_stage(taps, fft_len=256)], np.complex64,
                         frame_size=_FRAME, frames_in_flight=2)
        prod.policy = BlockPolicy(on_error="restart", max_restarts=4,
                                  backoff=0.002)
        b1 = TpuKernel([rotator_stage(0.05)], np.complex64,
                       frame_size=_FRAME, frames_in_flight=2)
        b2 = TpuKernel([mag2_stage()], np.complex64, frame_size=_FRAME,
                       frames_in_flight=2)
        s1, s2 = VectorSink(np.complex64), VectorSink(np.float32)
        src = VectorSource(data)
        fg.connect(src, prod)
        fg.connect(prod, b1, s1)
        fg.connect(prod, b2, s2)
        plan = faults.reset()
        if fault:
            plan.arm("dispatch", rate=0.15, max_faults=1, seed=6,
                     transient=False)
        try:
            Runtime().run(fg, timeout=60.0)
        finally:
            faults.reset()
        wp = fg.wrapped(prod)
        return (np.asarray(s1.items()), np.asarray(s2.items()),
                wp.restarts, bool(wp.metrics().get("fused_devchain")))

    e1, e2, _, fused0 = run(fault=False)
    assert fused0
    g1, g2, restarts, fused1 = run(fault=True)
    assert fused1 and restarts == 1
    np.testing.assert_array_equal(g1, e1)
    np.testing.assert_array_equal(g2, e2)


# ---------------------------------------------------------------------------
# isolate groups: retire a subgraph, not just one block (ISSUE 8 tentpole)
# ---------------------------------------------------------------------------

def test_isolate_group_retires_whole_subgraph():
    """Acceptance: one member of a named 3-block group dies → the whole
    group retires (topo-order EOS), the sibling branch finishes bit-correct,
    and policy_decisions carries ONE isolate_group verdict naming the group
    and every member."""
    from futuresdr_tpu.runtime import faults
    data = np.arange(100_000, dtype=np.float32)
    fg = Flowgraph()
    snk_a = VectorSink(np.float32)
    fg.connect(VectorSource(data), Copy(np.float32), snk_a)
    g1, g2, g3 = (Copy(np.float32) for _ in range(3))
    for g in (g1, g2, g3):
        g.policy = BlockPolicy(isolate_group="rx-branch")
    snk_b = VectorSink(np.float32)
    fg.connect(VectorSource(np.zeros(200_000, np.float32)), g1, g2, g3,
               snk_b)
    name = fg.wrapped(g2).instance_name
    members = [fg.wrapped(g).instance_name for g in (g1, g2, g3)]
    faults.reset().arm(f"work:{name}", rate=1.0, max_faults=1, seed=5)
    try:
        with pytest.raises(FlowgraphError) as ei:
            Runtime().run(fg, timeout=30.0)
    finally:
        faults.reset()
    e = ei.value
    np.testing.assert_array_equal(np.asarray(snk_a.items()), data)
    dec = [d for d in e.policy_decisions if d["action"] == "isolate_group"]
    assert len(dec) == 1, e.policy_decisions
    assert dec[0]["group"] == "rx-branch"
    assert dec[0]["block"] == name
    assert dec[0]["members"] == members   # topological order
    assert e.blocks == [name]
    # the description surface carries the group per block
    desc = fg.describe().to_json()
    grouped = [b["instance_name"] for b in desc["blocks"]
               if b.get("isolate_group") == "rx-branch"]
    assert sorted(grouped) == sorted(members)


def test_isolate_group_from_config(monkeypatch):
    """config `block_isolate_groups = "name=group;…"` assigns groups to
    blocks with no own policy — same retirement semantics."""
    from futuresdr_tpu.runtime import faults
    data = np.arange(60_000, dtype=np.float32)
    fg = Flowgraph()
    snk_a = VectorSink(np.float32)
    fg.connect(VectorSource(data), Copy(np.float32), snk_a)
    b1, b2 = Copy(np.float32), Copy(np.float32)
    snk_b = VectorSink(np.float32)
    fg.connect(VectorSource(np.zeros(80_000, np.float32)), b1, b2, snk_b)
    n1 = fg.wrapped(b1).instance_name
    n2 = fg.wrapped(b2).instance_name
    monkeypatch.setattr(config(), "block_isolate_groups",
                        f"{n1}=grp;{n2}=grp")
    faults.reset().arm(f"work:{n1}", rate=1.0, max_faults=1, seed=5)
    try:
        with pytest.raises(FlowgraphError) as ei:
            Runtime().run(fg, timeout=30.0)
    finally:
        faults.reset()
    np.testing.assert_array_equal(np.asarray(snk_a.items()), data)
    dec = [d for d in ei.value.policy_decisions
           if d["action"] == "isolate_group"]
    assert dec and dec[0]["group"] == "grp"
    assert set(dec[0]["members"]) == {n1, n2}


def test_isolate_group_covers_init_failures():
    """A group member failing INIT retires the whole group during the
    barrier; the sibling branch still finishes."""
    data = np.arange(50_000, dtype=np.float32)
    fg = Flowgraph()
    snk_a = VectorSink(np.float32)
    fg.connect(VectorSource(data), Copy(np.float32), snk_a)
    bad = FlakyInit(np.float32, fail_times=99)
    tail = Copy(np.float32)
    for b in (bad, tail):
        b.policy = BlockPolicy(isolate_group="dead-branch")
    snk_b = VectorSink(np.float32)
    fg.connect(VectorSource(np.zeros(1000, np.float32)), bad, tail, snk_b)
    with pytest.raises(FlowgraphError) as ei:
        Runtime().run(fg, timeout=30.0)
    np.testing.assert_array_equal(np.asarray(snk_a.items()), data)
    dec = [d for d in ei.value.policy_decisions
           if d["action"] == "isolate_group"]
    assert len(dec) == 1 and dec[0]["group"] == "dead-branch"


def test_isolate_group_policy_validation():
    assert BlockPolicy(isolate_group="x").on_error == "isolate"
    assert BlockPolicy(on_error="isolate", isolate_group="x") \
        .isolate_group == "x"
    with pytest.raises(ValueError):
        BlockPolicy(on_error="restart", isolate_group="x")


# ---------------------------------------------------------------------------
# host staging arena × device-plane recovery (ISSUE 10 satellite): recycling
# under memory pressure must never alias a buffer fault recovery re-ships
# ---------------------------------------------------------------------------


def test_arena_recycling_under_recovery_bit_identical(monkeypatch):
    """Seeded h2d/d2h/dispatch faults while the staging arena recycles under
    MEMORY PRESSURE (a tiny pool cap keeps every released buffer in
    immediate circulation): replayed output
    is bit-identical to the fault-free run — a buffer the replay log pins is
    never recycled into a newer frame (ops/arena.py pinning contract)."""
    from futuresdr_tpu.config import config
    from futuresdr_tpu.ops import arena as arena_mod
    c = config()
    monkeypatch.setattr(c, "host_arena_mb", 1)
    arena_mod.reset_arena()
    try:
        data = _stateful_data()
        exp, _ = _run_stateful(data)
        for site, rate, seed, mf in (("dispatch", 0.12, 9, 1),
                                     ("h2d", 0.08, 4, 1),
                                     ("d2h", 0.03, 2, 2)):
            got, r = _run_stateful(data, fault=(site, rate, seed),
                                   restart=True, max_faults=mf)
            assert r >= 1, (site, seed)
            np.testing.assert_array_equal(got, exp, err_msg=f"{site}@{seed}")
        # K=4 megabatch under the same pressure: the STACKED arena-backed
        # parts (incl. the zero-padded EOS group) replay bit-identical
        exp4, _ = _run_stateful(data, k=4)
        got4, r = _run_stateful(data, fault=("dispatch", 0.3, 5),
                                restart=True, k=4)
        assert r == 1
        np.testing.assert_array_equal(got4, exp4)
    finally:
        arena_mod.reset_arena()
