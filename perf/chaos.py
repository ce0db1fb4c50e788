#!/usr/bin/env python
"""perf/chaos — seeded chaos campaign for the fault-tolerant runtime (ISSUE 6).

Injects faults at every documented site (``runtime/faults.py``: work,
dispatch, h2d, d2h, link) into small flowgraphs under every failure policy
(``BlockPolicy``: fail_fast, restart, isolate) and asserts the core
robustness invariants on EVERY run:

  I1  **no hang**: every run completes or errors within its deadline
      (``Runtime.run(timeout=)`` — the deadline path itself is under test);
  I2  **correct or honest**: the output is bit-correct, OR the run raised a
      structured ``FlowgraphError`` naming the faulted block/site;
  I3  **no leaked threads**: after teardown (plus gc for the scheduler
      finalizers), every non-daemon thread spawned by the trial is gone;
  I4  **state drained**: the flowgraph is restored (blocks readable), every
      block's metrics() answers, and no input ring still holds data unless
      the run errored.

Scenario × policy compatibility (docs/robustness.md policy matrix): host
blocks pair restart with work faults (fire before ``work()`` consumes input —
bit-correct by construction); transfer faults (h2d/d2h/link) ride the retry
plane (bit-correct by idempotent re-encode); device-plane ``dispatch`` faults
pair with fail_fast (honest structured error) OR, since the device-plane
recovery PR, with restart — the kernel's carry checkpoint/replay restores
the last committed checkpoint and replays the in-flight window from host
staging copies, so the recovered output is bit-identical too.

``--smoke`` (the check.sh gate) runs the named scenarios — including
``stateful-restart-replay`` (a carry-bearing device chain with a mid-stream
dispatch fault recovers BIT-IDENTICAL to the fault-free run via carry
checkpoint/replay, docs/robustness.md "Device-plane recovery") and
``isolate-group`` (one member's death retires the whole named subgraph while
the sibling branch finishes) — plus a short randomized campaign at a fixed
seed on the CPU backend.  ``--trials N --seed S`` runs a longer randomized
campaign.  Exit code 0 = every invariant held.
"""

import argparse
import gc
import os
import random
import sys
import threading
import time

sys.path.insert(0, ".")
sys.path.insert(0, "..")

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the campaign must neither read nor pollute the user-level autotune store,
# and fusion passes would bypass the per-block injection sites
os.environ.setdefault("FUTURESDR_TPU_AUTOTUNE_CACHE_DIR", "off")
os.environ.setdefault("FSDR_NO_FASTCHAIN", "1")

import numpy as np

DEADLINE_S = 30.0          # per-trial run deadline (I1); generous for CI boxes
GRACE_S = 5.0


# ---------------------------------------------------------------------------
# invariant helpers
# ---------------------------------------------------------------------------

def _threads_now():
    return set(threading.enumerate())


def _assert_no_leaked_threads(before, label):
    """I3: poll (with gc for the dropped-scheduler finalizers) until every
    trial-spawned non-daemon thread is gone."""
    deadline = time.monotonic() + 10.0
    while True:
        gc.collect()
        leaked = [t for t in threading.enumerate()
                  if t not in before and t.is_alive() and not t.daemon
                  and not t.name.startswith("fsdr-codec")]
        if not leaked:
            return
        if time.monotonic() > deadline:
            raise AssertionError(
                f"[{label}] I3 violated — leaked threads: "
                f"{sorted(t.name for t in leaked)}")
        time.sleep(0.05)


def _assert_state_drained(fg, label, errored):
    """I4: blocks restored + metrics readable; healthy runs leave no input
    ring occupied."""
    for i in range(len(fg)):
        wk = fg.wrapped(i)                      # raises if not restored
        m = wk.metrics()
        assert isinstance(m, dict) and "work_calls" in m, (label, m)
        if not errored:
            for port, fill in (m.get("buffer_fill") or {}).items():
                assert fill == 0.0, \
                    f"[{label}] I4 violated — {wk.instance_name}.{port} " \
                    f"still holds data (fill={fill})"


def _journal_since() -> int:
    """Cursor into the lifecycle journal (telemetry/journal.py) taken at
    scenario start — `_journal_story` reads forward from it."""
    from futuresdr_tpu.telemetry import journal as _tj
    return _tj.journal().seq


def _journal_story(since, *expected, label=""):
    """I5 (frame-lineage plane): the journal must TELL THE STORY — every
    ``(cat, event)`` pair in ``expected`` appears after cursor ``since``,
    in that seq order (other events may interleave), and the seqs are
    strictly increasing (the REST cursor contract)."""
    from futuresdr_tpu.telemetry import journal as _tj
    evs = _tj.journal().events(since=since)["events"]
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(set(seqs)), \
        f"[{label}] I5 violated — journal seqs not strictly increasing: " \
        f"{seqs}"
    keys = [(e["cat"], e["event"]) for e in evs]
    i = 0
    for want in expected:
        while i < len(keys) and keys[i] != want:
            i += 1
        assert i < len(keys), \
            f"[{label}] I5 violated — journal missing {want} (in order) " \
            f"after seq {since}; recorded: {keys}"
        i += 1
    return evs


def _run_trial(build, label, expect=None):
    """Build → run under deadline → assert I1..I4.

    ``build()`` returns ``(fg, check)`` where ``check(error)`` asserts the
    scenario-specific I2 outcome (bit-correct output or a structured error
    naming the fault). ``expect`` ("error"/"ok"/None=either) guards the
    run-level outcome."""
    from futuresdr_tpu import FlowgraphCancelled, FlowgraphError, Runtime
    from futuresdr_tpu.config import config
    before = _threads_now()
    config().run_timeout_grace = GRACE_S
    fg, check = build()
    t0 = time.perf_counter()
    error = None
    try:
        Runtime().run(fg, timeout=DEADLINE_S)
    except FlowgraphError as e:
        error = e
    elapsed = time.perf_counter() - t0
    assert elapsed < DEADLINE_S + GRACE_S + 5.0, \
        f"[{label}] I1 violated — run took {elapsed:.1f}s"
    if error is not None:
        # only the RUN deadline counts as a hang — a transfer-plane
        # TransferError("... deadline exhausted") is a legitimate I2 outcome
        hung = any(isinstance(x, FlowgraphCancelled) and
                   "run deadline" in str(x) for x in error.errors)
        assert not hung, f"[{label}] I1 violated — run hit its deadline: " \
                         f"{error}"
    if expect == "error":
        assert error is not None, f"[{label}] expected a FlowgraphError"
    elif expect == "ok":
        assert error is None, f"[{label}] unexpected error: {error!r}"
    check(error)
    _assert_state_drained(fg, label, errored=error is not None)
    _assert_no_leaked_threads(before, label)
    return error


# ---------------------------------------------------------------------------
# named scenarios (the check.sh smoke gate)
# ---------------------------------------------------------------------------

def scenario_fail_fast_baseline():
    """No policy set anywhere: today's fail-fast cascade, byte-for-byte — the
    structured error still names the faulted block and the partial output is
    a prefix of the expected stream."""
    from futuresdr_tpu import Flowgraph
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.runtime import faults
    data = np.arange(100_000, dtype=np.float32)

    def build():
        from futuresdr_tpu.blocks import Copy
        fg = Flowgraph()
        src = VectorSource(data)
        cp = Copy(np.float32)
        snk = VectorSink(np.float32)
        fg.connect(src, cp, snk)
        name = fg.wrapped(cp).instance_name
        faults.reset().arm(f"work:{name}", rate=1.0, max_faults=1, seed=11)

        def check(error):
            assert error is not None
            assert error.blocks == [name], (error.blocks, name)
            assert [d["action"] for d in error.policy_decisions] == \
                ["fail_fast"]
            got = np.asarray(snk.items())
            np.testing.assert_array_equal(got, data[:len(got)])
        return fg, check

    try:
        _run_trial(build, "fail_fast_baseline", expect="error")
    finally:
        faults.reset()


def scenario_restart_recovers():
    """Acceptance: `restart` + a transient single work fault → bit-correct
    output, one billed restart, no graph teardown."""
    from futuresdr_tpu import BlockPolicy, Flowgraph
    from futuresdr_tpu.blocks import Copy, VectorSink, VectorSource
    from futuresdr_tpu.runtime import faults
    data = np.arange(150_000, dtype=np.float32)
    state = {}

    def build():
        fg = Flowgraph()
        src = VectorSource(data)
        cp = Copy(np.float32)
        cp.policy = BlockPolicy(on_error="restart", max_restarts=3,
                                backoff=0.002)
        snk = VectorSink(np.float32)
        fg.connect(src, cp, snk)
        name = fg.wrapped(cp).instance_name
        faults.reset().arm(f"work:{name}", rate=1.0, max_faults=1, seed=23)
        state["fg"], state["cp"] = fg, cp

        def check(error):
            assert error is None, repr(error)
            np.testing.assert_array_equal(np.asarray(snk.items()), data)
            assert fg.wrapped(cp).restarts == 1
        return fg, check

    try:
        _run_trial(build, "restart_recovers", expect="ok")
    finally:
        faults.reset()


def scenario_isolate_branches():
    """Acceptance: `isolate` retires the faulted branch; the independent
    branch finishes bit-correct; the error names the isolated block."""
    from futuresdr_tpu import BlockPolicy, Flowgraph
    from futuresdr_tpu.blocks import Copy, VectorSink, VectorSource
    from futuresdr_tpu.runtime import faults
    data = np.arange(120_000, dtype=np.float32)

    def build():
        fg = Flowgraph()
        snk_a = VectorSink(np.float32)
        fg.connect(VectorSource(data), Copy(np.float32), snk_a)
        bad = Copy(np.float32)
        bad.policy = BlockPolicy(on_error="isolate")
        snk_b = VectorSink(np.float32)
        fg.connect(VectorSource(np.zeros(60_000, np.float32)), bad, snk_b)
        name = fg.wrapped(bad).instance_name
        faults.reset().arm(f"work:{name}", rate=1.0, max_faults=1, seed=31)

        def check(error):
            assert error is not None
            assert error.blocks == [name]
            assert [d["action"] for d in error.policy_decisions] == \
                ["isolate"]
            np.testing.assert_array_equal(np.asarray(snk_a.items()), data)
        return fg, check

    try:
        _run_trial(build, "isolate_branches", expect="error")
    finally:
        faults.reset()


def scenario_transfer_retry_deterministic():
    """Acceptance: seeded fake-link faults on the TPU chain — retries recover
    to output bit-identical to the unfaulted run, and the same seed bills the
    same retry count twice."""
    from futuresdr_tpu import Flowgraph
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.ops import mag2_stage, xfer
    from futuresdr_tpu.tpu import TpuKernel
    n, frame = 1 << 16, 1 << 13
    tone = np.exp(2j * np.pi * 0.1 * np.arange(n)).astype(np.complex64)
    expected = (tone.real ** 2 + tone.imag ** 2).astype(np.float32)

    def retries():
        return xfer._RETRIES.get(direction="h2d") + \
            xfer._RETRIES.get(direction="d2h")

    def one_run(seed):
        from futuresdr_tpu.config import config
        config().xfer_backoff = 0.0005
        xfer.set_fake_link(fault_rate=0.35, fault_seed=seed)

        def build():
            fg = Flowgraph()
            snk = VectorSink(np.float32)
            fg.connect(VectorSource(tone),
                       TpuKernel([mag2_stage()], np.complex64,
                                 frame_size=frame, frames_in_flight=2),
                       snk)

            def check(error):
                assert error is None, repr(error)
                got = np.asarray(snk.items())
                np.testing.assert_allclose(got, expected, rtol=1e-5)
                one_run.last = got
            return fg, check

        before = retries()
        _run_trial(build, f"transfer_retry(seed={seed})", expect="ok")
        return retries() - before, one_run.last

    try:
        d1, out1 = one_run(seed=5)
        d2, out2 = one_run(seed=5)
        assert d1 == d2 and d1 > 0, \
            f"retry count not deterministic: {d1} vs {d2}"
        np.testing.assert_array_equal(out1, out2)
    finally:
        xfer.set_fake_link()


def scenario_stateful_restart_replay():
    """Acceptance (device-plane recovery): a CARRY-BEARING device chain
    (FIR history + rotator phase) with `restart` policy and a seeded
    mid-stream `dispatch` fault produces output BIT-IDENTICAL to the
    fault-free run — the checkpoint/replay contract, not the old
    forfeit-in-flight behavior."""
    from futuresdr_tpu import BlockPolicy, Flowgraph
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage, rotator_stage
    from futuresdr_tpu.runtime import faults
    from futuresdr_tpu.tpu import TpuKernel
    frame = 1 << 11
    n = frame * 21 + 517                 # partial tail frame too
    rng = np.random.default_rng(7)
    data = (rng.standard_normal(n) + 1j * rng.standard_normal(n)) \
        .astype(np.complex64)
    taps = firdes.lowpass(0.2, 31).astype(np.float32)

    def one_run(fault: bool):
        out = {}

        def build():
            fg = Flowgraph()
            tk = TpuKernel([fir_stage(taps, fft_len=256),
                            rotator_stage(0.05)], np.complex64,
                           frame_size=frame, frames_in_flight=2)
            tk.policy = BlockPolicy(on_error="restart", max_restarts=3,
                                    backoff=0.002)
            snk = VectorSink(np.complex64)
            fg.connect(VectorSource(data), tk, snk)
            name = fg.wrapped(tk).instance_name
            plan = faults.reset()
            if fault:
                # rate 0.12 @ seed 9 fires MID-STREAM (a committed
                # checkpoint exists, frames are in flight)
                plan.arm(f"dispatch:{name}", rate=0.12, max_faults=1,
                         seed=9, transient=False)

            def check(error):
                assert error is None, repr(error)
                out["got"] = np.asarray(snk.items())
                out["restarts"] = fg.wrapped(tk).restarts
            return fg, check

        try:
            _run_trial(build, f"stateful_restart_replay(fault={fault})",
                       expect="ok")
        finally:
            faults.reset()
        return out

    clean = one_run(fault=False)
    since = _journal_since()
    faulted = one_run(fault=True)
    assert faulted["restarts"] >= 1, "the dispatch fault did not fire"
    np.testing.assert_array_equal(faulted["got"], clean["got"])
    # the journal tells the story: a checkpoint was committed BEFORE the
    # fault, and the kernel recovered from it (telemetry/journal.py)
    _journal_story(since, ("kernel", "checkpoint-commit"),
                   ("kernel", "recover"),
                   label="stateful_restart_replay")


def scenario_arena_recycle_replay():
    """Acceptance (host staging arena × device-plane recovery): with the
    arena recycling under MEMORY PRESSURE (a tiny pool cap forces every
    released buffer back into circulation immediately), seeded mid-stream faults at the dispatch AND h2d sites
    recover BIT-IDENTICAL to the fault-free run — recycling must never alias
    a staging buffer the replay log still pins (the retry-safe pinning
    contract of ops/arena.py)."""
    from futuresdr_tpu import BlockPolicy, Flowgraph
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.config import config
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import arena as arena_mod
    from futuresdr_tpu.ops import fir_stage, rotator_stage
    from futuresdr_tpu.runtime import faults
    from futuresdr_tpu.tpu import TpuKernel
    frame = 1 << 11
    n = frame * 23 + 311                 # partial tail frame too
    rng = np.random.default_rng(11)
    data = (rng.standard_normal(n) + 1j * rng.standard_normal(n)) \
        .astype(np.complex64)
    taps = firdes.lowpass(0.2, 31).astype(np.float32)
    c = config()
    saved = c.host_arena_mb
    c.host_arena_mb = 1
    arena_mod.reset_arena()

    def one_run(fault):
        out = {}

        def build():
            fg = Flowgraph()
            tk = TpuKernel([fir_stage(taps, fft_len=256),
                            rotator_stage(0.05)], np.complex64,
                           frame_size=frame, frames_in_flight=2)
            tk.policy = BlockPolicy(on_error="restart", max_restarts=4,
                                    backoff=0.002)
            snk = VectorSink(np.complex64)
            fg.connect(VectorSource(data), tk, snk)
            plan = faults.reset()
            if fault:
                site, rate, seed = fault
                plan.arm(site, rate=rate, max_faults=2, seed=seed,
                         transient=False)

            def check(error):
                assert error is None, repr(error)
                out["got"] = np.asarray(snk.items())
            return fg, check

        try:
            _run_trial(build, f"arena_recycle_replay(fault={fault})",
                       expect="ok")
        finally:
            faults.reset()
        return out["got"]

    try:
        clean = one_run(None)
        for fault in (("dispatch", 0.10, 9), ("h2d", 0.06, 4)):
            got = one_run(fault)
            np.testing.assert_array_equal(got, clean)
    finally:
        c.host_arena_mb = saved
        arena_mod.reset_arena()


def scenario_adaptive_wire_switch():
    """Acceptance (mid-stream adaptive wire switching, ISSUE 18): the
    signal's crest factor collapses mid-stream → the armed controller's
    predicted quantization SNR falls under budget → the wire WIDENS
    (sc8 → sc16) at a quiescent dispatch boundary — and a fault-injected
    recovery straddling the switch replays bit-identically to the clean
    adaptive run (the wire-switch log restores the format timeline exactly
    like the retune log)."""
    import asyncio

    from futuresdr_tpu import Mocker
    from futuresdr_tpu.config import config
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage, rotator_stage
    from futuresdr_tpu.tpu import TpuKernel

    frame = 1 << 11
    taps = firdes.lowpass(0.2, 31).astype(np.float32)
    rng = np.random.default_rng(17)
    # phase 1: well-conditioned (sc8 SNR clears the 40 dB budget) — then
    # the crest factor collapses: one full-scale spike over a quiet floor
    # per frame drags the predicted sc8 SNR far under budget
    good = (0.5 * (rng.standard_normal(frame * 8)
                   + 1j * rng.standard_normal(frame * 8))
            ).astype(np.complex64)
    bad = np.full(frame * 40, 1e-4 + 0j, np.complex64)
    bad[frame // 2::frame] = 1.0 + 0j
    tail = (0.5 * (rng.standard_normal(frame * 6)
                   + 1j * rng.standard_normal(frame * 6))
            ).astype(np.complex64)

    c = config()
    saved = c.tpu_adaptive_wire
    c.tpu_adaptive_wire = True

    def one_run(fault_after_switch):
        mk = TpuKernel([fir_stage(taps, fft_len=256),
                        rotator_stage(0.05)], np.complex64,
                       frame_size=frame, frames_in_flight=2, wire="sc8",
                       checkpoint_every=2)
        assert mk._wirectl is not None, "controller failed to arm"
        m = Mocker(mk)
        m.init_output("out", (len(good) + len(bad) + len(tail)) * 2)
        m.init()
        m.input("in", good)
        m.run()
        assert mk.wire.name == "sc8", "no switch on healthy signal"
        m.input("in", bad)
        m.run()
        assert mk.wire.name == "sc16", \
            f"SNR drop did not widen the wire (still {mk.wire.name})"
        assert mk.extra_metrics()["wire_switches"] >= 1
        if fault_after_switch:
            assert asyncio.run(
                mk.recover(RuntimeError("injected chaos fault")))
            assert mk.wire.name == "sc16", "recovery lost the switch"
        m.input("in", tail)
        m.run()
        return m.output("out").copy()

    try:
        clean = one_run(fault_after_switch=False)
        faulted = one_run(fault_after_switch=True)
        np.testing.assert_array_equal(faulted, clean)
    finally:
        c.tpu_adaptive_wire = saved
    print("  adaptive_wire_switch: widened sc8->sc16 under SNR drop, "
          "bit-exact through recovery")


def scenario_isolate_group():
    """Acceptance (isolate groups): one member of a named 3-block subgraph
    dies → the WHOLE group retires (topo-order port EOS, clean drain), the
    sibling branch finishes bit-correct, and the structured error carries
    the group verdict naming every member."""
    from futuresdr_tpu import BlockPolicy, Flowgraph
    from futuresdr_tpu.blocks import Copy, VectorSink, VectorSource
    from futuresdr_tpu.runtime import faults
    data = np.arange(120_000, dtype=np.float32)

    def build():
        fg = Flowgraph()
        snk_a = VectorSink(np.float32)
        fg.connect(VectorSource(data), Copy(np.float32), snk_a)
        g1, g2, g3 = (Copy(np.float32) for _ in range(3))
        for g in (g1, g2, g3):
            g.policy = BlockPolicy(isolate_group="rx-branch")
        snk_b = VectorSink(np.float32)
        fg.connect(VectorSource(np.zeros(200_000, np.float32)),
                   g1, g2, g3, snk_b)
        name = fg.wrapped(g2).instance_name
        members = [fg.wrapped(g).instance_name for g in (g1, g2, g3)]
        faults.reset().arm(f"work:{name}", rate=1.0, max_faults=1, seed=5)

        def check(error):
            assert error is not None
            np.testing.assert_array_equal(np.asarray(snk_a.items()), data)
            dec = [d for d in error.policy_decisions
                   if d["action"] == "isolate_group"]
            assert len(dec) == 1, error.policy_decisions
            assert dec[0]["group"] == "rx-branch"
            assert dec[0]["block"] == name
            assert dec[0]["members"] == members
        return fg, check

    try:
        _run_trial(build, "isolate_group", expect="error")
    finally:
        faults.reset()


def scenario_tenant_isolation():
    """Acceptance (multi-tenant serving, docs/serving.md): one session's
    injected work/dispatch fault retires ONLY that session's slot — sibling
    sessions keep dispatching and their outputs stay BIT-IDENTICAL to a
    fault-free run, the batch itself never fails, and the retired session
    carries the structured error in its doctor view."""
    from futuresdr_tpu.ops.stages import Pipeline, fir_stage, rotator_stage
    from futuresdr_tpu.runtime import faults
    from futuresdr_tpu.serve import ServeEngine

    taps = np.hanning(21).astype(np.float32)
    pipe = Pipeline([fir_stage(taps, fft_len=128), rotator_stage(0.02)],
                    np.complex64)
    rng = np.random.default_rng(11)
    frames = {sid: [(rng.standard_normal(512) + 1j
                     * rng.standard_normal(512)).astype(np.complex64)
                    for _ in range(5)]
              for sid in ("csa", "csb", "csc")}

    def one_run():
        eng = ServeEngine(pipe, frame_size=512, app="chaos_serve",
                          buckets=(4,), queue_frames=8)
        for sid, tenant in (("csa", "t0"), ("csb", "t1"), ("csc", "t1")):
            eng.admit(tenant=tenant, sid=sid)
        outs = {sid: [] for sid in frames}
        for step in range(5):
            for sid in frames:
                s = eng.table.get(sid)
                if s is not None and s.state == "active":
                    eng.submit(sid, frames[sid][step])
            eng.step()
            for sid in frames:
                if eng.table.get(sid) is not None:
                    outs[sid].extend(eng.results(sid))
        return eng, outs

    before = _threads_now()
    clean_eng, clean = one_run()
    assert all(len(v) == 5 for v in clean.values()), \
        {k: len(v) for k, v in clean.items()}
    # fault addressed at ONE session id: only its slot may retire
    faults.reset().arm("work:csb", rate=1.0, max_faults=1, seed=3)
    since = _journal_since()
    try:
        eng, got = one_run()
    finally:
        faults.reset()
    # journal story: the session was admitted, then retired by the fault
    _journal_story(since, ("serve", "page-admit"), ("serve", "retire"),
                   label="tenant_isolation")
    vb = eng.session_view("csb")
    assert vb["state"] == "retired" and vb["error"], vb
    assert len(got["csb"]) == 0, "retired session still produced output"
    # siblings: full output, bit-identical to the fault-free run
    for sid in ("csa", "csc"):
        assert len(got[sid]) == 5, (sid, len(got[sid]))
        for a, b in zip(got[sid], clean[sid]):
            np.testing.assert_array_equal(a, b, err_msg=sid)
    # the batch kept dispatching every step (one dispatch per frame time)
    assert eng.dispatches == clean_eng.dispatches == 5, \
        (eng.dispatches, clean_eng.dispatches)
    _assert_no_leaked_threads(before, "tenant_isolation")


def _serve_chaos_pipe():
    """The crash/overload scenarios' stateful chain (oscillator phase + FIR
    history) — shared by the child process and the restarted parent so the
    pipeline signature (and therefore the snapshot files) match."""
    from futuresdr_tpu.ops.stages import Pipeline, fir_stage, rotator_stage
    taps = np.hanning(21).astype(np.float32)
    return Pipeline([fir_stage(taps, fft_len=128), rotator_stage(0.02)],
                    np.complex64)


def _serve_chaos_frames(sid: str, n: int = 64):
    import zlib
    # crc32, NOT hash(): the child process and the restarted parent must
    # derive the SAME stream (str hash is salted per process)
    rng = np.random.default_rng(zlib.crc32(sid.encode()))
    return [(rng.standard_normal(512) + 1j * rng.standard_normal(512))
            .astype(np.complex64) for _ in range(n)]


def _serve_child_main(workdir: str) -> int:
    """The ``--_serve-child`` entry: a serving loop with per-step durable
    persistence, printing a STEP marker after every flushed snapshot — the
    parent SIGKILLs it mid-serve at an arbitrary marker."""
    from futuresdr_tpu.serve import ServeEngine
    eng = ServeEngine(_serve_chaos_pipe(), frame_size=512, app="crash_serve",
                      buckets=(2,), queue_frames=8,
                      persist_dir=workdir, persist_every=1)
    frames = {sid: _serve_chaos_frames(sid) for sid in ("cr0", "cr1")}
    for sid, tenant in (("cr0", "t0"), ("cr1", "t1")):
        eng.admit(tenant=tenant, sid=sid)
    for i in range(64):
        for sid in frames:
            eng.submit(sid, frames[sid][i])
        eng.step()
        # flushed BEFORE the marker: once the parent has seen "STEP i",
        # a kill at any later instant leaves at least step i's snapshot
        # complete on disk (atomic rename covers the torn-write case)
        eng.flush_persist()
        print(f"STEP {i}", flush=True)
        time.sleep(0.005)
    return 0


def _serve_churn_child_main(workdir: str) -> int:
    """The ``--_serve-churn-child`` entry: a serving loop under CONSTANT
    page churn — every step the oldest session leaves and a never-seen
    sid joins at its own frame 0 (pure page-map edits on the resident
    capacity) with the overlapped step in flight (inflight=2) and
    per-step durable persistence. The parent SIGKILLs it mid-churn at an
    arbitrary marker; sids are NEVER reused, so whichever sessions the
    restart finds, their crc32-derived streams are reconstructible."""
    from futuresdr_tpu.serve import ServeEngine
    eng = ServeEngine(_serve_chaos_pipe(), frame_size=512,
                      app="churn_crash", buckets=(4,), queue_frames=8,
                      inflight=2, persist_dir=workdir, persist_every=1)
    live, cursors, streams = [], {}, {}
    next_id = 0

    def join():
        nonlocal next_id
        sid = f"ch{next_id}"
        next_id += 1
        eng.admit(tenant="t", sid=sid)
        live.append(sid)
        cursors[sid] = 0
        streams[sid] = _serve_chaos_frames(sid)
        return sid

    for _ in range(3):
        join()
    for i in range(64):
        gone = live.pop(0)                 # churn: leave + fresh join,
        eng.close(gone)                    # every single step
        streams.pop(gone), cursors.pop(gone)
        join()
        for sid in live:
            if eng.submit(sid, streams[sid][cursors[sid] % 64]):
                cursors[sid] += 1
        eng.step()
        # flushed BEFORE the marker (same contract as the plain serve
        # child): once "STEP i" is printed, a kill at any later instant
        # leaves at least step i's committed snapshots complete on disk
        eng.flush_persist()
        print(f"STEP {i}", flush=True)
        time.sleep(0.005)
    return 0


def _fleet_child_main(workdir: str, port: int) -> int:
    """The ``--_fleet-child`` entry: a REAL serving host — one ServeEngine
    with per-step durable persistence, registered on a control port so the
    fleet plane sees it (``/api/host/``) and the admission router can POST
    sessions to it — printing a STEP marker after every flushed snapshot.
    The parent SIGKILLs it mid-serve at an arbitrary marker."""
    from futuresdr_tpu.runtime.ctrl_port import ControlPort
    from futuresdr_tpu.serve import ServeEngine
    from futuresdr_tpu.serve import api as serve_api

    # fleet identity = the control-port address (what the aggregator polls)
    os.environ.setdefault("FUTURESDR_TPU_FLEET_HOST_ID", f"127.0.0.1:{port}")

    class _Handle:                         # host-only port: no flowgraphs
        def flowgraph_ids(self):
            return []

        def get_flowgraph(self, fg):
            return None

    eng = ServeEngine(_serve_chaos_pipe(), frame_size=512, app="app",
                      buckets=(2,), queue_frames=8,
                      persist_dir=workdir, persist_every=1)
    serve_api.register_app(eng, "app")
    cp = ControlPort(_Handle(), bind=f"127.0.0.1:{port}")
    cp.start()
    eng.admit(tenant="t0", sid="fc0")
    frames = _serve_chaos_frames("fc0", n=4096)
    for i in range(4096):                  # parks until the parent kills it
        eng.submit("fc0", frames[i])
        eng.step()
        # flushed BEFORE the marker: once the parent has seen "STEP i",
        # a kill at any later instant leaves at least step i's snapshot
        # complete on disk
        eng.flush_persist()
        print(f"STEP {i}", flush=True)
        time.sleep(0.005)
    return 0


def scenario_serve_crash_restart():
    """Acceptance (ISSUE 14): SIGKILL a serving process mid-serve with
    ``serve_persist_dir`` set → a virgin engine incarnation in a new
    process re-admits 100% of the persisted sessions and every resumed
    stream is BIT-IDENTICAL to an unfailed run from its persisted cursor —
    kill -9 loses in-flight work, never session state."""
    import shutil
    import subprocess
    import tempfile
    from futuresdr_tpu.serve import ServeEngine
    workdir = tempfile.mkdtemp(prefix="fsdr_serve_crash_")
    env = os.environ.copy()
    env.update(JAX_PLATFORMS="cpu", FUTURESDR_TPU_AUTOTUNE_CACHE_DIR="off")
    before = _threads_now()
    try:
        p = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--_serve-child", workdir],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True)
        try:
            # reader THREAD + queue: a blocking `for line in p.stdout` would
            # hang the harness forever on a silently-wedged child — the
            # deadline must bound the WAIT, not just the line count (chaos
            # invariant I1: no run hangs past its deadline)
            import queue
            lines: "queue.Queue" = queue.Queue()

            def _pump_stdout():
                for line in p.stdout:
                    lines.put(line)

            threading.Thread(target=_pump_stdout, daemon=True,
                             name="chaos-serve-child-stdout").start()
            steps_seen = 0
            deadline = time.monotonic() + 120.0
            while steps_seen < 6:
                wait = deadline - time.monotonic()
                assert wait > 0, \
                    f"serve child never reached 6 steps ({steps_seen})"
                try:
                    line = lines.get(timeout=min(wait, 5.0))
                except queue.Empty:
                    assert p.poll() is None, \
                        f"child exited early ({steps_seen} steps)"
                    continue
                if line.startswith("STEP"):
                    steps_seen += 1
            p.kill()                       # SIGKILL — no atexit, no flush
        finally:
            try:
                p.kill()
            except OSError:
                pass
            p.wait(timeout=30)
        # restart: a VIRGIN incarnation over the same persist dir
        eng = ServeEngine(_serve_chaos_pipe(), frame_size=512,
                          app="crash_serve", buckets=(2,), queue_frames=8,
                          persist_dir=workdir, persist_every=1)
        try:
            assert eng.restored_sessions == 2, eng.restored_sessions
            resumed_ok = 0
            for sid in ("cr0", "cr1"):
                s = eng.table.get(sid)
                assert s is not None and s.state == "active", sid
                start = s.frames_out
                assert start >= 1, (sid, start)
                frames = _serve_chaos_frames(sid)
                # unfailed reference: the bare pipeline over the FULL stream
                import jax
                fn = jax.jit(_serve_chaos_pipe().fn())
                carry = _serve_chaos_pipe().init_carry()
                ref = []
                for f in frames[:start + 8]:
                    carry, y = fn(carry, f)
                    ref.append(np.asarray(y))
                for f in frames[start:start + 8]:
                    assert eng.submit(sid, f)
                while eng.step():
                    pass
                got = eng.results(sid)
                assert len(got) == 8, (sid, len(got))
                for a, b in zip(got, ref[start:]):
                    np.testing.assert_array_equal(a, b, err_msg=sid)
                resumed_ok += 1
            assert resumed_ok == 2, "serve_restart_resume_frac < 1.0"
        finally:
            eng.shutdown()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    _assert_no_leaked_threads(before, "serve_crash_restart")


def scenario_serve_churn_crash():
    """Acceptance (ISSUE 20): SIGKILL a serving process MID-CHURN — a
    session leaving and a fresh sid joining every single step, with the
    overlapped step keeping speculative groups in flight — and a virgin
    incarnation over the same persist dir resumes EVERY surviving session
    bit-identically from its persisted cursor. Page-map churn and the
    launch/commit window never corrupt durable session state: carries are
    committed (and therefore persisted) only after D2H completes."""
    import shutil
    import subprocess
    import tempfile
    from futuresdr_tpu.serve import ServeEngine
    workdir = tempfile.mkdtemp(prefix="fsdr_serve_churn_")
    env = os.environ.copy()
    env.update(JAX_PLATFORMS="cpu", FUTURESDR_TPU_AUTOTUNE_CACHE_DIR="off")
    before = _threads_now()
    try:
        p = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--_serve-churn-child", workdir],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True)
        try:
            import queue
            lines: "queue.Queue" = queue.Queue()

            def _pump_stdout():
                for line in p.stdout:
                    lines.put(line)

            threading.Thread(target=_pump_stdout, daemon=True,
                             name="chaos-churn-child-stdout").start()
            steps_seen = 0
            deadline = time.monotonic() + 120.0
            # at least 8 churn steps: the kill lands with the page map
            # several join/leave generations away from the seed layout
            while steps_seen < 8:
                wait = deadline - time.monotonic()
                assert wait > 0, \
                    f"churn child never reached 8 steps ({steps_seen})"
                try:
                    line = lines.get(timeout=min(wait, 5.0))
                except queue.Empty:
                    assert p.poll() is None, \
                        f"churn child exited early ({steps_seen} steps)"
                    continue
                if line.startswith("STEP"):
                    steps_seen += 1
            p.kill()                       # SIGKILL — no atexit, no flush
        finally:
            try:
                p.kill()
            except OSError:
                pass
            p.wait(timeout=30)
        # restart: a VIRGIN incarnation over the same persist dir. Which
        # sids survived depends on where the kill landed — enumerate them.
        eng = ServeEngine(_serve_chaos_pipe(), frame_size=512,
                          app="churn_crash", buckets=(4,), queue_frames=8,
                          inflight=2, persist_dir=workdir, persist_every=1)
        try:
            survivors = sorted(sid for sid, s in eng.table.sessions.items()
                               if s.state == "active")
            assert eng.restored_sessions == len(survivors) >= 1, \
                (eng.restored_sessions, survivors)
            import jax
            fn = jax.jit(_serve_chaos_pipe().fn())
            for sid in survivors:
                s = eng.table.get(sid)
                start = s.frames_out
                frames = _serve_chaos_frames(sid)
                # unfailed reference: the bare pipeline over the full
                # stream this sid would have seen (crc32-seeded, so the
                # virgin process derives the identical frames)
                carry = _serve_chaos_pipe().init_carry()
                ref = []
                for f in frames[:start + 6]:
                    carry, y = fn(carry, f)
                    ref.append(np.asarray(y))
                for f in frames[start:start + 6]:
                    assert eng.submit(sid, f), sid
                while eng.step():
                    pass
                got = eng.results(sid)
                assert len(got) == 6, (sid, len(got))
                for a, b in zip(got, ref[start:]):
                    np.testing.assert_array_equal(a, b, err_msg=sid)
        finally:
            eng.shutdown()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    _assert_no_leaked_threads(before, "serve_churn_crash")


_SHARD_REPLAY_WORKER = r"""
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from futuresdr_tpu.ops.stages import Pipeline, fir_stage, rotator_stage, \
    mag2_stage
from futuresdr_tpu.runtime import faults as _faults
from futuresdr_tpu.shard import ShardRunner, ShardedProgram, plan_shard

# a STATEFUL chain (FIR history + oscillator phase carries) so recovery has
# real state to restore — the whole point of the whole-mesh snapshot
pipe = Pipeline([fir_stage(np.hanning(33).astype(np.float32)),
                 rotator_stage(0.07), mag2_stage()], np.complex64)
D, K, F, GROUPS = 8, 2, 8192, 5
rng = np.random.default_rng(11)
groups = [(rng.standard_normal((D, K, F))
           + 1j * rng.standard_normal((D, K, F))).astype(np.complex64)
          for _ in range(GROUPS)]

def sharded(name, faulted):
    prog = ShardedProgram(pipe, plan_shard(pipe, mode="data", n_devices=D),
                          name=name)
    runner = ShardRunner(prog, F, k=K, checkpoint_every=2, name=name)
    if faulted:
        # seeded mid-stream dispatch fault (site dispatch:<runner name>)
        _faults.arm(f"dispatch:{name}", rate=0.5, seed=5, max_faults=1)
    out, recoveries = [], 0
    try:
        for g in groups:
            for attempt in (0, 1):
                try:
                    out.append(runner.run_group(g))
                    break
                except _faults.InjectedFault:
                    assert attempt == 0, "fault re-raised after recovery"
                    runner.recover()
                    recoveries += 1
    finally:
        _faults.disarm()
    return out, recoveries

ref, _ = sharded("shard_ref", faulted=False)
got, recoveries = sharded("shard_hit", faulted=True)
assert recoveries >= 1, "the injected fault never fired"
for seq, (a, b) in enumerate(zip(ref, got)):
    np.testing.assert_array_equal(a, b, err_msg=f"group {seq}")
# the journal tells the story in seq order: a whole-mesh checkpoint was
# committed, the runner recovered from it, and the logged window replayed
from futuresdr_tpu.telemetry import journal as _tj
evs = _tj.journal().events()["events"]
keys = [(e["cat"], e["event"]) for e in evs]
i_c = keys.index(("shard", "checkpoint-commit"))
i_r = keys.index(("shard", "recover"))
assert i_c < i_r, keys
rec = evs[i_r]
if rec["replayed"]:
    assert ("shard", "replay") in keys[i_r:], keys
print(f"SHARD-REPLAY OK recoveries={recoveries}", flush=True)
"""


def scenario_shard_replay():
    """Acceptance (ISSUE 15): an injected dispatch fault on a DATA-SHARDED
    stateful chain (``futuresdr_tpu/shard``) recovers BIT-IDENTICALLY from
    the whole-mesh carry snapshot + per-shard replay logs. Runs in a fresh
    subprocess: the 8-device virtual mesh flag only acts before jax init,
    and the chaos parent's backend is already live."""
    import subprocess
    import tempfile
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pypath = repo + os.pathsep + os.environ.get("PYTHONPATH", "")
    env = os.environ.copy()
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               FUTURESDR_TPU_AUTOTUNE_CACHE_DIR="off",
               PYTHONPATH=pypath.rstrip(os.pathsep))
    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as wf:
        wf.write(_SHARD_REPLAY_WORKER)
        path = wf.name
    try:
        r = subprocess.run([sys.executable, path], env=env,
                           capture_output=True, text=True, timeout=240)
        assert r.returncode == 0, \
            f"shard-replay child rc={r.returncode}\n{r.stdout[-1500:]}" \
            f"\n{r.stderr[-1500:]}"
        assert "SHARD-REPLAY OK" in r.stdout, r.stdout[-1500:]
    finally:
        os.unlink(path)


def scenario_serve_overload_shed():
    """Acceptance (ISSUE 14): an admission storm at 2x capacity sheds ONLY
    via the documented ladder — newcomers refused (rung 1, billed on
    fsdr_serve_shed_total), resident sessions bit-identical to a storm-free
    run and under the latency ceiling, and the ladder unwinds in order once
    the storm passes."""
    import jax
    from futuresdr_tpu.serve import ServeEngine, ServeFull, ShedLadder
    from futuresdr_tpu.serve.engine import _SHED
    before = _threads_now()
    pipe_ref = _serve_chaos_pipe()
    frames = {sid: _serve_chaos_frames(sid, 12) for sid in ("ov0", "ov1")}
    fn = jax.jit(pipe_ref.fn())
    ref = {}
    for sid in frames:
        carry = pipe_ref.init_carry()
        ref[sid] = []
        for f in frames[sid]:
            carry, y = fn(carry, f)
            ref[sid].append(np.asarray(y))
    eng = ServeEngine(_serve_chaos_pipe(), frame_size=512,
                      app="overload_serve", buckets=(2,), queue_frames=2)
    eng._ladder = ShedLadder(hi=0.5, lo=0.25, trip=2, clear=2)
    since = _journal_since()
    try:
        for sid in frames:
            eng.admit(tenant=sid, sid=sid)
        backlog = {sid: list(frames[sid]) for sid in frames}
        out = {sid: [] for sid in frames}
        shed = 0
        for step in range(60):
            if not any(backlog.values()):
                break
            # storm: offer 2 frames per session per frame time (2x the
            # dispatch rate) and keep trying to admit newcomers
            for sid in frames:
                for _ in range(2):
                    if backlog[sid] and eng.submit(sid, backlog[sid][0]):
                        backlog[sid].pop(0)
            try:
                eng.admit(tenant="newcomer", sid=f"nc{step}")
                eng.close(f"nc{step}")     # got in while healthy: back out
            except ServeFull:
                shed += 1                  # ladder rung 1 (or bucket-full)
            eng.step()
            for sid in frames:
                out[sid].extend(eng.results(sid))
        assert not any(backlog.values()), "resident frames never accepted"
        # drain the tail: a resident the ladder evicted at rung 2 readmits
        # BIT-IDENTICALLY once the pressure clears (the evict/readmit leaf
        # contract under the shedding ladder — the documented recovery)
        for _ in range(80):
            if all(len(out[sid]) == 12 for sid in frames):
                break
            for sid in frames:
                s = eng.table.get(sid)
                if s.state == "evicted":
                    try:
                        eng.readmit(sid)
                    except ServeFull:
                        pass               # ladder still engaged: next pass
            eng.step()
            for sid in frames:
                out[sid].extend(eng.results(sid))
        assert eng._ladder.escalations >= 1, "storm never tripped the ladder"
        assert shed >= 1, "no admission was shed"
        assert _SHED.get(app="overload_serve", tenant="newcomer",
                         reason="admission") >= 1
        # zero resident-session corruption: every resident output
        # bit-identical to the storm-free reference
        for sid in frames:
            assert len(out[sid]) == 12, (sid, len(out[sid]))
            for a, b in zip(out[sid], ref[sid]):
                np.testing.assert_array_equal(a, b, err_msg=sid)
        # latency ceiling: resident p99 stays sane under the storm (the
        # regress gate grades the measured figure; this is the smoke bound)
        for sid in frames:
            p99 = eng.tenant_latency_ms(sid)
            assert p99 is not None and p99 < 5000.0, (sid, p99)
        # hysteretic recovery: idle frame times unwind the ladder in order
        for _ in range(12):
            eng.step()
        assert eng._ladder.level == 0, eng._ladder.level
        eng.close("ov0")                   # free a lane (bucket is full)
        s = eng.admit(tenant="late")       # admissions reopen
        assert s.state == "active"
        # the journal tells the WHOLE story in seq order: residents
        # admitted -> the storm tripped the ladder (a shed-rung transition
        # UP, with a rung-1 refusal) -> traffic passed -> the ladder
        # unwound (the LAST shed-rung transition lands back at level 0)
        evs = _journal_story(since, ("serve", "page-admit"),
                             ("serve", "shed-rung"), ("serve", "refuse"),
                             label="serve_overload_shed")
        rungs = [e for e in evs if (e["cat"], e["event"]) ==
                 ("serve", "shed-rung")]
        assert rungs[0]["level"] > rungs[0]["prev"], rungs[0]
        assert rungs[-1]["level"] == 0, rungs[-1]
        # IF rung 2 fired, the evict precedes its readmit in seq order
        evicts = [e["seq"] for e in evs if (e["cat"], e["event"]) ==
                  ("serve", "evict")]
        readmits = [e["seq"] for e in evs if (e["cat"], e["event"]) ==
                    ("serve", "readmit")]
        if evicts and readmits:
            assert min(evicts) < max(readmits), (evicts, readmits)
    finally:
        eng.shutdown()
    _assert_no_leaked_threads(before, "serve_overload_shed")


def scenario_fleet_host_crash():
    """Acceptance (ISSUE 19): SIGKILL one host of a live two-host fleet
    mid-serve → the aggregator journals the staleness story IN ORDER
    (host-stale → host-down at exactly ``fleet_down_errors`` consecutive
    misses, BEFORE any post-crash route event), every admission routed after
    the down flip lands on the survivor, and a virgin engine incarnation
    over the dead host's persist dir resumes its session BIT-IDENTICALLY
    from the persisted cursor — a host crash loses in-flight work, never
    session state and never the fleet's routing sanity."""
    import queue
    import shutil
    import socket
    import subprocess
    import tempfile
    from futuresdr_tpu.serve import ServeEngine
    from futuresdr_tpu.serve.router import AdmissionRouter
    from futuresdr_tpu.telemetry import journal as journal_mod
    from futuresdr_tpu.telemetry.fleet import FleetView

    def _free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    workdir = tempfile.mkdtemp(prefix="fsdr_fleet_crash_")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = os.environ.copy()
    env.update(JAX_PLATFORMS="cpu", FUTURESDR_TPU_AUTOTUNE_CACHE_DIR="off",
               PYTHONPATH=(root + os.pathsep
                           + env.get("PYTHONPATH", "")).rstrip(os.pathsep))
    before = _threads_now()
    port_a, port_b = _free_port(), _free_port()
    host_a, host_b = f"127.0.0.1:{port_a}", f"127.0.0.1:{port_b}"
    interval = 0.15
    view = None
    pa = pb = None
    try:
        # host A: the REAL serving child (engine + persistence + control
        # port); host B: the jax-free control-port survivor serving the
        # same app name (tests/_fleet_child — the routed failover target)
        pa = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--_fleet-child", workdir, str(port_a)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True)
        pb = subprocess.Popen(
            [sys.executable, os.path.join(root, "tests", "_fleet_child.py"),
             str(port_b), "0.3"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True)
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            line = pb.stdout.readline()
            if "READY" in line or not line:
                break
        assert line and "READY" in line, f"survivor child failed: {line!r}"

        lines: "queue.Queue" = queue.Queue()

        def _pump_stdout():
            for ln in pa.stdout:
                lines.put(ln)

        threading.Thread(target=_pump_stdout, daemon=True,
                         name="chaos-fleet-child-stdout").start()
        steps_seen = 0
        while steps_seen < 6:              # >= 6 flushed snapshots on disk
            wait = deadline - time.monotonic()
            assert wait > 0, \
                f"fleet child never reached 6 steps ({steps_seen})"
            try:
                ln = lines.get(timeout=min(wait, 5.0))
            except queue.Empty:
                assert pa.poll() is None, \
                    f"fleet child exited early ({steps_seen} steps)"
                continue
            if ln.startswith("STEP"):
                steps_seen += 1

        view = FleetView([host_a, host_b], poll_interval=interval).start()
        router = AdmissionRouter(view, hysteresis=0.05)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and len(view.ready_hosts()) < 2:
            time.sleep(interval / 3)
        assert len(view.ready_hosts()) == 2, view.hosts()
        # a pre-crash routed admission exercises the live path (either host
        # is a legal pick; the post-crash contract is what the gate pins)
        router.admit("app", tenant="rt")

        j0 = journal_mod.journal().seq
        pa.kill()                          # SIGKILL — no atexit, no flush
        pa.wait(timeout=30)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if view.hosts()[host_a]["state"] == "down":
                break
            time.sleep(interval / 3)
        assert view.hosts()[host_a]["state"] == "down", view.hosts()
        evs = journal_mod.events(since=j0, cat="fleet")["events"]
        a_evs = [e for e in evs if e.get("host") == host_a]
        assert [e["event"] for e in a_evs][:2] == \
            ["host-stale", "host-down"], [e["event"] for e in a_evs]
        down = next(e for e in a_evs if e["event"] == "host-down")
        assert down["errors"] == view.down_errors, down

        # routing shift: every post-flip admit lands on the survivor, and
        # every one is journaled AFTER the down flip (seq order)
        targets = [router.admit("app", tenant=f"rt{i}")["host"]
                   for i in range(6)]
        assert set(targets) == {host_b}, targets
        routes = [e for e in
                  journal_mod.events(since=j0, cat="fleet")["events"]
                  if e["event"] == "route" and e["seq"] > down["seq"]]
        assert len(routes) >= 6 and \
            all(e["host"] == host_b for e in routes), routes

        # bit-identical resume "on the survivor": a virgin incarnation over
        # the dead host's persist dir readmits fc0 and continues its stream
        # from the persisted cursor, matched against an unfailed reference
        eng = ServeEngine(_serve_chaos_pipe(), frame_size=512, app="app",
                          buckets=(2,), queue_frames=8,
                          persist_dir=workdir, persist_every=1)
        try:
            s = eng.table.get("fc0")
            assert s is not None and s.state == "active", s
            start = s.frames_out
            assert start >= 1, start
            frames = _serve_chaos_frames("fc0", n=start + 8)
            import jax
            fn = jax.jit(_serve_chaos_pipe().fn())
            carry = _serve_chaos_pipe().init_carry()
            ref = []
            for f in frames:
                carry, y = fn(carry, f)
                ref.append(np.asarray(y))
            for f in frames[start:]:
                assert eng.submit("fc0", f)
            while eng.step():
                pass
            got = eng.results("fc0")
            assert len(got) == 8, len(got)
            for a, b in zip(got, ref[start:]):
                np.testing.assert_array_equal(a, b, err_msg="fc0")
        finally:
            eng.shutdown()
    finally:
        if view is not None:
            view.stop()
        for p in (pa, pb):
            if p is not None:
                try:
                    p.kill()
                except OSError:
                    pass
                p.wait(timeout=30)
        shutil.rmtree(workdir, ignore_errors=True)
    _assert_no_leaked_threads(before, "fleet_host_crash")


def scenario_deadline_bounds_wedge():
    """Acceptance: a wedged sink + run deadline → structured FlowgraphError
    within deadline+grace instead of an indefinite hang."""
    from futuresdr_tpu import (Flowgraph, FlowgraphCancelled, FlowgraphError,
                               Kernel, Runtime)
    from futuresdr_tpu.blocks import Copy, NullSource
    from futuresdr_tpu.config import config

    class Wedge(Kernel):
        def __init__(self, dtype):
            super().__init__()
            self.input = self.add_stream_input("in", dtype)

        async def work(self, io, mio, meta):
            pass

    before = _threads_now()
    config().run_timeout_grace = 3.0
    fg = Flowgraph()
    fg.connect(NullSource(np.float32), Copy(np.float32), Wedge(np.float32))
    t0 = time.perf_counter()
    try:
        Runtime().run(fg, timeout=1.0)
    except FlowgraphError as e:
        assert any(isinstance(x, FlowgraphCancelled) for x in e.errors), e
    else:
        raise AssertionError("wedged run did not error")
    elapsed = time.perf_counter() - t0
    assert elapsed < 1.0 + 3.0 + 3.0, f"deadline not honored: {elapsed:.1f}s"
    _assert_no_leaked_threads(before, "deadline_bounds_wedge")


# ---------------------------------------------------------------------------
# randomized campaign
# ---------------------------------------------------------------------------

def _random_trial(rng: random.Random, idx: int):
    """One seeded random trial: host chain or TPU chain × compatible
    (site, policy) pairing (module docstring matrix)."""
    from futuresdr_tpu import BlockPolicy, Flowgraph
    from futuresdr_tpu.blocks import Copy, VectorSink, VectorSource
    from futuresdr_tpu.ops import xfer
    from futuresdr_tpu.runtime import faults
    label = f"trial_{idx}"
    topology = rng.choice(("host", "tpu", "serve"))
    n = rng.choice((50_000, 120_000))
    seed = rng.randrange(1 << 16)

    if topology == "serve":
        # serving plane: serve steps paired with work:<sid> faults and
        # durable persistence on — the faulted session retires alone, the
        # siblings stay bit-identical AND survive a process-restart resume
        _random_serve_trial(rng, label, seed)
        return

    if topology == "host":
        data = np.arange(n, dtype=np.float32)
        site_kind = rng.choice(("work", "none"))
        policy = rng.choice(("fail_fast", "restart", "isolate"))
        max_faults = rng.choice((1, 2))

        def build():
            fg = Flowgraph()
            cp = Copy(np.float32)
            if policy != "fail_fast":
                cp.policy = BlockPolicy(on_error=policy, max_restarts=3,
                                        backoff=0.002)
            snk = VectorSink(np.float32)
            fg.connect(VectorSource(data), cp, snk)
            name = fg.wrapped(cp).instance_name
            plan = faults.reset()
            if site_kind == "work":
                plan.arm(f"work:{name}", rate=1.0, max_faults=max_faults,
                         seed=seed)

            def check(error):
                if error is not None:
                    # I2 (honest error): the faulted block is named
                    assert name in error.blocks, (label, error.blocks)
                    got = np.asarray(snk.items())
                    np.testing.assert_array_equal(got, data[:len(got)])
                else:
                    # I2 (correct): only reachable when recovery succeeded
                    np.testing.assert_array_equal(np.asarray(snk.items()),
                                                  data)
            return fg, check

        expect = None
        if site_kind == "none":
            expect = "ok"
        elif policy == "restart":
            expect = "ok"           # work faults fire pre-consume: recoverable
        else:
            expect = "error"
        try:
            _run_trial(build, label, expect=expect)
        finally:
            faults.reset()
        return

    # tpu topology: transfer faults ride the retry plane (recovered); a
    # dispatch fault under fail_fast is an honest structured error, under
    # `restart` it recovers via checkpoint/replay (device-plane recovery) —
    # either way the output is bit-correct or the error names the block
    from futuresdr_tpu.config import config
    from futuresdr_tpu.ops import mag2_stage
    from futuresdr_tpu.tpu import TpuKernel
    tone = np.exp(2j * np.pi * 0.07 * np.arange(n)).astype(np.complex64)
    expected = (tone.real ** 2 + tone.imag ** 2).astype(np.float32)
    site = rng.choice(("h2d", "d2h", "link", "dispatch"))
    policy = rng.choice(("fail_fast", "restart")) if site == "dispatch" \
        else "fail_fast"
    config().xfer_backoff = 0.0005

    def build():
        fg = Flowgraph()
        tk = TpuKernel([mag2_stage()], np.complex64, frame_size=1 << 13,
                       frames_in_flight=2)
        if policy == "restart":
            tk.policy = BlockPolicy(on_error="restart", max_restarts=3,
                                    backoff=0.002)
        snk = VectorSink(np.float32)
        fg.connect(VectorSource(tone), tk, snk)
        name = fg.wrapped(tk).instance_name
        plan = faults.reset()
        if site == "dispatch":
            plan.arm(f"dispatch:{name}", rate=1.0, max_faults=1, seed=seed)
        else:
            plan.arm(site, rate=1.0, max_faults=rng.choice((1, 2)), seed=seed)

        def check(error):
            if site == "dispatch" and policy == "fail_fast":
                assert error is not None
                assert name in error.blocks, (label, error.blocks)
            else:
                assert error is None, (label, repr(error))
                np.testing.assert_allclose(np.asarray(snk.items()), expected,
                                           rtol=1e-5)
        return fg, check

    expect = "error" if (site == "dispatch" and policy == "fail_fast") \
        else "ok"
    try:
        _run_trial(build, label, expect=expect)
    finally:
        faults.reset()


def _random_serve_trial(rng: random.Random, label: str, seed: int) -> None:
    """One randomized serving trial: 3 sessions, a seeded ``work:<sid>``
    fault at one of them, persistence on. Invariants: only the victim
    retires (siblings bit-identical to their solo runs), its snapshot is
    purged, and a virgin incarnation resumes exactly the two survivors."""
    import jax
    import shutil
    import tempfile
    from futuresdr_tpu.runtime import faults
    from futuresdr_tpu.serve import ServeEngine
    before = _threads_now()
    workdir = tempfile.mkdtemp(prefix="fsdr_chaos_serve_")
    sids = ("rs0", "rs1", "rs2")
    victim = rng.choice(sids)
    nframes = rng.choice((4, 6))
    frames = {sid: _serve_chaos_frames(sid, nframes) for sid in sids}
    pipe_ref = _serve_chaos_pipe()
    fn = jax.jit(pipe_ref.fn())
    ref = {}
    for sid in sids:
        carry = pipe_ref.init_carry()
        ref[sid] = []
        for f in frames[sid]:
            carry, y = fn(carry, f)
            ref[sid].append(np.asarray(y))
    try:
        eng = ServeEngine(_serve_chaos_pipe(), frame_size=512,
                          app=f"chaos_{label}", buckets=(4,), queue_frames=8,
                          persist_dir=workdir, persist_every=1)
        for sid in sids:
            eng.admit(tenant=sid, sid=sid)
        faults.reset().arm(f"work:{victim}", rate=1.0, max_faults=1,
                           seed=seed)
        out = {sid: [] for sid in sids}
        for i in range(nframes):
            for sid in sids:
                s = eng.table.get(sid)
                if s is not None and s.state == "active":
                    eng.submit(sid, frames[sid][i])
            eng.step()
            for sid in sids:
                out[sid].extend(eng.results(sid))
        vv = eng.session_view(victim)
        assert vv["state"] == "retired" and vv["error"], (label, vv)
        for sid in sids:
            if sid == victim:
                continue
            assert len(out[sid]) == nframes, (label, sid, len(out[sid]))
            for a, b in zip(out[sid], ref[sid]):
                np.testing.assert_array_equal(a, b, err_msg=f"{label}:{sid}")
        eng.flush_persist()
        eng.shutdown()
        # virgin incarnation: exactly the two survivors resume (the
        # victim's snapshot was purged at retirement)
        eng2 = ServeEngine(_serve_chaos_pipe(), frame_size=512,
                           app=f"chaos_{label}", buckets=(4,),
                           queue_frames=8, persist_dir=workdir,
                           persist_every=1)
        assert eng2.restored_sessions == 2, (label, eng2.restored_sessions)
        assert eng2.table.get(victim) is None, label
        eng2.shutdown()
    finally:
        faults.reset()
        shutil.rmtree(workdir, ignore_errors=True)
    _assert_no_leaked_threads(before, label)


def campaign(trials: int, seed: int) -> None:
    rng = random.Random(seed)
    for i in range(trials):
        t0 = time.perf_counter()
        _random_trial(rng, i)
        print(f"  trial {i}: ok ({time.perf_counter() - t0:.2f}s)")


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

SCENARIOS = (
    ("fail_fast_baseline", scenario_fail_fast_baseline),
    ("restart_recovers", scenario_restart_recovers),
    ("isolate_branches", scenario_isolate_branches),
    ("transfer_retry_deterministic", scenario_transfer_retry_deterministic),
    ("stateful-restart-replay", scenario_stateful_restart_replay),
    ("arena-recycle-replay", scenario_arena_recycle_replay),
    ("adaptive-wire-switch", scenario_adaptive_wire_switch),
    ("isolate-group", scenario_isolate_group),
    ("tenant-isolation", scenario_tenant_isolation),
    ("serve-crash-restart", scenario_serve_crash_restart),
    ("serve-churn-crash", scenario_serve_churn_crash),
    ("serve-overload-shed", scenario_serve_overload_shed),
    ("fleet-host-crash", scenario_fleet_host_crash),
    ("shard-replay", scenario_shard_replay),
    ("deadline_bounds_wedge", scenario_deadline_bounds_wedge),
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="named scenarios + a short fixed-seed campaign "
                         "(the check.sh gate)")
    ap.add_argument("--trials", type=int, default=12,
                    help="randomized campaign length (ignored with --smoke)")
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--_serve-child", dest="serve_child", default=None,
                    metavar="DIR", help=argparse.SUPPRESS)
    ap.add_argument("--_serve-churn-child", dest="serve_churn_child",
                    default=None, metavar="DIR", help=argparse.SUPPRESS)
    ap.add_argument("--_fleet-child", dest="fleet_child", default=None,
                    nargs=2, metavar=("DIR", "PORT"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.fleet_child:
        import jax
        jax.config.update("jax_platforms",
                          os.environ.get("JAX_PLATFORMS", "cpu"))
        return _fleet_child_main(args.fleet_child[0],
                                 int(args.fleet_child[1]))
    if args.serve_child:
        import jax
        jax.config.update("jax_platforms",
                          os.environ.get("JAX_PLATFORMS", "cpu"))
        return _serve_child_main(args.serve_child)
    if args.serve_churn_child:
        import jax
        jax.config.update("jax_platforms",
                          os.environ.get("JAX_PLATFORMS", "cpu"))
        return _serve_churn_child_main(args.serve_churn_child)
    import jax
    jax.config.update("jax_platforms", os.environ.get("JAX_PLATFORMS", "cpu"))
    t_all = time.perf_counter()
    for name, fn in SCENARIOS:
        t0 = time.perf_counter()
        fn()
        print(f"chaos scenario {name}: ok ({time.perf_counter() - t0:.2f}s)")
    n = 4 if args.smoke else args.trials
    print(f"chaos campaign: {n} randomized trials (seed {args.seed})")
    campaign(n, args.seed)
    print(f"CHAOS OK — every invariant held "
          f"({time.perf_counter() - t_all:.1f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
