#!/usr/bin/env python
"""perf/serve_ab — multi-tenant serving gates (docs/serving.md).

Drives the ``futuresdr_tpu/serve`` engine on a toy receiver chain (all
sessions ride ONE vmapped dispatch per frame time, ragged admission masking
the idle lanes) and asserts COUNTS AND EQUALITIES that hold on any backend.
It times nothing: what serving costs on the chip is the ``fm_serve_sat`` and
``fm_serve_paced`` cells of ``BENCHMARK.json``.

``--smoke`` (the check.sh gate): dispatches/frame-time == 1 regardless of the
active session count, session churn causes ZERO recompiles of resident
buckets, a simulated crash + restart resumes every persisted session
bit-identically to the same engine run without the crash, and an admission
storm at 2x capacity sheds newcomers while the residents keep delivering.

``--churn --smoke`` (the check.sh churn gate): join/leave EVERY step for 100
events at N=64, K∈{1,4}, buckets pinned to N — ZERO recompiles of the
resident capacity. Without ``--smoke`` the same matrix runs over
N∈{16,64,256} and prints the counts.
"""

import argparse
import os
import sys

import numpy as np

FRAME = 512
N_TENANTS = 4


def build_pipeline():
    """A light stateful receiver chain (rotator + short FIR): carries real
    per-session state (oscillator phase, filter history)."""
    from futuresdr_tpu.ops.stages import Pipeline, fir_stage, rotator_stage
    taps = np.hanning(17).astype(np.float32)
    return Pipeline([rotator_stage(0.013), fir_stage(taps, fft_len=128)],
                    np.complex64)


def session_data(n_sessions: int, frames_each: int, frame: int):
    rng = np.random.default_rng(42)
    return [
        [(rng.standard_normal(frame) + 1j * rng.standard_normal(frame))
         .astype(np.complex64) for _ in range(frames_each)]
        for _ in range(n_sessions)
    ]


def run_serve(pipe, data, steps: int, churn_every: int = 0,
              queue_frames: int = 4, k: int = 1, inflight: int = 1,
              buckets=None):
    """The serving engine: one dispatch per frame time for every active
    session. ``churn_every`` > 0 closes the oldest session and admits a
    fresh one every that-many steps (join/leave under load — with the paged
    carry pool a join is a page-map edit, landing mid-megabatch at the new
    session's own frame cursor). ``k`` > 1 rides the megabatch axis (k
    frames per session per dispatch); ``inflight`` > 1 engages the
    overlapped step. Returns the engine, its ``stats`` filled in."""
    from futuresdr_tpu.serve import ServeEngine
    n = len(data)
    eng = ServeEngine(pipe, frame_size=FRAME, app="serve_ab",
                      queue_frames=max(queue_frames, 2 * k),
                      frames_per_dispatch=k, inflight=inflight,
                      buckets=buckets)
    sessions = [eng.admit(tenant=f"t{i % N_TENANTS}") for i in range(n)]
    # warmup/compile the resident bucket (a compile under the first dispatch
    # is not churn)
    for i, s in enumerate(sessions):
        eng.submit(s.sid, data[i][0])
    eng.step()
    for s in sessions:
        eng.results(s.sid)
    compiles_at_start = eng.compiles
    churned = 0
    for step in range(1, steps + 1):
        if churn_every and step % churn_every == 0:
            old = sessions.pop(0)
            eng.close(old.sid)
            fresh = eng.admit(tenant=f"t{churned % N_TENANTS}")
            sessions.append(fresh)
            data.append(data.pop(0))          # the new session reuses a lane
            churned += 1
        for i, s in enumerate(sessions):
            for j in range(k):
                eng.submit(s.sid, data[i][(step * k + j) % len(data[i])])
        eng.step()
        for s in sessions:
            eng.results(s.sid)
    while eng.step():                 # settle in-flight groups (overlap)
        pass
    eng.stats = {
        "dispatches_per_step": eng.dispatches and
        (eng.dispatches - 1) / steps,       # -1: the warmup dispatch
        "compiles_during_run": eng.compiles - compiles_at_start,
        "churned": churned,
    }
    return eng


def _resume_run(data, sids, workdir, crash: bool):
    """All of ``data`` through an engine persisting to ``workdir``; with
    ``crash`` the engine is abandoned halfway (never closed or drained) and
    a VIRGIN incarnation restored from the snapshots serves the second half.
    Returns per-session result lists of the second half."""
    from futuresdr_tpu.serve import ServeEngine
    frames_each = len(data[0])
    half = frames_each // 2

    def engine(persist_every):
        return ServeEngine(build_pipeline(), frame_size=FRAME,
                           app="serve_resume", queue_frames=frames_each,
                           persist_dir=workdir, persist_every=persist_every)

    def feed(eng, lo, hi):
        for i, sid in enumerate(sids):
            if eng.table.get(sid) is not None:
                for f in data[i][lo:hi]:
                    eng.submit(sid, f)
        while eng.step():
            pass

    a = engine(1)
    for i, sid in enumerate(sids):
        a.admit(tenant=f"t{i % N_TENANTS}", sid=sid)
    feed(a, 0, half)
    for sid in sids:
        a.results(sid)
    b = a
    if crash:
        a.flush_persist()
        a.shutdown()                       # "crash": never closed or drained
        b = engine(0)
    feed(b, half, frames_each)
    out = []
    for sid in sids:
        s = b.table.get(sid)
        ok = s is not None and s.frames_out == frames_each
        out.append(b.results(sid) if ok else None)
    b.shutdown()
    return out


def restart_resume_frac(n_sessions: int = 6, frames_each: int = 10) -> float:
    """Fraction of persisted sessions a VIRGIN engine incarnation resumes
    BIT-IDENTICALLY after a simulated crash: the reference is the SAME
    engine and slot program serving the same frames without the crash (the
    chaos ``serve-crash-restart`` scenario proves the same with a real
    SIGKILL). Target 1.0."""
    import shutil
    import tempfile
    data = session_data(n_sessions, frames_each, FRAME)
    sids = [f"rr{i}" for i in range(n_sessions)]
    half = frames_each // 2
    dirs = [tempfile.mkdtemp(prefix="fsdr_serve_resume_") for _ in range(2)]
    try:
        refs = _resume_run(data, sids, dirs[0], crash=False)
        got = _resume_run(data, sids, dirs[1], crash=True)
    finally:
        for d in dirs:
            shutil.rmtree(d, ignore_errors=True)
    ok = sum(
        1 for g, r in zip(got, refs)
        if g is not None and r is not None
        and len(g) == len(r) == frames_each - half
        and all(np.array_equal(x, y) for x, y in zip(g, r)))
    return ok / float(n_sessions)


def overload_shed(n_resident: int = 8, steps: int = 40):
    """An admission storm at 2x capacity (offered load 2x the dispatch rate
    + a stream of refused admissions). Returns ``(shed_admissions,
    resident_frames_delivered)`` — the ladder must shed newcomers while the
    residents keep delivering."""
    from futuresdr_tpu.serve import ServeEngine, ServeFull, ShedLadder
    data = session_data(n_resident, steps + 4, FRAME)
    eng = ServeEngine(build_pipeline(), frame_size=FRAME, app="serve_shed",
                      buckets=(n_resident,), queue_frames=2)
    eng._ladder = ShedLadder(hi=0.5, lo=0.25, trip=2, clear=4)
    sessions = [eng.admit(tenant=f"t{i % N_TENANTS}", sid=f"ovr{i}")
                for i in range(n_resident)]
    for i, s in enumerate(sessions):
        eng.submit(s.sid, data[i][0])
    eng.step()
    for s in sessions:
        eng.results(s.sid)
    shed = 0
    delivered = 0
    for step in range(1, steps + 1):
        for i, s in enumerate(sessions):
            # 2x offered load: two submits per frame time (the second one
            # rides or bounces on the credit guard — backpressure, not loss)
            eng.submit(s.sid, data[i][step % len(data[i])])
            eng.submit(s.sid, data[i][(step + 1) % len(data[i])])
        try:
            eng.admit(tenant="storm", sid=f"st{step}")
            eng.close(f"st{step}")
        except ServeFull:
            shed += 1
        eng.step()
        for s in sessions:
            delivered += len(eng.results(s.sid))
    eng.shutdown()
    return shed, delivered


def churn_matrix(counts, ks, steps: int, smoke: bool = False):
    """``--churn``: the join/leave-every-step matrix over N × K, buckets
    pinned to N so "resident capacity" is one compiled program. ``smoke``
    (the check.sh churn gate) runs N=64, K∈{1,4}, 100 steps == 100
    join/leave events, and asserts ZERO recompiles of the resident
    capacity with one dispatch per step."""
    pipe = build_pipeline()
    print(f"# serve_ab --churn: frame={FRAME}, join/leave EVERY step, "
          f"steps={steps}")
    print(f"{'N':>4} {'K':>3} {'churned':>8} {'compiles':>9} "
          f"{'disp/step':>10}")
    for n in counts:
        data = session_data(n, 8, FRAME)
        for k in ks:
            eng = run_serve(pipe, list(data), steps, churn_every=1, k=k,
                            buckets=(n,))
            cc = eng.stats["compiles_during_run"]
            dps = eng.stats["dispatches_per_step"]
            print(f"{n:4d} {k:3d} {eng.stats['churned']:8d} {cc:9d} "
                  f"{dps:10.3f}")
            if smoke:
                assert eng.stats["churned"] >= 100, \
                    f"only {eng.stats['churned']} churn events"
                assert cc == 0, \
                    f"churn recompiled resident capacity {cc}x at " \
                    f"N={n} K={k}"
                assert abs(dps - 1.0) < 1e-9, \
                    f"dispatches/step {dps} != 1 at N={n} K={k}"
    if smoke:
        print("serve_ab churn smoke OK")
    return 0


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--sessions", default="8,32,64",
                   help="comma list of concurrent session counts to sweep")
    p.add_argument("--steps", type=int, default=60,
                   help="dispatch steps per point")
    p.add_argument("--churn-every", type=int, default=1,
                   help="churn phase: close+admit one session every N steps")
    p.add_argument("--churn", action="store_true",
                   help="join/leave-every-step matrix over N x K (with "
                        "--smoke: the check.sh churn gate — 100 events, "
                        "zero recompiles)")
    p.add_argument("--smoke", action="store_true",
                   help="check.sh gate: single point + hard assertions")
    args = p.parse_args()

    if args.churn:
        counts = [64] if args.smoke else [16, 64, 256]
        steps = 100 if args.smoke else max(args.steps, 100)
        return churn_matrix(counts, [1, 4], steps, smoke=args.smoke)

    counts = ([64] if args.smoke
              else [int(x) for x in args.sessions.split(",") if x.strip()])
    steps = 24 if args.smoke else args.steps

    pipe = build_pipeline()
    print(f"# serve_ab: frame={FRAME}, chain="
          f"{[s.name for s in pipe.stages]}, steps={steps}, "
          f"tenants={N_TENANTS}")
    print(f"{'N':>4} {'disp/frame':>11} {'churned':>8} "
          f"{'churn compiles':>15}")
    for n in counts:
        data = session_data(n, 8, FRAME)
        eng = run_serve(pipe, list(data), steps)
        churn_eng = run_serve(pipe, list(data), steps,
                              churn_every=args.churn_every)
        dpf = eng.stats["dispatches_per_step"]
        cc = churn_eng.stats["compiles_during_run"]
        print(f"{n:4d} {dpf:11.3f} {churn_eng.stats['churned']:8d} "
              f"{cc:15d}")
        if args.smoke:
            # one batched dispatch per frame time, no matter how many
            # sessions are active (the tentpole invariant)
            assert abs(dpf - 1.0) < 1e-9, \
                f"dispatches/frame {dpf} != 1 at N={n}"
            # join/leave under load never recompiles a resident bucket
            assert cc == 0, f"churn recompiled {cc} resident bucket(s)"
            assert churn_eng.stats["churned"] > 0
    # crash-safety + overload (ISSUE 14): resumed fraction after a simulated
    # crash (target 1.0 — every persisted session bit-identical) and an
    # admission storm at 2x capacity
    resume_frac = restart_resume_frac()
    shed_n, delivered = overload_shed()
    print(f"# restart resume frac: {resume_frac:.3f}   storm: {shed_n} "
          f"admissions shed, {delivered} resident frames delivered")
    if args.smoke:
        assert resume_frac == 1.0, \
            f"restart resume frac {resume_frac} != 1.0"
        assert shed_n > 0, "the admission storm shed nothing"
        assert delivered > 0, "the residents delivered nothing in the storm"
        print("serve_ab smoke OK")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ".")
    sys.path.insert(0, "..")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("FUTURESDR_TPU_AUTOTUNE_CACHE_DIR", "off")
    sys.exit(main())
