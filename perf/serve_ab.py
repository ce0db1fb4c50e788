#!/usr/bin/env python
"""perf/serve_ab — multi-tenant serving A/B (docs/serving.md).

A/B of the SAME receiver chain serving N concurrent sessions two ways:

* **independent** — N dedicated dispatch loops, one per session: each frame
  time every session pays its own H2D, program dispatch and D2H (what N
  separate flowgraphs with one ``TpuKernel`` each do, minus their thread
  overhead — a deliberately generous baseline: the real actor path also
  pays per-block supervision);
* **serve** — the ``futuresdr_tpu/serve`` engine: all sessions ride ONE
  vmapped dispatch per frame time (the input in lane groups, one program
  call, one D2H per sink), with ragged admission masking the idle lanes.

At a matched per-session throughput target T, sessions/chip = aggregate
session-frames-per-second / T — so the serve:independent ratio of aggregate
rates IS the sessions-per-chip ratio at any matched T. The CHURN phase
closes and admits sessions under load (two tenants) and reports per-tenant
p99 submit→result latency plus the zero-recompile pin (resident slot
buckets never recompile on join/leave).

``--smoke`` (the check.sh gate) asserts: dispatches/frame-time == 1
regardless of the active session count, session churn causes ZERO
recompiles of resident buckets, and the sessions/chip ratio clears a
conservative floor (the committed artifact documents the full curve).

``--churn`` is the PAGED-ENGINE matrix (join/leave EVERY step over
N∈{16,64,256} × K∈{1,4}, buckets pinned to N): no-churn p99 vs
churn-every-step p99, the zero-recompile pin, and sessions/chip at high
churn; ``--churn --smoke`` is the check.sh churn gate (100 join/leave
events, zero recompiles of resident capacity, churn p99 ≤ 1.5× no-churn).

Stamps a JSON line: ``serve_sessions_per_chip`` (N × ratio: sessions one
chip serves at the per-session rate the independent baseline sustained for
N), ``serve_speedup``, ``serve_p99_under_churn_ms`` (churn = join/leave
every step), ``serve_churn_sessions_per_chip`` (capacity retained under
that churn), ``serve_dispatches_per_frame`` — graded by ``perf/regress.py``.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

FRAME = 512          # small frames: the regime where per-dispatch host cost
#                      dominates per-session compute — the serving win
N_TENANTS = 4


def build_pipeline():
    """A light stateful receiver chain (rotator + short FIR): carries real
    per-session state (oscillator phase, filter history) while keeping
    per-session compute small enough that dispatch amortization — the thing
    under test — is visible on the CPU backend too."""
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


def run_independent(pipe, data, steps: int) -> float:
    """N dedicated per-session dispatch loops; returns aggregate
    session-frames/s. The compiled program is shared across sessions (same
    shape → same executable, as N real flowgraphs would get from the jit
    cache); every session still pays its own H2D/dispatch/D2H per frame."""
    import jax

    from futuresdr_tpu.ops import xfer
    from futuresdr_tpu.tpu.instance import instance
    dev = instance().device
    n = len(data)
    fn = jax.jit(pipe.fn())
    carries = [jax.device_put(pipe.init_carry(), dev) for _ in range(n)]
    # warmup/compile
    c, y = fn(carries[0], xfer.to_device(data[0][0], dev))
    jax.block_until_ready(y)
    carries[0] = jax.device_put(pipe.init_carry(), dev)
    # median per-step duration: robust to shared-host straggler steps (the
    # suite's median-of-runs methodology applied per frame time)
    durs = []
    for step in range(steps):
        t0 = time.perf_counter()
        for i in range(n):
            x = xfer.to_device(data[i][step % len(data[i])], dev)
            carries[i], y = fn(carries[i], x)
            xfer.to_host(y)
        durs.append(time.perf_counter() - t0)
    return n / float(np.median(durs))


def run_serve(pipe, data, steps: int, churn_every: int = 0,
              queue_frames: int = 4, k: int = 1, inflight: int = 1,
              buckets=None):
    """The serving engine: one dispatch per frame time for every active
    session. ``churn_every`` > 0 closes the oldest session and admits a
    fresh one every that-many steps (join/leave under load — with the paged
    carry pool a join is a page-map edit, landing mid-megabatch at the new
    session's own frame cursor). ``k`` > 1 rides the megabatch axis (k
    frames per session per dispatch); ``inflight`` > 1 engages the
    overlapped step. Returns ``(aggregate_fps, engine, p99_ms)``."""
    from futuresdr_tpu.serve import ServeEngine
    n = len(data)
    eng = ServeEngine(pipe, frame_size=FRAME, app="serve_ab",
                      queue_frames=max(queue_frames, 2 * k),
                      frames_per_dispatch=k, inflight=inflight,
                      buckets=buckets)
    sessions = [eng.admit(tenant=f"t{i % N_TENANTS}") for i in range(n)]
    # warmup/compile the resident bucket (excluded from the timing AND the
    # latency sample — a compile under the first dispatch is not churn p99)
    for i, s in enumerate(sessions):
        eng.submit(s.sid, data[i][0])
    eng.step()
    for s in sessions:
        eng.results(s.sid)
    compiles_at_start = eng.compiles
    dispatched = 0
    churned = 0
    lat_s = []                   # steady-state per-frame submit→result
    durs = []
    for step in range(1, steps + 1):
        if churn_every and step % churn_every == 0:
            old = sessions.pop(0)
            eng.close(old.sid)
            fresh = eng.admit(tenant=f"t{churned % N_TENANTS}")
            sessions.append(fresh)
            data.append(data.pop(0))          # the new session reuses a lane
            churned += 1
        t0 = time.perf_counter()
        for i, s in enumerate(sessions):
            for j in range(k):
                eng.submit(s.sid, data[i][(step * k + j) % len(data[i])])
        before = {s.sid: s.frames_out for s in sessions}
        dispatched += eng.step()
        for s in sessions:
            if s.frames_out > before.get(s.sid, 0) \
                    and s.last_latency_s is not None:
                lat_s.append(s.last_latency_s)
            eng.results(s.sid)
        durs.append(time.perf_counter() - t0)
    while eng.step():                 # settle in-flight groups (overlap)
        pass
    p99 = float(np.percentile(lat_s, 99)) * 1e3 if lat_s else 0.0
    eng.stats = {
        "dispatches_per_step": eng.dispatches and
        (eng.dispatches - 1) / steps,       # -1: the warmup dispatch
        "compiles_during_run": eng.compiles - compiles_at_start,
        "churned": churned,
    }
    return len(sessions) * k / float(np.median(durs)), eng, p99


def _stamp(n, indep, serve, p99, eng, churn_eng, churn_fps=None,
           resume_frac=None, shed_p99=None) -> dict:
    """The ONE stamp schema — shared by :func:`measure` (the ``bench.py``
    serve section) and the standalone harness, so the two output paths
    cannot drift from what ``perf/regress.py`` grades.

    ``serve_p99_under_churn_ms`` and ``serve_churn_sessions_per_chip`` are
    measured under join/leave EVERY STEP (the paged-engine acceptance
    regime): sessions/chip at high churn is N × the churn-phase aggregate
    rate over the independent baseline — the capacity one chip actually
    delivers while the tenancy is in constant flux."""
    ratio = serve / indep if indep > 0 else 0.0
    out = {
        "serve_sessions": n,
        "serve_indep_fps": round(indep, 1),
        "serve_fps": round(serve, 1),
        "serve_speedup": round(ratio, 2),
        "serve_sessions_per_chip": round(n * ratio, 1),
        "serve_p99_under_churn_ms": round(p99, 3),
        "serve_dispatches_per_frame": round(
            eng.stats["dispatches_per_step"], 3),
        "serve_churn_compiles": churn_eng.stats["compiles_during_run"],
        "serve_churned_sessions": churn_eng.stats["churned"],
    }
    if churn_fps is not None:
        out["serve_churn_sessions_per_chip"] = round(
            n * churn_fps / indep, 1) if indep > 0 else 0.0
    if resume_frac is not None:
        out["serve_restart_resume_frac"] = round(resume_frac, 3)
    if shed_p99 is not None:
        out["serve_shed_p99_ms"] = round(shed_p99, 3)
    return out


def _solo_refs(pipe, data):
    import jax
    fn = jax.jit(pipe.fn())
    refs = []
    for frames in data:
        carry = pipe.init_carry()
        r = []
        for f in frames:
            carry, y = fn(carry, f)
            r.append(np.asarray(y))
        refs.append(r)
    return refs


def measure_restart_resume(n_sessions: int = 6, frames_each: int = 10
                           ) -> float:
    """``serve_restart_resume_frac``: fraction of persisted sessions a
    VIRGIN engine incarnation resumes BIT-IDENTICALLY after a simulated
    crash (abandoned engine, durable snapshots on disk — the chaos
    ``serve-crash-restart`` scenario proves the same with a real SIGKILL;
    this is the regress-graded figure, target 1.0)."""
    import shutil
    import tempfile

    from futuresdr_tpu.serve import ServeEngine
    pipe = build_pipeline()
    data = session_data(n_sessions, frames_each, FRAME)
    refs = _solo_refs(pipe, data)
    half = frames_each // 2
    workdir = tempfile.mkdtemp(prefix="fsdr_serve_resume_")
    try:
        a = ServeEngine(build_pipeline(), frame_size=FRAME,
                        app="serve_resume", queue_frames=frames_each,
                        persist_dir=workdir, persist_every=1)
        sids = []
        for i in range(n_sessions):
            sids.append(a.admit(tenant=f"t{i % N_TENANTS}",
                                sid=f"rr{i}").sid)
        for i, sid in enumerate(sids):
            for f in data[i][:half]:
                a.submit(sid, f)
        while a.step():
            pass
        a.flush_persist()
        a.shutdown()                       # "crash": never closed or drained
        b = ServeEngine(build_pipeline(), frame_size=FRAME,
                        app="serve_resume", queue_frames=frames_each,
                        persist_dir=workdir, persist_every=0)
        for i, sid in enumerate(sids):
            if b.table.get(sid) is not None:
                for f in data[i][half:]:
                    b.submit(sid, f)
        while b.step():
            pass
        ok = 0
        for i, sid in enumerate(sids):
            s = b.table.get(sid)
            if s is None or s.frames_out != frames_each:
                continue
            got = b.results(sid)
            if len(got) == frames_each - half and all(
                    np.array_equal(g, r)
                    for g, r in zip(got, refs[i][half:])):
                ok += 1
        b.shutdown()
        return ok / float(n_sessions)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def measure_overload_shed(n_resident: int = 8, steps: int = 40):
    """``serve_shed_p99_ms``: resident per-frame p99 during an admission
    storm at 2x capacity (offered load 2x the dispatch rate + a stream of
    refused admissions). Returns ``(p99_ms, shed_admissions,
    resident_frames_ok)`` — residents must lose nothing to the storm."""
    from futuresdr_tpu.serve import ServeEngine, ServeFull, ShedLadder
    pipe = build_pipeline()
    data = session_data(n_resident, steps + 4, FRAME)
    eng = ServeEngine(build_pipeline(), frame_size=FRAME, app="serve_shed",
                      buckets=(n_resident,), queue_frames=2)
    eng._ladder = ShedLadder(hi=0.5, lo=0.25, trip=2, clear=4)
    sessions = [eng.admit(tenant=f"t{i % N_TENANTS}", sid=f"ovr{i}")
                for i in range(n_resident)]
    # warmup compile outside the latency sample
    for i, s in enumerate(sessions):
        eng.submit(s.sid, data[i][0])
    eng.step()
    for s in sessions:
        eng.results(s.sid)
    lat = []
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
        before = {s.sid: s.frames_out for s in sessions}
        eng.step()
        for s in sessions:
            if s.frames_out > before[s.sid] and s.last_latency_s is not None:
                lat.append(s.last_latency_s)
            delivered += len(eng.results(s.sid))
    eng.shutdown()
    p99 = float(np.percentile(lat, 99)) * 1e3 if lat else 0.0
    return p99, shed, delivered


def measure(n_sessions: int = 32, steps: int = 60, churn_every: int = 1):
    """One full A/B at ``n_sessions``; returns the stamp dict (the
    ``bench.py`` serve section calls this). The churn phase joins/leaves
    every ``churn_every`` steps (default: EVERY step — the paged-engine
    acceptance regime)."""
    pipe = build_pipeline()
    data = session_data(n_sessions, 8, FRAME)
    indep_fps = run_independent(pipe, data, steps)
    serve_fps, eng, _ = run_serve(pipe, list(data), steps)
    churn_fps, churn_eng, p99 = run_serve(pipe, list(data), steps,
                                          churn_every=churn_every)
    resume_frac = measure_restart_resume()
    shed_p99, _, _ = measure_overload_shed()
    return _stamp(n_sessions, indep_fps, serve_fps, p99, eng, churn_eng,
                  churn_fps=churn_fps, resume_frac=resume_frac,
                  shed_p99=shed_p99)


def churn_matrix(counts, ks, steps: int, smoke: bool = False):
    """``--churn``: the join/leave-every-step matrix over N × K. For each
    point: no-churn p99 vs churn-every-step p99 at the SAME capacity
    (buckets pinned to N so "resident capacity" is one compiled program),
    the zero-recompile pin, and sessions/chip at high churn. ``smoke``
    (the check.sh churn gate) runs N=64, K∈{1,4}, 100 steps == 100
    join/leave events, and asserts the paged-engine acceptance criteria:
    ZERO recompiles of the resident capacity and churn p99 ≤ 1.5× the
    no-churn p99 (one retry damps shared-CI-host noise). Returns the stamp
    dict from the N=64, K=1 point (the graded figure)."""
    pipe = build_pipeline()
    print(f"# serve_ab --churn: frame={FRAME}, join/leave EVERY step, "
          f"steps={steps}")
    print(f"{'N':>4} {'K':>3} {'base p99 ms':>12} {'churn p99 ms':>13} "
          f"{'ratio':>7} {'compiles':>9} {'churn s/chip':>13}")
    stamp = None
    for n in counts:
        data = session_data(n, 8, FRAME)
        indep = run_independent(pipe, data, min(steps, 24))
        for k in ks:
            base_fps, base_eng, base_p99 = run_serve(
                pipe, list(data), steps, k=k, buckets=(n,))
            churn_fps, churn_eng, churn_p99 = run_serve(
                pipe, list(data), steps, churn_every=1, k=k, buckets=(n,))
            if smoke and base_p99 > 0 and churn_p99 > 1.5 * base_p99:
                # one retry before failing the gate: p99 on a shared CI
                # host eats scheduler noise; a REAL churn regression (a
                # recompile, a restack) reproduces, noise does not
                base_fps, base_eng, base_p99 = run_serve(
                    pipe, list(data), steps, k=k, buckets=(n,))
                churn_fps, churn_eng, churn_p99 = run_serve(
                    pipe, list(data), steps, churn_every=1, k=k,
                    buckets=(n,))
            ratio = churn_p99 / base_p99 if base_p99 > 0 else 0.0
            cc = churn_eng.stats["compiles_during_run"]
            spc = n * churn_fps / indep if indep > 0 else 0.0
            print(f"{n:4d} {k:3d} {base_p99:12.3f} {churn_p99:13.3f} "
                  f"{ratio:7.2f} {cc:9d} {spc:13.1f}")
            if smoke:
                assert churn_eng.stats["churned"] >= 100, \
                    f"only {churn_eng.stats['churned']} churn events"
                assert cc == 0, \
                    f"churn recompiled resident capacity {cc}x at " \
                    f"N={n} K={k}"
                assert base_p99 > 0 and churn_p99 <= 1.5 * base_p99, \
                    f"churn p99 {churn_p99:.3f}ms > 1.5x no-churn " \
                    f"{base_p99:.3f}ms at N={n} K={k}"
            if k == 1 and (stamp is None or n == 64):
                stamp = _stamp(n, indep, base_fps, churn_p99, base_eng,
                               churn_eng, churn_fps=churn_fps)
    print(json.dumps(stamp))
    if smoke:
        print("serve_ab churn smoke OK")
    return 0


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--sessions", default="8,32,64",
                   help="comma list of concurrent session counts to sweep")
    p.add_argument("--steps", type=int, default=60,
                   help="dispatch steps per measurement")
    p.add_argument("--churn-every", type=int, default=1,
                   help="churn phase: close+admit one session every N steps")
    p.add_argument("--churn", action="store_true",
                   help="join/leave-every-step matrix over N x K (with "
                        "--smoke: the check.sh churn gate — 100 events, "
                        "zero recompiles, p99 within 1.5x of no-churn)")
    p.add_argument("--smoke", action="store_true",
                   help="check.sh gate: single point + hard assertions")
    args = p.parse_args()

    if args.churn:
        counts = [64] if args.smoke else [16, 64, 256]
        ks = [1, 4]
        steps = 100 if args.smoke else max(args.steps, 100)
        return churn_matrix(counts, ks, steps, smoke=args.smoke)

    counts = ([64] if args.smoke
              else [int(x) for x in args.sessions.split(",") if x.strip()])
    steps = 24 if args.smoke else args.steps

    pipe = build_pipeline()
    print(f"# serve_ab: frame={FRAME}, chain="
          f"{[s.name for s in pipe.stages]}, steps={steps}, "
          f"tenants={N_TENANTS}")
    print(f"{'N':>4} {'indep fps':>12} {'serve fps':>12} {'ratio':>7} "
          f"{'disp/frame':>11} {'churn p99 ms':>13} {'churn compiles':>15}")
    stamp = None
    for n in counts:
        data = session_data(n, 8, FRAME)
        indep = run_independent(pipe, data, steps)
        serve, eng, _ = run_serve(pipe, list(data), steps)
        churn_fps, churn_eng, p99 = run_serve(pipe, list(data), steps,
                                              churn_every=args.churn_every)
        stamp = _stamp(n, indep, serve, p99, eng, churn_eng,
                       churn_fps=churn_fps)
        ratio = serve / indep if indep else 0.0
        dpf = eng.stats["dispatches_per_step"]
        cc = churn_eng.stats["compiles_during_run"]
        print(f"{n:4d} {indep:12.1f} {serve:12.1f} {ratio:7.2f} "
              f"{dpf:11.3f} {p99:13.3f} {cc:15d}")
        if args.smoke:
            # one batched dispatch per frame time, no matter how many
            # sessions are active (the tentpole invariant)
            assert abs(dpf - 1.0) < 1e-9, \
                f"dispatches/frame {dpf} != 1 at N={n}"
            # join/leave under load never recompiles a resident bucket
            assert cc == 0, f"churn recompiled {cc} resident bucket(s)"
            assert churn_eng.stats["churned"] > 0
            # conservative smoke floor — the artifact documents the full
            # curve (>= 8x at the committed settings); CI boxes are noisy
            assert ratio >= 3.0, \
                f"sessions/chip ratio {ratio:.2f} under the 3.0 smoke floor"
    # crash-safety + overload figures (ISSUE 14): resumed fraction after a
    # simulated crash (target 1.0 — every persisted session bit-identical)
    # and resident p99 under an admission storm at 2x capacity. Routed
    # through _stamp (the ONE schema) like measure() — the two output
    # paths must not drift from what perf/regress.py grades
    resume_frac = measure_restart_resume()
    shed_p99, shed_n, delivered = measure_overload_shed()
    if stamp is not None:
        stamp = _stamp(n, indep, serve, p99, eng, churn_eng,
                       churn_fps=churn_fps, resume_frac=resume_frac,
                       shed_p99=shed_p99)
    print(f"# restart resume frac: {resume_frac:.3f}   storm p99: "
          f"{shed_p99:.3f} ms ({shed_n} admissions shed, {delivered} "
          f"resident frames delivered)")
    if args.smoke:
        assert resume_frac == 1.0, \
            f"serve_restart_resume_frac {resume_frac} != 1.0"
        assert shed_n > 0, "the admission storm shed nothing"
        assert shed_p99 > 0.0
    print(json.dumps(stamp))
    if args.smoke:
        print("serve_ab smoke OK")
    return 0


if __name__ == "__main__":
    # standalone-harness environment only — bench.py imports measure()
    # in-process and must NOT inherit these (a live-TPU bench would be
    # silently forced onto the CPU backend with cache persistence off)
    sys.path.insert(0, ".")
    sys.path.insert(0, "..")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("FUTURESDR_TPU_AUTOTUNE_CACHE_DIR", "off")
    sys.exit(main())
