"""Driver ``serve``: the configuration's ``ServeEngine`` behind the REST plane
on a ``ControlPort``. Sessions are admitted, closed and retuned over REST;
frames are submitted in-process and ``step()`` is called by this driver's pump
thread, which collects every session's results after each step.

Threads, all of this one process (the chip belongs to it):

* pump       ``step()`` back to back; idle steps sleep 0.5 ms. In a closed
             loop it also tops every session's queue up to ``queue_depth``
             before each step: submit, step, collect, in one thread
* generator  open loop only: submits each frame when it is due
* control    the seeded schedule of retunes, leaves and joins, over HTTP
* main       warm-up, the window, the traced sub-window

Latency of a frame runs from the instant its last input sample was due
(its session's join time + (k + 1) frame periods) to the instant the pump has
its audio in hand. The clocks are the benchmark's own.
"""

from __future__ import annotations

import heapq
import json
import os
import queue
import socket
import threading
import time
import urllib.error
import urllib.request
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from harness import hostspans, stats
from harness.reading import HostSpan, Outcome, Reading, Run


class Rest:
    def __init__(self, port: int, app: str):
        self.base = f"http://127.0.0.1:{port}/api/serve/{app}"

    def call(self, method: str, path: str, body=None):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.base + path, data=data, method=method,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Listener:
    """The client's side of one session."""

    def __init__(self, sid: str, tenant: str, lane: int, buf: np.ndarray,
                 t_join_ns: int):
        self.sid, self.tenant, self.lane = sid, tenant, lane
        self.buf = buf                          # [period_frames, frame]
        self.t_join_ns = t_join_ns
        self.k_next = 0                         # next frame to submit
        self.pending: deque = deque()           # (k, due_ns) submitted, no result yet
        self.dirty = False                      # a retune was posted
        self.leaving = False

    def frame(self, k: int) -> np.ndarray:
        return self.buf[k % len(self.buf)]


class Client:
    """Everything the threads share. Counters are written by one thread each."""

    def __init__(self, eng, rest: Rest, period_ns: int, seed: int,
                 n_sample: int, record_spans: bool):
        self.eng, self.rest, self.period_ns = eng, rest, period_ns
        self.live: Dict[str, Listener] = {}
        self.live_lock = threading.Lock()
        self.stop = threading.Event()
        self.new_listeners: queue.SimpleQueue = queue.SimpleQueue()
        self.record_spans = record_spans
        self.spans: List[HostSpan] = []
        # results: (collect_ns, due_ns) per session-frame; due 0 in closed loop
        self.done: List[tuple] = []
        self.dispatch_ns: List[int] = []
        self.steps_ns: List[tuple] = []         # (called, returned, collected)
        self.closed_depth = 0                   # closed loop: queue kept at this
        self.refused = 0                        # submit() returned False
        self.rest_failed: List[tuple] = []      # (perf_counter_ns, what)
        self.late_ms: List[tuple] = []          # (due_ns, late_ms)
        self.rng = np.random.default_rng([seed, 99])
        self.n_sample = n_sample
        self.seen = 0
        self.sampled: List[Optional[tuple]] = [None] * n_sample
        self.first_frames: List[tuple] = []     # every joiner's frame 0
        self.sampling = False
        self.errors: List[str] = []

    # -- pump ------------------------------------------------------------------
    def _keep(self, li: Listener, k: int, audio: np.ndarray) -> None:
        if li.dirty or not self.sampling:
            return
        item = (li, k, np.array(audio, copy=True))
        if k == 0:
            self.first_frames.append(item)
            return
        self.seen += 1
        slot = self.seen - 1 if self.seen <= self.n_sample \
            else int(self.rng.integers(self.seen))
        if slot < self.n_sample:
            self.sampled[slot] = item

    def collect(self, t_ns: int) -> None:
        with self.live_lock:
            listeners = list(self.live.values())
        for li in listeners:
            try:
                res = self.eng.results(li.sid)
            except KeyError:
                continue                        # closed under our feet
            for audio in res:
                if not li.pending:
                    self.errors.append(f"{li.sid}: a result nobody submitted")
                    continue
                k, due = li.pending.popleft()
                self.done.append((t_ns, due))
                self._keep(li, k, audio)

    def pump(self) -> None:
        try:
            while not self.stop.is_set():
                if self.closed_depth:
                    with self.live_lock:
                        listeners = list(self.live.values())
                    for li in listeners:
                        while len(li.pending) < self.closed_depth:
                            self._submit(li, 0)
                tb = time.perf_counter_ns()
                n = self.eng.step()
                if n:
                    t = time.perf_counter_ns()
                    self.collect(t)
                    self.dispatch_ns.append(t)
                    self.steps_ns.append((tb, t, time.perf_counter_ns()))
                    if self.record_spans:
                        self.spans.append(HostSpan(
                            "bench", "collect", t, time.perf_counter_ns() - t))
                else:
                    time.sleep(0.0005)
        except Exception as e:                          # noqa: BLE001
            self.errors.append(f"pump: {e!r}")
            self.stop.set()

    # -- generator -------------------------------------------------------------
    def _submit(self, li: Listener, due_ns: int) -> None:
        k = li.k_next
        li.k_next += 1
        t = time.perf_counter_ns()
        # queued before the call: the pump may hold the answer before
        # submit() returns to this thread
        li.pending.append((k, due_ns))
        try:
            ok = self.eng.submit(li.sid, li.frame(k))
        except (KeyError, ValueError) as e:
            self.errors.append(f"submit {li.sid}: {e!r}")
            ok = False
        if not ok:
            li.pending.pop()                    # ours: only this thread appends
            li.dirty = True                     # its stream now has a hole
            self.refused += 1
            self.done.append((0, due_ns))       # collect 0 = never: failed
        if self.record_spans:
            self.spans.append(HostSpan("bench", "generator", t,
                                       time.perf_counter_ns() - t))

    def generate_open(self) -> None:
        heap: List[tuple] = []
        seq = 0
        idle_before = True
        try:
            while not self.stop.is_set():
                while True:
                    try:
                        li = self.new_listeners.get_nowait()
                    except queue.Empty:
                        break
                    seq += 1
                    heapq.heappush(heap, (li.t_join_ns + self.period_ns, seq, li))
                now = time.perf_counter_ns()
                if not heap or heap[0][0] > now:
                    wait = 0.002 if not heap else min(0.002, (heap[0][0] - now) * 1e-9)
                    time.sleep(max(wait, 0.0))
                    idle_before = True
                    continue
                due, _, li = heapq.heappop(heap)
                if li.leaving:
                    continue
                if idle_before:                 # the generator, not the system
                    self.late_ms.append((due, (now - due) * 1e-6))
                self._submit(li, due)
                idle_before = False
                seq += 1
                heapq.heappush(heap, (due + self.period_ns, seq, li))
        except Exception as e:                          # noqa: BLE001
            self.errors.append(f"generator: {e!r}")
            self.stop.set()

    # -- control plane ---------------------------------------------------------
    def admit(self, tenant: str, lane: int, buf: np.ndarray,
              t_join_ns: Optional[int] = None) -> Optional[Listener]:
        st, body = self.rest.call("POST", "/session/", {"tenant": tenant})
        if st != 201:
            self.rest_failed.append((time.perf_counter_ns(), f"admit -> {st}"))
            return None
        sid = json.loads(body)["sid"]
        li = Listener(sid, tenant, lane, buf,
                      time.perf_counter_ns() if t_join_ns is None else t_join_ns)
        with self.live_lock:
            self.live[sid] = li
        return li

    def leave(self, li: Listener, grace_s: float) -> None:
        li.leaving = True
        deadline = time.monotonic() + grace_s
        while li.pending and time.monotonic() < deadline:
            time.sleep(0.002)
        with self.live_lock:
            self.live.pop(li.sid, None)
        for _k, due in li.pending:              # never answered: failed
            self.done.append((0, due))
        st, _ = self.rest.call("DELETE", f"/session/{li.sid}/")
        if st != 200:
            self.rest_failed.append((time.perf_counter_ns(), f"leave -> {st}"))

    def retune(self, li: Listener, body: dict) -> None:
        li.dirty = True
        st, _ = self.rest.call("POST", f"/session/{li.sid}/ctrl/", body)
        if st != 200:
            self.rest_failed.append((time.perf_counter_ns(), f"retune -> {st}"))


def _tenant_shares(tr: dict, tenants: List[str]) -> np.ndarray:
    if tr["tenant_assignment"] == "zipf":
        w = 1.0 / np.arange(1, len(tenants) + 1) ** float(tr["zipf_s"])
    else:
        w = np.asarray(tr["tenant_shares"], np.float64)
    return w / w.sum()


def _schedule(tr: dict, rng, n_slots: int, horizon_s: float) -> List[tuple]:
    """Seeded ``(t_s, kind, slot, value)`` events: retunes and leave+joins at
    the mix's mean rates per listener. Every seed gets the same AMOUNT of
    work: events of a kind fall one into each of ``rate x listeners x
    horizon`` equal strata of the horizon, at an instant the seed draws inside
    its stratum, so any window of the run holds the same number of them to
    within one; the listeners take turns in an order the seed shuffles."""
    ev = []
    for kind, rate in (("retune", float(tr.get("retune_per_s", 0.0))),
                       ("churn", float(tr.get("churn_per_s", 0.0)))):
        n = int(round(rate * n_slots * horizon_s))
        if n <= 0:
            continue
        stratum = horizon_s / n
        turns = np.concatenate([rng.permutation(n_slots)
                                for _ in range(-(-n // n_slots))])[:n]
        for i in range(n):
            t = (i + float(rng.uniform(0, 1))) * stratum
            hz = float(rng.uniform(-1, 1)) * float(tr.get("retune_span_hz", 0))
            ev.append((t, kind, int(turns[i]), hz))
    return sorted(ev)


def _precheck(cl: Client, cm, cfg: dict, seed: int, n_sess: int, fs: int,
              tenants: List[str]) -> dict:
    """chip_smoke.py's serve check through the objects the window will use:
    every lane admitted over REST, ``precheck_frames`` frames each drained
    step by step, one leave, one join, one lane retune. Compiles the resident
    program and the retune surgery. Returns what the comparison needs."""
    c = cfg["correctness"]
    n_frames, leave_at, retune_at = (c["precheck_frames"], c["precheck_leave_at"],
                                     c["precheck_retune_at"])
    theta = 2 * np.pi * c["precheck_retune_hz"] / 1e6
    eng = cl.eng
    ls = [cl.admit(tenants[i % len(tenants)], 10_000 + i,
                   cm.lane_signal(cfg, seed, 10_000 + i, fs))
          for i in range(n_sess)]
    if any(li is None for li in ls):
        raise RuntimeError(f"precheck: admission refused: {cl.rest_failed}")
    out = {li.sid: [] for li in ls}
    first = {li.sid: 0 for li in ls}
    leaver = ls[1 % n_sess]
    retuned = ls[2 % n_sess]
    live, everyone = list(ls), list(ls)

    def run_frame(t: int) -> None:
        for li in live:
            if not eng.submit(li.sid, li.frame(t - first[li.sid])):
                raise RuntimeError(f"precheck: submit refused at frame {t}")
        while eng.step():
            pass
        for li in live:
            out[li.sid].extend(eng.results(li.sid))

    run_frame(0)
    cl.retune(ls[3 % n_sess], cm.retune_body(0.0))      # compiles the surgery
    ls[3 % n_sess].dirty = False
    for t in range(1, n_frames):
        if t == leave_at and n_sess > 2:
            cl.leave(leaver, 0.0)
            live.remove(leaver)
            joiner = cl.admit(tenants[1], 20_000,
                              cm.lane_signal(cfg, seed, 20_000, fs))
            first[joiner.sid], out[joiner.sid] = t, []
            live.append(joiner)
            everyone.append(joiner)
        if t == retune_at:
            cl.retune(retuned, cm.retune_body(theta))
        run_frame(t)
    for li in live:                             # clear the table for the window
        cl.leave(li, 0.0)
    return {"out": out, "first": first, "listeners": everyone,
            "leaver": leaver.sid, "retuned": retuned.sid,
            "n_frames": n_frames, "leave_at": leave_at,
            "retune_at": retune_at, "theta": theta}


def _judge_precheck(pc: dict, cm, cfg: dict, fs: int) -> tuple:
    worst, ok_all = 0.0, True
    for li in pc["listeners"]:
        frames = pc["n_frames"] - pc["first"][li.sid]
        if li.sid == pc["leaver"] and len(pc["listeners"]) > 2:
            frames = pc["leave_at"]
        x = np.concatenate([li.frame(k) for k in range(frames)])
        want = cm.reference(
            cfg, x, retune_at=pc["retune_at"] * fs
            if li.sid == pc["retuned"] else -1, theta=pc["theta"])
        got = np.concatenate(pc["out"][li.sid]) if pc["out"][li.sid] \
            else np.zeros(0)
        ok, err = cm.judge(cfg, got, want)
        worst, ok_all = max(worst, err), ok_all and ok
    return ok_all, worst


def _judge_sampled(items: List[tuple], cm, cfg: dict) -> tuple:
    """Each kept audio frame against the reference of its own listener's
    station: frame k needs frame k-1 as history (every filter is FIR and
    shorter than a frame); frame 0 starts from the zero state."""
    worst, bad = 0.0, 0
    for li, k, audio in items:
        if k == 0:
            want = cm.reference(cfg, li.frame(0))
        else:
            x = np.concatenate([li.frame(k - 1), li.frame(k)])
            want = cm.reference(cfg, x)[len(audio):]
        ok, err = cm.judge(cfg, audio, want)
        worst = max(worst, err)
        bad += not ok
    return bad, worst


def run(run: Run) -> Outcome:
    from futuresdr_tpu import Runtime
    from futuresdr_tpu.runtime.ctrl_port import ControlPort
    from futuresdr_tpu.serve.api import register_app, unregister_app

    cell, cfg = run.cell, run.cell.config
    tr = dict(run.cell.traffic)
    if run.rehearse:
        tr.update(tr.get("rehearsal", {}))
    cm = cell.config_module
    tenants = list(cfg["parameters"]["tenants"])

    eng = cm.make_engine(cfg, run.rehearse)
    fs, cap = eng.frame_size, eng.capacity
    period_ns = int(fs / 1e6 * 1e9)             # 1 Msps in
    register_app(eng)
    port = _free_port()
    cp = ControlPort(Runtime().handle, bind=f"127.0.0.1:{port}")
    cp.start()
    cl = Client(eng, Rest(port, eng.app), period_ns, run.seed,
                int(cfg["correctness"]["sampled_frames"]), run.trace)
    threads: List[threading.Thread] = []
    notes: dict = {"frame_size": fs, "capacity": cap}
    try:
        pc = _precheck(cl, cm, cfg, run.seed, cap, fs, tenants)
        compiles_warm = eng.compiles

        # -- the window's listeners, admitted over REST -------------------------
        n_sess = int(tr["sessions"])
        rng = np.random.default_rng([run.seed, 7])
        shares = _tenant_shares(tr, tenants)
        open_loop = tr["loop"] == "open"
        warm_s = float(tr["warmup_s"])
        horizon = warm_s + run.seconds + 1.0 \
            + (float(tr.get("traced_s", 4.0)) + 1.0 if run.trace else 0.0)
        events = _schedule(tr, rng, n_sess, horizon) if open_loop else []
        n_join = sum(1 for e in events if e[1] == "churn")
        # listeners per tenant by largest remainder of the shares: the same
        # counts for every seed (the seed shuffles who is who), and a joiner
        # takes the tenant of the listener it replaces
        exact = shares * n_sess
        counts = np.floor(exact).astype(int)
        for i in np.argsort(-(exact - counts))[:n_sess - counts.sum()]:
            counts[i] += 1
        tenant_of = [t for t, c in zip(tenants, counts) for _ in range(c)]
        if tr["tenant_assignment"] == "round_robin":
            tenant_of = [tenants[i % len(tenants)] for i in range(n_sess)]
        else:
            tenant_of = [tenant_of[i] for i in rng.permutation(n_sess)]
        bufs = [cm.lane_signal(cfg, run.seed, i, fs)
                for i in range(n_sess + n_join)]
        slots: List[Optional[Listener]] = []
        t_start = time.perf_counter_ns() + int(0.3e9) + n_sess * int(4e6)
        for i in range(n_sess):
            li = cl.admit(tenant_of[i], i, bufs[i],
                          t_start + int(i / n_sess * period_ns))
            if li is None:
                raise RuntimeError(f"admission refused: {cl.rest_failed}")
            slots.append(li)
            if open_loop:
                cl.new_listeners.put(li)
        if time.perf_counter_ns() > t_start:
            t_start = time.perf_counter_ns()    # admits ran long: start now
            for i, li in enumerate(slots):
                li.t_join_ns = t_start + int(i / n_sess * period_ns)

        def control() -> None:
            nxt = n_sess
            try:
                for t_s, kind, slot, hz in events:
                    wait = t_start * 1e-9 + t_s - time.perf_counter_ns() * 1e-9
                    if wait > 0 and cl.stop.wait(wait):
                        return
                    if cl.stop.is_set():
                        return
                    li = slots[slot]
                    if li is None:
                        continue
                    if kind == "retune":
                        cl.retune(li, cm.retune_body(2 * np.pi * hz / 1e6))
                    else:
                        cl.leave(li, float(tr["leave_grace_s"]))
                        new = cl.admit(li.tenant, nxt, bufs[nxt])
                        nxt += 1
                        slots[slot] = new
                        if new is not None:
                            cl.new_listeners.put(new)
            except Exception as e:                      # noqa: BLE001
                cl.errors.append(f"control: {e!r}")
                cl.stop.set()

        workers = [("bench-pump", cl.pump), ("bench-control", control)]
        if open_loop:
            workers.append(("bench-generator", cl.generate_open))
        else:
            cl.closed_depth = int(tr["queue_depth"])
        for name, fn in workers:
            th = threading.Thread(target=fn, name=name, daemon=True)
            th.start()
            threads.append(th)

        # -- warm-up, then the window ------------------------------------------
        time.sleep(max(0.0, (t_start - time.perf_counter_ns()) * 1e-9) + warm_s)
        if run.trace:
            hostspans.drain_program_spans()
        gcw = hostspans.GcWatch()
        d0, f0 = eng.dispatches, eng.frames
        w0 = time.perf_counter_ns()
        cl.sampling = True
        cl.stop.wait(run.seconds)
        w1 = time.perf_counter_ns()
        cl.sampling = False
        d1, f1 = eng.dispatches, eng.frames
        gcw.close()
        notes["gc"] = gcw.summary(w0, w1)
        notes["jax_stages_in_window"] = run.meter.stages_between(w0, w1)[:20]
        if run.trace and not cl.stop.is_set():
            # the traced seconds come AFTER the window, with the same traffic
            # still offered: the profiler's host tracer more than doubles a
            # 33.5 MB step, and inside the window that overload would be
            # counted as the system's (frames refused, the shed ladder)
            time.sleep(float(tr.get("settle_s", 0.25)))
            run.trace_window.start()
            cl.stop.wait(float(tr.get("traced_s", 4.0)))
            run.trace_window.stop()
        # grace: frames due inside the window may still be in flight
        deadline = time.monotonic() + float(tr["grace_s"])
        if open_loop:
            for li in list(cl.live.values()):
                li.leaving = True
            while time.monotonic() < deadline and any(
                    li.pending for li in list(cl.live.values())):
                time.sleep(0.005)
        cl.stop.set()
        for th in threads:
            th.join(timeout=10.0)
        alive = [th.name for th in threads if th.is_alive()]
        for li in list(cl.live.values()):       # still unanswered: failed
            for _k, due in li.pending:
                cl.done.append((0, due))
        spans = hostspans.drain_program_spans() + cl.spans if run.trace else []

        if spans:
            notes["longest_spans"] = hostspans.longest(spans, w0)

        # -- the window's arithmetic ------------------------------------------
        done = np.asarray(cl.done, np.int64).reshape(-1, 2)
        e2e, lat_ms, late_ms = {}, [], []
        if open_loop:
            due_in = (done[:, 1] >= w0) & (done[:, 1] < w1)
            answered = due_in & (done[:, 0] > 0)
            attempted = int(np.count_nonzero(due_in))
            failed = attempted - int(np.count_nonzero(answered))
            lat_ms = ((done[answered, 0] - done[answered, 1]) * 1e-6).tolist()
            late_ms = [l for d, l in cl.late_ms if w0 <= d < w1]
            notes["gen_late_p95_ms"] = stats.percentile(late_ms, 95) \
                if late_ms else None
            if lat_ms:
                e2e["latency_p50_ms"] = stats.percentile(lat_ms, 50)
                p95 = stats.percentile(lat_ms, 95)
                if "p95_slice_s" in tr:
                    # the tail of a usual stretch: the median, over the
                    # window's whole slices, of each slice's 95th percentile
                    # (by the instant a frame was due). One stall of the
                    # process lifts the whole window's p95 by a tenth and is
                    # there in one run of four; the whole window's tail is
                    # the per-layer tail.latency_p99_ms
                    by_slice = stats.slice_percentiles(
                        done[answered, 1], lat_ms, w0, w1,
                        int(float(tr["p95_slice_s"]) * 1e9), 95,
                        int(tr.get("p95_slice_min_readings", 20)))
                    notes["latency_p95_window_ms"] = p95
                    notes["latency_p95_by_slice_ms"] = [
                        round(x, 2) for x in by_slice]
                    if by_slice:
                        p95 = stats.median(by_slice)
                e2e["latency_p95_ms"] = p95
        else:
            got_in = (done[:, 0] >= w0) & (done[:, 0] < w1)
            n_in = int(np.count_nonzero(got_in))
            attempted, failed = n_in + cl.refused, cl.refused
            # the median, over the window's consecutive blocks of
            # ``rate_block_dispatches`` dispatches, of the block's rate: a
            # stretch in which the host stood still costs one block, not a
            # share of the whole window's count
            stamps = np.asarray(cl.dispatch_ns, np.int64)
            per_dispatch = n_in / max(1, int(np.count_nonzero(
                (stamps >= w0) & (stamps < w1))))
            rates = stats.block_rates_per_s(
                stamps[(stamps >= w0) & (stamps < w1)],
                int(tr["rate_block_dispatches"]))
            notes["window_rate_msps"] = n_in * fs / ((w1 - w0) * 1e-9) / 1e6
            notes["blocks"] = len(rates)
            if rates:
                e2e["throughput_msps"] = \
                    stats.median(rates) * per_dispatch * fs / 1e6
        rest_failed = [what for t, what in cl.rest_failed if w0 <= t < w1]
        failed += len(rest_failed)
        attempted += len(rest_failed)

        # -- correctness, outside the window -----------------------------------
        correct = not cl.errors and not alive
        ok, worst = _judge_precheck(pc, cm, cfg, fs)
        notes["precheck"] = {"ok": ok, "max_abs_err": worst,
                             "sessions": len(pc["listeners"])}
        correct &= ok
        items = [s for s in cl.sampled if s is not None] + cl.first_frames
        bad, worst = _judge_sampled(items, cm, cfg)
        notes["sampled"] = {"frames": len(items), "bad": bad,
                            "max_abs_err": worst,
                            "first_frames": len(cl.first_frames)}
        failed += bad
        correct &= bad == 0 and len(items) >= min(16, max(1, attempted))
        correct &= eng.compiles == compiles_warm
        audio_len = {len(a) for _l, _k, a in items}
        exp = cfg.get("expected_on_chip", {})
        if not run.rehearse:
            correct &= fs == exp["frame_size"] and cap == exp["capacity"] \
                and audio_len <= {exp["audio_per_frame"]}
        gaps = np.diff(np.asarray(cl.dispatch_ns, np.int64))
        stamps = np.asarray(cl.dispatch_ns[1:], np.int64)
        in_w = (stamps >= w0) & (stamps < w1)
        notes["dispatch_gap_ms"] = {
            "p50": float(np.median(gaps[in_w]) * 1e-6) if in_w.any() else None,
            "max": float(gaps[in_w].max() * 1e-6) if in_w.any() else None,
            "over_100_at_s": [round((t - w0) * 1e-9, 2) for t, g in
                              zip(stamps[in_w], gaps[in_w]) if g > 100e6][:20]}
        steps = np.asarray(cl.steps_ns, np.int64).reshape(-1, 3)
        steps = steps[(steps[:, 1] >= w0) & (steps[:, 1] < w1)]
        if len(steps) > 1:
            notes["step_ms"] = {
                "call": stats.quantiles_ms(steps[:, 1] - steps[:, 0]),
                "collect": stats.quantiles_ms(steps[:, 2] - steps[:, 1]),
                "cycle": stats.quantiles_ms(np.diff(steps[:, 1])),
                "cycle_mean": float(np.diff(steps[:, 1]).mean() * 1e-6)}
        dump = os.environ.get("BENCH_DUMP_DIR")
        if dump:                    # tools/measure_cells.py --dump: raw stamps
            os.makedirs(dump, exist_ok=True)
            np.savez_compressed(
                os.path.join(dump, f"{cell.name}_{run.seed}.npz"),
                steps=np.asarray(cl.steps_ns, np.int64).reshape(-1, 3),
                done=done, window=np.asarray([w0, w1], np.int64))
        notes.update(
            sessions=n_sess, events=len(events), joins=n_join,
            refused=cl.refused, rest_failed=rest_failed, errors=cl.errors,
            threads_alive=alive, engine_compiles=eng.compiles,
            dispatches=d1 - d0, session_frames=f1 - f0,
            shed_level=eng.health().get("shed_level"),
            tenants={t: tenant_of[:n_sess].count(t) for t in tenants})

        reading = Reading(
            driver="serve", window_ns=(w0, w1), unit="dispatch",
            unit_stamps_ns=np.asarray(cl.dispatch_ns, np.int64), spans=spans,
            counters={"dispatches": d1 - d0, "session_frames": f1 - f0,
                      "capacity": cap, "frame_period_ms": period_ns * 1e-6},
            latencies_ms=lat_ms, gen_late_ms=late_ms,
            compiles_in_window=len(run.meter.between(w0, w1)),
            cost_per_unit=cm.dispatch_cost(cfg, fs, cap), peaks=run.peaks)
        return Outcome(correct=bool(correct), attempted=int(attempted),
                       failed=int(failed), window_start_ns=w0, end_to_end=e2e,
                       reading=reading,
                       host_spans_named=hostspans.named_for_breakdown(spans),
                       notes=notes)
    finally:
        cl.stop.set()
        for th in threads:
            th.join(timeout=10.0)
        cp.stop()
        unregister_app(eng.app)
        eng.shutdown()
