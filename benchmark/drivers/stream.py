"""Driver ``stream``: one flowgraph, generator block → the configuration's
``TpuKernel`` → stamping sink, through ``Runtime().start``.

The traffic file picks the loop:

* ``closed``: the generator writes whenever its ring has room; the result is
  samples per second at the sink.
* ``open``: samples become due on the wall clock at ``rate_msps``; the result
  is each frame's latency from the instant its last input sample was due to
  the instant its last output item is in the sink's hands.

The clocks are the benchmark's: the generator's due times and the sink's
stamps. Nothing the program stamps is read for an end-to-end metric.
"""

from __future__ import annotations

import time
from typing import List

import numpy as np

from harness import hostspans, stats
from harness.reading import HostSpan, Outcome, Reading, Run


def _blocks():
    """The generator and the sink, as flowgraph blocks (built lazily: the
    program is imported only once a run has set its environment)."""
    from futuresdr_tpu import Kernel

    class ReplaySource(Kernel):
        """Replays ``block`` for ever. A radio's source block is BLOCKING
        (the driver read blocks); so is this one, which lets it sleep on
        ``time.sleep`` instead of the event loop's millisecond timer."""

        BLOCKING = True

        def __init__(self, block: np.ndarray, frame: int, rate_sps: float,
                     chunk: int, max_backlog_frames: int, record_spans: bool):
            super().__init__()
            self.block, self.frame = block, frame
            self.rate = rate_sps                   # 0.0 = closed loop
            self.chunk = chunk
            self.max_backlog = max_backlog_frames * frame
            self.record_spans = record_spans
            self.spans: List[tuple] = []
            self.pos = 0                           # next sample of the block
            self.written = 0                       # samples written, total
            self.skipped = 0                       # samples dropped (overrun)
            self.dropped_due_ns: List[int] = []    # due time of dropped frames
            self.t0_ns = None                      # the pacing clock's zero
            self.stop = False
            cap = 1 << 16
            self.frame_due_ns = np.zeros(cap, np.int64)     # per written frame
            self.frame_written_ns = np.zeros(cap, np.int64)
            self.frame_blocked = np.zeros(cap, bool)
            self._blocked = False
            self.output = self.add_stream_output("out", block.dtype)

        def _copy(self, out: np.ndarray, n: int) -> None:
            """``n`` samples of the replay block into ``out``, wrapping."""
            done = 0
            while done < n:
                take = min(n - done, len(self.block) - self.pos)
                out[done:done + take] = self.block[self.pos:self.pos + take]
                self.pos = (self.pos + take) % len(self.block)
                done += take

        def _write(self, n: int, now_ns: int) -> None:
            out = self.output.slice()
            self._copy(out, n)
            first = self.written // self.frame
            self.written += n
            last = self.written // self.frame      # frames now complete
            for j in range(first, last):
                if j < len(self.frame_due_ns):
                    due = 0
                    if self.rate:
                        due = self.t0_ns + int(
                            ((j + 1) * self.frame + self.skipped)
                            / self.rate * 1e9)
                    self.frame_due_ns[j] = due
                    self.frame_written_ns[j] = now_ns
                    self.frame_blocked[j] = self._blocked
                self._blocked = False
            self.output.produce(n)

        async def work(self, io, mio, meta):
            if self.stop:
                io.finished = True
                return
            t_in = time.perf_counter_ns()
            if self.t0_ns is None:
                self.t0_ns = t_in
            space = self.output.space()
            if not self.rate:                      # closed loop
                if space:
                    self._write(space, t_in)
                    if self.record_spans:
                        self.spans.append((t_in, time.perf_counter_ns()))
                    io.call_again = True
                return                             # full: the reader's consume wakes us
            # open loop: what is due by now, in whole chunks
            due = int((t_in - self.t0_ns) * 1e-9 * self.rate) - self.skipped
            due -= due % self.chunk
            pending = due - self.written
            if pending > self.max_backlog:
                # overrun: drop the oldest whole frames, keep the rest due
                n_drop = (pending - self.max_backlog + self.frame - 1) // self.frame
                for i in range(n_drop):
                    self.dropped_due_ns.append(self.t0_ns + int(
                        (self.written + self.skipped + (i + 1) * self.frame)
                        / self.rate * 1e9))
                self.skipped += n_drop * self.frame
                pending -= n_drop * self.frame
            if pending > 0:
                n = min(pending, space)
                if n < pending:
                    self._blocked = True           # the ring, not the generator
                if n:
                    self._write(n, time.perf_counter_ns())
                    if self.record_spans:
                        self.spans.append((t_in, time.perf_counter_ns()))
                if n < pending:
                    time.sleep(0.0002)             # ring full: look again soon
                io.call_again = True
                return
            t_next = self.t0_ns + (self.written + self.skipped + self.chunk) \
                / self.rate * 1e9
            wait = (t_next - time.perf_counter_ns()) * 1e-9
            if wait > 0:
                time.sleep(wait)
            io.call_again = True

    class StampSink(Kernel):
        """Consumes everything, stamps the instant each frame's last item
        arrived, keeps the first ``keep_first`` frames and a seeded reservoir
        of ``reservoir`` frames of the window for the reference."""

        def __init__(self, dtype, out_frame: int, keep_first: int,
                     reservoir: int, seed: int):
            super().__init__()
            self.out_frame = out_frame
            self.total = 0
            self.stamps = np.zeros(1 << 16, np.int64)
            self.frames_done = 0
            self.keep_first = keep_first
            self.first = np.zeros((keep_first, out_frame), dtype)
            self.kept = np.zeros((reservoir, out_frame), dtype)
            self.kept_frame = [-1] * reservoir     # frame index per slot
            self.rng = np.random.default_rng(seed)
            self.in_window = False
            self.seen = 0
            self._slot = None                      # where the current frame goes
            self.input = self.add_stream_input("in", dtype)

        def _select(self, frame_idx: int):
            if frame_idx < self.keep_first:
                return self.first[frame_idx]
            if not self.in_window:
                return None
            self.seen += 1
            k = len(self.kept_frame)
            slot = self.seen - 1 if self.seen <= k \
                else int(self.rng.integers(self.seen))
            if slot >= k:
                return None
            self.kept_frame[slot] = frame_idx
            return self.kept[slot]

        async def work(self, io, mio, meta):
            inp = self.input.slice()
            n, done = len(inp), 0
            while done < n:
                off = self.total % self.out_frame
                if off == 0:
                    self._slot = self._select(self.total // self.out_frame)
                take = min(n - done, self.out_frame - off)
                if self._slot is not None:
                    self._slot[off:off + take] = inp[done:done + take]
                done += take
                self.total += take
                if self.total % self.out_frame == 0:
                    j = self.total // self.out_frame - 1
                    if j < len(self.stamps):
                        self.stamps[j] = time.perf_counter_ns()
                    self.frames_done = j + 1
            if n:
                self.input.consume(n)
            if self.input.finished():
                io.finished = True

    return ReplaySource, StampSink


def _wait_for(cond, timeout_s: float, what: str) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond():
        if time.monotonic() > deadline:
            raise TimeoutError(f"stream driver: {what} within {timeout_s} s")
        time.sleep(0.005)


def run(run: Run) -> Outcome:
    from futuresdr_tpu import Flowgraph, Runtime

    cell, cfg, tr = run.cell, run.cell.config, run.cell.traffic
    cm = cell.config_module
    ReplaySource, StampSink = _blocks()

    kernel = cm.make_kernel(cfg, run.rehearse)
    frame, out_frame = kernel.frame_size, kernel.out_frame
    replay = int(tr["replay_frames"])
    block = cm.make_input(cfg, run.seed, replay, frame)
    rate = float(tr.get("rate_msps", 0.0)) * 1e6 if tr["loop"] == "open" else 0.0
    if run.rehearse and rate:
        rate = float(tr.get("rehearsal_rate_msps", 1.0)) * 1e6
    chunk = min(int(tr.get("chunk_samples", frame)), frame)
    n_first = int(cfg["correctness"]["precheck_frames"])
    n_keep = int(cfg["correctness"]["sampled_frames"])
    src = ReplaySource(block, frame, rate, chunk,
                       int(tr.get("max_backlog_frames", 8)), run.trace)
    snk = StampSink(kernel.pipeline.out_dtype, out_frame, n_first, n_keep,
                    run.seed)
    fg = Flowgraph()
    fg.connect(src, kernel, snk)
    running = Runtime().start(fg)           # returns past the init barrier

    warm = int(tr["warmup_frames"])
    _wait_for(lambda: snk.frames_done >= warm, 600.0,
              f"{warm} warm-up frames at the sink")
    if run.trace:
        hostspans.drain_program_spans()              # what came before the window
        src.spans.clear()
    gcw = hostspans.GcWatch()
    m0 = kernel.extra_metrics()
    w0 = time.perf_counter_ns()
    snk.in_window = True
    if run.trace:
        traced = min(float(tr.get("traced_s", 4.0)), run.seconds / 2)
        time.sleep(run.seconds / 4)
        run.trace_window.start()
        time.sleep(traced)
        run.trace_window.stop()
    left = run.seconds - (time.perf_counter_ns() - w0) * 1e-9
    if left > 0:
        time.sleep(left)
    w1 = time.perf_counter_ns()
    snk.in_window = False
    m1 = kernel.extra_metrics()
    gcw.close()
    src.stop = True
    stuck = None                    # or why the flowgraph did not end
    try:
        running.wait_sync(timeout=float(tr["grace_s"]))
    except Exception as e:                              # noqa: BLE001
        stuck = repr(e)
    spans = hostspans.drain_program_spans() if run.trace else []
    if run.trace:
        spans += [HostSpan("bench", "generator", a, b - a) for a, b in src.spans]

    # -- the window's arithmetic ---------------------------------------------
    n_written = src.written // frame
    n_done = snk.frames_done
    stamps = snk.stamps[:n_done]
    notes = {"frame_size": frame, "wire": m1["wire"],
             "frames_written": n_written, "frames_at_sink": n_done,
             "frames_dropped": len(src.dropped_due_ns),
             "credits": m1["inflight_credits"], "stuck": stuck}
    stuck = stuck is not None
    notes["gc"] = gcw.summary(w0, w1)
    notes["jax_stages_in_window"] = run.meter.stages_between(w0, w1)[:20]
    if spans:
        notes["longest_spans"] = hostspans.longest(
            [s for s in spans if s.cat != "park"], w0)
    e2e, lat_ms, late_ms = {}, [], []
    in_win = (stamps >= w0) & (stamps < w1)
    if not rate:
        done_in = int(np.count_nonzero(in_win))
        attempted, failed = done_in, 0
        e2e["throughput_msps"] = done_in * frame / ((w1 - w0) * 1e-9) / 1e6
    else:
        due = src.frame_due_ns[:n_written]
        sel = np.nonzero((due >= w0) & (due < w1))[0]
        dropped = sum(1 for d in src.dropped_due_ns if w0 <= d < w1)
        missing = int(np.count_nonzero(sel >= n_done))
        got = sel[sel < n_done]
        lat_ms = ((stamps[got] - due[got]) * 1e-6).tolist()
        attempted = len(sel) + dropped
        failed = dropped + missing
        ok = ~src.frame_blocked[:n_written][sel]
        late_ms = ((src.frame_written_ns[:n_written][sel][ok] - due[sel][ok])
                   * 1e-6).tolist()
        if lat_ms:
            e2e["latency_p50_ms"] = stats.percentile(lat_ms, 50)
            e2e["latency_p95_ms"] = stats.percentile(lat_ms, 95)
        notes.update(drops_at_s=[round((d - w0) * 1e-9, 3)
                                 for d in src.dropped_due_ns][:40],
                     latency_max_ms=max(lat_ms) if lat_ms else None,
                     gen_late_max_ms=max(late_ms) if late_ms else None,
                     frames_blocked=int(np.count_nonzero(
                         src.frame_blocked[:n_written][sel])))
        notes.update(rate_msps=rate / 1e6, missing=missing,
                     gen_late_p95_ms=stats.percentile(late_ms, 95)
                     if late_ms else None)

    # -- correctness, outside the window -------------------------------------
    correct = not stuck
    frames = block.reshape(replay, frame)

    def ref_of(j: int) -> np.ndarray:
        hist = None if j == 0 else frames[(j - 1) % replay]
        return cm.reference(cfg, frames[j % replay], hist)

    ok, detail = cm.judge(
        cfg, snk.first[:min(n_first, n_done)].reshape(-1),
        np.concatenate([ref_of(j) for j in range(min(n_first, n_done))])
        if n_done else np.zeros(0), run.rehearse)
    notes["precheck"] = detail
    correct &= ok and n_done >= n_first
    kept = [(j, snk.kept[i]) for i, j in enumerate(snk.kept_frame)
            if 0 <= j < n_done]
    if kept:
        cache = {}
        for j, _ in kept:
            key = j % replay if j else -1
            if key not in cache:
                cache[key] = ref_of(j)
        ok, detail = cm.judge(
            cfg, np.concatenate([g for _, g in kept]),
            np.concatenate([cache[j % replay if j else -1] for j, _ in kept]),
            run.rehearse)
        notes["sampled"] = dict(detail, frames=len(kept))
        if not ok:
            failed += len(kept)
        correct &= ok
    correct &= len(kept) >= min(n_keep, max(1, attempted))
    # every item accounted for: what went in came out, 1 item per sample
    # (a partial last frame is flushed at the end of the stream, so samples
    # are compared, not whole frames)
    items_ok = (not stuck) and snk.total == src.written * out_frame // frame
    notes["items"] = {"in": src.written, "out": snk.total}
    correct &= items_ok
    exp = cfg.get("expected_on_chip", {})
    if not run.rehearse:
        for key in ("frame_size", "wire", "h2d_starts_per_frame"):
            if key in exp and m1.get(key) != exp[key]:
                notes[f"unexpected_{key}"] = m1.get(key)
                correct = False

    reading = Reading(
        driver="stream", window_ns=(w0, w1), unit="frame",
        unit_stamps_ns=stamps.copy(), spans=spans,
        counters={"h2d_starts_per_frame": m1["h2d_starts_per_frame"],
                  "frames_dispatched": m1["frames_dispatched"]
                  - m0["frames_dispatched"],
                  "frame_period_ms": frame / rate * 1e3 if rate else 0.0},
        latencies_ms=lat_ms, gen_late_ms=late_ms,
        compiles_in_window=len(run.meter.between(w0, w1)),
        cost_per_unit=cm.frame_cost(cfg, frame, m1["wire"]), peaks=run.peaks)
    return Outcome(correct=bool(correct), attempted=int(attempted),
                   failed=int(failed), window_start_ns=w0, end_to_end=e2e,
                   reading=reading,
                   host_spans_named=hostspans.named_for_breakdown(spans),
                   notes=notes)
