"""TpuKernel: run a fused stage pipeline on the TPU inside a flowgraph.

This is the TPU re-design of the reference's accelerator compute blocks
(``blocks/vulkan.rs:96+``, ``blocks/wgpu.rs:105+``) and their full/empty staging-buffer
circuits (``buffer/vulkan/h2d.rs``, SURVEY §3.5): stream samples are batched into fixed-size
frames, moved host→HBM with ``jax.device_put``, pushed through ONE jitted XLA program (the
fused block chain), and results stream back. Instead of the reference's explicit buffer
circulation, pipelining uses XLA's async dispatch: up to ``frames_in_flight`` frames are
enqueued with their carry chained on-device, so H2D transfer, compute, and D2H of
neighbouring frames overlap — the double-buffering of `SURVEY §7.5` without bespoke queues.

The block is ``BLOCKING`` (dedicated thread), so the host sync in result retrieval never
stalls the scheduler loop — the reference marks its hardware blocks ``#[blocking]`` the same
way (`seify/source.rs`).

The HOST side of the path is its own executor (docs/tpu_notes.md "The host
data path"): ring-exit staging copies, quantizing wire-encode payloads and
megabatch stacks live in a recycled buffer arena (``ops/arena.py`` — pinned
per dispatch group until its outputs drain, and by the replay log until a
checkpoint covers it, so recycling never aliases a retry/replay re-ship);
host encode/decode can ride a small worker pool (``ops/codec_pool.py`` —
encode offload for aliasing wires whose staging copy exists anyway, the
D2H-landing + decode lane for every wire), and the in-flight window is a
live credit budget (:class:`CreditController`) seeded by the
``autotune_streamed`` pick instead of a static depth.

Stream tags ride the device segment (SURVEY §7): each dispatched frame snapshots the
tags of its input window, their indices are rebased by the pipeline's rate contract
(the ``blocks/dsp.py`` remap; reference ``buffer/circular.rs:37-64``), and they are
re-emitted on the output stream when the frame's results drain — going beyond the
reference, whose GPU staging buffers drop tags.

Carry checkpoint/replay (docs/robustness.md "Device-plane recovery"): because
the compiled program is a pure function of (carry, frame), a ``restart``-policy
recovery does NOT have to forfeit in-flight frames. At a configurable cadence
(``checkpoint_every``, default each dispatch group; self-armed only when a
restart consumer exists — see ``_resolve_ckpt_every``) the kernel snapshots the
post-dispatch carry to the host — the copy rides the existing D2H lane and is
materialized before the next dispatch donates the buffers — and commits it once
that group's outputs have safely drained. Every dispatch group's host STAGING
parts (the same immutable copies the transfer-retry plane re-puts) stay in a
bounded replay log until a committed checkpoint covers them. :meth:`recover`
then restores the newest VALID checkpoint (seq + tree/shape/dtype integrity —
a corrupted candidate falls back to the previous one) and replays the logged
groups through the same program: outputs land bit-identical to an unfailed
run, on the actor path and on fused devchains alike. Megabatch groups replay
their exact shipped (zero-padded) stacks, so partial-batch semantics hold; a
fan-out kernel's flat composed carry checkpoints as one tree while its
per-branch drain cursors ride the drop-aware group metadata.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..log import logger
from ..ops import arena as _arena_mod
from ..ops import codec_pool as _codec_mod
from ..ops import xfer
from ..ops.stages import Pipeline, Stage
from ..telemetry import journal as _journal
from ..telemetry import lineage as _lineage
from ..telemetry import profile as _profile
from ..telemetry import prom as _prom
from ..telemetry.doctor import E2E_LATENCY as _E2E_LATENCY
from ..telemetry.spans import recorder as _trace_recorder
from ..runtime import faults as _faults
from ..runtime.kernel import Kernel, message_handler
from ..runtime.tag import ItemTag
from ..types import Pmt
from ..utils import snapshot as _snapshot
from .frames import emit_with_tags, rebase_frame_tags
from .instance import TpuInstance, instance

__all__ = ["TpuKernel", "TpuFanoutKernel", "TpuDagKernel",
           "CreditController"]

log = logger("tpu.kernel")
_trace = _trace_recorder()

# recovery-cost accounting (docs/observability.md): a fresh re-init drops the
# failed incarnation's consumed-but-unemitted frames; a checkpoint restore
# replays them from the host staging copies instead — both are billed so the
# cost of every recovery path is auditable from /metrics
_FORFEITED = _prom.counter(
    "fsdr_frames_forfeited_total",
    "in-flight frames dropped by a fresh device-kernel (re-)initialization",
    ("block",))
_REPLAYED = _prom.counter(
    "fsdr_frames_replayed_total",
    "frames replayed from host staging copies after a checkpoint restore",
    ("block",))


# single-thread executor for checkpoint persistence (snapshot writes +
# clean-EOS purges): ONE worker is the ordering guarantee — writes land
# newest-last and a purge queued after pending writes wins. (The codec
# pool's encode executor has several workers, so routing persistence
# through it let two writes share a tmp file and tear each other.) Shared
# with the serving plane's session store (utils/snapshot.py owns it now).
_persist_executor = _snapshot.persist_executor


def _stamp_metas(metas, lane: str, t_ns=None) -> None:
    """Lineage stamp for every SAMPLED frame of one dispatch group. Metas
    tuples carry the trace id LAST (``m[-1]``; 0 = unsampled), so the input
    form ``(valid_in, tags, t_in, tid)``, the single-output result form
    ``(valid_out, tags, t_in, tid)`` and the fan-out result form
    ``(per_branch, t_in, tid)`` all stamp through this one helper. The
    common (unsampled) case is one falsy check per frame — inside the ≤3%
    telemetry overhead budget."""
    for m in metas:
        if m[-1]:
            _lineage.tracer().stamp(m[-1], lane, t_ns)


def _settle_future(fut) -> None:
    """Wait out a codec-pool task, swallowing its outcome: quiescing before
    recovery/re-init only needs the task's side effects (replay-log insert,
    arena registration) to have landed — its error already surfaced (or will
    be superseded by the restart)."""
    try:
        fut.result()
    except BaseException:                  # noqa: BLE001 — quiesce only
        pass


class CreditController:
    """Adaptive in-flight credit budget for the streamed drain loop.

    Replaces the static ``frames_in_flight`` window with runtime credits:
    seeded by the ``autotune_streamed`` pick (or config), BOUNDED
    (``[lo, hi]``) and HYSTERETIC (at most ±1 per observation window, and a
    shrink needs two consecutive slack windows). Signals, all O(1) per
    dispatch, collected by ``TpuKernel._launch_staged``:

    * **grow** — the up-link idled between consecutive dispatch groups'
      modeled wire windows (the ``_wire`` attribute of the H2D finishes —
      populated under a fake/measured link) while the credit budget was the
      binding constraint (staged work waited on a full in-flight window):
      one more credit lets one more frame's wire time ride under compute.
    * **shrink** — the window never came within 2 credits of the budget for
      two consecutive windows and was never credit-limited: the budget is
      oversized; shrink toward what steady state actually uses (each unused
      credit is a frame of latency and device memory for nothing).
    * **rollback** — every grow is a PROBE: the next window's dispatch rate
      must improve by >5% or the grow reverts, and growing backs off
      EXPONENTIALLY on consecutive rollbacks (4, 8, 16 … windows). Wire
      idle that extra credits cannot cure (synchronous CPU compute pacing
      the loop, a genuinely host-bound cycle) — or that is just measurement
      noise on a loaded host — therefore cannot ratchet the budget up and
      hold latency hostage.

    Without a wire-window signal (a real backend with no fake link) the
    controller holds the seed — autotune's measured pick — rather than
    guessing from noise. An EXPLICIT depth (per-kernel ``frames_in_flight``
    argument or config ``tpu_inflight`` > 0) pins the budget entirely:
    ``adaptive=False`` makes every note a no-op, so depth=1 A/B baselines
    keep their strictly-serial contract.

    The serving plane reuses this controller verbatim for its overlapped
    step (``ServeEngine``, config ``serve_inflight``): one dispatch GROUP
    per credit instead of one frame, same signals, same hysteresis."""

    __slots__ = ("credits", "lo", "hi", "adaptive", "window",
                 "_prev_deadline", "_idle_s", "_limited", "_max_seen",
                 "_count", "_slack_windows", "_grow_windows", "_t0",
                 "_probe", "_hold", "_rollbacks")

    def __init__(self, seed: int, adaptive: bool, lo: int = 2,
                 hi: Optional[int] = None, window: int = 16):
        seed = max(1, int(seed))
        self.credits = seed
        self.adaptive = bool(adaptive) and seed > 1
        self.lo = min(lo, seed)
        # headroom is deliberately TIGHT (+2): the seed is autotune's
        # measured pick, adaptation is fine-tuning around it — and on a
        # loaded host, rate noise wins enough probes that a generous cap
        # would ratchet latency up for nothing
        self.hi = seed if not self.adaptive else \
            (hi if hi is not None else min(16, seed + 2))
        self.window = int(window)
        self._prev_deadline = 0.0
        self._idle_s = 0.0
        self._limited = False
        self._max_seen = 0
        self._count = 0
        self._slack_windows = 0
        self._grow_windows = 0       # consecutive idle+limited windows seen
        self._probe = None           # (credits before grow, rate before grow)
        self._hold = 0               # windows to skip growing after a rollback
        self._rollbacks = 0          # consecutive rollbacks (backoff exponent)
        self._t0 = time.perf_counter()

    def note_dispatch(self, wire: Optional[tuple], inflight: int) -> None:
        """One dispatch group launched: fold in its H2D wire window and the
        in-flight occupancy after the launch."""
        if not self.adaptive:
            return
        if wire:
            service, deadline = wire
            if deadline:
                if self._prev_deadline and service > self._prev_deadline:
                    self._idle_s += service - self._prev_deadline
                if deadline > self._prev_deadline:
                    self._prev_deadline = deadline
        if inflight > self._max_seen:
            self._max_seen = inflight
        self._count += 1
        if self._count >= self.window:
            self._tick()

    def note_limited(self) -> None:
        """Staged work is waiting because the in-flight window is full."""
        if self.adaptive:
            self._limited = True

    def _tick(self) -> None:
        span = max(time.perf_counter() - self._t0, 1e-9)
        rate = self._count / span          # dispatch groups per second
        if self._probe is not None:
            # last window grew the budget as a probe: keep it only if the
            # dispatch rate CLEARLY improved (>5% — under that, host-load
            # noise wins more probes than real wins do) — idle the extra
            # credit cannot cure must not ratchet the budget (and its
            # latency) up; consecutive rollbacks back off exponentially
            prev_credits, prev_rate = self._probe
            self._probe = None
            if rate < prev_rate * 1.05:
                self.credits = prev_credits
                self._hold = min(32, 4 << self._rollbacks)
                self._rollbacks += 1
            else:
                self._rollbacks = 0
        if self._hold > 0:
            self._hold -= 1
            self._grow_windows = 0
        elif self._limited and self._idle_s > 0.02 * span \
                and self.credits < self.hi:
            # hysteresis on the grow side too: one noisy window must not
            # trigger a probe (each probe costs a window at the new budget)
            self._grow_windows += 1
            if self._grow_windows >= 2:
                self._probe = (self.credits, rate)
                self.credits += 1
                self._grow_windows = 0
            self._slack_windows = 0
        elif not self._limited and self._max_seen <= self.credits - 2:
            self._grow_windows = 0
            self._slack_windows += 1
            if self._slack_windows >= 2 and self.credits > self.lo:
                self.credits -= 1
                self._slack_windows = 0
        else:
            self._slack_windows = 0
            self._grow_windows = 0
        self._count = 0
        self._idle_s = 0.0
        self._limited = False
        self._max_seen = 0
        self._t0 = time.perf_counter()


class WireController:
    """Mid-stream adaptive wire-format policy (opt-in: ``tpu_adaptive_wire``).

    Sits next to :class:`CreditController` in the drain loop and watches two
    live signals, both O(1) amortized per dispatch group:

    * **signal quality** — a strided sample of each staged frame's float
      components (peak + mean power). From it the controller PREDICTS the
      quantization SNR each ladder format would give the current signal:
      a uniform quantizer with step ``Δ = peak/qmax`` contributes
      ``Δ²/12`` noise power, so ``snr = p_mean / (Δ²/12)`` — the same
      model ``ops/wire.measure_snr_db`` verifies empirically.
    * **link occupancy** — the modeled wire windows the transfer plane
      attaches to each H2D finish (``_wire = (start, deadline)``, populated
      under a fake/measured link): the busy fraction of the inter-dispatch
      span. No wire signal (a real backend with no link model) reads as
      idle, so the controller can only ever WIDEN there — it will not
      chase throughput it cannot observe.

    Decisions are HYSTERETIC, mirroring the credit controller: windowed
    (``window`` dispatch groups per evaluation), two consecutive windows
    must agree before a switch is proposed, and a holdoff follows every
    switch so the ladder cannot oscillate. The policy:

    * WIDEN (toward f32) when the ACTIVE format's predicted SNR falls
      below the budget — the signal's dynamic range outgrew the wire.
    * NARROW (toward sc8) only when the link is BUSY (occupancy above
      ``occupancy_bar``) and the narrower format's predicted SNR clears
      the budget plus a safety margin — bytes are the bottleneck and the
      signal has headroom to spare.

    The controller only PROPOSES; the kernel applies the switch at a
    quiescent dispatch-group boundary (``_maybe_switch_wire``) so no
    in-flight frame ever spans two programs."""

    LADDER = ("f32", "sc16", "sc8")      # widest → narrowest
    QMAX = {"sc16": 32767.0, "sc8": 127.0}

    __slots__ = ("budget_db", "margin_db", "window", "holdoff",
                 "occupancy_bar", "_peak", "_power", "_nstat", "_busy_s",
                 "_count", "_vote", "_votes", "_hold", "_t0",
                 "last_snr_db")

    def __init__(self, budget_db: float, window: int = 16,
                 holdoff: int = 4, margin_db: float = 6.0,
                 occupancy_bar: float = 0.92):
        self.budget_db = float(budget_db)
        self.margin_db = float(margin_db)
        self.window = int(window)
        self.holdoff = int(holdoff)           # windows muted after a switch
        self.occupancy_bar = float(occupancy_bar)
        self.reset()

    def reset(self) -> None:
        self._peak = 0.0
        self._power = 0.0
        self._nstat = 0
        self._busy_s = 0.0
        self._count = 0
        self._vote = None            # format the current streak argues for
        self._votes = 0              # consecutive windows agreeing on it
        self._hold = 0
        self._t0 = time.perf_counter()
        self.last_snr_db = float("inf")   # the deciding window's active SNR

    # -- signal feeds --------------------------------------------------------
    def observe_frame(self, frame: np.ndarray) -> None:
        """Fold a strided sample of one staged frame's float components
        (≤512 points — the stats cost must vanish next to the encode)."""
        x = np.asarray(frame)
        if x.dtype.kind == "c":
            x = x.view(np.float64 if x.dtype == np.complex128
                       else np.float32)
        elif x.dtype.kind != "f":
            return                   # int passthrough: no quantization story
        x = x.reshape(-1)
        if not x.size:
            return
        s = np.abs(x[::max(1, x.size // 512)].astype(np.float32))
        peak = float(s.max())
        if peak > self._peak:
            self._peak = peak
        self._power += float(np.mean(np.square(s)))
        self._nstat += 1

    def note_dispatch(self, wire: Optional[tuple]) -> None:
        """Fold one dispatch group's H2D wire window (same tuple the credit
        controller reads)."""
        if wire:
            start, deadline = wire
            if deadline and deadline > start:
                self._busy_s += deadline - start
        self._count += 1

    # -- prediction ----------------------------------------------------------
    def predicted_snr_db(self, fmt: str) -> float:
        """The windowed signal's predicted SNR under ``fmt`` (inf for exact
        formats or when no stats accumulated)."""
        qmax = self.QMAX.get(fmt)
        if qmax is None or self._nstat == 0 or self._peak <= 0.0:
            return float("inf")
        p_mean = self._power / self._nstat
        if p_mean <= 0.0:
            return float("inf")
        delta = self._peak / qmax
        return 10.0 * float(np.log10(p_mean / (delta * delta / 12.0)))

    # -- decision ------------------------------------------------------------
    def propose(self, current: str) -> Optional[str]:
        """Evaluate at window boundaries; the target format after two
        agreeing windows, else None. Callers apply the switch themselves
        (at a quiescent boundary) — a returned proposal arms the holdoff."""
        if self._count < self.window or current not in self.LADDER:
            return None
        span = max(time.perf_counter() - self._t0, 1e-9)
        occupancy = min(1.0, self._busy_s / span)
        want = None
        pos = self.LADDER.index(current)
        self.last_snr_db = self.predicted_snr_db(current)
        if self.last_snr_db < self.budget_db and pos > 0:
            want = self.LADDER[pos - 1]                  # widen
        elif occupancy >= self.occupancy_bar and pos + 1 < len(self.LADDER) \
                and self.predicted_snr_db(self.LADDER[pos + 1]) \
                >= self.budget_db + self.margin_db:
            want = self.LADDER[pos + 1]                  # narrow
        # window bookkeeping (stats are per-window, votes persist across)
        self._peak = 0.0
        self._power = 0.0
        self._nstat = 0
        self._busy_s = 0.0
        self._count = 0
        self._t0 = time.perf_counter()
        if self._hold > 0:
            self._hold -= 1
            self._vote, self._votes = None, 0
            return None
        if want is None or want != self._vote:
            self._vote, self._votes = want, (1 if want else 0)
            return None
        self._votes += 1
        if self._votes < 2:
            return None
        self._vote, self._votes = None, 0
        self._hold = self.holdoff
        return want


class TpuKernel(Kernel):
    BLOCKING = True

    #: carry-donation setting for every compile of this kernel's program
    #: (init, warmup recompile, recover — ONE setting, so the jit cache never
    #: holds two executables of different aliasing for the same kernel).
    #: TpuDagKernel narrows it (see its override).
    _donate = True

    #: the last stage's ``counters`` of the group being emitted (tracing only)
    _emit_args: Optional[dict] = None

    def __init__(self, stages: Sequence[Stage], in_dtype,
                 frame_size: Optional[int] = None,
                 inst: Optional[TpuInstance] = None,
                 frames_in_flight: Optional[int] = None,
                 wire=None, frames_per_dispatch: Optional[int] = None,
                 checkpoint_every: Optional[int] = None,
                 interior_precision: Optional[str] = None,
                 _pipeline: Optional[Pipeline] = None):
        super().__init__()
        from ..config import config
        self.inst = inst or instance()
        self.pipeline = _pipeline if _pipeline is not None \
            else Pipeline(stages, in_dtype)
        self._apply_interior_precision(interior_precision)
        self._apply_pallas_blocks()
        fs = frame_size or self.inst.frame_size
        m = self.pipeline.frame_multiple
        self.frame_size = max(m, (fs // m) * m)
        self.out_frame = self.pipeline.out_items(self.frame_size)
        self.depth = frames_in_flight or self.inst.frames_in_flight
        self._depth_explicit = frames_in_flight is not None
        # megabatch K: lax.scan K frames through the compiled program per
        # dispatch (ops/stages.py wired_fn(k)) — per-call host overhead is paid
        # once per K frames instead of once per frame. A partial batch is only
        # flushed at EOS (zero-padded; pad outputs dropped): padding mid-stream
        # would corrupt the stage carries (filter history, oscillator phase)
        # of every later real frame, so K>1 trades up to K-1 frames of latency
        # while the input trickles.
        self.k_batch = max(1, int(frames_per_dispatch
                                  or config().tpu_frames_per_dispatch))
        # explicit per-kernel K (even K=1) must not be second-guessed by the
        # devchain's cached-autotune pick
        self._k_explicit = frames_per_dispatch is not None
        from ..ops.wire import resolve_wire
        # wire codec for both link crossings (None → config/auto, ops/wire.py):
        # decode/encode ride INSIDE the jitted program (compile_wired)
        self.wire = resolve_wire(wire, self.inst.platform)
        self._needs_staging = xfer.h2d_needs_staging(self.inst.platform)
        self._init_hostpath()
        self._compiled = None
        self._carry = None
        # frames consumed from the ring, awaiting a full K-batch (k_batch > 1
        # only): (host frame, valid_in, tags, t_in_ns, trace_id, handle)
        self._accum: List[tuple] = []
        # H2D started, compute not yet dispatched: (h2d_finish, metas, seq,
        # drop) with metas = one (valid_in, tags, t_in_ns, trace_id) per real
        # frame of the group; t_in_ns is the frame's ingestion stamp — the
        # doctor's end-to-end latency histogram measures ring-exit →
        # host-side decode per frame; trace_id is the frame's lineage sample
        # (telemetry/lineage.py; 0 = unsampled, always the LAST meta slot).
        # seq is the dispatch-group sequence number; drop marks a
        # replayed group whose outputs were already emitted before the fault
        # (the replay advances the carry, the emission is suppressed)
        self._staged: Deque[tuple] = deque()
        # compute dispatched, D2H riding: (d2h_finish, out_metas, seq, drop)
        # with out_metas = one (valid_out, rebased tags, t_in_ns, trace_id)
        # per frame
        self._inflight: Deque[tuple] = deque()
        self._init_recovery_state(checkpoint_every)
        self._e2e_hist = None         # bound at init (instance name is final)
        self._prof = None             # profile-plane entry, bound at init
        self._pending_out: Optional[np.ndarray] = None
        #: ``(seq, t_ins)`` of the group being emitted, set only while the
        #: span recorder is on: its ``frame`` spans close with its last item
        self._emitting: Optional[tuple] = None
        self._pending_tags: List[ItemTag] = []
        self._frames_dispatched = 0
        self._dispatches = 0
        self.input = self.add_stream_input("in", in_dtype, min_items=self.frame_size)
        self.output = self.add_stream_output(
            "out", self.pipeline.out_dtype, min_items=self.out_frame,
            min_buffer_size=(self.depth * self.k_batch + 1) * self.out_frame *
            np.dtype(self.pipeline.out_dtype).itemsize)

    def _apply_interior_precision(self, interior_precision=None) -> None:
        """Interior-precision lowering (ops/precision.py): the SNR-budgeted
        pass rewrites ``self.pipeline`` BEFORE anything derives from it
        (frame multiples, out frames, the cost registration). "off" (the
        default) never touches the object — the bit-identical contract. A
        failing calibration degrades to f32, never takes the kernel down.
        Shared by TpuKernel and TpuFanoutKernel construction."""
        from ..config import config
        self._base_pipeline = self.pipeline
        self._precision_mode = str(
            interior_precision if interior_precision is not None
            else config().get("interior_precision", "off") or "off")
        self._precision_overrides: dict = {}
        self._precision_plan = None
        if self._precision_mode in ("", "off"):
            return
        from ..ops import precision as _precision_mod
        try:
            self._precision_overrides = _precision_mod.parse_overrides(
                config().get("interior_precision_overrides", ""))
            self.pipeline, self._precision_plan = \
                _precision_mod.plan_interior_precision(
                    self.pipeline, mode=self._precision_mode,
                    overrides=self._precision_overrides)
        except Exception as e:                 # noqa: BLE001 — degrade to f32
            log.warning("%s: interior-precision lowering failed (%r); "
                        "staying f32", type(self).__name__, e)
            self.pipeline = self._base_pipeline
            self._precision_plan = None

    def _apply_pallas_blocks(self) -> None:
        """Install this chain's cached Pallas block sweep (the
        ``pallas_blocks`` autotune axis, tpu/pallas_tune.py) BEFORE the
        program compiles — ``impl="pallas"`` stages resolve ``block=None``
        against the process-wide tuned table at trace time, so a cached
        winner reaches every kernel without a per-stage parameter. No
        cache entry for this chip generation (or any lookup failure)
        leaves the hand-picked defaults in place. Shared by TpuKernel and
        TpuFanoutKernel construction."""
        try:
            from ..ops.pallas_kernels import set_tuned_blocks
            from .autotune import cached_pallas_blocks
            from .pallas_tune import device_key
            sig = self.pipeline \
                if getattr(self.pipeline, "n_branches", 0) \
                else self.pipeline.stages
            blocks = cached_pallas_blocks(sig, self.pipeline.in_dtype,
                                          self.inst.platform, device_key())
        except Exception:              # noqa: BLE001 — defaults only
            return
        if blocks:
            set_tuned_blocks(blocks)
            log.info("%s: pallas block shapes from cached sweep: %s",
                     type(self).__name__, blocks)

    def _init_hostpath(self) -> None:
        """Host-data-path state shared by TpuKernel and TpuFanoutKernel
        construction (docs/tpu_notes.md "The host data path"): the staging
        arena, the codec worker pool, and the in-flight credit controller.
        Requires ``self.depth`` / ``self._depth_explicit`` / ``self.wire`` /
        ``self.pipeline`` to be set. Resolves the credit SEED: an explicit
        per-kernel depth pins it; else config ``tpu_inflight`` > 0 pins that
        value; else the seed is the cached ``autotune_streamed`` pick's
        winning depth (falling back to the instance default) and the
        controller adapts at runtime."""
        from ..config import config
        self._arena = _arena_mod.arena()
        self._codec_pool = _codec_mod.pool()
        adaptive = not self._depth_explicit
        if not self._depth_explicit:
            pinned = int(config().get("tpu_inflight", 0))
            if pinned > 0:
                self.depth = pinned
                adaptive = False
            else:
                try:
                    from .autotune import cached_streamed_pick
                    sig = self.pipeline \
                        if getattr(self.pipeline, "n_branches", 0) \
                        else self.pipeline.stages
                    pick = cached_streamed_pick(sig, self.pipeline.in_dtype,
                                                self.inst.platform)
                except Exception:              # noqa: BLE001 — seed only
                    pick = None
                if pick and pick.get("inflight"):
                    self.depth = int(pick["inflight"])
                    log.info("%s: in-flight credit seed %d from cached "
                             "autotune_streamed pick",
                             type(self).__name__, self.depth)
        self._credits = CreditController(self.depth, adaptive=adaptive)
        # H2D staging read-ahead BEYOND the in-flight budget: at steady state
        # the in-flight deque is full, so without extra headroom a frame would
        # be staged and launched in the same work cycle — its wire time would
        # serialize after the previous frame's compute instead of riding under
        # it (depth=1 keeps 0: strictly serial semantics for A/B baselines)
        self.stage_ahead = 1 if self.depth > 1 else 0
        # ---- the single-shot uplink plane (docs/tpu_notes.md) --------------
        self._resolve_uplink()
        self._ingest_frames = 0
        self._staged_frames = 0
        self._consume_event = None     # armed per staged frame (see _stage_*)
        self._pending_consume = None   # (event, n_items, seq) awaiting consume()
        # mid-stream adaptive wire switching (off by default: the wire is
        # part of the numerics contract) — controller lives in _init_wirectl
        self._init_wirectl()

    def _resolve_uplink(self) -> None:
        """(Re-)derive how a frame leaves the ring and crosses the link from
        the CURRENT ``(wire, in_dtype, k_batch)``. Called at construction
        and again by every wire switch.

        * ``_packed``: the coalescing layout (``ops/xfer.PackedLayout.probe``).
          Multi-part wires (quantizers shipping payload + scale) pack a
          dispatch group into ONE contiguous buffer, shipped as 32-bit words
          and unpacked by a slicing prolog fused into the program
          (``ops/stages.packed_wired_fn``); ``_word_slots`` counts the slots
          that prolog decodes a word a sample (sc16 under a complex dtype).
          None for single-part wires: they already cost one H2D start, and
          packing would add a copy of the f32 pairs view for nothing. A
          probe failure falls back to the per-part path.
        * ``_encode_offload``: the wire's host encode ALIASES its input (f32
          pairs view), so the frame pays the ring-exit staging copy
          regardless and shipping encode + H2D start to a codec worker is
          free.
        * ``_ingest_enabled``: on such a wire a frame backed by a REGISTERED
          externally-owned read-only buffer (``ops/ingest.py``) skips that
          copy.
        * ``_deferred_consume``: a quantizing wire at K=1 has the worker's
          encode read the ring slot IN PLACE (``consume()`` waits until it
          has), so only the int payload lands in the arena. At K>1 a frame
          waits in ``_accum`` past ``consume()``, so it is copied out and
          the group encodes on the staging thread."""
        self._packed = None
        try:
            self._packed = xfer.PackedLayout.probe(
                self.wire, self.frame_size, self.pipeline.in_dtype,
                k=self.k_batch)
        except Exception as e:         # noqa: BLE001 — per-part fallback
            log.warning("%s: uplink coalescing probe failed (%r) — "
                        "shipping per-part", type(self).__name__, e)
        self._word_slots = sum(
            self.pipeline.pair_word_slots(self.wire, self._packed))
        aliases = self.wire.encode_may_alias(self.pipeline.in_dtype)
        self._encode_offload = aliases
        self._ingest_enabled = aliases
        self._deferred_consume = not aliases and self.k_batch == 1

    def _init_wirectl(self) -> None:
        """Arm the adaptive wire controller (``tpu_adaptive_wire``, off by
        default: the wire format is part of the numerics contract, so
        retuning it mid-stream must be an explicit opt-in). Disarms itself
        when the starting wire is off the controller's ladder (bf16,
        passthrough) or the input is not float/complex — there is no
        quantization-SNR story to steer by."""
        from ..config import config
        self._wire0 = self.wire.name        # the built format (restore base)
        self._wire_floor_fmt = self.wire.name
        # (seq, fmt) per applied switch, seq = first dispatch group shipped
        # under fmt — pruned by the committed-checkpoint floor exactly like
        # the retune log, replayed by recover() so a restore point before a
        # switch re-applies it at its original group boundary
        self._wire_log: Deque[tuple] = deque()
        self._replay_wire_switches: Deque[tuple] = deque()
        self._wire_switch_target = None     # proposed, awaiting quiescence
        self._wire_switches = 0
        self._wirectl = None
        if not bool(config().get("tpu_adaptive_wire", False)):
            return
        if self.wire.name not in WireController.LADDER or \
                np.dtype(self.pipeline.in_dtype).kind not in "fc":
            log.info("%s: adaptive wire disarmed (wire %s / in dtype %s "
                     "off the f32/sc16/sc8 ladder)", type(self).__name__,
                     self.wire.name, np.dtype(self.pipeline.in_dtype))
            return
        self._wirectl = WireController(
            float(config().get("tpu_wire_snr_budget_db", 40.0)))
        # arming the controller hands it the wire format — start from the
        # point the last autotune_streamed measured fastest for this chain
        # (the round-22 "wire" axis of the streamed-pick cache) instead of
        # the build-time default; the live SNR/occupancy windows take over
        # from there. Construction-time swap: nothing is compiled yet, so
        # this is a re-derivation, not a switch (the replay log stays empty
        # and _wire0/_wire_floor_fmt rebase onto the adopted format).
        try:
            from .autotune import cached_wire_start
            sig = self.pipeline if getattr(self.pipeline, "n_branches", 0) \
                else self.pipeline.stages
            fmt = cached_wire_start(sig, self.pipeline.in_dtype,
                                    self.inst.platform)
        except Exception:                  # noqa: BLE001 — seed only
            fmt = None
        if fmt and fmt != self.wire.name and fmt in WireController.LADDER:
            from ..ops.wire import get_wire
            log.info("%s: adaptive wire starts at %s (cached "
                     "autotune_streamed pick; built %s)",
                     type(self).__name__, fmt, self.wire.name)
            self.wire = get_wire(fmt)
            self._wire0 = self._wire_floor_fmt = fmt
            self._resolve_uplink()

    def _adopt_credit_mode(self, adaptive: bool) -> None:
        """Re-arm the credit controller post-construction. The device-graph
        fusion builders pass the members' depth as an explicit argument
        (which pins credits), but whether the FUSED kernel may adapt follows
        the members' own explicitness — a chain of default-depth kernels
        keeps its adaptive budget across fusion. A config ``tpu_inflight``
        pin always wins: "N>0 pins the budget" must survive fusion too."""
        from ..config import config
        if int(config().get("tpu_inflight", 0)) > 0:
            adaptive = False
        self._credits = CreditController(self.depth, adaptive=adaptive)

    def extra_metrics(self) -> dict:
        # the scrape thread reads the replay log while codec workers insert
        # into it out of band — same lock as every other rlog access
        with self._rlog_lock:
            replay_frames = sum(len(m) for _, _, m, _ in self._rlog)
        return {
            "frame_size": self.frame_size,
            "wire": self.wire.name,
            "frames_per_dispatch": self.k_batch,
            "frames_staged": sum(len(m) for _, m, _, _ in self._staged)
            + len(self._accum),
            "frames_in_flight": sum(len(m) for _, m, _, _ in self._inflight),
            "frames_dispatched": self._frames_dispatched,
            "dispatches": self._dispatches,
            "inflight_credits": self._credits.credits,
            "checkpoint_every": self._ckpt_every,
            "checkpoint_seq": self._ckpts[-1][0] if self._ckpts else -1,
            "replay_log_frames": replay_frames,
            "interior_precision": self._precision_mode,
            "interior_lowered": (self._precision_plan.lowered
                                 if self._precision_plan is not None else 0),
            # the single-shot uplink plane: physical h2d starts per dispatch
            # group (coalesced multi-part wires collapse to 1; single-part
            # wires were already 1), the zero-copy ingest hit fraction, and
            # the adaptive-wire policy state
            "uplink_coalesced": int(self._packed is not None),
            "uplink_word_slots": self._word_slots,
            "h2d_starts_per_frame": (
                1 if self._packed is not None
                else self.wire.part_count(self.pipeline.in_dtype)),
            "ingest_zero_copy_frac": (
                self._ingest_frames / self._staged_frames
                if self._staged_frames else 0.0),
            "deferred_consume": int(self._deferred_consume),
            "adaptive_wire": int(self._wirectl is not None),
            "wire_switches": self._wire_switches,
        }

    def _warm_parts(self, jax, in_dtype) -> tuple:
        """Device input parts for a compile-cache warmup call: an encode of
        zeros, K-stacked for megabatch programs, packed into one buffer when
        the uplink coalesces (the warm call must trace the SAME program
        signature the hot path dispatches). Raw ``device_put`` — the fake
        link must not bill warmup bytes."""
        parts = self.wire.encode_host(
            np.zeros(self.frame_size, dtype=in_dtype))
        if self.k_batch > 1:
            parts = tuple(np.stack([np.asarray(p)] * self.k_batch)
                          for p in parts)
        if self._packed is not None:
            words = self._packed.pack([np.asarray(p) for p in parts],
                                      np.empty(self._packed.nbytes, np.uint8))
            return (jax.device_put(words, self.inst.device),)
        return tuple(jax.device_put(np.asarray(p), self.inst.device)
                     for p in parts)

    async def init(self, mio, meta):
        import jax
        # fresh-incarnation contract: init drops every trace of a previous
        # incarnation — staged/in-flight dispatch groups, accumulated
        # megabatch frames, pending host output — and recompiles a FRESH
        # carry below. Dropped frames are billed (their input was already
        # consumed; fsdr_frames_forfeited_total). The RECOVERY path under a
        # `restart` policy goes through :meth:`recover` instead, which
        # restores the last committed checkpoint and replays the logged
        # groups bit-correct; init is only the fallback when no usable
        # checkpoint exists (checkpoint_every=0, or every candidate invalid).
        # quiesce codec-pool tasks first: a straggling encode worker must not
        # insert into the replay log after the reset below clears it, and
        # arena buffers must be registered before they are released
        self._settle_staged()
        # drop-flagged replayed groups are excluded everywhere: their outputs
        # were already emitted, so losing them forfeits nothing
        forfeit = len(self._accum) \
            + sum(len(m) for _, m, _, d in self._staged if not d) \
            + sum(len(m) for _, m, _, d in self._inflight if not d) \
            + sum(len(m) for _, _, m, d in self._replay_queue if not d)
        if forfeit:
            if self._forfeit_ctr is None:
                self._forfeit_ctr = _FORFEITED.labels(
                    block=self.meta.instance_name or type(self).__name__)
            self._forfeit_ctr.inc(forfeit)
            log.warning("%s: fresh re-init forfeits %d in-flight frame(s)",
                        self.meta.instance_name, forfeit)
        for entry in self._accum:          # arena staging copies of queued
            h = entry[4]                   # megabatch frames die with them
            if h is not None:
                h.release()
        self._accum.clear()
        self._staged.clear()
        self._inflight.clear()
        self._pending_out = None
        self._pending_tags = []
        self._emitting = None              # (seq, t_ins) while tracing
        self._recovery_reset()
        self._ckpt_every = self._resolve_ckpt_every()
        prog_name = self.meta.instance_name or type(self).__name__
        self._e2e_hist = _E2E_LATENCY.labels(
            source=self.meta.instance_name or "TpuKernel")
        # compile observability (telemetry/profile.py): the whole
        # compile+warm window is billed (fsdr_compiles_total{program,reason}
        # + fsdr_compile_seconds) and visible to the doctor's "compiling"
        # verdict — a long first compile of a big fused devchain must never
        # false-trip the watchdog as a deadlock. First init is `warmup`;
        # a restart's fresh re-init is `reinit` (storm-detection signal).
        reason = "warmup" if self._compiled is None else "reinit"
        prog_sig = (f"frame={self.frame_size},wire={self.wire.name},"
                    f"k={self.k_batch}")
        # lifecycle journal (telemetry/journal.py): a fresh (re-)init is a
        # DECISION — a restart that forfeited frames must tell the
        # post-mortem how many, next to the recover/replay events
        _journal.emit("kernel", "init", block=prog_name, reason=reason,
                      forfeited=forfeit)
        with _profile.compiling(prog_name, reason, prog_sig):
            self._compiled, self._carry = self.pipeline.compile_wired(
                self.frame_size, self.wire, device=self.inst.device,
                k=self.k_batch, donate=self._donate, packed=self._packed)
            # warm the compile cache off the hot path (raw device_put: the
            # fake link must not bill warmup bytes), then reset carry state
            dev = self._warm_parts(jax, self.pipeline.in_dtype)
            warm_carry, y = self._compiled(self._carry, *dev)
            jax.block_until_ready(y)
        del warm_carry  # donated buffers; fresh carry below
        _, self._carry = self.pipeline.compile_wired(
            self.frame_size, self.wire, device=self.inst.device,
            k=self.k_batch, donate=self._donate, packed=self._packed)
        # roofline attribution: register the DISPATCHED program form's
        # cost_analysis() flops/bytes (wired + megabatch scan) — lazily, so
        # init pays nothing; the cost-analysis AOT compile happens once per
        # signature when the profile plane is actually read (ensure_costs)
        pipe, fs, wn, kb = self.pipeline, self.frame_size, self.wire.name, \
            self.k_batch

        def _program_cost():
            from ..utils.roofline import program_cost
            return program_cost(pipe, fs, wire=wn, k=kb)

        from ..utils.roofline import dominant_dtype
        self._prof = _profile.register(prog_name, cost_thunk=_program_cost,
                                       dtype=dominant_dtype(pipe.stages))
        # interior-precision observability: the applied plan lands under the
        # SAME program name the profile plane bills (doctor.report() and the
        # REST profile view read the registry), and the APPLIED mode rides
        # the streamed-pick cache next to (k, inflight, serve_buckets) —
        # recorded unconditionally ("off" included), else a kernel reverted
        # to off would leave a previous round's "bf16" stamp describing the
        # wrong program for every later cached-K launch
        if self._precision_plan is not None:
            from ..ops import precision as _precision_mod
            _precision_mod.note_plan(prog_name, self._precision_plan)
        try:
            from .autotune import (cached_interior_precision,
                                   record_interior_precision)
            sig = self._base_pipeline \
                if getattr(self._base_pipeline, "n_branches", 0) \
                else self._base_pipeline.stages
            mode = self._precision_mode or "off"
            if mode != "off" or cached_interior_precision(
                    sig, self.pipeline.in_dtype,
                    self.inst.platform) is not None:
                # off-mode kernels only CORRECT an existing entry (a stale
                # "bf16" from a previous round must not describe an f32
                # rebuild) — they never create entries for untuned chains
                record_interior_precision(sig, self.pipeline.in_dtype,
                                          self.inst.platform, mode)
        except Exception:                      # noqa: BLE001 — cache only
            pass
        if self._ckpt_every:
            # fresh-init sentinel: "restore = recompile the init carry" — a
            # fault before the first committed checkpoint replays from the
            # very first group (the log holds everything until a commit)
            self._ckpts.append((-1, None, None))

    @message_handler(name="ctrl")
    async def ctrl_handler(self, io, mio, meta, p: Pmt) -> Pmt:
        """Runtime stage control: ``{"stage": <name-or-index>, <param>: <value>, …}``.

        Swaps carry-resident parameters (FIR taps, rotator phase_inc, …) between
        dispatches — frames already in flight finish with the old values, every
        later frame uses the new ones; no recompile, no pipeline stall. The
        device-path retune of the reference's fm-receiver ``freq`` handler
        (``examples/fm-receiver/src/main.rs:83-155``)."""
        from .frames import parse_ctrl
        try:
            stage, params = parse_ctrl(p)
            if set(params) == {"interior_precision"}:
                # per-stage precision retune: re-plan + recompile, carry
                # converted in place (apply_precision_retune docstring)
                self.apply_precision_retune(stage,
                                            params["interior_precision"])
                return Pmt.ok()
            if self._carry is None:
                # the runtime's init barrier answers pre-init messages itself
                # (init() compiles the carry eagerly), so this only triggers on
                # direct handler calls before init
                raise RuntimeError("ctrl before init")
            self.apply_retune(stage, params)
        except Exception as e:
            log.warning("ctrl update rejected: %r", e)
            return Pmt.invalid_value()
        return Pmt.ok()

    def apply_retune(self, stage, params: dict) -> None:
        """Replay-exact carry surgery — THE retune entry point (the ctrl
        handler and the devchain member-addressed path both land here).

        Normal operation: the surgery applies immediately (frames in flight
        keep the old parameters, later dispatches see the new ones) and is
        LOGGED against the next dispatch-group sequence number, pruned by
        the same committed-checkpoint floor as the replay log. A later
        checkpoint recovery whose restore point precedes a logged retune
        RE-APPLIES it at exactly its original group boundary
        (:meth:`_launch_staged`), so the recovered stream reproduces the
        original retune frame instead of losing the surgery to the restored
        (pre-retune) carry.

        Inside an active replay window the surgery is instead DEFERRED to
        the post-replay boundary (``_replay_high + 1``): the replayed frames
        re-dispatch with their ORIGINAL parameters — bit-identical to the
        unfailed run — and the new retune lands right after the window,
        which is exactly "now" in the recovered timeline. The PR 8
        structured warning survives, upgraded from "recovered output may
        differ" to reporting the exactness-preserving deferral."""
        if self._replay_pending():
            # validate the FULL surgery FIRST — stage address AND params —
            # by applying it to the current carry and discarding the result
            # (functional update, side-effect free): a bad retune must
            # reject at the call site, because the deferred application
            # cannot answer the caller (address-only validation would
            # return ok and then silently drop an unknown-param retune).
            # Validation precedes the deferral warning so a rejected retune
            # never logs a deferral that will not happen.
            self.pipeline.update_stage(self._carry, stage, **params)
            self.warn_retune_in_replay()
            entry = (self._replay_high + 1, stage, dict(params))
            self._replay_retunes.append(entry)
            if self._ckpt_every:
                self._retune_log.append(entry)
            _journal.emit("kernel", "retune",
                          block=self.meta.instance_name, stage=str(stage),
                          params=sorted(params), deferred=True)
            return
        self._carry = self.pipeline.update_stage(self._carry, stage, **params)
        _journal.emit("kernel", "retune", block=self.meta.instance_name,
                      stage=str(stage), params=sorted(params),
                      deferred=False)
        if self._ckpt_every:
            # the new parameters are visible from the oldest
            # staged-but-unlaunched group onward (frames the credit budget is
            # holding back dispatch with the mutated carry), not from the next
            # group to be STAGED — log the boundary replay must reproduce
            seq = self._staged[0][2] if self._staged else self._seq
            self._retune_log.append((seq, stage, dict(params)))

    def apply_precision_retune(self, stage, precision) -> None:
        """Per-stage interior-precision retune (the ctrl verb
        ``{"stage": <name-or-index>, "interior_precision": "off"|"auto"|
        "bf16"|"int8"}``). Unlike a parameter retune this is a PROGRAM
        change, so it re-plans the lowering from the pristine pipeline with
        the stage pinned, recompiles (billed ``reason="reinit"`` on the
        profile plane — visible, never a silent storm), and CONVERTS the
        live carry leaf-by-leaf into the new program's dtypes — streaming
        state (filter history, oscillator phase) survives the precision
        flip. Frames already in flight finish under the old program; the
        next dispatch uses the new one. Checkpoints of the old incarnation
        fail the restore-path dtype integrity check and fall back — honest,
        never corrupting."""
        import jax
        import jax.numpy as jnp

        from ..ops import precision as _precision_mod
        prec = str(precision)
        if prec not in ("off", "auto", "bf16", "int8"):
            raise ValueError(f"interior_precision retune {prec!r}: expected "
                             f"off|auto|bf16|int8")
        # resolve the stage against the BASE pipeline (lowering keeps names).
        # Overrides are NAME-keyed (the config-string contract), so a retune
        # cannot address one of two same-named stages — reject ambiguity
        # instead of silently lowering both (update_stage's name rule; an
        # index resolving to a duplicated name is just the name form in
        # disguise and gets the same rejection)
        base = self._base_pipeline
        names = [s.name for s in base.stages]
        if isinstance(stage, str):
            if stage not in names:
                raise KeyError(f"no stage named {stage!r} in {names}")
            name = stage
        else:
            idx = int(stage)
            if not 0 <= idx < len(base.stages):
                raise KeyError(f"stage index {idx} out of range "
                               f"({len(base.stages)} stages)")
            name = names[idx]
        if names.count(name) > 1:
            raise KeyError(
                f"stage name {name!r} is ambiguous (appears "
                f"{names.count(name)}x) — interior-precision overrides are "
                f"name-keyed; give the stages distinct name= arguments")
        if self._precision_mode in ("", "off"):
            # an "off" kernel entering the planner via a single-stage retune
            # must stay a SINGLE-stage change: pin every other stage "off" so
            # switching the plan mode to "auto" cannot silently lower the
            # rest of the chain (later retunes overwrite their own pin)
            for s in base.stages:
                self._precision_overrides.setdefault(s.name, "off")
        self._precision_overrides[name] = prec
        mode = self._precision_mode if self._precision_mode not in ("", "off") \
            else "auto"
        new_pipe, plan = _precision_mod.plan_interior_precision(
            base, mode=mode, overrides=self._precision_overrides)
        assert new_pipe.frame_multiple == self.pipeline.frame_multiple, \
            "lowering must preserve the rate contract"
        if new_pipe is self.pipeline:
            # no-op retune (e.g. pinning "off" on an already-off kernel):
            # the program is unchanged, so no recompile, no mode flip — the
            # override is kept so a LATER retune of another stage honors it
            log.info("%s: interior precision retune %s=%s is a no-op "
                     "(program unchanged)",
                     getattr(self.meta, "instance_name", None)
                     or type(self).__name__, name, prec)
            return
        if self._carry is None:
            # pre-init: init() compiles whatever self.pipeline holds
            self.pipeline = new_pipe
            self._precision_plan = plan
            self._precision_mode = mode
            return
        old_carry = self._carry
        prog_name = self.meta.instance_name or type(self).__name__
        with _profile.compiling(prog_name, "reinit",
                                f"precision:{name}={prec}"):
            self._compiled, fresh = new_pipe.compile_wired(
                self.frame_size, self.wire, device=self.inst.device,
                k=self.k_batch, donate=self._donate, packed=self._packed)
            dev = self._warm_parts(jax, new_pipe.in_dtype)
            warm_carry, y = self._compiled(fresh, *dev)
            jax.block_until_ready(y)
        del warm_carry
        # convert the LIVE carry into the new program's leaf dtypes: same
        # stage structure by construction, so the trees match — only leaf
        # dtypes (bf16 weight matrices) change. Direction matters:
        # NARROWING (f32→bf16) casts the old leaf, preserving any runtime
        # parameter retune at exactly the loss the lowering was budgeted
        # for; WIDENING (bf16→f32) takes the PRISTINE template leaf —
        # upcasting the old values would freeze the narrow incarnation's
        # quantization into a program that claims full precision (lowering
        # only changes PARAMETER leaf dtypes, so the template leaf IS the
        # full-precision parameter; a tap retune applied under the old
        # incarnation must be re-sent — logged).
        template = new_pipe.init_carry()
        o_leaves, o_def = jax.tree_util.tree_flatten(old_carry)
        t_leaves, t_def = jax.tree_util.tree_flatten(template)
        if o_def == t_def and all(
                np.shape(a) == np.shape(b)
                for a, b in zip(o_leaves, t_leaves)):
            from ..ops.xfer import to_device
            conv, rederived = [], 0
            for a, b in zip(o_leaves, t_leaves):
                da = np.dtype(getattr(a, "dtype", np.float32))
                db = np.dtype(getattr(b, "dtype", np.float32))
                if da == db:
                    conv.append(a)
                elif db.itemsize > da.itemsize:
                    conv.append(to_device(np.asarray(b), self.inst.device))
                    rederived += 1
                else:
                    conv.append(jnp.asarray(a).astype(db))
            self._carry = jax.tree_util.tree_unflatten(t_def, conv)
            if rederived:
                log.info("%s: precision retune re-derived %d widened "
                         "parameter leaf(s) from build-time values — "
                         "re-send any runtime tap/parameter retunes",
                         prog_name, rederived)
        else:                                  # pragma: no cover — structure
            log.warning("%s: precision retune could not convert the live "
                        "carry (structure changed); streaming state reset",
                        prog_name)
            self._carry = jax.device_put(template, self.inst.device)
        self.pipeline = new_pipe
        self._precision_plan = plan
        self._precision_mode = mode
        _precision_mod.note_plan(prog_name, plan)
        # the registered cost thunk must describe the NEW program (the old
        # closure would mis-cost every later MFU gauge); re-registration also
        # restarts the run-average window at this incarnation
        fs2, wn2, kb2 = self.frame_size, self.wire.name, self.k_batch

        def _cost():
            from ..utils.roofline import program_cost
            return program_cost(new_pipe, fs2, wire=wn2, k=kb2)

        from ..utils.roofline import dominant_dtype
        self._prof = _profile.register(prog_name, cost_thunk=_cost,
                                       dtype=dominant_dtype(new_pipe.stages))
        log.info("%s: interior precision retune %s=%s (lowered %d stage(s), "
                 "min SNR %s dB)", prog_name, name, prec, plan.lowered,
                 plan.min_snr_db)

    def apply_wire_retune(self, fmt: str) -> None:
        """Request a mid-stream wire-format switch (the ctrl-style manual
        entry point; the adaptive controller lands on the same path). The
        switch is DEFERRED to the next quiescent dispatch-group boundary —
        no in-flight frame may span two wire programs — and applied by
        :meth:`_maybe_switch_wire` from the staging loop."""
        from ..ops.wire import WIRE_FORMATS
        fmt = str(fmt)
        if fmt not in WIRE_FORMATS:
            raise ValueError(f"unknown wire format {fmt!r} "
                             f"(expected one of {sorted(WIRE_FORMATS)})")
        if fmt == self.wire.name:
            return
        self._wire_switch_target = fmt

    def _apply_wire_program(self, fmt: str, reason: str = "adaptive") -> None:
        """Swap the wire codec and rebuild everything derived from it — the
        PROGRAM-change surgery of the adaptive wire plane. Must run at a
        dispatch-group boundary: the live path enters via
        :meth:`_maybe_switch_wire` only when nothing is staged or in flight;
        the replay path applies it between groups in ``_launch_staged``
        (younger in-flight groups decode with their dispatch-time codec —
        ``_wrap_landing`` captures it). The carry is wire-INDEPENDENT (the
        codec lives at the program boundary, not in the state), so unlike a
        precision retune no leaf conversion is needed; the recompile is
        billed ``reason="reinit"`` on the profile plane and the switch lands
        in the event journal."""
        import jax
        from ..ops.wire import get_wire
        if fmt == self.wire.name:
            return
        old = self.wire.name
        self.wire = get_wire(fmt)
        self._resolve_uplink()
        if getattr(self, "_part_counts", None) is not None:
            self._part_counts = self.pipeline.part_counts(self.wire)
        self._wire_switches += 1
        prog_name = self.meta.instance_name or type(self).__name__
        if self._carry is not None:
            # recompile + warm with a scratch carry: the LIVE carry must
            # survive (donation would eat it), and switching BACK to a
            # previously-used format hits the cached wired fn / jit entry
            with _profile.compiling(prog_name, "reinit", f"wire:{fmt}"):
                self._compiled, fresh = self.pipeline.compile_wired(
                    self.frame_size, self.wire, device=self.inst.device,
                    k=self.k_batch, donate=self._donate,
                    packed=self._packed)
                dev = self._warm_parts(jax, self.pipeline.in_dtype)
                warm_carry, y = self._compiled(fresh, *dev)
                jax.block_until_ready(y)
            del warm_carry
            # the registered cost thunk must describe the NEW wire program
            pipe, fs2, wn2, kb2 = self.pipeline, self.frame_size, \
                self.wire.name, self.k_batch

            def _cost():
                from ..utils.roofline import program_cost
                return program_cost(pipe, fs2, wire=wn2, k=kb2)

            from ..utils.roofline import dominant_dtype
            self._prof = _profile.register(
                prog_name, cost_thunk=_cost,
                dtype=dominant_dtype(pipe.stages))
        _journal.emit("kernel", "wire-switch", block=prog_name,
                      old=old, new=fmt, reason=reason, seq=int(self._seq))
        log.info("%s: wire switched %s -> %s (%s) at group %d", prog_name,
                 old, fmt, reason, self._seq)

    def _maybe_switch_wire(self) -> None:
        """The staging-loop gate of the adaptive wire plane: collect the
        controller's proposal, then apply the pending switch once the
        dispatch window is QUIESCENT (nothing staged, in flight, accumulated
        or consume-deferred reads the old program). While a target is
        pending the staging loop pauses and ``work()`` drains toward the
        boundary."""
        if self._wire_switch_target is None:
            if self._wirectl is None or self._replay_pending():
                return               # controller paused inside a replay
            tgt = self._wirectl.propose(self.wire.name)
            if tgt is None:
                return
            self._wire_switch_target = tgt
            log.info("%s: adaptive wire proposes %s -> %s (snr %.1f dB, "
                     "budget %.1f dB) — draining to the switch boundary",
                     self.meta.instance_name or type(self).__name__,
                     self.wire.name, tgt, self._wirectl.last_snr_db,
                     self._wirectl.budget_db)
        if self._staged or self._inflight or self._accum or \
                self._replay_queue or self._pending_consume is not None:
            return
        tgt, self._wire_switch_target = self._wire_switch_target, None
        if self._ckpt_every:
            # replay contract: seq = the first group shipped under the new
            # format (nothing is staged, so the next staged group is _seq)
            self._wire_log.append((self._seq, tgt))
        self._apply_wire_program(tgt)

    def _apply_replay_retunes(self, seq: int) -> None:
        """Re-apply logged carry surgery at its ORIGINAL dispatch boundary:
        called by :meth:`_launch_staged` before dispatching group ``seq``,
        this lands every queued retune recorded at or before that group —
        during replay the recovered carry walks through exactly the
        parameter timeline of the unfailed run (and a mid-replay retune's
        deferred boundary lands right after the window)."""
        while self._replay_retunes and self._replay_retunes[0][0] <= seq:
            _, stage, params = self._replay_retunes.popleft()
            try:
                self._carry = self.pipeline.update_stage(
                    self._carry, stage, **params)
            except Exception as e:                     # noqa: BLE001
                # the surgery validated cleanly when accepted — a failure
                # here can only follow a pipeline contract change; narrowing
                # the replay to parameter-divergent is the honest fallback
                log.warning("%s: replayed retune @%d failed (%r) — recovered "
                            "output may diverge at that boundary",
                            self.meta.instance_name, seq, e)

    def _replay_pending(self) -> int:
        """Frames of the active replay window still in flight (0 = no
        active window; a fully-drained window disarms)."""
        if self._replay_high < 0:
            return 0
        pending = sum(len(m) for _, _, m, _ in self._replay_queue)
        pending += sum(len(m) for _, m, s, _ in self._staged
                       if s <= self._replay_high)
        pending += sum(len(m) for _, m, s, _ in self._inflight
                       if s <= self._replay_high)
        if pending == 0:
            self._replay_high = -1       # window fully drained: disarm
        return pending

    def warn_retune_in_replay(self) -> int:
        """Structured observability for retunes landing inside an active
        checkpoint-replay window (docs/robustness.md): since the
        replay-aware retune upgrade the surgery is deferred to the
        post-replay boundary (see :meth:`apply_retune`) so recovered output
        stays bit-identical — the warning now reports that deferral instead
        of a divergence. Returns the pending replayed-frame count (0 = no
        active replay window)."""
        pending = self._replay_pending()
        if pending == 0:
            return 0
        log.warning(
            "%s: ctrl retune landed inside an active replay window — "
            "deferred to the post-replay boundary (seq %d) so the %d "
            "replayed frame(s) still in flight re-dispatch with their "
            "ORIGINAL parameters and recovered output stays bit-identical "
            "to the unfailed run (docs/robustness.md replay-aware retunes)",
            self.meta.instance_name or type(self).__name__,
            self._replay_high + 1, pending)
        return pending

    # -- helpers ---------------------------------------------------------------
    def _stage(self, frame: np.ndarray, valid_in: int,
               tags: Sequence[ItemTag] = (), handle=None,
               t_in: Optional[int] = None) -> None:
        """Queue one frame toward a dispatch group. ``k_batch == 1``: encode
        into wire parts and START its H2D immediately (compute dispatch waits
        for :meth:`_launch_staged`) — the encode and the H2D start run on a
        codec worker so they ride under this thread's dispatch of older
        frames. ``k_batch > 1``: accumulate until the group
        fills, then :meth:`_flush_accum` ships the whole batch as one
        transfer. ``valid_in`` (a frame_multiple multiple) bounds how much of
        the output is real data vs zero-pad tail; ``tags`` are
        frame-relative; ``handle`` is the arena buffer backing ``frame``
        (None when the frame is allocation-fresh). ``t_in`` is the frame's
        ingestion stamp, taken before the staging copy where there is one
        (the ``frame`` span and the e2e latency start there)."""
        if t_in is None:
            t_in = time.perf_counter_ns()
        self._staged_frames += 1
        if self._wirectl is not None:
            self._wirectl.observe_frame(frame)
        # frame-lineage sampling (telemetry/lineage.py): 1-in-N frames get a
        # trace id that rides the metas through every pipeline boundary;
        # stride 0 makes sample() one falsy check, tid 0 makes every
        # downstream stamp site one falsy check per frame
        tid = _lineage.tracer().sample()
        if tid:
            _lineage.tracer().stamp(tid, "ingest", t_in)
        if self.k_batch == 1:
            self._submit_group([frame],
                               ((valid_in, tuple(tags), t_in, tid),),
                               [handle] if handle is not None else [])
            return
        self._accum.append((frame, valid_in, tuple(tags), t_in, tid, handle))
        if len(self._accum) >= self.k_batch:
            self._flush_accum()

    def _encode_group(self, frames: list, frame_handles: list,
                      seq: Optional[int] = None) -> tuple:
        """Encode one dispatch group's frames into wire parts (``k>1``:
        stacked along a leading frame axis, into recycled arena buffers) and
        partition the arena buffers by lifetime: aliasing encodes' parts are
        views of the staging frame (the f32 pairs view), so that frame's
        handle must stay PINNED with the group; every other staging frame
        dies with the encode and its handle is merely RELEASABLE — the
        caller releases on success, or leaves ownership with the restored
        input retention on a fatal H2D start (``_flush_accum``). Runs on the
        staging thread or a codec worker — either way the encode span lands
        in the running thread's ring, so the doctor's lane unions attribute
        the host codec time to where it was actually paid.

        Returns ``(parts, pinned_handles, releasable_handles)``."""
        if self._packed is not None:
            return self._encode_group_packed(frames, frame_handles, seq)
        t0 = _trace.now() if _trace.enabled else 0
        alloc = _arena_mod.GroupAlloc(self._arena)
        if self.k_batch == 1:
            frame = frames[0]
            parts = self.wire.encode_into(frame, alloc)
            aliases = self.wire.encode_may_alias(frame.dtype)
            pinned = (list(frame_handles) if aliases else []) + alloc.handles
            rel = [] if aliases else list(frame_handles)
            if t0:
                _trace.complete("tpu", "encode", t0,
                                args={"wire": self.wire.name,
                                      "items": len(frame), "seq": seq})
            return parts, pinned, rel
        # megabatch: the staging frames never alias the stacked parts, so
        # every frame handle is releasable
        parts = self._encode_stacked(frames, alloc)
        if t0:
            _trace.complete("tpu", "encode", t0,
                            args={"wire": self.wire.name,
                                  "items": len(frames) * self.frame_size,
                                  "frames": len(frames), "seq": seq})
        return parts, alloc.handles, list(frame_handles)

    def _encode_stacked(self, frames: list, alloc) -> tuple:
        """Encode a megabatch's frames and stack each wire part along a
        leading frame axis. The per-frame encodes are SCRATCH (they ride the
        temp side of ``alloc`` and are dropped before return); the K-stacked
        copies are the group's payload, allocated ``(k,) + shape`` from
        ``alloc`` itself — under a :class:`~futuresdr_tpu.ops.arena.PackedAlloc`
        exactly the layout's slots, so the stack writes land at their packed
        offsets directly."""
        sub = alloc.temps_only()
        parts_list = [self.wire.encode_into(f, sub) for f in frames]
        stacked = []
        for j in range(len(parts_list[0])):
            rows = [np.asarray(p[j]) for p in parts_list]
            out = alloc((len(rows),) + rows[0].shape, rows[0].dtype)
            for i, r in enumerate(rows):
                out[i] = r
            stacked.append(out)
        alloc.drop_temps()
        return tuple(stacked)

    def _encode_group_packed(self, frames: list, frame_handles: list,
                             seq: Optional[int] = None) -> tuple:
        """Coalesced-uplink form of :meth:`_encode_group`: every wire part of
        the dispatch group lands in ONE contiguous packed buffer
        (``ops/arena.PackedAlloc`` — the encode writes payloads through slot
        views, so coalescing costs zero extra payload copies; bare parts
        like the quantizer's scale scalar are settled in by
        ``PackedLayout.pack``). The group ships as a single-element part
        tuple, the buffer as the ``uint32`` words the program takes, so the
        transfer plane bills ONE h2d start with the summed bytes, the replay
        log retains the EXACT shipped array, and a retry/replay re-ships
        identical packed words. Packed wires are
        quantizers — their parts never alias the staging frame — so every
        frame handle is releasable."""
        lay = self._packed
        t0 = _trace.now() if _trace.enabled else 0
        alloc = _arena_mod.PackedAlloc(self._arena, lay)
        if self.k_batch == 1:
            parts = self.wire.encode_into(frames[0], alloc)
        else:
            parts = self._encode_stacked(frames, alloc)
        packed = alloc.finish(parts)
        if t0:
            _trace.complete("tpu", "encode", t0,
                            args={"wire": self.wire.name,
                                  "items": len(frames) * self.frame_size,
                                  "frames": len(frames),
                                  "packed_bytes": lay.nbytes,
                                  "uplink_word_slots": self._word_slots,
                                  "seq": seq})
        return (packed,), alloc.handles, list(frame_handles)

    def _rlog_insert(self, seq: int, parts: tuple, metas: tuple,
                     handles) -> None:
        """Insert one group into the replay log in SEQUENCE order (codec
        workers may complete out of order), retaining its arena buffers for
        the log's lifetime. The leak guard of the old append path applies:
        commits normally prune the log, but PERSISTENT snapshot failures
        would grow it without bound — past several windows' worth the head
        is dropped, and recovery then declines non-contiguous checkpoints
        and falls back to the billed forfeiting re-init instead of the
        process leaking until OOM."""
        for h in handles:
            h.retain()
        dropped = False
        with self._rlog_lock:
            entry = (seq, parts, metas, tuple(handles))
            if not self._rlog or self._rlog[-1][0] < seq:
                self._rlog.append(entry)
            else:
                i = 0
                for i, e in enumerate(self._rlog):      # noqa: B007
                    if e[0] > seq:
                        break
                self._rlog.insert(i, entry)
            cap = 64 + 4 * (self.depth + self.stage_ahead + self._ckpt_every)
            while len(self._rlog) > cap:
                _, _, _, hs = self._rlog.popleft()
                for h in hs:
                    h.release()
                self._rlog_dropped += 1
                dropped = self._rlog_dropped == 1
        if dropped:
            log.warning(
                "%s: replay log exceeded its cap (checkpoints not "
                "committing?) — dropping oldest; a restart may now "
                "forfeit instead of replaying", self.meta.instance_name)

    def _submit_group(self, frames: list, metas: tuple,
                      frame_handles: list) -> None:
        """Route one dispatch group toward the wire.

        Synchronous (a quantizing wire at K>1, or its zero-padded last
        frame at K=1: neither offload nor deferred consume, see
        :meth:`_resolve_uplink`): encode on the staging thread, then
        :meth:`_stage_group` starts the H2D and logs the group only AFTER
        the start succeeds (a fatally-failed start leaves the input in its
        previous retention: the ring for ``k==1``, or ``_accum`` restored by
        ``_flush_accum``).

        Encode offload (the wire's encode aliases) or a deferred-consume
        staged frame: encode AND the H2D start run on a codec worker — the
        encode(t+1) ∥ H2D(t) lanes. The frames already left the ring at
        submit (consume() runs right after ``_stage`` returns), so the
        replay log is the group's ONLY retention: this path logs BEFORE the
        start attempt, and a fatally-failed start surfaces at the join in
        :meth:`_launch_staged` with the group still replayable (and still
        counted by the forfeit accounting when checkpointing is off)."""
        # the pool path runs for aliasing-wire encode offload AND for a
        # deferred-consume staged frame (quantizing K=1: the worker's encode
        # reads the ring slot in place; ev signals the slot has been read so
        # the staging loop may consume() — ops/ingest + docs/tpu_notes.md)
        ev = self._consume_event
        self._consume_event = None
        if not (self._encode_offload or ev is not None):
            parts, pinned, rel = self._encode_group(frames, frame_handles,
                                                    self._seq)
            _stamp_metas(metas, "encode")
            # a fatal start releases `pinned` inside _stage_group and leaves
            # `rel` with the restored input retention (_flush_accum puts the
            # frames — still backed by those buffers — back into _accum)
            self._stage_group(parts, metas, pinned)
            for h in rel:
                h.release()
            return
        seq = self._seq
        self._seq = seq + 1
        ck = self._ckpt_every

        def task():
            try:
                parts, pinned, rel = self._encode_group(frames,
                                                        frame_handles, seq)
            finally:
                if ev is not None:
                    # the encode has read (or abandoned) the ring slot —
                    # the deferred consume() may advance the reader
                    ev.set()
            # stamped on the codec WORKER thread — the flow link then renders
            # the encode hop where the work actually ran
            _stamp_metas(metas, "encode")
            for h in rel:      # pool-mode frames never return to a ring
                h.release()
            if ck:
                self._rlog_insert(seq, parts, metas, pinned)
            if pinned:
                self._group_handles[seq] = pinned
            return xfer.start_device_transfer_parts(parts, self.inst.device,
                                                    seq)

        try:
            fut = self._codec_pool.submit_encode(task)
        except BaseException:
            if ev is not None:
                ev.set()       # never leave the staging loop waiting
            raise

        def join():
            fin = fut.result()
            join._wire = getattr(fin, "_wire", None)
            return fin()

        join._settle = lambda: _settle_future(fut)
        self._staged.append((join, metas, seq, False))

    def _stage_group(self, parts: tuple, metas: tuple,
                     handles: Sequence = ()) -> None:
        """Synchronous-path H2D start + sequence assignment + replay
        logging (see :meth:`_submit_group` for the retention contract).
        ``handles`` are the arena buffers backing ``parts`` — released here
        on a fatal start (the input retention reverts to the ring/_accum),
        pinned with the group otherwise."""
        seq = self._seq             # assigned only once the start succeeded
        try:
            fin = xfer.start_device_transfer_parts(parts, self.inst.device,
                                                   seq)
        except BaseException:
            for h in handles:
                h.release()
            raise
        self._seq = seq + 1
        if handles:
            self._group_handles[seq] = list(handles)
        if self._ckpt_every:
            self._rlog_insert(seq, parts, metas, handles)
        self._staged.append((fin, metas, seq, False))

    def _settle_staged(self) -> None:
        """Quiesce pool-mode tasks still running for this kernel (exceptions
        swallowed — they already surfaced, or the restart supersedes them):
        recovery and re-init must observe a settled replay log and a
        complete arena-handle registry before clearing either."""
        # a deferred ring consume must land first: the frame was staged and
        # logged, so leaving it unconsumed would re-deliver it after recovery
        self._settle_deferred_consume()
        for dq in (self._staged, self._inflight):
            for entry in dq:
                s = getattr(entry[0], "_settle", None)
                if s is not None:
                    s()

    def _flush_accum(self) -> None:
        """Encode the accumulated frames, stack each wire part along a leading
        ``[k]`` frame axis and start ONE H2D for the dispatch group. A partial
        group (EOS only) is zero-padded to the static scan length; the pad
        frames' outputs are dropped at drain (no meta entry) and their carry
        effect is moot — nothing real follows them."""
        if not self._accum:
            return
        group, self._accum = self._accum, []
        frames = [f for f, _, _, _, _, _ in group]
        while len(frames) < self.k_batch:
            frames.append(np.zeros(self.frame_size,
                                   dtype=self.pipeline.in_dtype))
        metas = tuple((v, t, tin, tid) for _, v, t, tin, tid, _ in group)
        handles = [h for _, _, _, _, _, h in group if h is not None]
        # the stacked (zero-padded) parts are what the replay log retains, so
        # a replayed partial EOS batch re-ships the exact same scan payload.
        # On the synchronous path a fatally-failed start restores the group
        # to _accum: its frames already left the ring, and only _accum (or
        # the replay log) may retain them — the restored entries keep their
        # arena handles (releasable ones are only released on success), so
        # the arena cannot recycle a buffer a restored frame still views.
        try:
            self._submit_group(frames, metas, handles)
        except Exception:
            self._accum = group + self._accum
            raise

    def _start_result_d2h(self, y_parts, metas, seq=None) -> tuple:
        """Start the D2H of one dispatch group's results and build its
        in-flight entry ``(finish, out_metas)`` — the single-output form;
        :class:`TpuFanoutKernel` overrides with the per-branch form. Starting
        the transfer immediately means it rides the wire the moment the frame
        finishes instead of waiting for _drain_one's sync (read-ahead,
        VERDICT r2 weak 2)."""
        finish = xfer.start_host_transfer_parts(y_parts, seq)
        out_metas = []
        for valid_in, tags, t_in, tid in metas:
            valid_out = min(self.pipeline.out_items(valid_in),
                            self.out_frame)
            out_metas.append((valid_out,
                              tuple(rebase_frame_tags(tags, self.pipeline,
                                                      valid_out)),
                              t_in, tid))
        return (finish, tuple(out_metas))

    def _launch_staged(self) -> None:
        """Dispatch compute for staged groups, oldest first, and start each
        result's D2H immediately (:meth:`_start_result_d2h`). Waiting happens
        only on the OLDEST group's remaining H2D wire time — younger frames
        keep transferring, dispatched frames keep computing, finished frames'
        D2H keeps draining: the H2D(t+1) ∥ compute(t) ∥ D2H(t−1) overlap of
        the reference's circulating h2d/d2h staging pairs, on XLA's async
        dispatch queue (encode and decode are the codec pool's own lanes
        around it). The in-flight bound is the credit
        controller's LIVE budget, not the construction-time depth. Shared
        verbatim by the fan-out kernel — only the result-side hook differs."""
        fplan = _faults.plan()
        while self._staged and len(self._inflight) < self._credits.credits:
            if fplan.armed():
                # `dispatch` site (runtime/faults.py): fault BEFORE the group
                # leaves the staging deque, so recovery replays (or
                # fail_fast/isolate forfeit) a deterministic amount of work
                fplan.maybe("dispatch", self.meta.instance_name)
            # peek-then-pop: a pool-mode group whose H2D start failed fatally
            # raises at the join below with the group STILL staged — the
            # forfeit accounting and the replay log both keep sight of it
            h2d, metas, seq, drop = self._staged[0]
            t0 = _trace.now() if _trace.enabled else 0
            x_parts = h2d()
            if t0:
                # blocked on the codec worker's encode + H2D start (pool
                # mode), or on a fake link's modelled wire
                _trace.complete("tpu", "h2d_wait", t0,
                                args={"seq": seq, "at": "launch"})
            self._staged.popleft()
            _stamp_metas(metas, "H2D")
            # replay-aware retunes: logged carry surgery recorded at or
            # before this group re-applies NOW, at its original boundary
            # (empty deque outside recovery — one truthiness check)
            if self._replay_retunes:
                self._apply_replay_retunes(seq)
            # replay-aware wire switches: a logged format switch recorded at
            # or before this group re-applies NOW, so every replayed group
            # dispatches under the exact program (and packed layout) that
            # first shipped it — bit-exact through the switch boundary
            while self._replay_wire_switches and \
                    self._replay_wire_switches[0][0] <= seq:
                self._apply_wire_program(
                    self._replay_wire_switches.popleft()[1],
                    reason="replay")
            # donation fence: the snapshot D2H of the previous carry must be
            # host-side before this dispatch donates and reuses its buffers
            self._materialize_pending_ckpts()
            t0 = _trace.now() if _trace.enabled else 0
            self._carry, y_parts = self._compiled(self._carry, *x_parts)
            if t0:
                # the enqueue call: dispatch on accelerators, the execution
                # itself on the CPU backend (synchronous jit). `program`
                # beside it ends when the OUTPUTS are ready (the watcher's
                # stamp): the device's queue wait + run time. The carry is
                # donated to the next call and is never watched
                args = {"frame": self.frame_size, "frames": len(metas),
                        "seq": seq}
                _trace.complete("tpu", "compute", t0, args=args)
                xfer.watch(y_parts, "program", t0, args)
            _stamp_metas(metas, "dispatch")
            fin, out_metas = self._start_result_d2h(y_parts, metas, seq)
            self._inflight.append(
                (self._wrap_landing(fin, out_metas, drop, seq), out_metas,
                 seq, drop))
            self._checkpoint_tick(seq)
            self._frames_dispatched += len(metas)
            self._dispatches += 1
            if self._prof is not None:
                # live-roofline unit: ONE dispatch group (the registered
                # cost covers the whole wired megabatch program); the
                # group stamp is this drive loop's clock to pay, keeping
                # the per-call hook itself a bare add
                self._prof.dispatch(t=time.monotonic())
            self._credits.note_dispatch(getattr(h2d, "_wire", None),
                                        len(self._inflight))
            if self._wirectl is not None:
                self._wirectl.note_dispatch(getattr(h2d, "_wire", None))
        if self._staged and len(self._inflight) >= self._credits.credits:
            self._credits.note_limited()

    def _wrap_landing(self, finish, out_metas, drop: bool, seq=None):
        """Turn one dispatch group's D2H finish into a zero-arg ``land()``
        yielding the DECODED payload (None for a drop-marked replayed group —
        its transfer still lands, the duplicate emission is suppressed).
        The whole landing — D2H wire wait + host decode — runs on a decode
        worker starting NOW, so decode(t−1) rides
        under this thread's staging/dispatch of younger frames; emission
        order is preserved because the caller joins the in-flight deque
        oldest-first."""
        # decode with the codec active at DISPATCH time: during an adaptive
        # wire switch's replay window, in-flight groups may precede a
        # re-applied switch — each must land under its own wire
        wire = self.wire

        def land():
            raw = finish()
            _stamp_metas(out_metas, "D2H")
            if drop:
                return None
            payload = self._decode_group(raw, out_metas, wire, seq)
            _stamp_metas(out_metas, "decode")
            return payload

        fut = self._codec_pool.submit_decode(land)

        def join():
            return fut.result()

        join._settle = lambda: _settle_future(fut)
        return join

    def _decode_group(self, raw, out_metas, wire=None, seq=None):
        """Host-decode one landed dispatch group (runs on the drain thread,
        or on a codec worker under the pool; ``wire`` is the codec captured
        at dispatch — see :meth:`_wrap_landing`). Returns
        ``(result, tags, t_ins)``."""
        wire = wire if wire is not None else self.wire
        t0 = _trace.now() if _trace.enabled else 0
        if self.k_batch == 1:
            ((valid, tags, t_in, _tid),) = out_metas
            arr = wire.decode_host(raw, self.pipeline.out_dtype)
            result, all_tags = arr[:valid], list(tags)
            t_ins = (t_in,)
        else:
            chunks, all_tags, off = [], [], 0
            for i, (valid, tags, _tin, _tid) in enumerate(out_metas):
                row = tuple(p[i] for p in raw)
                chunks.append(
                    wire.decode_host(row, self.pipeline.out_dtype)[:valid])
                all_tags.extend(ItemTag(t.index + off, t.tag) for t in tags)
                off += valid
            result = (np.concatenate(chunks) if chunks
                      else np.empty(0, dtype=self.pipeline.out_dtype))
            t_ins = tuple(tin for _, _, tin, _ in out_metas)
        if t0:
            _trace.complete("tpu", "decode", t0,
                            args={"wire": wire.name,
                                  "items": len(result), "seq": seq})
        return result, all_tags, t_ins

    def _drain_one(self) -> Optional[Tuple[np.ndarray, list]]:
        land, out_metas, seq, _drop = self._inflight.popleft()
        # sync point: blocks only this block's thread (joins the decode
        # worker's already-running landing task)
        payload = self._land(land, seq)
        if payload is None:
            # replayed group whose outputs were emitted before the fault: the
            # replay only re-advanced the carry — suppress the duplicate
            self._note_drained(seq)
            return None
        result, all_tags, t_ins = payload
        end = time.perf_counter_ns()
        if _trace.enabled:
            self._emitting = (seq, t_ins)    # closed by _emit's last item
            counters = self.pipeline.stages[-1].counters
            if counters is not None:     # what the program found in the frame
                self._emit_args = counters(result)
        if self._e2e_hist is not None:
            # per-frame end-to-end latency: ring exit → decoded host result
            # (encode + H2D queue/wire + compute + D2H + decode; the doctor's
            # p50/p99 stamp and ``fsdr_e2e_latency_seconds{source}``). Frames
            # of one megabatch group land together — each still observes its
            # OWN ingestion stamp, so K>1 trickle latency stays visible.
            for tin in t_ins:
                self._e2e_hist.observe((end - tin) * 1e-9)
        self._finish_lineage(out_metas, end)
        # mark drained only AFTER the decode succeeded: a fault inside the
        # decode/rebase window must replay this group WITH its outputs, not
        # drop them as already-emitted
        self._note_drained(seq)
        return result, all_tags

    def _land(self, land, seq):
        """``land()`` under the ``d2h_wait`` span: this thread blocked until
        the group's results are on the host and decoded."""
        if not _trace.enabled:
            return land()
        t0 = _trace.now()
        payload = land()
        _trace.complete("tpu", "d2h_wait", t0, args={"seq": seq})
        return payload

    def _emit(self, output, data: np.ndarray, tags) -> tuple:
        """``emit_with_tags`` under the ``emit`` span (the copy into the
        output ring); returns its ``(pending_data, pending_tags)``."""
        if not _trace.enabled:
            return emit_with_tags(output, data, tags)
        t0 = _trace.now()
        rest = emit_with_tags(output, data, tags)
        n = len(data) - (0 if rest[0] is None else len(rest[0]))
        em = self._emitting
        args = {"seq": em[0] if em else None, "bytes": n * data.itemsize}
        if self._emit_args:
            args.update(self._emit_args)
            self._emit_args = None
        _trace.complete("tpu", "emit", t0, args=args)
        return rest

    def _close_frames(self) -> None:
        """The emitting group's last output item is in the output ring: one
        ``frame`` span per input frame of the group, from its ingestion
        stamp — the parent of the frame's other spans."""
        (seq, t_ins), self._emitting = self._emitting, None
        for t_in in t_ins:
            _trace.complete("tpu", "frame", t_in, args={"seq": seq})

    def _finish_lineage(self, out_metas, end_ns: int) -> None:
        """Emit-stamp + finalize the lineage records of a drained group's
        sampled frames, attaching each one's e2e latency as an OpenMetrics
        exemplar on the histogram (telemetry/prom.py) so a dashboard bucket
        links to a concrete trace. One falsy check per frame when nothing
        was sampled; a replayed frame whose record already finished is a
        silent no-op inside the tracer."""
        for m in out_metas:
            tid = m[-1]
            if not tid:
                continue
            lin = _lineage.tracer()
            lin.stamp(tid, "emit", end_ns)
            lin.finish(tid, source=getattr(
                getattr(self, "meta", None), "instance_name", None)
                or type(self).__name__)
            if self._e2e_hist is not None:
                self._e2e_hist.exemplar((end_ns - m[-2]) * 1e-9, tid)

    # -- carry checkpoint/replay (docs/robustness.md "Device-plane recovery") --
    def _init_recovery_state(self, checkpoint_every) -> None:
        """Checkpoint/replay state (module docstring), shared by TpuKernel and
        TpuFanoutKernel construction — ONE definition of the recovery-state
        invariants (cadence clamp, 2-deep checkpoint ring)."""
        from ..config import config
        # configured cadence: snapshot every Nth dispatch group; 0 disables
        # checkpointing entirely (restart falls back to fresh-carry
        # forfeiture) and MUST be free on the dispatch path (the telemetry
        # overhead gate covers it)
        self._ckpt_cadence = max(0, int(
            checkpoint_every if checkpoint_every is not None
            else config().tpu_checkpoint_every))
        self._ckpt_explicit = checkpoint_every is not None
        # ACTIVE cadence, re-resolved at init(): only a restart consumer (a
        # restart policy on this kernel / the config default / a restartable
        # fused chain) or an explicit per-kernel cadence can ever read a
        # checkpoint, so default fail_fast runs skip the snapshot D2H and
        # the replay-log staging retention entirely
        self._ckpt_every = self._ckpt_cadence if self._ckpt_explicit else 0
        self._seq = 0                    # next dispatch-group sequence number
        self._drained_seq = -1           # newest group whose outputs drained
        # replay log: (seq, host wire parts, metas, arena handles) per
        # un-covered dispatch group — the parts are the idempotent host
        # STAGING copies the transfer-retry plane already relies on (no
        # extra copy); the handles PIN the arena buffers backing them so
        # recycling can never alias a frame fault recovery may re-ship
        self._rlog: Deque[tuple] = deque()
        # codec workers insert into the log out of band — one lock guards
        # every rlog mutation (insert, prune, cap-drop, clear)
        self._rlog_lock = threading.Lock()
        # seq -> arena handles of the group's live staging buffers, released
        # when the group's outputs drain (or at forfeiture)
        self._group_handles: Dict[int, list] = {}
        # cross-process checkpoint persistence (docs/robustness.md): each
        # commit also lands on disk when `checkpoint_dir` is set, and
        # recover() falls back to it when no in-kernel state survives.
        # Writes COALESCE through a one-slot latest box: at most one write
        # task is queued per kernel, and it drains the NEWEST snapshot — a
        # disk slower than the commit rate skips intermediate snapshots
        # instead of backlogging MB-scale carries without bound.
        d = str(config().get("checkpoint_dir", "") or "")
        self._ckpt_dir = os.path.expanduser(d) if d else ""
        self._persist_lock = threading.Lock()
        self._persist_box = None         # newest un-written (seq, leaves)
        self._persist_queued = False
        # committed checkpoints (seq, host leaves | None, treedef | None),
        # newest last; ring of 2 so a corrupted candidate can fall back to
        # the previous one. (seq=-1, None, None) is the fresh-init sentinel.
        self._ckpts: Deque[tuple] = deque(maxlen=2)
        # snapshots taken at dispatch, not yet committed: (seq, payload,
        # treedef) — payload entries are host-fetch thunks until the donation
        # fence materializes them, host leaves afterwards
        self._pending_ckpts: Deque[tuple] = deque()
        # groups queued by recover() awaiting re-staging: (seq, parts, metas,
        # drop). Drained into _staged under the NORMAL depth budget by
        # _stage_available_input — re-uploading the whole replay window at
        # once would burst device memory past what the budget bounds
        self._replay_queue: Deque[tuple] = deque()
        self._rlog_dropped = 0           # leak-guard drops (see _stage_group)
        # newest replayed group's seq while a recovery's replay window is
        # active (-1 = none): ctrl retunes landing inside the window defer
        # to the post-window boundary (apply_retune) with a structured
        # warning (warn_retune_in_replay) instead of silently shifting
        # where the swap lands in the recovered stream
        self._replay_high = -1
        # retune log: (seq, stage, params) per applied carry surgery, seq =
        # the first dispatch group that saw the new parameters — pruned by
        # the same committed-checkpoint floor as the replay log, replayed by
        # recover() so a restore point BEFORE a retune re-applies it at
        # exactly its original boundary (replay-aware retunes,
        # docs/robustness.md)
        self._retune_log: Deque[tuple] = deque()
        # surgery queued for application at a dispatch boundary (recovery
        # re-application + mid-replay deferrals), consumed in seq order by
        # _launch_staged
        self._replay_retunes: Deque[tuple] = deque()
        self._forfeit_ctr = None
        self._replay_ctr = None

    def _resolve_ckpt_every(self) -> int:
        """The cadence this incarnation runs at: the configured cadence when
        a recovery consumer exists, else 0 (checkpointing is pure cost when
        nothing can ever call :meth:`recover`)."""
        if not self._ckpt_cadence:
            return 0
        if self._ckpt_explicit or getattr(self, "_dc_restartable", False):
            return self._ckpt_cadence
        pol = getattr(self, "policy", None)
        if getattr(pol, "on_error", None) == "restart":
            return self._ckpt_cadence
        from ..config import config
        if str(config().get("block_policy", "fail_fast")) == "restart":
            return self._ckpt_cadence
        return 0

    def _checkpoint_tick(self, seq: int) -> None:
        """Per-dispatch checkpoint hook. With ``checkpoint_every=0`` this is
        ONE falsy-int check and a return — the telemetry overhead gate holds
        checkpointing-off to the same ≤3% budget as the disabled span hooks."""
        if not self._ckpt_every:
            return
        if (seq + 1) % self._ckpt_every == 0:
            self._start_ckpt(seq)

    def _start_ckpt(self, seq: int) -> None:
        """Snapshot the post-dispatch carry (= the restore point for replaying
        groups > ``seq``): the host copies start NOW and ride the D2H lane
        with the result transfers; commit waits until group ``seq``'s outputs
        have drained (a checkpoint must never skip outputs that were lost
        with the failed incarnation). A snapshot failure only narrows the
        restore window — it must not fail the dispatch path."""
        try:
            fins, treedef = self.pipeline.snapshot_carry(self._carry)
        except Exception as e:                         # noqa: BLE001
            log.warning("%s: carry snapshot @%d failed (%r) — skipped",
                        self.meta.instance_name, seq, e)
            return
        self._pending_ckpts.append((seq, fins, treedef))

    def _materialize_snapshot(self, seq: int, payload) -> Optional[list]:
        """Turn one snapshot payload's fetch thunks into host leaves; None
        (logged) on failure — a dropped snapshot only narrows the restore
        window. The ONE materialization/error-handling implementation shared
        by the donation fence and the commit loop."""
        try:
            return [p() if callable(p) else p for p in payload]
        except Exception as e:                         # noqa: BLE001
            log.warning("%s: carry snapshot @%d dropped (%r)",
                        self.meta.instance_name, seq, e)
            return None

    def _materialize_pending_ckpts(self) -> None:
        """Donation fence: turn pending snapshot thunks into host leaves
        before the next dispatch donates (and reuses) the carry buffers a
        thunk would still read. Runs at most once per cadence interval."""
        if not self._pending_ckpts:
            return
        keep: Deque[tuple] = deque()
        for seq, payload, treedef in self._pending_ckpts:
            payload = self._materialize_snapshot(seq, payload)
            if payload is not None:
                keep.append((seq, payload, treedef))
        self._pending_ckpts = keep

    def _note_drained(self, seq: int) -> None:
        """Group ``seq``'s outputs are host-side: release its pinned arena
        staging buffers, advance the drain cursor, commit every snapshot it
        covers, and prune the replay log back to the PREVIOUS committed
        checkpoint (kept so a corrupted newest candidate can still fall back
        and replay from the older restore point)."""
        for h in self._group_handles.pop(seq, ()):
            h.release()
        if seq > self._drained_seq:
            self._drained_seq = seq
        if not self._ckpt_every:
            return
        fplan = _faults.plan()
        while self._pending_ckpts and self._pending_ckpts[0][0] <= seq:
            s, payload, treedef = self._pending_ckpts.popleft()
            leaves = self._materialize_snapshot(s, payload)
            if leaves is None:
                continue
            if fplan.armed():
                try:
                    # `carry` site (runtime/faults.py): corrupt this
                    # checkpoint CANDIDATE — the restore-path integrity check
                    # must reject it and fall back to the previous checkpoint
                    fplan.maybe("carry", self.meta.instance_name)
                except _faults.InjectedFault as e:
                    log.warning("%s: checkpoint @%d corrupted by injected "
                                "fault (%r)", self.meta.instance_name, s, e)
                    leaves = [np.zeros(int(np.size(l)) + 1, np.uint8)
                              for l in leaves] or [np.zeros(1, np.uint8)]
            if self._ckpts and self._ckpts[-1][0] >= s:
                continue                 # replay re-commit of a covered seq
            self._ckpts.append((s, leaves, treedef))
            _journal.emit("kernel", "checkpoint-commit",
                          block=self.meta.instance_name, seq=int(s))
            self._persist_ckpt(s, leaves)
            if len(self._ckpts) >= 2:
                floor = self._ckpts[0][0]
                with self._rlog_lock:
                    while self._rlog and self._rlog[0][0] <= floor:
                        _, _, _, hs = self._rlog.popleft()
                        for h in hs:
                            h.release()
                # retunes at or before the floor are baked into every
                # restorable checkpoint — same retention rule as the log
                while self._retune_log and self._retune_log[0][0] <= floor:
                    self._retune_log.popleft()
                # wire switches prune the same way, but the format is NOT in
                # the carry — remember the format in effect AT the floor so
                # a restore below every surviving entry knows its wire
                while self._wire_log and self._wire_log[0][0] <= floor:
                    self._wire_floor_fmt = self._wire_log.popleft()[1]

    def _recovery_reset(self, purge_disk: bool = False) -> None:
        """Drop every checkpoint/replay artifact (fresh incarnation, or a
        cleanly finished stream — a later re-run must not replay stale
        groups into a new flowgraph's buffers), releasing the arena buffers
        the log and the live groups pinned. ``purge_disk`` additionally
        removes the persisted snapshot (clean EOS only: the stream's state
        is complete, a later process must start fresh — a RE-INIT must NOT
        purge, the disk snapshot is exactly what a process restart resumes
        from)."""
        self._seq = 0
        self._drained_seq = -1
        with self._rlog_lock:
            for _, _, _, hs in self._rlog:
                for h in hs:
                    h.release()
            self._rlog.clear()
        for hs in self._group_handles.values():
            for h in hs:
                h.release()
        self._group_handles.clear()
        self._ckpts.clear()
        self._pending_ckpts.clear()
        self._replay_queue.clear()
        self._replay_high = -1
        self._retune_log.clear()
        self._replay_retunes.clear()
        self._wire_log.clear()
        self._replay_wire_switches.clear()
        self._wire_floor_fmt = self.wire.name
        self._wire_switch_target = None
        if self._wirectl is not None:
            self._wirectl.reset()
        if purge_disk and self._ckpt_dir:
            path = self._ckpt_file()
            if path:
                def purge():
                    try:
                        os.unlink(path)
                    except OSError:
                        pass
                # same FIFO executor as the writes: a purge queued after a
                # pending persist deletes what that persist wrote, so a
                # cleanly-finished stream can never leave a snapshot behind
                self._persist_submit(purge)

    # -- cross-process checkpoint persistence (config `checkpoint_dir`) -------
    def _ckpt_file(self) -> Optional[str]:
        """The snapshot path of THIS kernel: instance name (sanitized) plus a
        hash of the pipeline signature (stage names + in dtype), so a
        restarted process with the same flowgraph maps to the same file and
        a DIFFERENT pipeline under a reused name can never restore a
        mismatched carry (the integrity check would reject it anyway — the
        name just keeps unrelated snapshots from colliding)."""
        if not self._ckpt_dir:
            return None
        name = self.meta.instance_name or type(self).__name__
        h = _snapshot.snapshot_signature(self.pipeline, name)
        safe = _snapshot.sanitize_name(name)
        return os.path.join(self._ckpt_dir, f"{safe}-{h}.ckpt.npz")

    def _persist_submit(self, fn) -> None:
        """Run a persistence task (snapshot write, clean-EOS purge) off the
        drain thread on the ONE-worker persistence executor
        (:func:`_persist_executor`) — strictly serialized, so writes land
        newest-last and a purge queued after pending writes wins."""
        _persist_executor().submit(fn)

    def _persist_ckpt(self, seq: int, leaves) -> None:
        """Serialize one COMMITTED checkpoint under ``checkpoint_dir``:
        atomic rename (a reader sees the old or the new snapshot, never a
        torn one), CRC-integrity-checked on load. Best-effort — a write
        failure only narrows the cross-process restore window, it must
        never fail the drain path — queued off-thread
        (:meth:`_persist_submit`, the CRC + npz write of an MB-scale carry
        must not stall the dispatch/drain loop every cadence interval) and
        COALESCED (the one-slot latest box of ``_init_recovery_state``):
        only the newest snapshot matters, so a slow disk skips intermediate
        commits instead of queueing them without bound. ``leaves`` are
        already-materialized host arrays the checkpoint ring owns
        immutably, so the task reads stable bytes."""
        path = self._ckpt_file()
        if not path:
            return
        name = self.meta.instance_name
        with self._persist_lock:
            self._persist_box = (seq, leaves)
            if self._persist_queued:
                return                   # the queued task drains the box
            self._persist_queued = True

        def write():
            with self._persist_lock:
                item = self._persist_box
                self._persist_box = None
                self._persist_queued = False
            if item is None:
                return
            s, lv = item
            if not _snapshot.write_snapshot(path, s, lv):
                log.warning("%s: checkpoint persist @%d failed", name, s)

        self._persist_submit(write)

    def _load_disk_ckpt(self) -> Optional[tuple]:
        """``(seq, leaves)`` of the persisted snapshot, or None when absent,
        unreadable, or failing the CRC — a corrupted file is logged and
        ignored (recovery falls through to the fresh-init path)."""
        got = _snapshot.read_snapshot(self._ckpt_file() or "")
        if got is None:
            return None
        seq, leaves, _meta = got
        return seq, leaves

    def _restore_candidates(self):
        """Committed checkpoints newest-first, each validated lazily by
        :meth:`recover`."""
        return reversed(list(self._ckpts))

    async def recover(self, err) -> bool:
        """Restart recovery WITHOUT forfeiting in-flight work: restore the
        newest VALID committed checkpoint and re-stage every logged dispatch
        group after it from its host staging parts — the replayed program is
        a pure function of (carry, frame), so outputs land bit-identical to
        an unfailed run. Returns False (caller falls back to the forfeiting
        fresh re-init) when checkpointing is off or no candidate passes the
        integrity check. Called by the restart machinery
        (``runtime/block.py _reinit_for_restart``, the devchain drive loop);
        host-side state (_accum frames, pending output) is deliberately
        untouched — it was never lost."""
        if not self._ckpt_every or not self._ckpts:
            return False
        # quiesce codec-pool tasks: the replay log must be settled (workers
        # insert out of band) before it is read as the recovery source
        self._settle_staged()
        # integrity template: the pipeline's OWN fresh carry for this compile
        # (cached jit — usually no recompilation; a failed incarnation that
        # never finished init recompiles here). Billed as reason="recover"
        # either way — the profile plane's storm detector and the doctor's
        # "compiling" verdict both want recovery re-resolves attributed.
        with _profile.compiling(
                self.meta.instance_name or type(self).__name__, "recover",
                f"frame={self.frame_size},wire={self.wire.name},"
                f"k={self.k_batch}"):
            self._compiled, fresh = self.pipeline.compile_wired(
                self.frame_size, self.wire, device=self.inst.device,
                k=self.k_batch, donate=self._donate, packed=self._packed)
        if self._seq == 0 and not self._rlog and self._ckpt_dir:
            # VIRGIN incarnation (nothing dispatched, nothing to replay):
            # the only meaningful state is a previous PROCESS's persisted
            # snapshot — prefer it over the fresh-init sentinel. In-kernel
            # candidates always win once this process has dispatched
            # anything (docs/robustness.md "persisting checkpoints").
            disk = self._load_disk_ckpt()
            if disk is not None:
                seq_d, leaves_d = disk
                import jax
                treedef_d = jax.tree_util.tree_flatten(fresh)[1]
                if self.pipeline.carry_matches(leaves_d, treedef_d, fresh):
                    self._carry = self.pipeline.restore_carry(
                        leaves_d, treedef_d, self.inst.device)
                    self._staged.clear()
                    self._inflight.clear()
                    self._pending_ckpts.clear()
                    self._replay_queue.clear()
                    self._replay_retunes.clear()
                    self._replay_wire_switches.clear()
                    self._wire_switch_target = None
                    # seed the ring with the DISK carry as a real candidate
                    # at the pre-stream position: a later in-process fault
                    # (before the first new commit) must replay this
                    # incarnation's groups on top of the restored carry,
                    # not on a fresh one
                    self._ckpts.clear()
                    self._ckpts.append(
                        (-1, [np.asarray(l) for l in leaves_d], treedef_d))
                    log.info("%s: restored carry from persisted checkpoint "
                             "@%d (%s) after a process restart — the replay "
                             "window of the previous process is lost, "
                             "resuming from the snapshot after %r",
                             self.meta.instance_name, seq_d,
                             self._ckpt_file(), err)
                    _trace.instant("tpu", "checkpoint_restore_disk",
                                   args={"block": self.meta.instance_name,
                                         "checkpoint_seq": seq_d})
                    _journal.emit("kernel", "recover",
                                  block=self.meta.instance_name,
                                  checkpoint_seq=int(seq_d), replayed=0,
                                  from_disk=True, error=repr(err))
                    return True
                log.warning("%s: persisted checkpoint failed the carry "
                            "contract check (pipeline changed?) — ignored",
                            self.meta.instance_name)
        chosen = None
        invalid: set = set()
        for seq, leaves, treedef in self._restore_candidates():
            if leaves is None:           # fresh-init sentinel (seq == -1)
                if not self._rlog or self._rlog[0][0] == 0:
                    chosen = (seq, None, None)
                    break
                log.warning("%s: init-sentinel checkpoint unusable (replay "
                            "log starts at %d)", self.meta.instance_name,
                            self._rlog[0][0])
                invalid.add(seq)
                continue
            if not self.pipeline.carry_matches(leaves, treedef, fresh):
                log.warning("%s: checkpoint @%d failed integrity check "
                            "(seq/shape/dtype) — falling back to the "
                            "previous checkpoint", self.meta.instance_name,
                            seq)
                invalid.add(seq)
                continue
            if self._rlog and self._rlog[0][0] > seq + 1:
                log.warning("%s: checkpoint @%d not contiguous with the "
                            "replay log (starts at %d)",
                            self.meta.instance_name, seq, self._rlog[0][0])
                invalid.add(seq)
                continue
            chosen = (seq, leaves, treedef)
            break
        if invalid:
            # evict failed candidates so a corrupted entry can never become
            # a later recovery's fallback
            self._ckpts = deque((c for c in self._ckpts
                                 if c[0] not in invalid), maxlen=2)
        if chosen is None:
            return False
        seq, leaves, treedef = chosen
        self._carry = fresh if leaves is None else \
            self.pipeline.restore_carry(leaves, treedef, self.inst.device)
        # adaptive-wire replay contract: the first replayed group (seq+1)
        # must dispatch under the wire it was FIRST shipped with — rewind
        # to the format in effect there, and queue every later logged
        # switch for re-application at its original boundary
        # (_launch_staged). A stale pending proposal dies with the fault.
        self._wire_switch_target = None
        fmt = self._wire_floor_fmt
        for s, f in self._wire_log:
            if s <= seq + 1:
                fmt = f
        self._replay_wire_switches = deque(
            (s, f) for s, f in self._wire_log if s > seq + 1)
        if fmt != self.wire.name:
            self._apply_wire_program(fmt, reason="recover")
        if self._wirectl is not None:
            self._wirectl.reset()
        # rebuild the dispatch window purely from the log: every group after
        # the checkpoint re-ships its exact staging parts; groups that had
        # already drained only re-advance the carry (drop=True). QUEUED, not
        # uploaded: _stage_available_input re-stages them under the normal
        # depth budget, so a long replay window (sparse cadence) cannot
        # burst device memory past what steady state is sized for.
        self._staged.clear()
        self._inflight.clear()
        self._pending_ckpts.clear()
        self._replay_queue.clear()
        # replay-aware retunes: surgery recorded AFTER the restore point is
        # not in the restored carry — queue it for re-application at its
        # original group boundary (_launch_staged applies in seq order), so
        # the replayed stream walks the unfailed run's parameter timeline
        self._replay_retunes = deque(
            e for e in self._retune_log if e[0] > seq)
        replayed = 0
        with self._rlog_lock:
            log_entries = list(self._rlog)
        for s, parts, metas, _hs in log_entries:
            if s <= seq:
                continue
            self._replay_queue.append((s, parts, metas,
                                       s <= self._drained_seq))
            self._replay_high = max(self._replay_high, s)
            replayed += len(metas)
        if replayed:
            if self._replay_ctr is None:
                self._replay_ctr = _REPLAYED.labels(
                    block=self.meta.instance_name or type(self).__name__)
            self._replay_ctr.inc(replayed)
        log.info("%s: restored carry checkpoint @%d, replaying %d frame(s) "
                 "after %r", self.meta.instance_name, seq, replayed, err)
        _trace.instant("tpu", "checkpoint_restore",
                       args={"block": self.meta.instance_name,
                             "checkpoint_seq": seq, "replayed": replayed})
        _journal.emit("kernel", "recover", block=self.meta.instance_name,
                      checkpoint_seq=int(seq), replayed=int(replayed),
                      from_disk=False, error=repr(err))
        if replayed:
            _journal.emit("kernel", "replay", block=self.meta.instance_name,
                          frames=int(replayed),
                          high_seq=int(self._replay_high))
        return True

    def _stage_copy(self, frame: np.ndarray) -> tuple:
        """The ring-exit staging copy, arena-backed: ``(frame', handle)``.
        The copy is needed when the encode may ALIAS the ring view (async
        H2D would read the ring after the writer reclaims it — the f32 pairs
        view; ``ops/xfer.h2d_needs_staging`` is always True); the
        worker-side encode then reads the copy, never the ring. The copy
        lands in the arena's recycled pages.

        Zero-copy ingest fast path (ops/ingest.py): a frame backed by a
        REGISTERED externally-owned read-only buffer skips the copy — nobody
        reclaims that memory behind the async H2D, so the ring-exit-race
        rationale above does not apply. The ingest handle rides the group's
        pin/replay retention exactly like the arena handle the copy would
        have had (retained here, released when the group drains / the
        replay log prunes), so the owner's ``pinned`` flag covers fault
        replay too. Writable frames never match (``ingest.lookup``) — the
        copying fallback is bit-identical."""
        if not self._needs_staging:
            return frame, None
        if self._ingest_enabled:
            from ..ops import ingest as _ingest_mod
            h = _ingest_mod.lookup(frame)
            if h is not None:
                self._ingest_frames += 1
                _ingest_mod.note_zero_copy()
                return frame, h.retain()
        if not self.wire.encode_may_alias(frame.dtype) and self.k_batch == 1:
            # quantizing wires materialize fresh arrays in the encode, which
            # runs on this thread before consume() (encode offload is for
            # aliasing wires, see _resolve_uplink) — no copy.
            # k==1 ONLY: a megabatch frame sits in _accum across work
            # cycles AFTER consume() freed its ring space, so it must leave
            # the ring regardless of the wire (the writer would otherwise
            # overwrite it before _flush_accum encodes)
            return frame, None
        return self._arena.copy_in(frame)

    def _stage_deferred(self, frame: np.ndarray, tags) -> None:
        """Stage one quantizing K=1 frame WITHOUT the ring-exit copy: the
        codec worker's ``encode_into`` reads the live ring slot in place
        (safe — the slot cannot be reclaimed before ``consume()``), so only
        the int payload lands in the arena. ``consume()`` is deferred until
        the worker signals the read (``_settle_deferred_consume``); if
        ``_stage`` fails before a worker picks the event up, it is set
        here."""
        ev = threading.Event()
        self._consume_event = ev
        self._pending_consume = (ev, self.frame_size, self._seq)
        try:
            self._stage(frame, self.frame_size, tags, None)
        finally:
            if self._consume_event is ev:
                # no pool task picked the event up: the failure happened on
                # this thread
                self._consume_event = None
                ev.set()

    def _settle_deferred_consume(self) -> None:
        """Land a deferred ring consume: wait until the worker's in-place
        encode has read the slot, then advance the reader. At most one
        consume is ever deferred, and the wait is bounded by the encode of
        one frame (which started when the frame was staged)."""
        if self._pending_consume is None:
            return
        ev, n, seq = self._pending_consume
        if _trace.enabled and not ev.is_set():
            # blocked on the codec worker's in-place encode of the ring
            # slot: the same wait as _launch_staged's join, met earlier
            t0 = _trace.now()
            ev.wait()
            _trace.complete("tpu", "h2d_wait", t0,
                            args={"seq": seq, "at": "consume"})
        else:
            ev.wait()
        self._pending_consume = None
        self.input.consume(n)

    def _stage_available_input(self):
        """Step 2 of the work loop, shared with the fan-out kernel: stage as
        many full frames as the pipeline depth allows — each one's H2D starts
        NOW, so while the oldest frame's compute is dispatched the younger
        frames' payloads are already on the wire. The copy is the H2D staging
        write (reference `vulkan/h2d.rs:29-37`): device_put is async, so
        handing it a live ring-buffer view would race with the writer
        overwriting consumed space — the frame must leave the ring before
        consume(). Returns ``(remaining input slice, eos)``."""
        # a deferred consume from the previous cycle must land before the
        # ring is sliced again (the unconsumed frame is still in the slice)
        self._settle_deferred_consume()
        # adaptive wire: collect the controller's proposal / apply a pending
        # switch at a quiescent boundary (pauses staging while pending)
        if self._wirectl is not None or self._wire_switch_target is not None:
            self._maybe_switch_wire()
        budget = self._credits.credits + self.stage_ahead
        # replayed groups re-enter the dispatch window FIRST (sequence
        # order), under the same budget as live staging
        while self._replay_queue and \
                len(self._staged) + len(self._inflight) < budget:
            s, parts, metas, drop = self._replay_queue.popleft()
            self._staged.append((xfer.start_device_transfer_parts(
                parts, self.inst.device, s), metas, s, drop))
        if self._replay_queue:
            # the window is full of replays; no NEW input may be staged
            # before they re-enter (their sequence numbers precede it)
            return self.input.slice(), self.input.finished()
        inp = self.input.slice()
        # a pending wire switch pauses staging so the window drains to the
        # switch boundary — except a part-filled megabatch group, which must
        # keep filling to its flush (mid-stream zero-padding would corrupt
        # the carries; the switch waits one group longer instead)
        while len(self._staged) + len(self._inflight) < budget and \
                (self._wire_switch_target is None or self._accum):
            # a pending deferred consume settles HERE, at the top: staging
            # the next frame needs the read cursor advanced, but the LAST
            # frame of a cycle stays pending into the next work() call so
            # the worker's in-place encode overlaps dispatch/drain below
            self._settle_deferred_consume()
            inp = self.input.slice()
            if len(inp) < self.frame_size:
                break
            tags = self.input.tags(self.frame_size)
            frame = inp[:self.frame_size]
            if self._deferred_consume:
                # quantizing K=1: the worker's encode reads the ring
                # slot IN PLACE and only the int payload lands in the arena
                # — consume() is deferred until the read (at most one)
                self._stage_deferred(frame, tags)
            else:
                t_in = time.perf_counter_ns()
                frame, handle = self._stage_copy(frame)
                if _trace.enabled:
                    # the ring-exit copy (none on the deferred path above:
                    # the codec worker encodes the ring slot in place)
                    _trace.complete("tpu", "stage", t_in,
                                    args={"seq": self._seq,
                                          "bytes": frame.nbytes})
                self._stage(frame, self.frame_size, tags, handle, t_in)
                self.input.consume(self.frame_size)
            inp = self.input.slice()

        eos = self.input.finished()
        if eos and len(inp) > 0 and len(inp) < self.frame_size and \
                self._pending_consume is None and \
                len(self._staged) + len(self._inflight) < budget:
            # final partial frame: zero-pad, emit only the valid prefix
            frame, handle = self._arena.take_array(
                (self.frame_size,), self.pipeline.in_dtype)
            frame.fill(0)
            frame[:len(inp)] = inp
            n = len(inp)
            tags = self.input.tags(n)
            # items beyond the last frame_multiple boundary cannot produce integral
            # output and are dropped at EOS (streaming frame contract)
            self._stage(frame, n - (n % self.pipeline.frame_multiple), tags,
                        handle)
            self.input.consume(n)
            inp = self.input.slice()
        if eos and self._accum:
            # EOS: a partial dispatch group cannot wait for more frames —
            # zero-pad it to the scan length and ship (pad outputs dropped)
            self._flush_accum()
        if self._pending_consume is not None:
            # the deferred frame is still in the ring slice but is already
            # staged — report only the input BEYOND it, so the caller's
            # starved/finished checks see the logical remainder
            inp = inp[self._pending_consume[1]:]
        return inp, eos

    async def work(self, io, mio, meta):
        # 1. flush pending host-side output first
        if self._pending_out is not None:
            self._pending_out, self._pending_tags = self._emit(
                self.output, self._pending_out, self._pending_tags)
            if self._pending_out is not None:
                return  # downstream full; its consume() will wake us
            if self._emitting is not None:
                self._close_frames()

        # 2. stage everything the depth budget allows (H2D rides now)
        inp, eos = self._stage_available_input()

        # 3. launch compute on staged frames (their transfers have been riding
        #    since step 2) and start each result's D2H
        self._launch_staged()

        # 4. retrieve: when the pipe is full, when the input is starved (no full frame
        #    waiting — flush for latency; when saturated the credit gate keeps overlap),
        #    on EOS drain, or while draining toward a pending wire switch
        should_drain = bool(self._inflight) and (
            len(self._inflight) >= self._credits.credits
            or len(inp) < self.frame_size or eos
            or self._wire_switch_target is not None)
        if should_drain:
            drained = self._drain_one()
            if drained is not None:      # None = replayed already-emitted group
                result, tags = drained
                self._pending_out, self._pending_tags = self._emit(
                    self.output, result, tags)
                if self._pending_out is None and self._emitting is not None:
                    self._close_frames()
            io.call_again = True
            return

        if eos and not self._inflight and not self._staged and \
                not self._accum and not self._replay_queue and \
                self._pending_out is None and len(inp) == 0:
            io.finished = True
            # stream cleanly finished: a later re-run of this kernel must
            # start from a fresh carry, never replay this stream's tail —
            # and the persisted snapshot (if any) is complete state, purged
            self._recovery_reset(purge_disk=True)
        elif eos and (self._inflight or self._staged or self._accum
                      or self._replay_queue):
            io.call_again = True


class _PathRatio:
    """Rate-contract shim for :func:`rebase_frame_tags`, which only reads
    ``.ratio`` — carries one fan-out branch's producer·branch path rate."""

    __slots__ = ("ratio",)

    def __init__(self, ratio):
        self.ratio = ratio


class TpuFanoutKernel(TpuKernel):
    """ONE fused dispatch driving N branch stream outputs.

    The block form of :class:`~futuresdr_tpu.ops.stages.FanoutPipeline`: a
    device-plane region shaped ``producer → broadcast → N consumer chains``
    runs as a single multi-output XLA program per frame (per megabatch
    window) — the input frame crosses the link ONCE, the producer computes
    once, and each branch's result streams out its own port. Constructed by
    the device-graph fusion pass (``runtime/devchain.py``) but usable
    directly: ``outputs[j]`` carries branch j (ports ``out0…out{N-1}``).

    The staging/megabatch/H2D/dispatch side is inherited unchanged from
    :class:`TpuKernel` (one input, one upload per frame group); only the
    result side — D2H metas, drain, emit — generalizes per branch. Under the
    devchain drive loop a branch whose downstream detaches is RETIRED
    (:meth:`retire_branch`): its output is dropped while the surviving
    branches keep streaming — the semantics the actor runtime gives a
    broadcast port group when one reader finishes early. NOTE: when run as a
    plain actor block instead (outside the devchain), the generic block
    event loop cannot attribute a ``StreamOutputDone`` to one port, so the
    FIRST detaching reader finishes the whole block — per-branch retirement
    needs the devchain's per-tail inbox routing.
    """

    def __init__(self, fanout, frame_size: Optional[int] = None,
                 inst: Optional[TpuInstance] = None,
                 frames_in_flight: Optional[int] = None,
                 wire=None, frames_per_dispatch: Optional[int] = None,
                 checkpoint_every: Optional[int] = None,
                 interior_precision: Optional[str] = None):
        from ..runtime.kernel import Kernel
        Kernel.__init__(self)
        from ..config import config
        self.inst = inst or instance()
        self.pipeline = fanout
        self._apply_interior_precision(interior_precision)
        self._apply_pallas_blocks()
        fanout = self.pipeline            # the (possibly lowered) rebuild
        fs = frame_size or self.inst.frame_size
        m = fanout.frame_multiple
        self.frame_size = max(m, (fs // m) * m)
        self.out_frames = [fanout.branch_out_items(j, self.frame_size)
                           for j in range(fanout.n_branches)]
        self.out_frame = sum(self.out_frames)      # linear-surface compat
        self.depth = frames_in_flight or self.inst.frames_in_flight
        self._depth_explicit = frames_in_flight is not None
        self.k_batch = max(1, int(frames_per_dispatch
                                  or config().tpu_frames_per_dispatch))
        self._k_explicit = frames_per_dispatch is not None
        from ..ops.wire import resolve_wire
        self.wire = resolve_wire(wire, self.inst.platform)
        self._needs_staging = xfer.h2d_needs_staging(self.inst.platform)
        self._init_hostpath()
        self._compiled = None
        self._carry = None
        self._accum = []
        self._staged = deque()
        self._inflight = deque()
        self._e2e_hist = None
        self._frames_dispatched = 0
        self._dispatches = 0
        # checkpoint/replay state — the FLAT composed carry (producer +
        # branches) snapshots as one tree, so one checkpoint covers every
        # branch; per-branch replay cursors ride each group's drop flag
        self._init_recovery_state(checkpoint_every)
        nb = fanout.n_branches
        self._pendings: List[Optional[np.ndarray]] = [None] * nb
        self._pending_tags_n: List[List[ItemTag]] = [[] for _ in range(nb)]
        self._branch_done = [False] * nb
        # fixed at compile: parts per branch in the wired program's FLAT
        # output tuple (the drain re-nesting key)
        self._part_counts = fanout.part_counts(self.wire)
        self.input = self.add_stream_input("in", fanout.in_dtype,
                                           min_items=self.frame_size)
        self.outputs = [
            self.add_stream_output(
                f"out{j}", fanout.out_dtypes[j], min_items=of,
                min_buffer_size=(self.depth * self.k_batch + 1) * of *
                np.dtype(fanout.out_dtypes[j]).itemsize)
            for j, of in enumerate(self.out_frames)]
        # single-output compat for code that pokes .output (metrics, repr);
        # work()/drain below always address self.outputs[j]
        self.output = self.outputs[0]
        self._pending_out = None
        self._pending_tags = []
        self._emitting = None

    async def init(self, mio, meta):
        # restart contract (TpuKernel.init): drop every per-branch trace of
        # the previous incarnation too
        nb = self.pipeline.n_branches
        self._pendings = [None] * nb
        self._pending_tags_n = [[] for _ in range(nb)]
        self._branch_done = [False] * nb
        await super().init(mio, meta)

    def retire_branch(self, j: int) -> None:
        """Stop emitting branch ``j`` (its downstream detached): produced
        frames for it are dropped, the other branches keep streaming. When
        every branch is retired the next work() finishes the block."""
        self._branch_done[j] = True
        self._pendings[j] = None
        self._pending_tags_n[j] = []

    def extra_metrics(self) -> dict:
        m = super().extra_metrics()
        m["branches"] = self.pipeline.n_branches
        m["branches_live"] = sum(not d for d in self._branch_done)
        return m

    # -- per-branch result side (the only specialization over TpuKernel) ------
    def _start_result_d2h(self, flat_parts, metas, seq=None) -> tuple:
        """ONE D2H for the whole flat part tuple: all branches' results ride
        the wire together, billed as one frame transfer. Metas carry one
        per-branch ``(valid_out, rebased tags)`` tuple per frame — each
        branch's tag indices rebased through ITS path rate."""
        fo = self.pipeline
        finish = xfer.start_host_transfer_parts(flat_parts, seq)
        # tag remap per branch: the item-COUNT ratio, unless the pipeline
        # carries separate tag ratios (a DagPipeline through a merge — tags
        # ride the primary chain, so a concat join must not scale indices by
        # the summed output rate)
        tag_ratios = getattr(fo, "tag_ratios", None) or fo.path_ratios
        # sinks downstream of a CONCAT merge cannot represent a partial
        # input frame as a valid-prefix count (the concat layout interleaves
        # full frames) — they emit only for full frames, exactly like the
        # actor-path TpuMergeStage (DagPipeline.concat_sinks)
        concat = getattr(fo, "concat_sinks", None)
        out_metas = []
        for valid_in, tags, t_in, tid in metas:
            per_branch = []
            for j in range(fo.n_branches):
                valid_out = min(fo.branch_out_items(j, valid_in),
                                self.out_frames[j])
                if concat and concat[j] and valid_in < self.frame_size:
                    valid_out = 0
                per_branch.append(
                    (valid_out,
                     tuple(rebase_frame_tags(
                         tags, _PathRatio(tag_ratios[j]), valid_out))))
            out_metas.append((tuple(per_branch), t_in, tid))
        return (finish, tuple(out_metas))

    def _decode_group(self, raw, out_metas, wire=None, seq=None):
        """Per-branch host decode of one landed group (the fan-out form of
        the base hook — runs on the drain thread, or on a codec worker under
        the pool; ``wire`` is the codec captured at dispatch). Returns
        ``(results, t_ins)`` with one ``(result, tags)``
        per branch (megabatch groups concatenate their frames per branch,
        tag indices rebased by the branch's running offset)."""
        fo = self.pipeline
        # the flat-output slicing key follows the dispatch-time wire too
        pc = self._part_counts if wire is None or wire is self.wire \
            else fo.part_counts(wire)
        wire = wire if wire is not None else self.wire
        t0 = _trace.now() if _trace.enabled else 0
        nb = fo.n_branches
        results: List[Tuple[np.ndarray, list]] = []
        if self.k_batch == 1:
            ((per_branch, t_in, _tid),) = out_metas
            off = 0
            for j, cnt in enumerate(pc):
                parts_j = raw[off:off + cnt]
                off += cnt
                if self._branch_done[j]:
                    # retired reader: don't pay the host decode for frames
                    # work() would drop anyway
                    results.append((np.empty(0, fo.out_dtypes[j]), []))
                    continue
                valid, tags = per_branch[j]
                arr = wire.decode_host(parts_j, fo.out_dtypes[j])
                results.append((arr[:valid], list(tags)))
            t_ins = (t_in,)
        else:
            chunks = [[] for _ in range(nb)]
            all_tags: List[List[ItemTag]] = [[] for _ in range(nb)]
            offsets = [0] * nb
            for i, (per_branch, _tin, _tid) in enumerate(out_metas):
                off = 0
                for j, cnt in enumerate(pc):
                    parts_j = tuple(p[i] for p in raw[off:off + cnt])
                    off += cnt
                    if self._branch_done[j]:
                        continue         # retired: skip the decode + concat
                    valid, tags = per_branch[j]
                    chunks[j].append(wire.decode_host(
                        parts_j, fo.out_dtypes[j])[:valid])
                    all_tags[j].extend(ItemTag(t.index + offsets[j], t.tag)
                                       for t in tags)
                    offsets[j] += valid
            results = [
                (np.concatenate(c) if c else np.empty(0, fo.out_dtypes[j]),
                 all_tags[j])
                for j, c in enumerate(chunks)]
            t_ins = tuple(tin for _, tin, _ in out_metas)
        if t0:
            _trace.complete("tpu", "decode", t0,
                            args={"wire": wire.name,
                                  "items": sum(len(r) for r, _ in results),
                                  "branches": nb, "seq": seq})
        return results, t_ins

    def _drain_one(self) -> Optional[List[Tuple[np.ndarray, list]]]:
        """Land the oldest dispatch group; returns one ``(result, tags)`` per
        BRANCH, or None for a replayed group every branch already emitted."""
        land, out_metas, seq, _drop = self._inflight.popleft()
        payload = self._land(land, seq)      # joins the pool-mode landing
        if payload is None:
            self._note_drained(seq)
            return None
        results, t_ins = payload
        end = time.perf_counter_ns()
        if _trace.enabled:
            self._emitting = (seq, t_ins)
        if self._e2e_hist is not None:
            for tin in t_ins:                # one observation per input frame
                self._e2e_hist.observe((end - tin) * 1e-9)
        self._finish_lineage(out_metas, end)
        # drained only after every branch decoded (the base-class contract)
        self._note_drained(seq)
        return results

    async def work(self, io, mio, meta):
        nb = self.pipeline.n_branches
        # 1. flush pending per-branch host output first; if ANY live branch is
        #    still blocked downstream, park — its consume() will wake us
        blocked = False
        for j in range(nb):
            if self._branch_done[j]:
                continue
            if self._pendings[j] is not None:
                self._pendings[j], self._pending_tags_n[j] = self._emit(
                    self.outputs[j], self._pendings[j],
                    self._pending_tags_n[j])
                if self._pendings[j] is not None:
                    blocked = True
        if blocked:
            return
        if self._emitting is not None:
            self._close_frames()
        if all(self._branch_done):
            io.finished = True               # every reader detached
            return

        # 2. stage (shared with TpuKernel: one upload per frame group),
        # 3. dispatch + per-branch D2H (shared loop, per-branch result hook)
        inp, eos = self._stage_available_input()
        self._launch_staged()

        # 4. per-branch retrieve/emit (wire-switch drain: base-class rule)
        should_drain = bool(self._inflight) and (
            len(self._inflight) >= self._credits.credits
            or len(inp) < self.frame_size or eos
            or self._wire_switch_target is not None)
        if should_drain:
            drained = self._drain_one()
            for j, (result, tags) in enumerate(drained or ()):
                if self._branch_done[j]:
                    continue                 # retired reader: drop its frames
                self._pendings[j], self._pending_tags_n[j] = self._emit(
                    self.outputs[j], result, tags)
            if self._emitting is not None and \
                    all(p is None for p in self._pendings):
                self._close_frames()
            io.call_again = True
            return

        if eos and not self._inflight and not self._staged and \
                not self._accum and not self._replay_queue \
                and all(p is None for p in self._pendings) \
                and len(inp) == 0:
            io.finished = True
            self._recovery_reset(purge_disk=True)  # clean-EOS contract (base)
        elif eos and (self._inflight or self._staged or self._accum
                      or self._replay_queue):
            io.call_again = True


class TpuDagKernel(TpuFanoutKernel):
    """ONE fused dispatch driving a general device-plane DAG's SINK set.

    The block form of :class:`~futuresdr_tpu.ops.stages.DagPipeline`: a
    region shaped as an arbitrary device DAG — nested fan-out, fan-IN
    (:class:`~futuresdr_tpu.ops.stages.MergeStage` joins), and the diamond
    ``producer → broadcast → branches → merge`` closure — runs as a single
    multi-output XLA program per frame (per megabatch window). The input
    crosses the link ONCE, every interior edge stays device-resident (the
    merge point's D2H→host→H2D bounce disappears), and each SINK's result
    streams out its own port: ``outputs[j]`` carries sink j in the DAG's
    node order.

    Everything — staging, megabatch, H2D, dispatch, checkpoint/replay, and
    the per-output drain/emit/tag-rebase — is the shared
    ``_stage_available_input``/``_launch_staged``/fan-out drain path: the
    ``DagPipeline`` presents its sink set through the same per-branch
    surface (``n_branches``/``path_ratios``/``out_dtypes``/``part_counts``)
    a ``FanoutPipeline`` presents its branches, generalized with per-sink
    ``tag_ratios`` so tags crossing a merge rebase along the PRIMARY chain
    (``_start_result_d2h``). A single-sink DAG (the diamond) is simply
    ``n_branches == 1``. Constructed by the device-graph fusion pass
    (``runtime/devchain.py``); the direct-use caveat of
    :class:`TpuFanoutKernel` (per-sink retirement needs the devchain drive
    loop's per-tail inbox routing) applies unchanged.
    """

    @property
    def _donate(self):
        """Megabatch DAG programs compile WITHOUT carry donation: under the
        ``lax.scan`` form, donated carries let XLA pick aliased layouts for a
        multiply-consumed interior value's boundary stash that round a sink
        differently from the k=1 program (observed on the nested-fan-out
        shape, CPU backend) — and fused-vs-actor bit-equality is the
        contract. k=1 keeps donation: the single-frame program matches the
        per-hop numerics with it (pinned by the fused-vs-actor tests), and
        the carry reuse is free."""
        return self.k_batch <= 1
