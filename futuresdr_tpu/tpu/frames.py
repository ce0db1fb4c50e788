"""Device-frame plane: H2D/D2H staging blocks and device-resident stage blocks.

Re-design of the reference's accelerator buffer pairs (``buffer/vulkan/{h2d,d2h}.rs``,
SURVEY §3.5): there, full/empty staging buffers circulate between host and GPU around each
compute block. Here the analogous pipeline is explicit blocks over a **frame stream**
(in-place queue ports carrying whole jax device arrays):

    ... cpu stream → TpuH2D → TpuStage → TpuStage → TpuD2H → cpu stream ...

``TpuH2D`` batches the sample stream into frames and ``device_put``s them; ``TpuStage``
maps device frames through a jitted :class:`~futuresdr_tpu.ops.stages.Pipeline` — frames
stay in HBM between stages (no host round-trip, unlike the reference's per-block D2H);
``TpuD2H`` syncs results back into the sample stream. For a single fused chain prefer
:class:`~futuresdr_tpu.tpu.TpuKernel`; this frame plane is for pipelines whose stages
must remain separate blocks (e.g. different frame rates, taps swapped at runtime, or a
fan-out of device consumers).

**Tags ride the plane** (SURVEY §7 "item-indexed metadata must ride alongside
tensors"): ``TpuH2D`` snapshots the stream tags of each frame window (frame-relative
indices), they travel with the device frame through the inplace queues, each
``TpuStage`` rebases indices by its pipeline's rate contract (the remap of
``blocks/dsp.py`` — reference ``buffer/circular.rs:37-64``), and ``TpuD2H`` re-emits
them into the output stream at the rebased positions — so a retune tag crosses a
device FIR+decimation segment and lands on the correct output item.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from ..log import logger
from ..ops import xfer
from ..ops.stages import Pipeline, Stage
from ..runtime.kernel import Kernel, message_handler
from ..runtime.tag import ItemTag, rebase_tags
from ..telemetry.spans import recorder as _trace_recorder
from ..types import Pmt
from .instance import TpuInstance, instance

__all__ = ["TpuH2D", "TpuStage", "TpuMergeStage", "TpuD2H", "rebase_frame_tags",
           "emit_with_tags", "parse_ctrl"]

log = logger("tpu.frames")
_trace = _trace_recorder()


def parse_ctrl(p: Pmt):
    """``{"stage": <name-or-index>, <param>: <value>, …}`` → ``(stage, params)``.

    The shared grammar of the TpuKernel/TpuStage ``ctrl`` ports; raises on
    malformed input (callers translate to ``Pmt.invalid_value()``). Pmt.map
    wraps list elements as Pmt (VecPmt) — unwrapped here."""
    d = dict(p.to_map())
    stage = d.pop("stage").value
    if not isinstance(stage, str):
        stage = int(stage)
    params = {}
    for k, v in d.items():
        val = v.value
        if isinstance(val, (list, tuple)):
            val = [e.value if isinstance(e, Pmt) else e for e in val]
            params[k] = np.asarray(val)
        elif isinstance(val, np.ndarray):
            params[k] = val
        elif isinstance(val, (float, np.floating)):
            params[k] = float(val)        # genuine numerics normalize to float
        else:
            params[k] = val               # ints/bools/strs pass through untouched
    return stage, params


def rebase_frame_tags(tags: Sequence[ItemTag], pipeline: Pipeline,
                      out_valid: int) -> List[ItemTag]:
    """Remap frame-relative tag indices through a pipeline's rate change
    (out = in · ratio), clamped into the valid output window — the same index
    math as the CPU path's rate-changing blocks (``blocks/dsp.py``)."""
    if out_valid <= 0:
        return []
    r = pipeline.ratio
    return [ItemTag(min(t.index * r.numerator // r.denominator, out_valid - 1), t.tag)
            for t in tags]


def emit_with_tags(output, data: np.ndarray,
                   tags: Sequence[ItemTag]) -> tuple:
    """Write as much of ``data`` as the stream output accepts, emitting ``tags`` at
    their produced positions. Returns ``(pending_data, pending_tags)``: the unwritten
    tail and its rebased tags (``(None, [])`` when everything fit) — shared by the
    device sinks' partial-drain paths (TpuD2H, TpuKernel)."""
    out = output.slice()
    k = min(len(out), len(data))
    out[:k] = data[:k]
    for t in tags:
        if t.index < k:
            output.add_tag(t.index, t.tag)
    output.produce(k)
    if k < len(data):
        return data[k:].copy(), rebase_tags(tags, k)
    return None, []


class TpuH2D(Kernel):
    """Sample stream → device frames (`vulkan/h2d.rs` writer role).

    Frames cross the link in a configurable wire format (``ops/wire.py``;
    ``wire=None`` resolves via config/platform) and are dequantized by a tiny
    jitted prolog before entering the frame plane. Transfers are STAGED:
    every frame the queue bound allows has its H2D started before the oldest
    one is decoded, so frame t+1 rides the wire while t's decode dispatches
    and downstream stages compute (the reference's circulating empty-buffer
    half, `vulkan/h2d.rs:29-37`)."""

    BLOCKING = True

    def __init__(self, dtype, frame_size: Optional[int] = None,
                 inst: Optional[TpuInstance] = None,
                 max_inflight: Optional[int] = None, wire=None):
        super().__init__()
        from collections import deque
        from ..ops import arena as _arena_mod
        from ..ops.wire import resolve_wire
        self.inst = inst or instance()
        self.frame_size = frame_size or self.inst.frame_size
        self.max_inflight = 8 if max_inflight is None else max_inflight
        # an EXPLICIT queue bound must survive device-graph fusion: the
        # fused kernel's credit controller pins when any member pinned
        # (runtime/devchain.py _adopt_credit_mode)
        self._depth_explicit = max_inflight is not None
        # staging read-ahead BEYOND the queue bound (TpuKernel contract,
        # kernel_block.py): without it a frame is staged and launched in the
        # same work cycle at steady state, serializing its wire time behind
        # the previous frame's decode instead of riding under it
        self.stage_ahead = 1 if self.max_inflight > 1 else 0
        self.dtype = np.dtype(dtype)
        self.wire = resolve_wire(wire, self.inst.platform)
        # ring-exit staging copies ride the arena (ops/arena.py); a frame's
        # buffer is released once its decode dispatched — the jitted prolog's
        # output is a fresh XLA buffer, so nothing references the staging
        # pages after that (docs/tpu_notes.md "The host data path")
        self._arena = _arena_mod.arena()
        self._staged = deque()             # (h2d_finish, valid, tags, handle)
        self.input = self.add_stream_input("in", dtype, min_items=self.frame_size)
        self.output = self.add_inplace_output("out")

    def _stage(self, frame: np.ndarray, valid: int, tags,
               handle=None) -> None:
        t0 = _trace.now() if _trace.enabled else 0
        parts = self.wire.encode_host(frame)
        if t0:
            _trace.complete("tpu", "encode", t0,
                            args={"wire": self.wire.name, "items": len(frame)})
        self._staged.append((xfer.start_device_transfer_parts(
            parts, self.inst.device), valid, tags, handle))

    def _decode_frame(self, parts):
        t0 = _trace.now() if _trace.enabled else 0
        y = self.wire.jit_decode(self.dtype)(*parts)
        if t0:
            _trace.complete("tpu", "decode", t0, args={"wire": self.wire.name})
        return y

    async def work(self, io, mio, meta):
        inp = self.input.slice()
        sent = 0

        def slots() -> int:
            return self.max_inflight + self.stage_ahead \
                - self.output.queue_depth() - len(self._staged)

        # stage: start the wire transfer of every frame the queue bound allows
        while len(inp) >= self.frame_size and slots() > 0:
            tags = self.input.tags(self.frame_size)   # frame-relative indices
            frame = inp[:self.frame_size]
            handle = None
            if self.wire.encode_may_alias(frame.dtype):
                # async H2D must leave the ring before consume(); quantizing
                # wires materialize fresh arrays in encode_host already
                frame, handle = self._arena.copy_in(frame)
            self._stage(frame, self.frame_size, tags, handle)
            self.input.consume(self.frame_size)
            inp = self.input.slice()
        eos = self.input.finished()
        if eos and 0 < len(inp) < self.frame_size:
            tags = self.input.tags(len(inp))
            host = np.zeros(self.frame_size, dtype=self.input.dtype)
            host[:len(inp)] = inp
            self._stage(host, len(inp), tags)
            self.input.consume(len(inp))
            inp = self.input.slice()
        # launch: decode landed transfers onto the frame plane, oldest first —
        # waiting only on the oldest frame's remaining wire time
        while self._staged and self.output.queue_depth() < self.max_inflight:
            h2d, valid, tags, handle = self._staged.popleft()
            dev_parts = h2d()
            decoded = self._decode_frame(dev_parts)
            if handle is not None:
                # the staging pages are dead once nothing device-side still
                # READS them: on accelerators that is the H2D itself (the
                # async device_put may still be DMA-ing from the host
                # buffer after finish() — wait for the PUT to materialize;
                # the decode stays async); on the CPU client, device_put
                # zero-copy BORROWS the aligned buffer, so the decode that
                # consumes it must materialize first (free: CPU jit is
                # synchronous)
                import jax
                jax.block_until_ready(
                    decoded if self.inst.platform == "cpu" else dev_parts)
                handle.release()
            self.output.put_full(decoded, valid, tags)
            sent += 1
        if eos and len(inp) == 0 and not self._staged:
            io.finished = True
        elif sent and len(inp) >= self.frame_size:
            io.call_again = True
        # queue-full park: the consumer's get_full() notifies this block


class TpuStage(Kernel):
    """Device frame → device frame through a jitted stage pipeline; the frame never
    leaves HBM (`blocks/vulkan.rs` compute role, minus its D2H hop).

    Carries a ``ctrl`` message port with the same carry-surgery retune contract
    as :class:`~futuresdr_tpu.tpu.TpuKernel` — frame-plane pipelines retune
    while frames are in flight too."""

    BLOCKING = True

    def __init__(self, stages: Sequence[Stage], in_dtype,
                 inst: Optional[TpuInstance] = None):
        super().__init__()
        self.inst = inst or instance()
        self.pipeline = Pipeline(stages, in_dtype)
        self._compiled = None
        self._carry = None
        self._dispatches = 0                   # per-frame program invocations
        self._pending_ctrl: List[tuple] = []   # ctrl before the first frame
        self.input = self.add_inplace_input("in")
        self.output = self.add_inplace_output("out")

    def extra_metrics(self) -> dict:
        return {"dispatches": self._dispatches}

    @message_handler(name="ctrl")
    async def ctrl_handler(self, io, mio, meta, p):
        try:
            stage, params = parse_ctrl(p)
            if self._carry is None:
                # unlike TpuKernel (eager compile in init), the carry here is
                # compiled at the FIRST frame — queue the update; work() applies
                # it the moment the carry exists, so an early retune is not
                # lost. Validate what CAN be validated now (stage resolution +
                # update hook exist without a carry) so a bad stage name is
                # rejected here, not silently dropped at compile time.
                self.pipeline.update_stage(None, stage, _validate_only=True,
                                           **params)
                self._pending_ctrl.append((stage, params))
            else:
                self._carry = self.pipeline.update_stage(self._carry, stage,
                                                         **params)
        except Exception as e:
            log.warning("ctrl update rejected: %r", e)
            return Pmt.invalid_value()
        return Pmt.ok()

    async def work(self, io, mio, meta):
        while True:
            item = self.input.get_full()
            if item is None:
                break
            frame, valid, tags = item
            if self._compiled is None:
                n = frame.shape[0]
                assert n % self.pipeline.frame_multiple == 0, \
                    f"frame {n} not a multiple of {self.pipeline.frame_multiple}"
                self._compiled, self._carry = self.pipeline.compile(
                    n, device=self.inst.device)
                for stage, params in self._pending_ctrl:
                    try:
                        self._carry = self.pipeline.update_stage(
                            self._carry, stage, **params)
                    except Exception as e:          # validated only now
                        log.warning("queued ctrl update rejected: %r", e)
                self._pending_ctrl.clear()
            t0 = _trace.now() if _trace.enabled else 0
            self._carry, y = self._compiled(self._carry, frame)   # async dispatch
            self._dispatches += 1
            if t0:
                _trace.complete("tpu", "compute", t0,
                                args={"frame": int(frame.shape[0])})
            out_valid = self.pipeline.out_items(
                valid - valid % self.pipeline.frame_multiple)
            self.output.put_full(y, out_valid,
                                 rebase_frame_tags(tags, self.pipeline, out_valid))
        if self.input.finished() and len(self.input) == 0:
            io.finished = True


class _TagRatio:
    """Rate shim for :func:`rebase_frame_tags` (reads only ``.ratio``)."""

    __slots__ = ("ratio",)

    def __init__(self, ratio):
        self.ratio = ratio


class TpuMergeStage(Kernel):
    """Device frame fan-IN: K inplace inputs joined on-device into one output.

    The frame-plane merge node (``ops/stages.MergeStage``): K device frames —
    one full frame from EACH input queue — enter one jitted program (merge +
    optional post stages) and the joined frame continues on the plane without
    leaving HBM. This is the block form of the WLAN ``{demod, chan-est} →
    decode`` join and the FM ``{audio, RDS} → mux``; the device-graph fusion
    pass (``runtime/devchain.py``) collapses a whole ``producer → broadcast →
    branches → merge`` diamond containing it into ONE dispatch per frame.

    Actor-path semantics (the reference the fused path must bit-match):

    * the block waits until EVERY input holds a frame, then merges exactly one
      frame per input per dispatch;
    * stream tags ride the PRIMARY input (``in0``) — rebased through the
      merge + post rate contract; secondary inputs' tag copies are dropped
      (a broadcast upstream would otherwise duplicate every tag K times);
    * EOS follows ``blocks.Combine``: when ANY input is finished and drained,
      the block finishes (remaining partner frames can never join).

    Carries a ``ctrl`` port with the TpuStage retune contract addressing the
    ``[merge] + post_stages`` list.
    """

    BLOCKING = True

    def __init__(self, merge, post_stages: Sequence[Stage] = (),
                 inst: Optional[TpuInstance] = None):
        from ..ops.stages import MergeStage
        super().__init__()
        assert isinstance(merge, MergeStage), merge
        self.inst = inst or instance()
        self.merge = merge
        self.post = list(post_stages)
        #: ctrl addressing surface (Pipeline.update_stage reads .stages)
        self.stages = [merge] + self.post
        self._compiled = None
        self._carry = None
        self._post_pipe: Optional[Pipeline] = None
        self._tag_ratio = None
        self._dispatches = 0
        self._pending_ctrl: List[tuple] = []
        self.inputs = [self.add_inplace_input(f"in{i}")
                       for i in range(merge.k)]
        self.input = self.inputs[0]
        self.output = self.add_inplace_output("out")

    def extra_metrics(self) -> dict:
        return {"dispatches": self._dispatches}

    # Pipeline.update_stage only touches the duck-typed ``.stages`` surface,
    # so the linear implementation serves the merge block's ctrl addressing
    update_stage = Pipeline.update_stage

    @message_handler(name="ctrl")
    async def ctrl_handler(self, io, mio, meta, p):
        try:
            stage, params = parse_ctrl(p)
            if self._carry is None:
                # lazy-carry contract, exactly TpuStage's: queue until the
                # first frame compiles the carry, validating what can be
                self.update_stage(None, stage, _validate_only=True, **params)
                self._pending_ctrl.append((stage, params))
            else:
                self._carry = self.update_stage(self._carry, stage, **params)
        except Exception as e:                         # noqa: BLE001
            log.warning("ctrl update rejected: %r", e)
            return Pmt.invalid_value()
        return Pmt.ok()

    def _compile(self, frames) -> None:
        import jax
        dts = {np.dtype(f.dtype) for f in frames}
        assert len(dts) == 1, f"merge inputs disagree on dtype: {dts}"
        in_dt = dts.pop()
        merge, post = self.merge, self.post
        for f in frames:
            assert f.shape[0] % merge.frame_multiple == 0, \
                (f.shape[0], merge.frame_multiple)
        mid_dt = np.dtype(merge.out_dtype) if merge.out_dtype is not None \
            else in_dt
        self._post_pipe = Pipeline(list(post), mid_dt, optimize=False)
        self._tag_ratio = _TagRatio(merge.ratio * self._post_pipe.ratio)

        def fn(carries, xs):
            c, v = merge.fn(carries[0], xs)
            new = [c]
            for i, s in enumerate(post):
                c, v = s.fn(carries[1 + i], v)
                new.append(c)
            return tuple(new), v

        self._compiled = jax.jit(fn, donate_argnums=(0,))
        carries = [merge.init_carry(in_dt)]
        dt = mid_dt
        for s in post:
            carries.append(s.init_carry(dt))
            if s.out_dtype is not None:
                dt = np.dtype(s.out_dtype)
        self._carry = jax.device_put(tuple(carries), self.inst.device) \
            if self.inst.device is not None else tuple(carries)
        for stage, params in self._pending_ctrl:
            try:
                self._carry = self.update_stage(self._carry, stage, **params)
            except Exception as e:                     # noqa: BLE001
                log.warning("queued ctrl update rejected: %r", e)
        self._pending_ctrl.clear()

    def _out_valid(self, valids, frames) -> int:
        # clamp to the merge's own contract BEFORE applying the ratio
        # (TpuStage's `valid - valid % frame_multiple` rule): a ragged EOS
        # tail under a fractional-ratio or frame_multiple>1 merge drops the
        # sub-multiple items instead of tripping the integrality assert
        step = int(np.lcm(self.merge.frame_multiple,
                          self.merge.ratio.denominator))
        if self.merge.mode == "equal":
            # elementwise/interleave joins consume index-aligned prefixes, so
            # the shortest input bounds the valid output
            n = min(valids) // step * step
        else:
            # concat lays the inputs' FULL frames back to back: a partial
            # (EOS-tail) input frame cannot be expressed as a valid-prefix
            # count of that layout — input 0's zero padding would be emitted
            # as data and input 1's tail dropped. Concat joins therefore emit
            # only full frames; the tail rides the devchain EOS divergence
            # contract (the fused path applies the same rule,
            # DagPipeline.concat_sinks)
            if any(v < f.shape[0] for v, f in zip(valids, frames)):
                return 0
            n = sum(valids) // step * step
        q = n * self.merge.ratio
        assert q.denominator == 1, (n, self.merge.ratio)
        n = int(q)
        pp = self._post_pipe
        return pp.out_items(n - n % pp.frame_multiple)

    async def work(self, io, mio, meta):
        while True:
            if any(len(p) == 0 for p in self.inputs):
                break
            items = [p.get_full() for p in self.inputs]
            frames = tuple(it[0] for it in items)
            valids = [it[1] for it in items]
            if self._compiled is None:
                self._compile(frames)
            t0 = _trace.now() if _trace.enabled else 0
            self._carry, y = self._compiled(self._carry, frames)
            self._dispatches += 1
            if t0:
                _trace.complete("tpu", "compute", t0,
                                args={"frame": int(frames[0].shape[0]),
                                      "merge_k": self.merge.k})
            out_valid = self._out_valid(valids, frames)
            # tags ride the primary input only (class docstring)
            tags = rebase_frame_tags(items[0][2], self._tag_ratio, out_valid)
            self.output.put_full(y, out_valid, tags)
        if any(p.finished() and len(p) == 0 for p in self.inputs):
            io.finished = True


class TpuD2H(Kernel):
    """Device frames → sample stream (`vulkan/d2h.rs` reader role); the only sync
    point of the device pipeline.

    Results cross the link in a configurable wire format: a tiny jitted EPILOG
    quantizes the device frame into wire parts (``ops/wire.py``) and the host
    dequantizes after the transfer lands. Read-ahead drain: every completed
    frame waiting in the inplace queue has its host transfer STARTED before the
    oldest one is synced — frame t+1's D2H rides the wire while frame t's
    samples are being emitted, instead of serializing transfer-after-transfer
    behind the per-frame sync (VERDICT r2 weak-item 2)."""

    BLOCKING = True

    def __init__(self, dtype, inst: Optional[TpuInstance] = None,
                 read_ahead: Optional[int] = None, wire=None):
        super().__init__()
        from collections import deque
        from ..ops.wire import resolve_wire
        self.inst = inst or instance()
        # read_ahead=0 disables read-ahead = serial drain (pull one, sync it);
        # the work loop needs bound >= 1 to make progress at all
        self.read_ahead = max(1, read_ahead if read_ahead is not None
                              else self.inst.frames_in_flight)
        self.dtype = np.dtype(dtype)
        self.wire = resolve_wire(wire, self.inst.platform)
        self.input = self.add_inplace_input("in")
        self.output = self.add_stream_output("out", dtype)
        self._pending: Optional[np.ndarray] = None
        self._pending_tags: List[ItemTag] = []
        self._inflight = deque()                  # (finish, valid, tags)

    def _start_d2h(self, frame):
        t0 = _trace.now() if _trace.enabled else 0
        parts = self.wire.jit_encode()(frame)       # device-side epilog dispatch
        if t0:
            _trace.complete("tpu", "encode", t0, args={"wire": self.wire.name})
        return xfer.start_host_transfer_parts(parts)

    async def work(self, io, mio, meta):
        if self._pending is not None:
            self._pending, self._pending_tags = emit_with_tags(
                self.output, self._pending, self._pending_tags)
            if self._pending is not None:
                return              # downstream full; its consume() wakes us
        # read-ahead, BOUNDED: frames beyond the bound stay in the inplace queue
        # so the producer's queue_depth gate still parks it (backpressure intact)
        while len(self._inflight) < self.read_ahead:
            item = self.input.get_full()
            if item is None:
                break
            frame, valid, tags = item
            self._inflight.append((self._start_d2h(frame), valid, tags))
        if self._inflight:
            finish, valid, tags = self._inflight.popleft()
            # sync point (oldest frame only)
            raw = finish()
            t0 = _trace.now() if _trace.enabled else 0
            host = self.wire.decode_host(raw, self.dtype)[:valid]
            if t0:
                _trace.complete("tpu", "decode", t0,
                                args={"wire": self.wire.name, "items": valid})
            self._pending, self._pending_tags = emit_with_tags(
                self.output, host, tags)
            io.call_again = True
            return
        if self.input.finished() and len(self.input) == 0 \
                and self._pending is None and not self._inflight:
            io.finished = True
