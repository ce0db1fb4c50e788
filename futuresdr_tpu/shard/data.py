"""Data-sharded device plane: one fused program, D independent stream lanes.

The always-sound lift of a fused device program onto the chip mesh
(``shard/plan.py`` mode ``data``): the megabatch dispatch's ``[K, frame]``
wire parts gain a leading DEVICE axis — ``[D, K, frame]`` with a
``NamedSharding(mesh, P("dev"))`` on every input, carry leaf and output —
so each device owns one carry shard and runs an independent continuation
of its own stream. ``jax.vmap`` over the device axis + sharded placement
is the whole transform: GSPMD keeps every op local to its shard (the
compiled program carries ZERO collectives — :func:`collective_ops` is the
``perf/multichip_ab.py`` smoke's assert), host↔device traffic exists only
at the program boundary, and each device's row is BIT-identical to the
D=1 program fed that row AT THE SAME MEGABATCH FORM — matched K, the
repo's established scan-rounding convention (``docs/tpu_notes.md``:
K>1 scan programs round differently from K=1 by contract; sharding adds
no further divergence, which is the ``tests/test_shard.py`` pin).

:class:`ShardRunner` is the host drive loop with the recovery contract:
whole-mesh carry snapshots ride the EXISTING ``Pipeline.snapshot_carry``/
``carry_matches`` surface (the stacked ``[D, …]`` leaves ARE the per-shard
leaves — row d is device d's state), and a bounded PER-SHARD replay log of
host staging rows re-ships the exact original bytes after a fault, so a
recovered run is bit-identical to an unfailed one (the chaos
``shard-replay`` scenario).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from ..log import logger
from ..runtime import faults as _faults
from ..telemetry import journal as _journal
from ..telemetry import lineage as _lineage
from ..telemetry import profile as _profile
from ..telemetry.spans import recorder as _trace_recorder
from .plan import AXIS, ShardPlan, note_plan, plan_shard

__all__ = ["ShardedProgram", "ShardRunner", "shard_pipeline",
           "collective_ops", "shard_mesh"]

log = logger("shard.data")
_trace = _trace_recorder()

#: HLO op markers of cross-shard communication — a data-sharded program
#: must compile to none of these (interior edges never leave their shard)
_COLLECTIVE_MARKERS = ("all-reduce", "all-gather", "all-to-all",
                      "collective-permute", "collective-broadcast",
                      "reduce-scatter")


def shard_mesh(n_devices: int, axis: str = AXIS):
    """A 1-D device mesh over the first ``n_devices`` devices (refused
    loudly when fewer exist — ``parallel/mesh.make_mesh``)."""
    from ..parallel.mesh import make_mesh
    return make_mesh((axis,), shape=(int(n_devices),))


def collective_ops(compiled_text: str) -> List[str]:
    """The cross-shard collective ops present in a compiled program's HLO
    (empty == every interior edge stays on its shard)."""
    return [m for m in _COLLECTIVE_MARKERS if m in compiled_text]


class ShardedProgram:
    """A fused pipeline lifted onto a 1-D device mesh as D independent
    stream lanes (``plan.applied == "data"``).

    Duck-types the slice of the :class:`~futuresdr_tpu.ops.stages.Pipeline`
    surface the drive loops need (``in_dtype``/``out_dtype``/``ratio``/
    ``frame_multiple``/``stages``/``init_carry``/``out_items`` plus the
    snapshot trio), with the carry and frame axes generalized: every carry
    leaf and every frame batch carries a leading ``[D]`` axis sharded over
    the mesh. The wrapped pipeline object is untouched — ``shard=off``
    callers keep using it directly (the bit-identity contract).
    """

    def __init__(self, pipeline, plan: Optional[ShardPlan] = None,
                 n_devices: Optional[int] = None, name: str = "shard"):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        self.pipeline = pipeline
        self.plan = plan if plan is not None else plan_shard(
            pipeline, mode="data", n_devices=n_devices)
        if not self.plan.active:
            raise ValueError(
                "ShardedProgram needs an ACTIVE data plan (use "
                "shard_pipeline(), which returns the pipeline object "
                "unchanged for shard=off / D=1)")
        self.name = str(name)
        self.n_devices = self.plan.n_devices
        self.axis = self.plan.axis
        self.mesh = shard_mesh(self.n_devices, self.axis)
        self._sharding = NamedSharding(self.mesh, P(self.axis))
        self._fns: Dict[tuple, object] = {}    # (wire name|None, k) -> fn
        self._jits: Dict[tuple, object] = {}   # same key -> jitted wrapper
        # pass-through pipeline contract (per-lane semantics are unchanged)
        self.in_dtype = pipeline.in_dtype
        self.out_dtype = pipeline.out_dtype
        self.ratio = pipeline.ratio
        self.frame_multiple = pipeline.frame_multiple
        self.stages = pipeline.stages
        note_plan(self.name, self.plan)

    # -- placement ---------------------------------------------------------
    def sharding(self):
        return self._sharding

    def place(self, x):
        """Land a host batch (leading ``[D]`` axis) sharded over the mesh.
        Plain ``device_put``: the complex pair shim (``ops/xfer.py``) is a
        single-device transfer path and never carries a sharded mesh."""
        import jax
        return jax.device_put(x, self._sharding)

    def init_carry(self):
        """D fresh per-lane carries stacked on the leading axis and sharded
        one row per device — the whole-mesh carry."""
        import jax
        import jax.numpy as jnp
        fresh = self.pipeline.init_carry()
        stacked = jax.tree_util.tree_map(
            lambda l: jnp.stack([jnp.asarray(l)] * self.n_devices), fresh)
        return jax.device_put(stacked, self._sharding)

    # -- program forms -----------------------------------------------------
    def _shmap(self, inner, n_args: int):
        """Wrap the per-lane form in a ``shard_map`` over the device axis:
        each device strips its leading ``[1]`` block and runs EXACTLY the
        single-lane program locally. ``vmap`` + sharded placement was
        tried and rejected: GSPMD does not batch-partition the ``fft`` HLO
        op, so every FFT-bearing chain all-gathered its input and each
        device computed ALL shards' transforms — ``shard_map`` removes the
        partitioner's choice entirely (zero collectives by construction,
        and per-shard numerics are the D=1 program's own)."""
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        spec = P(self.axis)

        def local(carries, *xs):
            c = jax.tree_util.tree_map(lambda l: l[0], carries)
            c, y = inner(c, *(x[0] for x in xs))
            return (jax.tree_util.tree_map(lambda l: l[None], c),
                    jax.tree_util.tree_map(lambda l: l[None], y))

        return shard_map(local, mesh=self.mesh,
                         in_specs=(spec,) + (spec,) * n_args,
                         out_specs=(spec, spec), check_vma=False)

    def fn(self, k: int = 1, wire=None):
        """The sharded program: the per-lane (wired) megabatch form run
        per-device under ``shard_map`` (see :meth:`_shmap`). Cached per
        ``(wire, k)`` so the jit identity stays stable (the
        ``Pipeline.wired_fn`` discipline)."""
        if wire is not None:
            from ..ops.wire import get_wire
            wire = get_wire(wire)
            key = (wire.name, int(k))
            if key not in self._fns:
                self._fns[key] = self._shmap(
                    self.pipeline.wired_fn(wire, k),
                    wire.part_count(self.in_dtype))
            return self._fns[key]
        key = (None, int(k))
        if key not in self._fns:
            inner = self.pipeline.fn()
            if int(k) > 1:
                import jax
                base = inner

                def inner(carry, xs):          # noqa: F811 — megabatch form
                    return jax.lax.scan(
                        lambda c, xk: base(c, xk), carry, xs)

            self._fns[key] = self._shmap(inner, 1)
        return self._fns[key]

    def compile(self, frame_size: int, k: int = 1, wire=None):
        """Jit the sharded form for a fixed per-lane frame size; returns
        ``(compiled_fn, whole-mesh carry)``. No donation: the runner's
        recovery contract reads live carries between dispatches (snapshot
        thunks materialize against undonated buffers), exactly the serving
        engine's no-donation rationale."""
        import jax
        assert frame_size % self.frame_multiple == 0, \
            f"frame_size {frame_size} not a multiple of {self.frame_multiple}"
        from ..ops.wire import get_wire
        key = (get_wire(wire).name if wire is not None else None, int(k))
        fn = self._jits.get(key)
        if fn is None:
            # cache the JITTED wrapper too (not just the traced callable):
            # a fresh jax.jit per compile() call would discard the trace/
            # compile cache and re-pay XLA for the identical program
            fn = self._jits[key] = jax.jit(self.fn(k, wire),
                                           donate_argnums=())
        return fn, self.init_carry()

    def compiled_text(self, frame_size: int, k: int = 1, wire=None) -> str:
        """The compiled HLO of the sharded program (the collectives
        audit's input — see :func:`collective_ops`)."""
        fn, carries = self.compile(frame_size, k, wire)
        zero = np.zeros(frame_size, dtype=self.in_dtype)
        if wire is not None:
            from ..ops.wire import get_wire
            parts = get_wire(wire).encode_host(zero)
            lead = (self.n_devices,) if k == 1 else (self.n_devices, k)
            args = tuple(self.place(np.broadcast_to(
                np.asarray(p), lead + np.shape(p)).copy()) for p in parts)
        else:
            shape = (self.n_devices, frame_size) if k == 1 \
                else (self.n_devices, k, frame_size)
            args = (self.place(np.zeros(shape, dtype=self.in_dtype)),)
        return fn.lower(carries, *args).compile().as_text()

    def out_items(self, in_items: int) -> int:
        return self.pipeline.out_items(in_items)

    # -- whole-mesh snapshot (the existing per-pipeline surface, applied to
    # the stacked carries: each leaf's row d IS device d's shard) ----------
    def snapshot_carry(self, carries):
        return self.pipeline.snapshot_carry(carries)

    def carry_matches(self, leaves, treedef, template) -> bool:
        return self.pipeline.carry_matches(leaves, treedef, template)

    def restore_carry(self, leaves, treedef):
        """Rebuild the whole-mesh carry from a materialized host snapshot,
        re-sharded one row per device."""
        import jax
        tree = jax.tree_util.tree_unflatten(
            treedef, [np.asarray(l) for l in leaves])
        return jax.device_put(tree, self._sharding)


def shard_pipeline(pipeline, mode: Optional[str] = None,
                   n_devices: Optional[int] = None,
                   frame_size: Optional[int] = None, name: str = "shard"):
    """The plan-then-apply entry point. ``shard=off`` (the default) or a
    one-device resolution returns the SAME pipeline object — bit-identical
    by construction; an active data plan returns a :class:`ShardedProgram`;
    an active model plan returns a
    :class:`~futuresdr_tpu.shard.model.ModelShardedProgram`."""
    plan = plan_shard(pipeline, mode=mode, n_devices=n_devices,
                      frame_size=frame_size)
    if not plan.active:
        return pipeline
    if plan.applied == "model":
        from .model import ModelShardedProgram
        return ModelShardedProgram(pipeline, plan, name=name)
    return ShardedProgram(pipeline, plan, name=name)


class ShardRunner:
    """Host drive loop for a data-sharded program: per-group dispatch with
    whole-mesh carry checkpoints and per-shard replay logs.

    One :meth:`run_group` call dispatches ONE program over all D shards
    (``[D, K, frame]`` in, one sharded output out — the per-shard dispatch
    count the multichip smoke asserts is ``dispatches == groups``, never
    ``groups x D``). Recovery contract (``docs/parallel.md``):

    * every committed group may snapshot the WHOLE-MESH carry (cadence
      ``checkpoint_every``, ring of 2) through the pipeline's own
      ``snapshot_carry`` surface — the stacked host leaves carry one row
      per shard;
    * each shard's input rows ride a bounded PER-SHARD replay log until a
      committed checkpoint covers their group (the exact host bytes, so a
      replayed dispatch re-ships what the failed one saw);
    * :meth:`recover` restores the newest snapshot passing
      ``carry_matches`` integrity (invalid candidates evicted) and
      re-dispatches the logged window per shard — already-emitted groups
      only re-advance the carry, so recovered output is BIT-identical to
      an unfailed run.

    The injected-fault site is ``dispatch`` addressed by the runner name
    (``runtime/faults.py``), polled before each group launches — the chaos
    ``shard-replay`` scenario's hook.

    ``checkpoint_every=0`` turns the recovery contract OFF AND FREE (the
    kernel checkpoint convention): no snapshots, no replay logging —
    :meth:`recover` then falls back to a fresh whole-mesh carry with
    nothing to replay.
    """

    def __init__(self, prog: ShardedProgram, frame_size: int, k: int = 1,
                 checkpoint_every: int = 1, name: Optional[str] = None):
        self.prog = prog
        self.frame_size = int(frame_size)
        self.k = max(1, int(k))
        self.checkpoint_every = max(0, int(checkpoint_every))
        self.name = str(name if name is not None else prog.name)
        self._fn, self._carries = prog.compile(self.frame_size, self.k)
        self._template = self._carries      # shape/dtype contract for matches
        self.seq = 0                        # dispatched groups (monotonic)
        self.dispatches = 0
        self.replayed = 0
        #: committed whole-mesh snapshots: (seq, leaves, treedef), ring of 2
        self._ckpts: deque = deque(maxlen=2)
        #: per-shard replay logs: shard -> deque of (seq, rows[k, frame])
        self._rlog: Dict[int, deque] = {d: deque()
                                        for d in range(prog.n_devices)}
        self._lock = threading.Lock()
        # profile plane: one aggregate entry (unit = one lane-frame) plus a
        # per-DEVICE entry per shard — fsdr_mfu{program,device} attribution
        pipe, fs = prog.pipeline, self.frame_size

        def _cost():
            from ..utils.roofline import program_cost
            return program_cost(pipe, fs)

        from ..utils.roofline import dominant_dtype
        dt = dominant_dtype(pipe.stages)
        self._prof = _profile.register(self.name, cost_thunk=_cost, dtype=dt)
        self._prof_dev = [
            _profile.register(self.name, cost_thunk=_cost, dtype=dt,
                              device=str(d))
            for d in range(prog.n_devices)]
        # pay the XLA compile NOW, billed through the profile plane like
        # every other program-compile boundary (reason="warmup"): the
        # doctor sees a benign in-progress window instead of tripping a
        # wedge on a multi-second first dispatch, and fsdr_compiles_total
        # counts shard programs. The warmup dispatches a zero group on a
        # THROWAWAY carry — the live carry stays fresh (bit-equality vs a
        # from-fresh D=1 run is the contract).
        D = prog.n_devices
        with _profile.compiling(self.name, "warmup",
                                f"D={D},frame={self.frame_size},k={self.k}"):
            warm = prog.init_carry()
            shape = (D, self.frame_size) if self.k == 1 \
                else (D, self.k, self.frame_size)
            zeros = prog.place(np.zeros(shape, dtype=prog.in_dtype))
            _warm_c, y = self._fn(warm, zeros)
            np.asarray(y)
        self._note()

    def _note(self) -> None:
        note_plan(self.name, self.prog.plan, extra={
            "dispatches": self.dispatches,
            "frames_per_shard": self.seq * self.k,
            "replayed_groups": self.replayed,
            "checkpoint_seq": (self._ckpts[-1][0] if self._ckpts else None),
            "replay_log_depth": max((len(q) for q in self._rlog.values()),
                                    default=0),
        })

    def _norm_rows(self, rows) -> np.ndarray:
        rows = np.asarray(rows)
        D, K = self.prog.n_devices, self.k
        if K == 1 and rows.ndim == 2:
            rows = rows[:, None, :]
        assert rows.shape == (D, K, self.frame_size), \
            (rows.shape, (D, K, self.frame_size))
        return np.ascontiguousarray(rows)

    def _dispatch(self, rows: np.ndarray, seq: int, replay: bool,
                  tid: int = 0):
        t0 = _trace.now() if _trace.enabled else 0
        lin = _lineage.tracer() if tid else None
        if self.k == 1:
            x = self.prog.place(rows[:, 0, :])
        else:
            x = self.prog.place(rows)
        if lin is not None:
            lin.stamp(tid, "H2D")
        self._carries, y = self._fn(self._carries, x)
        if lin is not None:
            lin.stamp(tid, "dispatch")
        out = np.asarray(y)                 # the SINK D2H (gathers shards)
        if lin is not None:
            lin.stamp(tid, "D2H")
        now = time.monotonic()
        self.dispatches += 1
        self._prof.dispatch(self.prog.n_devices * self.k, t=now)
        for p in self._prof_dev:
            # t=now for the per-device entries too: a frozen t_last would
            # leave mfu_avg permanently absent on the @devN axis (the PR 11
            # run-average window contract)
            p.dispatch(self.k, t=now)
        if t0:
            _trace.complete("tpu", "compute", t0,
                            args={"devices": self.prog.n_devices,
                                  "seq": seq, "replay": replay})
            for d in range(self.prog.n_devices):
                _trace.complete("shard", f"shard:d{d}", t0,
                                args={"seq": seq, "frames": self.k,
                                      "runner": self.name})
        return out

    def _checkpoint(self) -> None:
        """Snapshot the whole-mesh carry NOW (outputs of the covered group
        already drained — the commit ordering of the kernel checkpoint
        contract) and prune every shard's replay log to the PREVIOUS
        snapshot, so a corrupted newest candidate still has a replayable
        window behind it."""
        fins, treedef = self.prog.snapshot_carry(self._carries)
        leaves = [np.asarray(f()) for f in fins]
        self._ckpts.append((self.seq, leaves, treedef))
        _journal.emit("shard", "checkpoint-commit", runner=self.name,
                      seq=int(self.seq))
        # prune to the PREVIOUS snapshot, not the one just committed: while
        # only ONE candidate exists, a corrupt candidate must still leave a
        # fresh-init + full-replay path, so the whole window stays logged
        floor = self._ckpts[0][0] if len(self._ckpts) > 1 else 0
        for q in self._rlog.values():
            while q and q[0][0] <= floor:
                q.popleft()

    def run_group(self, rows) -> np.ndarray:
        """Dispatch one group (``[D, K, frame]`` host rows; ``[D, frame]``
        accepted at K=1) and return the gathered host output
        ``[D, K, out]``. Raises the injected fault (site
        ``dispatch:<runner name>``) BEFORE any state advances — the caller
        recovers with :meth:`recover`."""
        with self._lock:
            rows = self._norm_rows(rows)
            _faults.maybe("dispatch", self.name)
            # frame lineage: one sampled trace per GROUP (the runner's unit
            # of dispatch) — replayed groups re-dispatch with tid 0
            tid = _lineage.tracer().sample()
            if tid:
                _lineage.tracer().stamp(tid, "ingest")
            seq = self.seq + 1
            if self.checkpoint_every:
                # cadence 0 = recovery off AND FREE: no snapshots means
                # nothing ever prunes the logs, so nothing may enter them
                for d in range(self.prog.n_devices):
                    self._rlog[d].append((seq, rows[d].copy()))
            out = self._dispatch(rows, seq, replay=False, tid=tid)
            self.seq = seq
            if self.checkpoint_every and seq % self.checkpoint_every == 0:
                self._checkpoint()
            if tid:
                lin = _lineage.tracer()
                lin.stamp(tid, "emit")
                lin.finish(tid, source=f"shard:{self.name}")
            self._note()
            return out

    def recover(self) -> int:
        """Bit-identical recovery: restore the newest VALID whole-mesh
        snapshot (integrity via ``carry_matches`` against the live carry
        template; invalid candidates evicted in favor of the previous
        one), then replay every logged group above it per shard — emitted
        groups advance the carry only. Returns the number of replayed
        groups."""
        with self._lock:
            restore_seq = 0
            restored = None
            while self._ckpts:
                seq, leaves, treedef = self._ckpts[-1]
                if self.prog.carry_matches(leaves, treedef, self._template):
                    restored = (seq, leaves, treedef)
                    break
                log.warning("%s: evicting corrupt checkpoint candidate "
                            "seq=%d", self.name, seq)
                self._ckpts.pop()
            if restored is not None:
                restore_seq, leaves, treedef = restored
                self._carries = self.prog.restore_carry(leaves, treedef)
            else:
                self._carries = self.prog.init_carry()
            # assemble the replay window per seq from the per-shard logs
            seqs = sorted({s for q in self._rlog.values()
                           for s, _ in q if s > restore_seq})
            replayed = 0
            for seq in seqs:
                rows = np.stack([
                    next(r for s, r in self._rlog[d] if s == seq)
                    for d in range(self.prog.n_devices)])
                self._dispatch(rows, seq, replay=True)
                replayed += 1
            self.replayed += replayed
            self.seq = max(self.seq, restore_seq + replayed)
            _journal.emit("shard", "recover", runner=self.name,
                          checkpoint_seq=int(restore_seq),
                          replayed=int(replayed),
                          fresh_init=restored is None)
            if replayed:
                _journal.emit("shard", "replay", runner=self.name,
                              groups=int(replayed),
                              high_seq=int(self.seq))
            log.info("%s: recovered at seq=%d, replayed %d group(s)",
                     self.name, restore_seq, replayed)
            self._note()
            return replayed
