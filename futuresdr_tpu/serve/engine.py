"""ServeEngine: batch N concurrent sessions of ONE receiver DAG into one
dispatch per frame.

The production serving plane of docs/serving.md. Every fused
``Pipeline``/``FanoutPipeline``/``DagPipeline`` program computes exactly one
session per dispatch on the actor path — at SDR frame rates that leaves the
chip almost entirely idle (MFU 5.6% on the resident chain, ROADMAP). This
engine multiplexes N concurrent sessions running the SAME program through a
single per-frame dispatch by compiling the pipeline ONCE per slot bucket
with a leading session axis:

* ``jax.vmap`` over the inputs AND the flat composed carry — the carry
  layout per lane stays exactly the linear contract, so ``update_stage``
  addressing and the checkpoint ``snapshot_carry``/``restore_carry``
  surface keep working per slot;
* RAGGED admission in the style of Ragged Paged Attention
  (arXiv:2604.15464): a fixed-capacity slot axis with padded inactive
  lanes masked by an ``active`` lanes vector threaded as a program input —
  sessions join, leave and stall mid-flight by flipping mask lanes, with
  ZERO recompiles of resident buckets (``self.compiles`` is the pin);
* PAGED carry storage (docs/serving.md "Paged session carries"): per-lane
  carries live in a fixed-size page pool indexed by the session→page
  permutation the :class:`~futuresdr_tpu.serve.slots.SlotTable` maintains;
  the compiled program gathers each lane's page, substitutes the fresh
  template on ``fresh``-flagged lanes, steps, and scatters back — so a
  join lands at its own frame cursor MID-megabatch as a page-map edit, a
  leave parks the page, and eviction reads one page, never a restack;
* an OVERLAPPED step: the dispatch group launched at step t rides async
  H2D starts (its input in LANE GROUPS, each filled and put on the wire
  while the next is filled; a group in which no lane rides is not shipped)
  and ``start_host_transfer`` D2H finishes, governed by the streamed path's
  :class:`~futuresdr_tpu.tpu.kernel_block.CreditController`, so
  H2D(t+1) ∥ compute(t) ∥ D2H(t−1) holds for serving exactly as for the
  streamed kernel — committed carries advance ONLY after a group's D2H
  lands (a failed drain re-queues every uncommitted group's frames:
  PR 10's rollback contract, now over a window);
* ONE frame format per engine (``wire=``, docs/serving.md "Frames on the
  radio's wire"): samples of the pipeline's ``in_dtype``, or the radio's
  own ``sc16`` words, which stay words in the queue, the staging sets and
  on the link and are decoded first thing inside the step's program;
* autotuned bucket sizes (``tpu/autotune.autotune_serve``): occupancy
  crossing the current bucket grows the PAGE POOL to the next bucket's
  capacity and compiles THAT capacity once;
* per-session carry slots riding the checkpoint machinery: ``evict`` lands
  a session's carry lane on the host via ``snapshot_carry``'s leaf
  contract, ``readmit`` restores it bit-identically (validated by
  ``carry_matches`` against the fresh-carry template, exactly like the
  kernel recovery path);
* per-tenant fairness over the shared admission budget
  (:class:`~futuresdr_tpu.serve.credits.TenantCreditController` — the
  multi-tenant generalization of the streamed path's CreditController);
* per-session fault isolation (the ``isolate_group``-per-session
  semantics): a work/dispatch fault addressed at one session retires ONLY
  that slot — siblings keep their lanes and their bit-exact outputs.

Masking semantics: inactive lanes still ride through the vmapped program
(their input rows are zeros, or whatever their lane group's staging array
last held), but their computed carries are DISCARDED by a
``where(active, new, old)`` merge inside the jitted program — a stalled
lane's filter history and oscillator phase are bit-frozen until its next
real frame, and an active lane's carry is exactly what the standalone
program would have produced (the N=1 ≡ bare-pipeline bit-equality
contract, test-pinned).
"""

from __future__ import annotations

import math
import os
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

import numpy as np

from ..log import logger
from ..ops import xfer
from ..runtime import faults as _faults
from ..telemetry import fleet as _fleet
from ..telemetry import journal as _journal
from ..telemetry import lineage as _lineage
from ..telemetry import profile as _profile
from ..telemetry import prom as _prom
from ..telemetry.doctor import E2E_LATENCY as _E2E_LATENCY
from ..telemetry.spans import recorder as _trace_recorder
from .credits import TenantCreditController
from .overload import LATENCY_RUNG as _LATENCY_RUNG
from .overload import ShedLadder
from .persist import SessionStore
from .slots import (ServeDraining, ServeFull, ServeOverload, Session,
                    SlotTable)

__all__ = ["ServeEngine", "ServeFull", "ServeDraining", "ServeOverload",
           "default_buckets", "install_sigterm_drain"]

log = logger("serve.engine")
_trace = _trace_recorder()

# per-tenant Prometheus families (docs/serving.md "Observability"): every
# family carries {app, tenant} so one scrape separates tenants; label
# ordering in the exposition is stable (telemetry/prom.py sorts samples)
_SESSIONS = _prom.gauge(
    "fsdr_serve_sessions", "live serving sessions per state",
    ("app", "tenant", "state"))
_FRAMES = _prom.counter(
    "fsdr_serve_frames_total", "frames dispatched through the serving plane",
    ("app", "tenant"))
_DISPATCHES = _prom.counter(
    "fsdr_serve_dispatches_total",
    "batched serving dispatches (one per step with >= 1 active lane)",
    ("app",))
_LANES_SHIPPED = _prom.counter(
    "fsdr_serve_lanes_shipped_total",
    "input lanes uploaded by serving dispatches (lane groups shipped x "
    "lanes per group; the riding lanes are fsdr_serve_frames_total)",
    ("app",))
_WIRE_BYTES = _prom.counter(
    "fsdr_serve_wire_bytes_total",
    "bytes of session frames put on the host-device link by serving "
    "dispatches, as they crossed (wire: the engine's frame format)",
    ("app", "wire"))
_RETIRED = _prom.counter(
    "fsdr_serve_retired_total",
    "sessions retired by a per-session fault (slot-isolated)",
    ("app", "tenant"))
_EVICTIONS = _prom.counter(
    "fsdr_serve_evictions_total",
    "session carries evicted to the host", ("app", "tenant"))
_REJECTS = _prom.counter(
    "fsdr_serve_rejects_total",
    "frame submissions refused by the per-tenant credit guard",
    ("app", "tenant"))
_LATENCY = _prom.histogram(
    "fsdr_serve_latency_seconds",
    "submit -> decoded-result latency per frame", ("app", "tenant"))
_SHED = _prom.counter(
    "fsdr_serve_shed_total",
    "overload/drain shedding actions by the serving engine "
    "(reason: admission | evict | brownout | drain)",
    ("app", "tenant", "reason"))
_SHED_LEVEL = _prom.gauge(
    "fsdr_serve_shed_level",
    "current shedding-ladder rung (0 ok, 1 admission, 2 evict, 3 brownout)",
    ("app",))
_RESUMED = _prom.counter(
    "fsdr_serve_resumed_total",
    "sessions re-admitted from durable snapshots by a fresh incarnation",
    ("app", "tenant"))


#: lanes per upload group of a step's input (docs/serving.md "The overlapped
#: step"): small enough that a group's bytes cross while the next is filled and
#: that a few riding lanes ship little else, large enough that the starts do
#: not outweigh the bytes. Chosen on the chip among 1, 4, 8, 16, 32 and 64 at
#: 64 lanes of 524 KB (PERF.md section 6, PR 27): 16 serves a full bucket
#: fastest, 8 is 9 % slower there and a tenth quicker for a few riders
LANE_GROUP = 16

#: what one count of a 16-bit I/Q sample is worth on a serving wire: 2^-15,
#: fixed per engine and never worked out from a frame (UHD's convention for
#: ``sc16``: full scale is 32768 counts)
FULL_SCALE = 32768.0


def default_buckets() -> tuple:
    """The slot-bucket ladder when neither the caller nor the autotune cache
    provides one: config ``serve_buckets`` ("1,2,4,…"), else powers of two
    to 64."""
    from ..config import config
    spec = str(config().get("serve_buckets", "") or "").strip()
    if spec:
        try:
            out = sorted({int(x) for x in spec.replace(";", ",").split(",")
                          if x.strip()})
            if out and all(b > 0 for b in out):
                return tuple(out)
        except ValueError:
            log.warning("bad serve_buckets spec %r — using the default "
                        "ladder", spec)
    return (1, 2, 4, 8, 16, 32, 64)


def serve_wire(wire, in_dtype):
    """The wire an engine's sessions submit frames on, or None (frames of the
    pipeline's ``in_dtype``, shipped as they are). One format has a form a
    radio delivers and a program decodes without a relayout: ``sc16`` under a
    complex ``in_dtype``, a complex sample a 32-bit word (``Wire.pair_words``)."""
    if wire is None:
        return None
    from ..ops.wire import get_wire
    w = get_wire(wire)
    if not w.pair_words((1, 2), np.int16, in_dtype):
        raise ValueError(
            f"serving takes frames on the sc16 wire under a complex in_dtype "
            f"(a complex sample a 32-bit word), not {w.name!r} under "
            f"{np.dtype(in_dtype)}")
    return w


def build_slot_program(pipeline, capacity: int, k: int = 1, wire=None):
    """Compile the pipeline's PAGED slot-batched serving step for one
    page-pool capacity:

        step(pages, page_map, fresh, x, active) -> (pages', outs)

    with every page-pool leaf carrying a leading ``[capacity]`` page axis.
    ``page_map`` is the lane→page PERMUTATION of ``[0, capacity)`` the
    :class:`~futuresdr_tpu.serve.slots.SlotTable` maintains, threaded as a
    program INPUT: the step gathers each lane's carry page
    (``leaf[page_map]``), steps the lanes, and scatters the merged carries
    back (``leaf.at[page_map].set(...)``) — churn edits the map on the
    host, never the program. The permutation invariant is load-bearing:
    a duplicate scatter index would make the result order-undefined.
    ``fresh`` is a ``[capacity]`` bool vector flagging lanes admitted since
    the last dispatch: their gathered page (stale bits of whoever parked
    there last) is replaced by the pipeline's init-carry template INSIDE
    the program, so admission writes nothing to the device — a joining
    session starts at its own frame cursor mid-megabatch.

    ``k == 1`` (the default): ``x`` is ``[capacity, frame]``, ``active`` a
    ``[capacity]`` bool vector, outs ``[capacity, out]`` per sink.

    ``k > 1`` is the MEGABATCH serving form: ``x`` is ``[capacity, k,
    frame]``, ``active`` a ``[capacity, k]`` PER-FRAME mask, and a
    ``lax.scan`` chains the k frames through every lane in one program call
    (amortizing per-dispatch host cost exactly like ``TpuKernel``'s
    ``frames_per_dispatch``) — the mask is RAGGED per lane, so sessions
    with fewer than k queued frames ride the same dispatch with their tail
    masked and their carries frozen from their last real frame on (frames
    pack at the front of the k axis; a masked row can never corrupt a
    later real frame's carry). The page gather/scatter happens ONCE around
    the whole scan, not per frame.

    Inactive lanes keep their OLD carry (bit-frozen stall semantics) —
    except fresh lanes, which scatter the TEMPLATE back so their page is
    initialized by their first ride whether or not they had a frame.
    Output rows of inactive lane-frames are never delivered, so their
    value is irrelevant. No donation: eviction and lane surgery do
    functional page reads/updates on the live pool between dispatches, and
    the overlapped step keeps the committed pool alive while speculative
    groups are in flight — donation would invalidate exactly those
    buffers. Shared with ``tpu/autotune.autotune_serve`` so the measured
    program is exactly the served one.

    ``wire`` (:func:`serve_wire`): ``x`` is ``uint32`` words of the same
    shape, a complex sample a word as the radio delivered it (I the low
    half, Q the high half, int16 each), and the step decodes them under the
    ``wire_decode`` scope before the first stage: two shifts, two converts
    and one multiply a component by the fixed count ``1 / FULL_SCALE``, so
    a sample is exactly its 16 bits times 2^-15 and no array with a minor
    dimension of 2 exists. Without it the program is the one above, text
    for text."""
    import jax
    import jax.numpy as jnp

    inner = pipeline.fn()
    multi = bool(getattr(pipeline, "n_branches", 0))
    template = pipeline.init_carry()

    def ingest(x):
        if wire is None:
            return x
        # the count as the wire's per-frame scale: (qmax / FULL_SCALE) / qmax
        # is 2^-15 exactly, worked out in numpy before the trace
        scale = np.float32(wire.qmax / FULL_SCALE)
        with jax.named_scope("wire_decode"):
            return wire.decode_words_jax(
                (jax.lax.bitcast_convert_type(x, jnp.int32), scale),
                pipeline.in_dtype)

    def gather(pages, page_map, fresh):
        def pick(P, t):
            c = P[page_map]
            m = fresh.reshape((fresh.shape[0],) + (1,) * (c.ndim - 1))
            return jnp.where(m, jnp.asarray(t)[None], c)

        return jax.tree_util.tree_map(pick, pages, template)

    def scatter(pages, page_map, carries):
        return jax.tree_util.tree_map(
            lambda P, c: P.at[page_map].set(c), pages, carries)

    def masked_lane_step(carries, x, active):
        new_c, y = jax.vmap(inner)(carries, x)

        def sel(n, o):
            m = active.reshape((active.shape[0],) + (1,) * (n.ndim - 1))
            return jnp.where(m, n, o)

        return jax.tree_util.tree_map(sel, new_c, carries), y

    if int(k) <= 1:
        def step(pages, page_map, fresh, x, active):
            with jax.named_scope("serve_gather"):
                carries = gather(pages, page_map, fresh)
            new_c, y = masked_lane_step(carries, ingest(x), active)
            with jax.named_scope("serve_scatter"):
                pages = scatter(pages, page_map, new_c)
            return pages, (y if multi else (y,))
    else:
        def step(pages, page_map, fresh, x, active):
            with jax.named_scope("serve_gather"):
                carries = gather(pages, page_map, fresh)

            def body(c, xa):
                xk, ak = xa
                return masked_lane_step(c, xk, ak)

            carries, ys = jax.lax.scan(
                body, carries,
                (jnp.moveaxis(ingest(x), 1, 0), jnp.moveaxis(active, 1, 0)))
            # ys: [k, capacity, out] per sink -> [capacity, k, out]
            if multi:
                outs = tuple(jnp.moveaxis(yj, 0, 1) for yj in ys)
            else:
                outs = (jnp.moveaxis(ys, 0, 1),)
            with jax.named_scope("serve_scatter"):
                pages = scatter(pages, page_map, carries)
            return pages, outs

    return jax.jit(step, donate_argnums=())


class _DispatchGroup:
    """One launched-but-uncommitted serving dispatch (the overlapped step's
    unit of flight): the host-side batch bookkeeping assembled at step t,
    the speculative output pages the program produced, and the pending D2H
    finishes. Committed oldest-first; a failed drain rolls the whole chain
    back (every younger group derived its pages from this one's output)."""

    __slots__ = ("capacity", "k", "lanes", "n_frames", "active",
                 "fresh", "page_map", "fresh_lanes", "step_tids", "t_step",
                 "seq", "new_pages", "fins", "wire", "staging",
                 "lanes_shipped", "groups_shipped", "bytes_shipped")

    def __init__(self, capacity: int, k: int, lanes: list, active,
                 fresh, page_map, fresh_lanes: frozenset, step_tids: list,
                 t_step: int, seq: int):
        self.capacity = capacity
        self.k = k
        self.lanes = lanes            # (session, lane, popped, tids) tuples
        self.n_frames = sum(len(p) for _s, _l, p, _t in lanes)
        self.active = active
        self.fresh = fresh
        self.page_map = page_map
        self.fresh_lanes = fresh_lanes
        self.step_tids = step_tids
        self.t_step = t_step
        self.seq = seq                # the engine's step number: joins spans
        self.new_pages = None         # set by launch
        self.fins = None              # pending D2H finishes, one per sink
        self.wire = None              # H2D (service, deadline) wire window
        self.staging = None           # the host staging set it shipped from
        self.lanes_shipped = 0        # set by launch: what crossed the link
        self.groups_shipped = 0
        self.bytes_shipped = 0


class ServeEngine:
    """Multi-tenant serving front-end over one compiled receiver program.

    Host-driven: a serving loop (``perf/serve_ab.py``, an app's pump thread)
    calls :meth:`step` once per frame time; the REST session plane
    (``serve/api.py``) and any thread may ``admit``/``submit``/``evict``/
    ``close`` concurrently — one engine lock serializes table mutations
    against the dispatch walk.
    """

    def __init__(self, pipeline, frame_size: Optional[int] = None,
                 app: str = "serve", inst=None,
                 buckets: Optional[Sequence[int]] = None,
                 queue_frames: Optional[int] = None,
                 frames_per_dispatch: int = 1,
                 persist_dir: Optional[str] = None,
                 persist_every: Optional[int] = None,
                 slo_ms: Optional[float] = None,
                 shard_devices: Optional[int] = None,
                 inflight: Optional[int] = None,
                 wire: Optional[str] = None):
        from ..config import config
        from ..tpu.instance import instance
        self.pipeline = pipeline
        #: the format sessions submit frames in, one per engine (a deployment
        #: setting beside ``frame_size``; docs/serving.md "Frames on the
        #: radio's wire"): None = samples of ``pipeline.in_dtype``;
        #: ``"sc16"`` = ``uint32[frame]`` words, a complex sample a word as
        #: UHD / SoapySDR / IIO deliver it, decoded inside the step's program
        self.wire = serve_wire(wire, pipeline.in_dtype)
        #: what a frame is in the session queue, the staging sets, on the
        #: link and in the resident zero block: the wire's words, else the
        #: pipeline's samples
        self.frame_dtype = np.dtype(np.uint32 if self.wire is not None
                                    else pipeline.in_dtype)
        #: the format's name in spans, counters and views ("raw": samples
        #: of the pipeline's dtype, no codec)
        self.wire_name = self.wire.name if self.wire is not None else "raw"
        self._base_pipeline = pipeline     # pre-brownout program identity
        self.app = str(app)
        # per-lane e2e latency for the serving plane: the SAME
        # fsdr_e2e_latency_seconds family the streamed sinks observe, one
        # source child per app — so the doctor's e2e quantiles and the
        # lineage exemplars cover serving and streaming uniformly
        self._e2e_hist = _E2E_LATENCY.labels(source=f"serve:{self.app}")
        self.inst = inst or instance()
        self.k_batch = max(1, int(frames_per_dispatch))
        m = pipeline.frame_multiple
        fs = frame_size or config().tpu_frame_size
        self.frame_size = max(m, (fs // m) * m)
        self.n_sinks = int(getattr(pipeline, "n_branches", 0)) or 1
        self._multi = bool(getattr(pipeline, "n_branches", 0))
        if buckets is None:
            buckets = self._cached_buckets()
        self.buckets = tuple(sorted({int(b) for b in buckets})) \
            if buckets else default_buckets()
        # -- slot-axis sharding (docs/parallel.md "Mesh-sharded device
        # plane", docs/serving.md): a bucket's session lanes spread across
        # the chip mesh — the stacked carries, batch and mask shard on the
        # SLOT axis (one contiguous lane block per device), so a D-chip
        # mesh serves D x the lanes per dispatch with the same program.
        # Off (the default, serve_shard_devices=0 / D=1) is byte-for-byte
        # the single-device engine. Refusals are loud (make_mesh contract:
        # more devices than exist never truncates silently); a bucket whose
        # capacity does not divide by D stays UNSHARDED — evict/readmit and
        # lane surgery address (device, lane) through slot_device()
        sd = int(shard_devices if shard_devices is not None
                 else config().get("serve_shard_devices", 0) or 0)
        self._shard_d = max(1, sd)
        self._slot_sharding = None
        if self._shard_d > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ..shard.data import shard_mesh
            from ..shard.plan import AXIS
            self._shard_mesh = shard_mesh(self._shard_d)   # loud refusal
            self._slot_sharding = NamedSharding(self._shard_mesh, P(AXIS))
            self._replicated_sharding = NamedSharding(self._shard_mesh, P())
        #: compiled serving programs keyed (capacity, k, pipeline tag) — the
        #: session-churn contract is that this map only ever GAINS entries
        #: (join/leave/stall/evict inside resident buckets never recompiles;
        #: the k/tag axes exist for the brownout lever, which is a DOCUMENTED
        #: program change, never churn)
        self._programs: Dict[tuple, object] = {}
        self.compiles = 0                 # program builds (the recompile pin)
        start_cap = self.buckets[0]
        if buckets is None:
            # the autotune cache's paged-bucket axis (serve_pages): a
            # measured page-pool capacity pre-provisions the pool so the
            # first churn wave never climbs the ladder compile-by-compile
            start_cap = self._cached_pages() or start_cap
        self.table = SlotTable(start_cap)
        self._fresh = None                # fresh single-lane carry template
        #: committed page pool: one lane-sized carry page per slot of the
        #: current capacity, indexed by the SlotTable's lane→page
        #: permutation. Advances ONLY when a dispatch group's D2H lands.
        self._pages = self._stacked_fresh(self.table.capacity)
        #: speculative head of the page-pool chain: the newest launched
        #: group's output pages — the next group's input. Equal to
        #: ``_pages`` whenever nothing is in flight.
        self._head_pages = self._pages
        #: lanes admitted since their first dispatch: the program replaces
        #: their gathered page with the fresh template (see
        #: build_slot_program) — the bits clear when a group launches and
        #: are restored by rollback
        self._fresh_lanes: set = set()
        per_slot = int(queue_frames
                       if queue_frames is not None
                       else config().get("serve_queue_frames", 2))
        self._queue_frames = max(1, per_slot)
        self.credits = TenantCreditController(
            self._queue_frames * self.table.capacity)
        # overlapped step (docs/serving.md "The overlapped step"): up to
        # ``serve_inflight`` dispatch groups ride concurrently — launched
        # (H2D + program call + D2H started) but uncommitted. Depth 1 is
        # byte-for-byte the synchronous engine. The budget is governed by
        # the streamed path's CreditController: with a modeled wire it
        # probes one extra group when the up-link idles between launches
        # and rolls back probes that don't pay (kernel_block.py).
        from ..tpu.kernel_block import CreditController
        depth = max(1, int(inflight if inflight is not None
                           else config().get("serve_inflight", 1)))
        self._flight = CreditController(depth, adaptive=depth > 1)
        self._inflight: Deque = deque()   # launched, uncommitted groups
        #: step/quiesce lock — ALWAYS acquired before ``_lock``. Held by
        #: steppers across launch+drain (so the in-flight chain has one
        #: owner) and by page-touching surgery (evict/readmit/retune/
        #: growth/brownout), which must drain the chain first. The state
        #: lock ``_lock`` below is held only for table/queue mutation —
        #: never across a compile, transfer wait, or program call — so
        #: /metrics, health() and describe() answer mid-step.
        self._step_lock = threading.RLock()
        self._lock = threading.RLock()
        self._ticking = False             # _overload_tick re-entry guard
        # bounded retired-session retention: a faulted client rarely comes
        # back to DELETE its session, so retired views (and their
        # undelivered output) would otherwise accumulate forever in a
        # long-running process — keep the newest N, forget the oldest
        self._retired_keep = max(0, int(config().get("serve_retired_keep",
                                                     64)))
        self._retired: List[str] = []
        self.steps = 0                    # step() calls (incl. idle)
        self.dispatches = 0               # steps that launched the program
        self.frames = 0                   # session-frames dispatched
        self.lanes_shipped = 0            # input lanes uploaded (committed)
        self.groups_shipped = 0           # lane groups uploaded (committed)
        #: host staging sets not in use, per (capacity, k): one array per
        #: lane group (made on the group's first ride, then reused). A
        #: launched group HOLDS its set until it commits or rolls back: the
        #: link reads a staging array after ``device_put`` returns, so it may
        #: be rewritten only once its outputs are on the host
        self._staging: Dict[tuple, list] = {}
        #: device-resident zero input of one lane group per (lanes, k): what
        #: a group in which no lane rides passes to the join
        self._zero_parts: Dict[tuple, object] = {}
        self._gauge_cache: Dict[tuple, object] = {}
        # profile plane (telemetry/profile.py): capacities whose first
        # dispatch (the real jit compile — build_slot_program only wraps)
        # has been billed as reason="serve_bucket", and the live-roofline
        # entry whose unit is ONE SESSION-FRAME (lane) — the registered
        # cost is the single-lane program's cost_analysis(), so vmapped
        # bucket MFU attributes per lane regardless of the resident bucket
        pipe, fs = self.pipeline, self.frame_size

        def _lane_cost():
            from ..utils.roofline import program_cost
            return program_cost(pipe, fs)

        self._warmed: set = set()
        from ..utils.roofline import dominant_dtype
        self._prof = _profile.register(f"serve:{self.app}",
                                       cost_thunk=_lane_cost,
                                       dtype=dominant_dtype(pipe.stages))
        # -- crash safety + lifecycle + overload control (this PR) ---------
        # durable session state (docs/robustness.md "Serving-plane
        # recovery"): per-slot carry snapshots under serve_persist_dir,
        # background cadence serve_persist_every (0 = off and free — one
        # falsy check per step)
        d = persist_dir if persist_dir is not None \
            else config().get("serve_persist_dir", "")
        d = str(d or "")
        self._store = SessionStore(d, self.app, pipeline) if d else None
        self._persist_every = max(0, int(
            persist_every if persist_every is not None
            else config().get("serve_persist_every", 0)))
        self._steps_since_persist = 0
        # graceful lifecycle: draining refuses admissions, finishes
        # in-flight groups, persists all lanes; drained is terminal-ish
        # (a new incarnation, not this one, serves the next wave)
        self._draining = False
        self._drained = False
        # SLO-aware overload shedding (serve/overload.py): queue-pressure
        # watermarks + latency deadline budget drive the hysteretic ladder
        self._slo_ms = float(slo_ms if slo_ms is not None
                             else config().get("serve_slo_ms", 0.0))
        self._ladder = ShedLadder.from_config()
        self._brownout = str(config().get("serve_brownout", "off") or "off")
        bp = str(config().get("serve_brownout_precision", "bf16") or "bf16")
        # unknown modes fall back to bf16 — a typo'd config must not turn
        # the overload lever into a no-op at the worst possible moment
        self._brownout_prec = bp if bp in ("bf16", "int8") else "bf16"
        self._brownout_active = False
        self._low_pipe = None              # lazily-planned lowered brownout form
        self._pipe_tag = "base"            # program-cache axis for brownout
        self._base_dt = None               # base-pipeline leaf dtypes (lazy)
        self._lat_recent: Deque[float] = deque(maxlen=128)   # seconds
        self._step_stamps: Deque[float] = deque(maxlen=32)   # busy-step times
        self.restored_sessions = 0         # persisted sessions re-admitted
        self.shed_evictions = 0            # ladder rung-2 evictions
        # doctor coverage: the engine registers with the process-global
        # watchdog (weakref — test churn must not leak attachments) so a
        # wedged step()/drain trips a flight record naming the stuck app
        self._doctor_token = None
        try:
            from ..telemetry import doctor as _doctor
            self._doctor_token = _doctor.doctor().attach_serve(self)
        except Exception as e:             # noqa: BLE001 — observability only
            log.warning("%s: doctor attach failed: %r", self.app, e)
        if self._store is not None:
            self._restore_persisted()

    # -- carry plumbing --------------------------------------------------------
    def _fresh_carry(self):
        if self._fresh is None:
            self._fresh = self.pipeline.init_carry()
        return self._fresh

    def _shard_ok(self, capacity: int) -> bool:
        """Does this bucket shard over the mesh? Needs the slot-axis mesh
        armed AND an even lane split (one contiguous block per device)."""
        return (self._slot_sharding is not None
                and capacity % self._shard_d == 0)

    def slot_device(self, slot: int) -> tuple:
        """The ``(device_index, lane)`` pair a slot addresses under the
        slot-axis sharding (``(0, slot)`` unsharded): slots shard in
        contiguous blocks, so device ``slot // (capacity // D)`` owns lane
        ``slot % (capacity // D)`` of its shard. Evict/readmit and lane
        surgery stay slot-addressed — this is the observability mapping
        (session views, doctor)."""
        if not self._shard_ok(self.table.capacity):
            return (0, int(slot))
        per = self.table.capacity // self._shard_d
        return (int(slot) // per, int(slot) % per)

    def _stacked_fresh(self, capacity: int):
        import jax
        import jax.numpy as jnp
        fresh = self._fresh_carry()
        stacked = jax.tree_util.tree_map(
            lambda l: jnp.stack([jnp.asarray(l)] * capacity), fresh)
        if self._shard_ok(capacity):
            stacked = jax.device_put(stacked, self._slot_sharding)
        else:
            # COMMIT the pool to the instance device: the program's output
            # pages (the pool after the first commit) are committed arrays,
            # and jit keys on sharding — an uncommitted seed pool would buy
            # a second silent compile of the same capacity on step 2
            stacked = jax.device_put(stacked, self.inst.device)
        return stacked

    def _set_page(self, page: int, value_tree) -> None:
        """Write one carry page of the COMMITTED pool (readmit, restore,
        retune). Only legal at a quiescent boundary — the caller holds the
        step lock with nothing in flight, so the speculative head is
        re-synced here and the next launch derives from the write."""
        import jax
        assert not self._inflight, "page write with groups in flight"
        if self._shard_ok(self.table.capacity):
            # page values arrive committed to ONE device (restore_carry,
            # fresh-carry leaves) — replicate them over the mesh so the
            # scatter into the slot-sharded pool sees one device set
            value_tree = jax.device_put(value_tree,
                                        self._replicated_sharding)
        self._pages = jax.tree_util.tree_map(
            lambda L, v: L.at[page].set(v), self._pages, value_tree)
        self._head_pages = self._pages

    def _page_leaves(self, page: int) -> tuple:
        """One carry page as host leaves ``(leaves, treedef)`` — the same
        leaf contract as ``Pipeline.snapshot_carry`` materialized, so
        ``carry_matches``/``restore_carry`` validate and rebuild it."""
        import jax
        leaves, _ = jax.tree_util.tree_flatten(self._pages)
        treedef = jax.tree_util.tree_flatten(self._fresh_carry())[1]
        return [xfer.to_host(l[page]) for l in leaves], treedef

    def _fresh_host_leaves(self) -> tuple:
        """The fresh-template carry as host leaves: what a still-fresh
        lane's page WILL hold after its first ride — its page bits are
        stale until then, so evict/persist of a fresh lane snapshot the
        template, not the page."""
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(self._fresh_carry())
        return [np.asarray(l) for l in leaves], treedef

    def _session_leaves(self, s: Session) -> tuple:
        if s.slot is not None and s.slot in self._fresh_lanes:
            return self._fresh_host_leaves()
        return self._page_leaves(s.page)

    @property
    def _k_eff(self) -> int:
        """The megabatch K this step runs at: 1 under an active "k"-lever
        brownout (latency over throughput), else the configured K."""
        if self._brownout_active and self._brownout == "k":
            return 1
        return self.k_batch

    def _program(self, capacity: int, k: Optional[int] = None):
        k = self.k_batch if k is None else int(k)
        key = (capacity, k, self._pipe_tag)
        prog = self._programs.get(key)
        if prog is None:
            prog = build_slot_program(self.pipeline, capacity, k,
                                      wire=self.wire)
            self._programs[key] = prog
            self.compiles += 1
            log.info("%s: compiled serving program for slot bucket %d "
                     "(k=%d, %s; resident buckets: %s)", self.app, capacity,
                     k, self._pipe_tag, self.resident_buckets())
        return prog

    def resident_buckets(self) -> List[int]:
        return sorted({cap for cap, _k, _t in self._programs})

    def _cached_buckets(self) -> Optional[tuple]:
        try:
            from ..tpu.autotune import cached_serve_buckets
            got = cached_serve_buckets(self.pipeline, self.frame_dtype,
                                       self.inst.platform)
            return tuple(got) if got else None
        except Exception:                  # noqa: BLE001 — ladder seed only
            return None

    def _cached_pages(self) -> Optional[int]:
        """The autotune cache's measured page-pool capacity (the
        paged-bucket axis ``serve_pages``), honored only when it names a
        rung of this engine's ladder — a stale cache from a different
        ladder must not invent an uncompilable capacity."""
        try:
            from ..tpu.autotune import cached_serve_pages
            got = cached_serve_pages(self.pipeline, self.frame_dtype,
                                     self.inst.platform)
            return int(got) if got and int(got) in self.buckets else None
        except Exception:                  # noqa: BLE001 — pool seed only
            return None

    # -- occupancy / bucket growth ---------------------------------------------
    @property
    def capacity(self) -> int:
        return self.table.capacity

    def _grow_to_fit(self) -> None:
        """Called at a QUIESCENT boundary (step lock held, nothing in
        flight, state lock held) with no free slot: grow the page pool to
        the next bucket — append fresh tail pages, extend the table's
        page permutation, re-size the shared credit budget. Resident
        capacities keep their compiled programs untouched; only the new
        capacity compiles, once, on its first dispatch."""
        import jax
        import jax.numpy as jnp
        cur = self.table.capacity
        bigger = [b for b in self.buckets if b > cur]
        if not bigger:
            raise ServeFull(
                f"{self.app}: at the largest slot bucket ({cur}); "
                f"admission refused")
        cap = bigger[0]
        fresh = self._fresh_carry()
        extra = cap - cur
        self._pages = jax.tree_util.tree_map(
            lambda L, f: jnp.concatenate(
                [L, jnp.stack([jnp.asarray(f)] * extra)]),
            self._pages, fresh)
        if self._shard_ok(cap):
            # re-shard the grown pool: the concatenate above computed on
            # whatever sharding the old bucket had (a non-dividing small
            # bucket may have been unsharded) — the new bucket's lanes
            # split one contiguous block per device
            self._pages = jax.device_put(self._pages,
                                         self._slot_sharding)
        self._head_pages = self._pages
        self._staging.clear()             # the smaller bucket never comes back
        self.table.grow(cap)
        self.credits.set_total(self._queue_frames * cap)
        log.info("%s: page pool grew %d -> %d (active %d)", self.app, cur,
                 cap, self.table.active)

    # -- session lifecycle -----------------------------------------------------
    def _refuse_admission(self, tenant: str) -> None:
        """Lifecycle/overload admission gate (called with the lock held):
        draining and the shedding ladder's first rung both refuse NEW
        admissions — 503 + ``Retry-After`` on the REST plane, billed on
        ``fsdr_serve_shed_total{reason}``."""
        if self._draining:
            _SHED.inc(app=self.app, tenant=tenant, reason="drain")
            _journal.emit("serve", "refuse", app=self.app, tenant=tenant,
                          reason="drain")
            raise ServeDraining(
                f"{self.app}: draining — admission refused")
        if self._ladder.level >= 1:
            _SHED.inc(app=self.app, tenant=tenant, reason="admission")
            _journal.emit("serve", "refuse", app=self.app, tenant=tenant,
                          reason="overload", rung=self._ladder.rung)
            raise ServeOverload(
                f"{self.app}: overloaded (shed rung "
                f"{self._ladder.rung}) — admission refused")

    def admit(self, tenant: str = "default",
              sid: Optional[str] = None) -> Session:
        """Join: claim a lane and bind it a carry page, with a FRESH
        per-session carry. The fast path is a pure host-side page-map edit
        — the fresh template is substituted INSIDE the next dispatch, so a
        join never touches device memory, never waits for in-flight
        groups, and lands at its own frame cursor mid-megabatch. Only pool
        GROWTH (no free page) quiesces the in-flight window. Raises
        :class:`ServeFull` past the largest bucket, :class:`ServeDraining`
        while draining, and :class:`ServeOverload` while the shedding
        ladder is engaged."""
        while True:
            with self._lock:
                self._refuse_admission(tenant)
                if self.table.get(sid) is not None:
                    raise ValueError(f"session id {sid!r} already exists")
                if self.table.free_slots():
                    s = Session(tenant, sid)
                    slot = self.table.admit(s)
                    self._fresh_lanes.add(slot)
                    self.credits.register(s.tenant)
                    _journal.emit("serve", "page-admit", app=self.app,
                                  session=s.sid, tenant=s.tenant, slot=slot,
                                  page=s.page)
                    self._refresh_gauges()
                    return s
            # no free page: growth is page-touching surgery — drain the
            # in-flight window under the step lock, grow the pool once,
            # and retry the map-edit fast path (another admitter may have
            # won the race, which is fine: the re-check sees its free page)
            with self._step_lock:
                self._drain_inflight(0)
                with self._lock:
                    if not self.table.free_slots():
                        self._grow_to_fit()

    def readmit(self, sid: str) -> Session:
        """Re-admit an evicted session: restore its host carry snapshot into
        a page BIT-IDENTICALLY (validated against the fresh-carry template —
        a snapshot that no longer matches the pipeline contract is
        refused). A page write, so the in-flight window drains first."""
        with self._step_lock:
            self._drain_inflight(0)
            with self._lock:
                self._refuse_admission(self._session(sid).tenant)
                s = self._session(sid)
                if s.state != "evicted" or s.carry_leaves is None:
                    raise ValueError(f"session {sid!r} is not evicted "
                                     f"(state={s.state})")
                if not self.pipeline.carry_matches(
                        s.carry_leaves, s.carry_treedef, self._fresh_carry()):
                    raise ValueError(f"session {sid!r}: evicted carry fails "
                                     f"the pipeline contract check")
                if not self.table.free_slots():
                    self._grow_to_fit()
                slot = self.table.admit(s)
                self._set_page(s.page, self.pipeline.restore_carry(
                    s.carry_leaves, s.carry_treedef, self.inst.device))
                s.carry_leaves = None
                s.carry_treedef = None
                s.stall_steps = 0
                _journal.emit("serve", "readmit", app=self.app, session=s.sid,
                              tenant=s.tenant, slot=slot, page=s.page)
                self._refresh_gauges()
                return s

    def evict(self, sid: str) -> Session:
        """Stall handling: snapshot the session's carry page to the host and
        free the lane for a busier session; queued input stays queued. The
        snapshot rides the same leaf contract as the kernel checkpoint
        machinery, so :meth:`readmit` restores it bit-identically. A page
        read, so the in-flight window drains first (a still-fresh lane —
        admitted but never dispatched — snapshots the template instead of
        its stale page bits)."""
        with self._step_lock:
            self._drain_inflight(0)
            return self._evict_quiesced(sid)

    def _evict_quiesced(self, sid: str) -> Session:
        with self._lock:
            s = self._session(sid)
            if s.state != "active":
                raise ValueError(f"session {sid!r} not active "
                                 f"(state={s.state})")
            leaves, treedef = self._session_leaves(s)
            s.carry_leaves = leaves
            s.carry_treedef = treedef
            self._fresh_lanes.discard(s.slot)
            self.table.release_slot(s)
            s.state = "evicted"
            if self._store is not None:
                # evict-to-disk: the host snapshot is already materialized,
                # so the durable copy is a pure background write — a crash
                # between evict and readmit loses nothing
                self._persist_session(s)
            _EVICTIONS.inc(app=self.app, tenant=s.tenant)
            _journal.emit("serve", "evict", app=self.app, session=s.sid,
                          tenant=s.tenant, stall_steps=s.stall_steps)
            self._refresh_gauges()
            return s

    def close(self, sid: str) -> None:
        """Leave: release the lane and forget the session. The lane's stale
        carry is inert (masked) until the next admit overwrites it."""
        with self._lock:
            s = self._session(sid)
            self.credits.release(s.tenant, len(s.pending))
            s.pending.clear()
            if s.slot is not None:
                self._fresh_lanes.discard(s.slot)
            self.table.forget(s)
            s.state = "closed"
            if self._store is not None:
                # clean close: the session's state is complete — purge its
                # durable snapshot so a later incarnation starts it fresh
                self._store.purge(s.sid)
            if not self._tenant_live(s.tenant):
                self.credits.unregister(s.tenant)
            _journal.emit("serve", "close", app=self.app, session=s.sid,
                          tenant=s.tenant)
            self._refresh_gauges()

    def _tenant_live(self, tenant: str) -> bool:
        """Does the tenant still have a session that can submit (active or
        re-admissible)? Retired/closed sessions stay in the registry for
        their views, but they must not keep the tenant's fair share
        reserved in the credit controller."""
        return any(o.tenant == tenant and o.state in ("active", "evicted")
                   for o in self.table.sessions.values())

    def _retire(self, s: Session, err: BaseException) -> None:
        """Per-session fault isolation (the isolate_group-of-one semantics):
        the faulted session's slot is masked off and released — the batch,
        and every sibling's carry and output, is untouched."""
        self.credits.release(s.tenant, len(s.pending))
        s.pending.clear()
        if s.slot is not None:
            self._fresh_lanes.discard(s.slot)
        self.table.release_slot(s)
        s.state = "retired"
        s.error = repr(err)
        if self._store is not None:
            # a faulted session must not resurrect into a fresh incarnation
            self._store.purge(s.sid)
        if not self._tenant_live(s.tenant):
            self.credits.unregister(s.tenant)
        self._retired.append(s.sid)
        while len(self._retired) > self._retired_keep:
            old = self.table.get(self._retired.pop(0))
            if old is not None and old.state == "retired":
                self.table.forget(old)
        _RETIRED.inc(app=self.app, tenant=s.tenant)
        _journal.emit("serve", "retire", app=self.app, session=s.sid,
                      tenant=s.tenant, error=repr(err))
        log.warning("%s: session %s (tenant %s) retired by %r — siblings "
                    "unaffected", self.app, s.sid, s.tenant, err)
        self._refresh_gauges()

    def _session(self, sid: str) -> Session:
        s = self.table.get(sid)
        if s is None:
            raise KeyError(f"no session {sid!r}")
        return s

    # -- the data plane --------------------------------------------------------
    def submit(self, sid: str, frame: np.ndarray) -> bool:
        """Queue one input frame for ``sid``. Returns False (backpressure)
        when the tenant's fair credit share is exhausted — a stalled tenant
        cannot starve siblings of queue budget (docs/serving.md)."""
        with self._lock:
            s = self._session(sid)
            if s.state in ("retired", "closed"):
                raise ValueError(f"session {sid!r} is {s.state}")
            frame = self._queued_form(frame)
            if not self.credits.try_acquire(s.tenant):
                _REJECTS.inc(app=self.app, tenant=s.tenant)
                return False
            s.pending.append((frame, time.perf_counter_ns()))
            s.frames_in += 1
            return True

    def _queued_form(self, frame) -> np.ndarray:
        """One submitted frame as the queue holds it: contiguous
        ``frame_dtype[frame_size]``. On a wire that is the caller's own
        bytes, viewed: ``uint32[frame]`` words, or the interleaved
        little-endian ``int16[frame, 2]`` I/Q pairs they are. Without one,
        samples are cast to ``in_dtype`` within their kind as before. Raises
        ``ValueError`` for a frame of the other side of that line (samples
        into a wire engine, integer words into a sample engine): a cast
        there would turn one into garbage of the other silently."""
        frame = np.asarray(frame)
        want = self.frame_dtype
        if self.wire is not None and frame.dtype == np.int16 \
                and frame.shape == (self.frame_size, 2):
            frame = np.ascontiguousarray(frame).view(want).reshape(-1)
        if frame.shape != (self.frame_size,):
            raise ValueError(
                f"frame shape {frame.shape} != ({self.frame_size},)")
        have = frame.dtype
        if have != want and (self.wire is not None
                             or (have.kind in "biu") != (want.kind in "biu")):
            raise ValueError(
                f"frame dtype {have}: this engine takes {self.frame_format()}")
        return np.ascontiguousarray(frame, dtype=want)

    def frame_format(self) -> dict:
        """What ``submit`` takes, for a session's admission answer and
        :meth:`describe`: the wire's name (``"raw"``: samples of the
        pipeline's dtype, no codec), the frame's dtype and shape, and on a
        wire what one count is worth."""
        return {"wire": self.wire_name,
                "frame_dtype": str(self.frame_dtype),
                "frame_shape": [self.frame_size],
                "full_scale": FULL_SCALE if self.wire is not None else None}

    def uplink_word_parts(self) -> int:
        """Parts of the current bucket's step input that cross as words and
        are decoded a complex sample a word inside the program (as
        ``TpuKernel`` reports ``uplink_word_slots``)."""
        if self.wire is None:
            return 0
        C = self.table.capacity
        return 1 if self._shard_ok(C) else C // self._lane_group(C)

    def results(self, sid: str) -> list:
        """Drain the session's decoded results (oldest first)."""
        with self._lock:
            s = self._session(sid)
            out, s.out = list(s.out), type(s.out)()
            return out

    def step(self) -> int:
        """One frame-time dispatch: every active lane with pending frames
        rides ONE vmapped program call — the input uploaded in lane groups
        (only those in which a lane rides), one dispatch, one D2H per sink,
        regardless of the active session count.
        ``frames_per_dispatch > 1`` additionally megabatches up to k queued
        frames PER LANE through the in-program scan, ragged per lane (a
        session with fewer queued frames masks its tail; a JOINING session
        rides with whatever frames it has — the fresh-page substitution
        lands it at its own cursor mid-megabatch).

        OVERLAPPED (docs/serving.md "The overlapped step"): the group
        launched here is committed only once its D2H lands; with
        ``serve_inflight > 1`` up to that many groups ride concurrently,
        so H2D(t+1) ∥ compute(t) ∥ D2H(t−1). The state lock is held only
        to pop the riding frames and for commit bookkeeping — never across
        the copies, the compile, the transfers, or the program call — so
        ``submit()``, /metrics,
        ``health()`` and ``describe()`` answer mid-step.

        Returns the number of session-frames LAUNCHED this step. An idle
        step (no lane has pending input) first commits everything still in
        flight, then returns 0 — so a pump loop's
        ``while eng.step(): pass`` still means "fully drained"."""
        # fleet hot-path hook (telemetry/fleet.py): refresh this host's own
        # fleet gauges at poll cadence. ONE falsy check when the fleet
        # plane is disabled — the guard is INLINE (a module-global read, no
        # call frame) so the disabled cost matches the park guard's; it is
        # the sixth per-call hook class the telemetry overhead gate bills
        # (tests/test_telemetry.py). Outside the engine locks by design:
        # the refresh reads only lock-free surfaces
        if _fleet._tick_state is not None:
            _fleet.tick()
        t_lk = _trace.now() if _trace.enabled else 0
        with self._step_lock:
            t_held = _trace.now() if t_lk else 0
            g = self._assemble()
            if t_lk and g is not None:
                # (an idle tick records nothing)
                _trace.complete("serve", "lock_wait", t_lk, end_ns=t_held,
                                args={"lock": "step", "seq": g.seq})
            if g is None:
                self._drain_inflight(0)
                with self._lock:
                    if self._ladder.level:
                        # traffic stopped while the ladder was engaged: idle
                        # steps count as healthy observations so admissions
                        # reopen. idle=True: the latency window is FROZEN
                        # with the pre-idle samples, so the SLO term must
                        # not read a stale p99 as a live miss and ratchet
                        # the ladder up on an empty engine
                        self._overload_tick(idle=True)
                return 0
            try:
                self._launch(g)
            except Exception:
                # launch-failure rollback: a transfer/compile/dispatch error
                # must not silently drop the popped frames — re-queue them
                # at the front of their queues (original order), re-take
                # their credits, restore the fresh bits. The head never
                # advanced (launch's last effect), so older in-flight
                # groups stay valid and the caller's retry re-dispatches
                # the exact same frames
                self._rollback([g], reset_head=False)
                raise
            self._inflight.append(g)
            self._flight.note_dispatch(g.wire, len(self._inflight))
            n = g.n_frames
            self._drain_inflight(self._depth_limit() - 1)
            return n

    def _depth_limit(self) -> int:
        """The in-flight group budget this step: the flight controller's
        live credits, collapsed to 1 while the shed ladder is at or above
        the latency rung — an overloaded engine prefers per-frame latency
        over pipelining, the same trade as the ``"k"`` brownout lever."""
        if self._ladder.level >= _LATENCY_RUNG:
            return 1
        return max(1, int(self._flight.credits))

    def _assemble(self) -> Optional[_DispatchGroup]:
        """Build this step's dispatch group under the state lock: pop up to
        K pending frames per occupied lane (references: the copies are the
        launch's, outside this lock), snapshot the lane→page permutation
        and the fresh-lane vector, and CLEAR the fresh bits — the launch
        materializes those lanes' template pages (rollback restores the
        bits). Returns None on an idle step."""
        t_lk = _trace.now() if _trace.enabled else 0
        with self._lock:
            C = self.table.capacity
            K = self._k_eff
            fplan = _faults.plan()
            lanes: List[tuple] = []   # (session, lane, popped, tids)
            t_step = _trace.now() if t_lk else 0
            # idle frame-time ticks (no lane has pending input — the common
            # case for a pump loop ticking at frame rate) must cost nothing:
            # the mask allocates lazily on the first busy lane
            active = None
            step_tids: List[int] = []     # lineage-sampled frames this step
            for s in self.table.occupants():
                if not s.pending:
                    s.stall_steps += 1
                    continue
                if active is None:
                    active = np.zeros((C,) if K == 1 else (C, K), dtype=bool)
                if fplan.armed():
                    # per-session fault sites (runtime/faults.py): address a
                    # work/dispatch injector at ONE session id and only that
                    # slot retires — the tenant-isolation chaos scenario
                    try:
                        fplan.maybe("work", s.sid)
                        fplan.maybe("dispatch", s.sid)
                    except _faults.InjectedFault as e:
                        self._retire(s, e)
                        continue
                popped = []
                tids = []
                for j in range(min(K, len(s.pending))):
                    entry = s.pending.popleft()
                    t_sub = entry[1]
                    self.credits.release(s.tenant)
                    if K == 1:
                        active[s.slot] = True
                    else:
                        active[s.slot, j] = True
                    popped.append(entry)
                    # frame lineage (telemetry/lineage.py): 1-in-stride
                    # sampled frames get a trace id here; unsampled frames
                    # carry tid 0 and every stamp site below skips them
                    tid = _lineage.tracer().sample()
                    if tid:
                        _lineage.tracer().stamp(tid, "ingest", t_sub)
                        step_tids.append(tid)
                    tids.append(tid)
                s.stall_steps = 0
                lanes.append((s, s.slot, popped, tids))
            self.steps += 1
            if not lanes:
                return None
            if t_step:
                # submit() and the REST handlers hold `_lock` too, and
                # t_step started only once it was held
                _trace.complete("serve", "lock_wait", t_lk, end_ns=t_step,
                                args={"lock": "state", "seq": self.steps})
                t_pop = _trace.now()
                # how long this step's frames sat in `pending`: from the
                # oldest one's submit stamp to the pop, and the mean over all
                subs = [t for _s, _l, popped, _t in lanes for _f, t in popped]
                _trace.complete(
                    "serve", "queue_wait", min(subs), end_ns=t_pop,
                    args={"seq": self.steps, "frames": len(subs),
                          "mean_ms": (t_pop - sum(subs) / len(subs)) * 1e-6})
            # the fresh vector covers EVERY fresh lane, busy or not: its
            # first ride writes the template to its page either way, so
            # the page is real from this group on
            fresh = np.zeros((C,), dtype=bool)
            for lane in self._fresh_lanes:
                if lane < C:
                    fresh[lane] = True
            g = _DispatchGroup(
                C, K, lanes, active, fresh,
                np.asarray(self.table.page_of_lane, dtype=np.int32),
                frozenset(self._fresh_lanes), step_tids, t_step, self.steps)
            self._fresh_lanes.clear()
            return g

    def _launch(self, g: _DispatchGroup) -> None:
        """Launch one assembled group OUTSIDE the state lock (step lock
        held): program lookup/compile, the input's lane groups filled and
        put on the wire one after the other, the paged program call against
        the speculative head, async D2H starts. Advancing the head is the
        LAST effect — a failure anywhere above leaves the chain exactly as
        it was for the rollback path."""
        C, K = g.capacity, g.k
        prog = self._program(C, K)
        # one encode span and one h2d_put + H2D span pair for the step; the
        # two overlap: a group's bytes cross while the next group is filled
        if self._shard_ok(C):
            finish_x, grp = self._start_whole_batch(g)
        else:
            finish_x, grp = self._start_lane_groups(g)
        fa = self._start_h2d(g.active, True, grp)
        fm = self._start_h2d(g.page_map, False, grp)
        ff = self._start_h2d(g.fresh, False, grp)
        if grp is not None:
            grp.close()
        lin = _lineage.tracer() if g.step_tids else None
        for tid in g.step_tids:
            lin.stamp(tid, "encode")
        x, act = finish_x(), fa()
        pmap, fresh = fm(), ff()
        for tid in g.step_tids:
            lin.stamp(tid, "H2D")
        t0 = _trace.now() if _trace.enabled else 0
        key = (C, K, self._pipe_tag)
        if key in self._warmed:
            new_pages, outs = prog(self._head_pages, pmap, fresh, x, act)
        else:
            # a capacity's FIRST dispatch pays its jit compile: bill it
            # (fsdr_compiles_total{reason="serve_bucket"}) and mark the
            # window active so a slow compile reads as "compiling" to the
            # doctor, never as a stalled serving loop
            with _profile.compiling(f"serve:{self.app}", "serve_bucket",
                                    f"cap={C},k={K},"
                                    f"frame={self.frame_size},"
                                    f"pipe={self._pipe_tag}"):
                new_pages, outs = prog(self._head_pages, pmap, fresh, x, act)
            self._warmed.add(key)
        if t0:
            # `compute` is the enqueue call; `program` beside it ends when
            # the OUTPUTS are ready (the watcher's stamp). The page pool is
            # the next call's input and is never watched
            args = {"capacity": C, "active_lanes": len(g.lanes),
                    "seq": g.seq}
            _trace.complete("tpu", "compute", t0, args=args)
            xfer.watch(outs, "program", t0, args)
        for tid in g.step_tids:
            lin.stamp(tid, "dispatch")
        g.fins = [xfer.start_host_transfer(o, seq=g.seq) for o in outs]
        g.new_pages = new_pages
        self._head_pages = new_pages

    def _lane_group(self, capacity: int) -> int:
        """Lanes per upload group of a bucket: ``LANE_GROUP`` where it cuts
        a larger capacity evenly, else the whole bucket as one group."""
        if capacity > LANE_GROUP and capacity % LANE_GROUP == 0:
            return LANE_GROUP
        return capacity

    def _group_shape(self, lanes: int, k: int) -> tuple:
        return (lanes, self.frame_size) if k == 1 \
            else (lanes, k, self.frame_size)

    def _zero_part(self, lanes: int, k: int):
        """The device-resident all-zero input of one lane group, uploaded on
        the bucket's first launch or warm-up and passed for every group in
        which no lane rides."""
        z = self._zero_parts.get((lanes, k))
        if z is None:
            dev = self.inst.device
            zeros = np.zeros(self._group_shape(lanes, k), self.frame_dtype)
            (z,) = xfer.start_device_transfer_parts(
                (xfer.wire_part(zeros, dev),), dev)()
            self._zero_parts[(lanes, k)] = z
        return z

    @staticmethod
    def _fill_group(buf: np.ndarray, riders: list, base: int) -> None:
        """Copy the riding frames of lanes ``base..`` into their rows of
        ``buf``. Rows of lanes that do not ride keep what they held: the
        ``active`` mask freezes their carries and nobody is handed their
        output (``build_slot_program``)."""
        for lane, popped in riders:
            if buf.ndim == 2:
                buf[lane - base] = popped[0][0]
            else:
                for j, (frame, _t) in enumerate(popped):
                    buf[lane - base, j] = frame

    def _start_lane_groups(self, g: _DispatchGroup) -> tuple:
        """Fill and ship the step's input by lane groups: for each group
        with a riding lane, copy its frames into the group's staging array
        and start its ``device_put`` at once, so its bytes cross while the
        next group is filled. Returns ``(finish_x, span group)``;
        ``finish_x()`` joins the uploaded parts (and the resident zero block
        for every group that did not ride) into the program's input."""
        C, K = g.capacity, g.k
        G = self._lane_group(C)
        n = C // G
        dev = self.inst.device
        dtype = self.frame_dtype
        riders: Dict[int, list] = {}
        for _s, lane, popped, _tids in g.lanes:
            riders.setdefault(lane // G, []).append((lane, popped))
        shipped = self._note_shipped(g, len(riders), G)
        zero = self._zero_part(G, K) if n > 1 else None
        free = self._staging.setdefault((C, K), [])
        g.staging = staging = free.pop() if free else [None] * n
        tracing = _trace.enabled
        grp = None
        t_enc = t_filled = _trace.now() if tracing else 0
        fins: list = [None] * n
        for gi in sorted(riders):
            buf = staging[gi]
            if buf is None:
                buf = staging[gi] = np.zeros(self._group_shape(G, K), dtype)
            self._fill_group(buf, riders[gi], gi * G)
            if tracing:
                t_filled = _trace.now()
                if grp is None:         # the H2D pair opens at the first put
                    grp = xfer.H2DGroup(g.seq, **shipped)
            fins[gi] = xfer.start_device_transfer_parts(
                (xfer.wire_part(buf, dev),), dev, group=grp)
        if tracing:
            _trace.complete("tpu", "encode", t_enc, end_ns=t_filled,
                            args={"sessions": len(g.lanes), "capacity": C,
                                  "seq": g.seq, "bytes": g.bytes_shipped,
                                  **shipped})
        wires = [f._wire for f in fins if f is not None]
        g.wire = (wires[0][0], wires[-1][1])

        def finish_x():
            return xfer.join_parts(
                [zero if f is None else f()[0] for f in fins], dtype, dev)

        return finish_x, grp

    def _start_whole_batch(self, g: _DispatchGroup) -> tuple:
        """A slot-sharded bucket's input: one zeroed batch, one
        ``device_put`` against the mesh sharding, which owns that layout.
        Returns what :meth:`_start_lane_groups` returns."""
        C = g.capacity
        shipped = self._note_shipped(g, 1, C)
        t_enc = _trace.now() if _trace.enabled else 0
        batch = np.zeros(self._group_shape(C, g.k), self.frame_dtype)
        self._fill_group(batch, [(l, p) for _s, l, p, _t in g.lanes], 0)
        grp = None
        if t_enc:
            _trace.complete("tpu", "encode", t_enc,
                            args={"sessions": len(g.lanes), "capacity": C,
                                  "seq": g.seq, "bytes": g.bytes_shipped,
                                  **shipped})
            grp = xfer.H2DGroup(g.seq, **shipped)
        return self._start_h2d(batch, True, grp), grp

    def _note_shipped(self, g: _DispatchGroup, groups: int,
                      lanes_per_group: int) -> dict:
        """Set what a launch puts on the link for the group's input frames
        (lane groups, their lanes, their bytes as they cross); returns the
        args its ``encode``, ``h2d_put`` and ``H2D`` spans share."""
        g.groups_shipped = groups
        g.lanes_shipped = groups * lanes_per_group
        g.bytes_shipped = (g.lanes_shipped * g.k * self.frame_size
                           * self.frame_dtype.itemsize)
        return {"lanes_shipped": g.lanes_shipped,
                "groups_shipped": g.groups_shipped,
                "wire": self.wire_name}

    def _release_staging(self, g: _DispatchGroup) -> None:
        """Hand a committed or rolled-back group's staging set back."""
        if g.staging is not None:
            self._staging.setdefault((g.capacity, g.k), []).append(g.staging)
            g.staging = None

    def _start_h2d(self, arr: np.ndarray, shard: bool, group=None):
        """Start one async H2D for a group launch; returns a finish thunk.
        Unsharded buckets ride ``xfer.start_device_transfer``, whose finish
        models/measures the wire window (the ``_wire`` attribute feeding
        the flight controller) and emits the H2D trace span — the serving
        overlap evidence. Slot-sharded buckets place synchronously
        (``device_put`` owns the mesh layout)."""
        if self._shard_ok(self.table.capacity):
            import jax
            v = jax.device_put(arr, self._slot_sharding if shard
                               else self._replicated_sharding)
            if group is not None:
                group.add((v,), arr.nbytes)
            return lambda: v
        return xfer.start_device_transfer(arr, self.inst.device, group=group)

    def _drain_inflight(self, keep: int) -> None:
        """Commit in-flight groups oldest-first until at most ``keep``
        remain (step lock held; the state lock is NOT held across the D2H
        wait). ``keep=0`` is the quiescent barrier page-touching surgery
        uses. A failed wait rolls back EVERY uncommitted group — each
        younger group derived its pages from the failed one's output, so
        none of them can commit."""
        keep = max(0, int(keep))
        while len(self._inflight) > keep:
            if keep:
                self._flight.note_limited()
            g = self._inflight[0]
            t0 = _trace.now() if _trace.enabled else 0
            try:
                host = [np.asarray(f()) for f in g.fins]
            except Exception:
                doomed = list(self._inflight)
                self._inflight.clear()
                self._rollback(doomed, reset_head=True)
                raise
            if t0:
                # this thread blocked until the results were on the host:
                # the upload's tail + the program + the download, which the
                # watcher's H2D / program / D2H spans divide
                _trace.complete("tpu", "d2h_wait", t0, args={"seq": g.seq})
            self._inflight.popleft()
            self._commit(g, host)

    def _rollback(self, groups: list, reset_head: bool) -> None:
        """Re-queue every frame of the given UNCOMMITTED groups at the
        front of their sessions' queues (youngest group first, preserving
        order), re-take their credits and restore their fresh-lane bits —
        the retry re-dispatches the exact same frames. ``reset_head``: a
        drain failure abandons the whole speculative chain, so the head
        re-syncs to the committed pool; a LAUNCH failure never advanced
        the head, which must stay at the older in-flight groups' output."""
        with self._lock:
            for g in reversed(groups):
                for s, _lane, popped, _tids in g.lanes:
                    if s.state not in ("active", "evicted"):
                        continue          # closed/retired meanwhile: its
                    s.pending.extendleft(reversed(popped))   # credits were
                    self.credits.reacquire(s.tenant, len(popped))  # released
                self._fresh_lanes |= g.fresh_lanes
                self._release_staging(g)
            if reset_head:
                self._head_pages = self._pages

    def _commit(self, g: _DispatchGroup, host: list) -> None:
        """Land one finished group (its D2H already waited out): the
        committed pool advances to its output pages, results fan back per
        session, latency/lineage/persist/overload bookkeeping runs — all
        under the state lock. A session that left while its group was in
        flight (closed/retired, or its lane re-bound) is skipped: there is
        nobody to deliver to."""
        end = time.perf_counter_ns()
        t_lk = _trace.now() if _trace.enabled else 0
        K = g.k
        with self._lock:
            t_dec = _trace.now() if t_lk else 0
            if t_lk:
                _trace.complete("serve", "lock_wait", t_lk, end_ns=t_dec,
                                args={"lock": "state", "seq": g.seq})
            self._pages = g.new_pages
            self._release_staging(g)
            self.dispatches += 1
            self.lanes_shipped += g.lanes_shipped
            self.groups_shipped += g.groups_shipped
            dispatched = 0
            for s, lane, popped, tids in g.lanes:
                deliver = s.state == "active" and s.slot == lane
                if not deliver:
                    continue
                for j, (_, t_sub) in enumerate(popped):
                    rows = [h[lane] if K == 1 else h[lane, j] for h in host]
                    res = tuple(np.asarray(r) for r in rows) \
                        if self._multi else np.asarray(rows[0])
                    s.out.append(res)
                    s.frames_out += 1
                    lat = (end - t_sub) * 1e-9
                    s.last_latency_s = lat
                    self._lat_recent.append(lat)
                    _LATENCY.observe(lat, app=self.app, tenant=s.tenant)
                    # satellite of PR-4's stamp audit: each serving lane
                    # observes its OWN frame's submit->fan-back latency on
                    # the shared e2e family (the streamed sinks' histogram)
                    self._e2e_hist.observe(lat)
                    tid = tids[j]
                    if tid:
                        lin = _lineage.tracer()
                        lin.stamp(tid, "emit", end)
                        lin.finish(tid, source=f"serve:{self.app}",
                                   session=s.sid, tenant=s.tenant)
                        self._e2e_hist.exemplar(lat, tid)
                    _FRAMES.inc(app=self.app, tenant=s.tenant)
                    dispatched += 1
            self.frames += dispatched
            _DISPATCHES.inc(app=self.app)
            _LANES_SHIPPED.inc(g.lanes_shipped, app=self.app)
            _WIRE_BYTES.inc(g.bytes_shipped, app=self.app,
                            wire=self.wire_name)
            self._step_stamps.append(time.monotonic())
            if self._persist_every and self._store is not None:
                self._steps_since_persist += 1
                if self._steps_since_persist >= self._persist_every:
                    self._steps_since_persist = 0
                    self._persist_all()
            self._overload_tick()
            # live-roofline unit for serving: one SESSION-FRAME (the
            # registered cost is the single-lane program's); the commit
            # stamps its own group time
            self._prof.dispatch(dispatched, t=time.monotonic())
        if t_dec:
            _trace.complete("tpu", "decode", t_dec,
                            args={"frames": dispatched, "seq": g.seq})
        if g.t_step:
            _trace.complete("serve", "serve_step", g.t_step,
                            args={"sessions": len(g.lanes),
                                  "active_lanes": len(g.lanes),
                                  "frames": dispatched,
                                  "capacity": g.capacity, "seq": g.seq})

    # -- lane-addressed retunes ------------------------------------------------
    def retune(self, sid: str, stage, **params) -> Session:
        """Per-session mid-stream surgery: apply ``update_stage`` to ONE
        session's carry page at its next quiescent boundary (the in-flight
        window drains first), journaled as ``serve/lane-retune`` — one
        tenant retunes its receiver without touching a sibling's bits.
        ``stage`` addresses by name or index, ``params`` are the stage's
        ``update`` hook kwargs (the flat-carry contract of
        ``ops/stages.py``). Raises KeyError for an unknown session,
        ValueError for a non-active session or a refused update."""
        import jax
        with self._step_lock:
            self._drain_inflight(0)
            with self._lock:
                s = self._session(sid)
                if s.state != "active":
                    raise ValueError(f"session {sid!r} not active "
                                     f"(state={s.state})")
                page = s.page
                if s.slot in self._fresh_lanes:
                    # never dispatched: its page holds stale bits — retune
                    # the template it WILL start from, and materialize it
                    lane_carry = self._fresh_carry()
                else:
                    lane_carry = jax.tree_util.tree_map(
                        lambda P: P[page], self._pages)
                try:
                    new_carry = self.pipeline.update_stage(lane_carry, stage,
                                                           **params)
                except KeyError as e:
                    # a bad STAGE address is a client error on this app's
                    # contract (409), not a missing resource (404 is the
                    # session lookup's) — re-raise in the ValueError family
                    raise ValueError(f"retune of {sid!r}: {e}") from e
                self._set_page(page, new_carry)
                self._fresh_lanes.discard(s.slot)
                _journal.emit("serve", "lane-retune", app=self.app,
                              session=s.sid, tenant=s.tenant, slot=s.slot,
                              page=page, stage=str(stage),
                              params=sorted(params))
                log.info("%s: lane retune of %s (slot %d, page %d): "
                         "stage=%r params=%s", self.app, s.sid, s.slot,
                         page, stage, sorted(params))
                return s

    # -- durable session state (docs/robustness.md "Serving-plane recovery") --
    def _base_leaf_dtypes(self) -> list:
        """The BASE pipeline's flat carry leaf dtypes — the dtype contract
        every durable snapshot is written in, whatever the live program
        runs at (a brownout-lowered bf16 carry persisted as-is would fail
        ``carry_matches`` in the next incarnation and lose the session)."""
        if self._base_dt is None:
            import jax
            leaves = jax.tree_util.tree_flatten(
                self._base_pipeline.init_carry())[0]
            self._base_dt = [np.dtype(getattr(l, "dtype", "float32"))
                             for l in leaves]
        return self._base_dt

    def _persist_session(self, s: Session, sync: bool = False) -> None:
        """Queue one session's durable snapshot (state lock held). Active
        lanes capture their PAGE of the committed pool and fetch its host
        leaves off the step thread; evicted sessions already hold host
        leaves. Leaves are written in the BASE pipeline's dtypes (upcast
        when the precision brownout is live), so a kill -9 at any rung
        restores into a fresh base-pipeline incarnation."""
        import jax
        meta = {"sid": s.sid, "tenant": s.tenant,
                "frames_in": s.frames_in, "frames_out": s.frames_out}
        dts = self._base_leaf_dtypes()
        # a lane whose FIRST dispatch is still riding an in-flight group is
        # fresh too: assembly moved it out of ``_fresh_lanes`` (the program
        # does the template substitution in-flight) but the committed pool's
        # page still holds whatever a dead predecessor parked there — the
        # meta says frames_out=0, so the snapshot must say "start fresh"
        fresh_lane = s.slot is not None and (
            s.slot in self._fresh_lanes or
            any(s.slot in g.fresh_lanes for g in self._inflight))
        if s.state == "active" and fresh_lane:
            # admitted but never dispatched: its page holds stale bits —
            # the durable snapshot is the fresh template it will start from
            snap = self._fresh_host_leaves()[0]

            def fetch(_snap=snap, _dts=dts):
                raw = [np.asarray(a) for a in _snap]
                if len(raw) == len(_dts):
                    raw = [a if a.dtype == dt else a.astype(dt)
                           for a, dt in zip(raw, _dts)]
                return raw
        elif s.state == "active" and s.slot is not None:
            # page-granular capture: a reference to the COMMITTED pool's
            # leaves + this session's page index — the serving program
            # never donates, so the writer thread reads stable device
            # arrays even while later commits replace ``self._pages``
            leaves = jax.tree_util.tree_flatten(self._pages)[0]
            page = s.page

            def fetch(_leaves=leaves, _page=page, _dts=dts):
                raw = [np.asarray(xfer.to_host(l[_page])) for l in _leaves]
                if len(raw) == len(_dts):
                    raw = [a if a.dtype == dt else a.astype(dt)
                           for a, dt in zip(raw, _dts)]
                return raw
        elif s.state == "evicted" and s.carry_leaves is not None:
            snap = list(s.carry_leaves)

            def fetch(_snap=snap, _dts=dts):
                raw = [np.asarray(a) for a in _snap]
                if len(raw) == len(_dts):
                    raw = [a if a.dtype == dt else a.astype(dt)
                           for a, dt in zip(raw, _dts)]
                return raw
        else:
            return
        self._store.save(s.sid, fetch, meta, sync=sync)

    def _persist_all(self, sync: bool = False) -> int:
        """Snapshot every live (active/evicted) session (lock held).
        ``sync`` enqueues everything first and rides ONE flush barrier —
        every write still lands on the single-writer executor (two writer
        threads would tear the shared pid-keyed tmp file)."""
        n = 0
        for s in self.table.sessions.values():
            if s.state in ("active", "evicted"):
                self._persist_session(s)
                n += 1
        if sync and n and self._store is not None:
            self._store.flush()
        return n

    def flush_persist(self) -> None:
        """Barrier on the persistence executor: every snapshot queued before
        this call is durable after it (tests + pre-restart hooks)."""
        if self._store is not None:
            self._store.flush()

    def _restore_persisted(self) -> None:
        """Virgin-incarnation restore: re-admit every persisted session of
        this app+pipeline-signature bit-identically (the ``carry_matches``-
        validated readmit path). Corrupted files were already skipped by the
        store's reader; a snapshot failing the carry contract (pipeline
        changed under the same app name — the signature hash makes this
        near-impossible, but the check is cheap) is skipped per-session.
        Sessions beyond the largest bucket are left on disk (logged) — a
        smaller replacement deployment refuses gracefully instead of
        refusing to boot."""
        import jax
        records = self._store.load_all()
        if not records:
            return
        with self._lock:
            fresh = self._fresh_carry()
            treedef = jax.tree_util.tree_flatten(fresh)[1]
            skipped = 0
            for r in records:
                if self.table.get(r["sid"]) is not None:
                    continue
                if not self.pipeline.carry_matches(r["leaves"], treedef,
                                                   fresh):
                    log.warning("%s: persisted session %s fails the carry "
                                "contract — skipped", self.app, r["sid"])
                    skipped += 1
                    continue
                if not self.table.free_slots():
                    try:
                        self._grow_to_fit()
                    except ServeFull:
                        log.warning("%s: %d persisted session(s) exceed the "
                                    "largest slot bucket — left on disk",
                                    self.app,
                                    len(records) - self.restored_sessions
                                    - skipped)
                        break
                s = Session(r["tenant"], r["sid"])
                self.table.admit(s)
                self._set_page(s.page, self.pipeline.restore_carry(
                    r["leaves"], treedef, self.inst.device))
                s.frames_in = r["frames_in"]
                s.frames_out = r["frames_out"]
                self.credits.register(s.tenant)
                self.restored_sessions += 1
                _RESUMED.inc(app=self.app, tenant=s.tenant)
            self._refresh_gauges()
        if self.restored_sessions:
            _journal.emit("serve", "restore", app=self.app,
                          sessions=self.restored_sessions, skipped=skipped)
            log.info("%s: re-admitted %d persisted session(s) after a "
                     "process restart (%d skipped)", self.app,
                     self.restored_sessions, skipped)
            # warm the current bucket NOW: a restored pod must turn ready
            # (readyz 200) without waiting for traffic — restored sessions
            # have no pending frames, so no busy step would ever compile
            # the program and the pod would sit NotReady forever
            try:
                with self._lock:
                    self._warm_current_bucket()
            except Exception as e:         # noqa: BLE001 — a failed warmup
                log.warning("%s: restore warmup failed: %r", self.app, e)

    def _warm_current_bucket(self) -> None:
        """Compile + warm the current capacity's program with an ALL-MASKED
        no-op dispatch (lock held): every lane inactive and nothing fresh,
        so the in-program merge + permutation scatter keeps the restored
        pages bit-identical (the returned pool is discarded anyway) — the
        dispatch exists only to pay the jit compile before the orchestrator
        routes traffic. Billed ``serve_bucket`` like any first dispatch."""
        import jax
        C, K = self.table.capacity, self._k_eff
        key = (C, K, self._pipe_tag)
        if key in self._warmed:
            return
        prog = self._program(C, K)
        active = np.zeros((C,) if K == 1 else (C, K), dtype=bool)
        pmap = np.asarray(self.table.page_of_lane, dtype=np.int32)
        no_fresh = np.zeros((C,), dtype=bool)
        with _profile.compiling(f"serve:{self.app}", "serve_bucket",
                                f"cap={C},k={K},frame={self.frame_size},"
                                f"pipe={self._pipe_tag},warm=restore"):
            # _start_h2d, not bare to_device: a slot-sharded bucket's
            # pages are committed to the mesh, and single-device vectors
            # would make the warm dispatch raise (and the first real step
            # pay a second, unbilled compile)
            if self._shard_ok(C):
                x = self._start_h2d(np.zeros(self._group_shape(C, K),
                                             self.frame_dtype),
                                    shard=True)()
            else:
                # the input as a launch forms it, with no lane riding:
                # warms the lane groups' join and uploads the zero block
                G = self._lane_group(C)
                x = xfer.join_parts([self._zero_part(G, K)] * (C // G),
                                    self.frame_dtype, self.inst.device)
            _new_p, outs = prog(self._pages,
                                self._start_h2d(pmap, shard=False)(),
                                self._start_h2d(no_fresh, shard=False)(),
                                x, self._start_h2d(active, shard=True)())
            jax.block_until_ready(outs)
        self._warmed.add(key)

    # -- graceful lifecycle ----------------------------------------------------
    def drain(self, pump: bool = True, timeout: float = 30.0,
              persist: bool = True) -> dict:
        """Graceful shutdown for rolling restarts: refuse new admissions
        (:class:`ServeDraining` → 503 + ``Retry-After``), finish the
        in-flight megabatch groups and every queued frame (``pump=True``
        steps the engine here; an app with its own pump thread passes
        ``pump=False`` and keeps stepping), persist all live lanes, and
        report drained. Idempotent — a second call re-reports."""
        with self._lock:
            self._draining = True
        _journal.emit("serve", "drain", app=self.app,
                      timeout_s=float(timeout), persist=bool(persist))
        pumped = 0
        deadline = (time.monotonic() + float(timeout)) if timeout else None
        if pump:
            while True:
                if deadline is not None and time.monotonic() > deadline:
                    log.warning("%s: drain timed out with frames still "
                                "queued", self.app)
                    break
                got = self.step()
                pumped += got
                if not got:
                    # no lane dispatched anything: every ACTIVE queue is
                    # empty. Frames may remain on evicted sessions' queues
                    # — those cannot dispatch without a readmit, which
                    # draining refuses, so there is nothing left to finish
                    # (the report's pending_frames counts them honestly)
                    break
        persisted = 0
        if persist and self._store is not None:
            # step lock first: the final persist must read the COMMITTED
            # pool with nothing speculative in flight, and a brownout
            # release is page-dtype surgery
            with self._step_lock:
                self._drain_inflight(0)
                with self._lock:
                    if self._brownout_active:
                        # release the brownout before the final persist: the
                        # snapshots must land in the base dtype contract (the
                        # per-write upcast covers a kill -9; a graceful drain
                        # hands the NEXT incarnation full-precision carries)
                        self._set_brownout(False)
                    persisted = self._persist_all(sync=True)
        with self._lock:
            leftover = sum(len(s.pending) for s in self.table.sessions.values())
            self._drained = True
            report = {
                "app": self.app,
                "draining": True,
                "drained": True,
                "frames_drained": pumped,
                "pending_frames": leftover,
                "sessions_persisted": persisted,
                "sessions": len(self.table.sessions),
            }
        _journal.emit("serve", "drained", app=self.app,
                      frames_drained=pumped, sessions_persisted=persisted,
                      pending_frames=report["pending_frames"])
        log.info("%s: drained — %d frame(s) finished, %d session(s) "
                 "persisted, %d frame(s) left queued", self.app, pumped,
                 persisted, report["pending_frames"])
        return report

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drained(self) -> bool:
        return self._drained

    def retry_after_s(self) -> int:
        """``Retry-After`` seconds for a 503 (ServeFull/draining/overload),
        derived from the measured step rate: roughly how long until one
        queue-depth's worth of frames drains. Clamped to [1, 30].

        LOCK-FREE by design: the REST error path calls this on the aiohttp
        event loop, and step() holds the engine lock across an entire
        dispatch — including a new bucket's multi-second jit compile.
        Taking the lock here would freeze every control-port route (incl.
        /healthz) for that long. ``list(deque)`` under the GIL is safe
        against a concurrent append; ``_queue_frames`` is immutable."""
        stamps = list(self._step_stamps)
        qf = self._queue_frames
        if len(stamps) >= 2 and stamps[-1] > stamps[0]:
            rate = (len(stamps) - 1) / (stamps[-1] - stamps[0])
            est = qf / max(rate, 1e-3)
        else:
            est = 1.0
        return int(min(30, max(1, math.ceil(est))))

    def health(self) -> dict:
        """Liveness/readiness view for ``/healthz``/``/readyz``
        (docs/serving.md "Lifecycle"): ready = the CURRENT bucket's program
        has dispatched (compiled) — or nothing is admitted yet — and the
        engine is not draining. The readiness endpoint additionally refuses
        while the profile plane reports a serving-program compile storm.

        LOCK-FREE like :meth:`retry_after_s`: readyz runs on the aiohttp
        event loop, and while the overlapped step keeps the STATE lock
        narrow, a stepper can still be inside a capacity's first jit
        compile — exactly when an orchestrator probes hardest. Plain
        attribute/set reads under the GIL give an at-most-one-step-stale
        answer, which is all a probe needs; blocking here would freeze
        /healthz too and get a healthy pod killed mid-compile."""
        key = (self.table.capacity, self._k_eff, self._pipe_tag)
        active = self.table.active
        compiled = active == 0 or key in self._warmed
        return {"ready": bool(compiled and not self._draining),
                "compiled": bool(compiled),
                "draining": self._draining,
                "drained": self._drained,
                "shed_level": self._ladder.level,
                "shed_rung": self._ladder.rung,
                "active": active,
                "capacity": self.table.capacity}

    def watch_sample(self) -> Optional[dict]:
        """Cheap progress probe for the doctor's serve watchdog. Returns
        None when the engine lock is busy — a step() in flight IS progress
        (or a compile, which the doctor's ``compiling`` verdict explains),
        so the watchdog must not strike on it."""
        if not self._lock.acquire(timeout=0.05):
            return None
        try:
            stuck = sorted((s for s in self.table.occupants() if s.pending),
                           key=lambda s: -len(s.pending))
            return {"app": self.app,
                    "frames": self.frames,
                    "pending": sum(len(s.pending) for s in
                                   self.table.occupants()),
                    "draining": self._draining,
                    "capacity": self.table.capacity,
                    "active": self.table.active,
                    "shed_level": self._ladder.level,
                    "stuck_sessions": [s.sid for s in stuck[:4]]}
        finally:
            self._lock.release()

    def shutdown(self) -> None:
        """Detach from the doctor and stop persisting. Does NOT drain —
        call :meth:`drain` first for a graceful handoff."""
        if self._doctor_token is not None:
            try:
                from ..telemetry import doctor as _doctor
                _doctor.doctor().detach_serve(self._doctor_token)
            except Exception:                          # noqa: BLE001
                pass
            self._doctor_token = None

    # -- SLO-aware overload control (serve/overload.py) ------------------------
    def _overload_tick(self, idle: bool = False) -> None:
        """One shedding-ladder observation (lock held, busy steps + engaged
        idle steps): queue pressure vs the watermarks, rolling p99 vs the
        ``serve_slo_ms`` deadline budget. Escalations act on the transition
        — rung 2 evicts the most-stalled sessions, rung 3 engages the
        optional brownout lever; recovery unwinds one rung at a time.
        ``idle`` ticks skip the SLO term: the latency window holds only
        pre-idle samples, and a frozen p99 must read as "no current miss",
        not as a live violation that keeps escalating an empty engine.

        Re-entrant commits are guarded: a rung-2 shed evicts, eviction
        drains the in-flight window, and each nested commit would tick the
        ladder again mid-action — the ``_ticking`` flag makes the nested
        calls no-ops (the ladder loses one observation, not its
        hysteresis)."""
        if self._ticking:
            return
        p99_ms = None
        if self._slo_ms and self._lat_recent and not idle:
            p99_ms = float(np.quantile(
                np.asarray(self._lat_recent), 0.99)) * 1e3
        prev = self._ladder.level
        lvl = self._ladder.observe(self.credits.pressure(), p99_ms,
                                   self._slo_ms)
        if lvl == prev:
            return
        _SHED_LEVEL.set(float(lvl), app=self.app)
        # the shed-rung TRANSITION is the journal event (the gauge holds the
        # current level; the journal tells the story in seq order)
        _journal.emit("serve", "shed-rung", app=self.app,
                      level=lvl, prev=prev, rung=self._ladder.rung,
                      pressure=round(self.credits.pressure(), 4),
                      p99_ms=round(p99_ms, 3) if p99_ms is not None
                      else None)
        self._ticking = True
        try:
            if lvl > prev:
                log.warning("%s: overload ladder escalated to rung %d (%s) "
                            "— pressure %.2f, p99 %s ms (SLO %s)", self.app,
                            lvl, self._ladder.rung, self.credits.pressure(),
                            f"{p99_ms:.1f}" if p99_ms is not None else "-",
                            self._slo_ms or "-")
                if lvl >= 2:
                    self._shed_stalled()
                if lvl >= 3 and self._brownout != "off":
                    self._set_brownout(True)
            else:
                log.info("%s: overload ladder recovered to rung %d (%s)",
                         self.app, lvl, self._ladder.rung)
                if lvl < 3 and self._brownout_active:
                    self._set_brownout(False)
        finally:
            self._ticking = False

    def _shed_stalled(self) -> None:
        """Rung 2: evict the most-stalled sessions (no queued input, most
        consecutive inputless steps first) to host/disk — frees their lanes
        without touching a single resident bit (the evict/readmit leaf
        contract is bit-identical). At most a quarter of the active lanes
        per escalation, so one rung transition cannot empty the table."""
        cands = sorted((s for s in self.table.occupants()
                        if s.stall_steps >= 1 and not s.pending),
                       key=lambda s: -s.stall_steps)
        for s in cands[:max(1, self.table.active // 4)]:
            try:
                self.evict(s.sid)
            except (KeyError, ValueError) as e:
                log.warning("%s: shed-evict of %s failed: %r", self.app,
                            s.sid, e)
                continue
            self.shed_evictions += 1
            _SHED.inc(app=self.app, tenant=s.tenant, reason="evict")
            log.warning("%s: shed-evicted stalled session %s (tenant %s, "
                        "%d stalled steps)", self.app, s.sid, s.tenant,
                        s.stall_steps)

    def _set_brownout(self, on: bool) -> None:
        """Rung 3 (config ``serve_brownout``, default off): trade quality
        for headroom on resident buckets — ``"k"`` drops megabatch K to 1
        (per-dispatch latency over throughput; K>1 vs K=1 round differently
        by repo contract), ``"precision"`` retunes the interior to the
        configured ``serve_brownout_precision`` mode (bf16 default, or the
        deeper int8 rung) via ``ops/precision.py`` (SNR-bounded loss for
        the duration). Both
        compile their program form once (billed ``serve_bucket``) and keep
        the base programs cached — recovery never recompiles."""
        if on == self._brownout_active:
            return
        if self._brownout == "precision":
            # page-dtype surgery: every in-flight group was launched with
            # the OLD program form and must commit before the pool converts
            # (callers hold the step lock — the overload tick runs on the
            # step thread, drain takes it explicitly)
            self._drain_inflight(0)
            if not self._apply_precision_brownout(on):
                return
        self._brownout_active = on
        _journal.emit("serve", "brownout", app=self.app,
                      engaged=bool(on), lever=self._brownout)
        if on:
            _SHED.inc(app=self.app, tenant="-", reason="brownout")
        log.warning("%s: brownout lever (%s) %s", self.app, self._brownout,
                    "ENGAGED" if on else "released")

    def _apply_precision_brownout(self, on: bool) -> bool:
        """Swap the served pipeline between the base and the lowered form
        (``serve_brownout_precision``: bf16, or the deeper int8 rung),
        converting the stacked carries leaf-by-leaf (narrowing casts;
        widening upcasts the live values — the brownout's documented,
        bounded quality loss for its duration; int8 stages carry FLOAT
        weights and quantize in-trace, so their leaves convert as plain
        dtype casts like any other). Returns False (logged, no state
        change) when nothing lowers or the carry trees refuse."""
        import jax
        prev_pipe = self.pipeline
        if on:
            if self._low_pipe is None:
                try:
                    from ..ops import precision as _precision_mod
                    low, plan = _precision_mod.plan_interior_precision(
                        self._base_pipeline, mode=self._brownout_prec)
                except Exception as e:                 # noqa: BLE001
                    log.warning("%s: precision brownout plan failed (%r) — "
                                "lever disabled", self.app, e)
                    return False
                if low is self._base_pipeline:
                    log.warning("%s: precision brownout lowers nothing — "
                                "lever disabled", self.app)
                    return False
                self._low_pipe = low
            target, tag = self._low_pipe, self._brownout_prec
        else:
            target, tag = self._base_pipeline, "base"
        if target is self.pipeline:
            self._pipe_tag = tag
            return True
        old_leaves, old_def = jax.tree_util.tree_flatten(self._pages)
        self.pipeline = target
        self._fresh = None
        stacked = self._stacked_fresh(self.table.capacity)
        t_leaves, t_def = jax.tree_util.tree_flatten(stacked)
        if old_def != t_def or any(
                np.shape(a) != np.shape(b)
                for a, b in zip(old_leaves, t_leaves)):
            log.warning("%s: precision brownout carry trees mismatch — "
                        "lever disabled", self.app)
            self.pipeline = prev_pipe
            self._fresh = None
            return False
        conv = [a if getattr(a, "dtype", None) == getattr(b, "dtype", None)
                else a.astype(b.dtype)
                for a, b in zip(old_leaves, t_leaves)]
        self._pages = jax.tree_util.tree_unflatten(t_def, conv)
        self._head_pages = self._pages    # quiesced: re-root the chain
        # evicted sessions hold HOST snapshots in the old dtypes: convert
        # them too, or their readmit would fail the carry_matches dtype
        # check against the new template until a process restart
        lane = jax.tree_util.tree_flatten(self.pipeline.init_carry())[0]
        lane_dts = [np.dtype(getattr(l, "dtype", "float32")) for l in lane]
        for s in self.table.sessions.values():
            if s.state == "evicted" and s.carry_leaves is not None and \
                    len(s.carry_leaves) == len(lane_dts):
                s.carry_leaves = [
                    np.asarray(a) if np.asarray(a).dtype == dt
                    else np.asarray(a).astype(dt)
                    for a, dt in zip(s.carry_leaves, lane_dts)]
        self._pipe_tag = tag
        return True

    # -- observability ---------------------------------------------------------
    def _refresh_gauges(self) -> None:
        counts: Dict[tuple, int] = {}
        for s in self.table.sessions.values():
            counts[(s.tenant, s.state)] = counts.get((s.tenant, s.state), 0) + 1
        for key in set(self._gauge_cache) | set(counts):
            tenant, state = key
            _SESSIONS.set(float(counts.get(key, 0)), app=self.app,
                          tenant=tenant, state=state)
            self._gauge_cache[key] = True

    def tenant_latency_ms(self, tenant: str, q: float = 0.99) -> Optional[float]:
        v = _LATENCY.labels(app=self.app, tenant=tenant).quantile(q)
        return None if v is None else v * 1e3

    def describe(self) -> dict:
        """The app-level view served by ``GET /api/serve/{app}/``."""
        with self._lock:
            tenants = self.table.tenants()
            return {
                "app": self.app,
                "frame_size": self.frame_size,
                # the format sessions submit in, and how many parts of the
                # compiled step's input are decoded a complex sample a
                # 32-bit word (the lane groups on a wire; 0 for samples)
                **self.frame_format(),
                "uplink_word_parts": self.uplink_word_parts(),
                "frames_per_dispatch": self.k_batch,
                "buckets": list(self.buckets),
                "capacity": self.table.capacity,
                "resident_buckets": self.resident_buckets(),
                "compiles": self.compiles,
                "active": self.table.active,
                # paged carries + the overlapped step (this PR): the page
                # pool is the capacity; free/fresh counts and the in-flight
                # window tell an operator how churned and how pipelined the
                # engine currently is
                "pages": {"free": self.table.free_slots(),
                          "fresh_lanes": len(self._fresh_lanes)},
                "overlap": {"depth": int(self._flight.credits),
                            "in_flight": len(self._inflight)},
                "sessions": len(self.table.sessions),
                "steps": self.steps,
                "dispatches": self.dispatches,
                "frames": self.frames,
                # what crossed the link for them: lane groups in which a
                # lane rode, and their lanes over every dispatch's capacity
                # (frames / (dispatches x capacity) is the riding share)
                "groups_shipped": self.groups_shipped,
                "lanes_shipped": self.lanes_shipped,
                "shipped_lane_share": (
                    self.lanes_shipped
                    / (self.dispatches * self.table.capacity)
                    if self.dispatches else None),
                "credit_total": self.credits.total,
                "credit_fair_share": self.credits.fair_share(),
                "draining": self._draining,
                "drained": self._drained,
                # slot-axis sharding (docs/parallel.md): the mesh width and
                # whether the CURRENT bucket's lanes spread over it
                "shard": ({"devices": self._shard_d,
                           "sharded": self._shard_ok(self.table.capacity),
                           "lanes_per_device":
                               (self.table.capacity // self._shard_d
                                if self._shard_ok(self.table.capacity)
                                else self.table.capacity)}
                          if self._shard_d > 1 else None),
                "shed": {**self._ladder.view(),
                         "slo_ms": self._slo_ms or None,
                         "brownout": self._brownout,
                         "brownout_active": self._brownout_active,
                         "evictions": self.shed_evictions,
                         "pressure": round(self.credits.pressure(), 4),
                         "tenant_pressure": self.credits.tenant_pressure()},
                "persist": ({"dir": self._store._dir,
                             "every": self._persist_every,
                             "restored_sessions": self.restored_sessions}
                            if self._store is not None else None),
                "tenants": {
                    t: {"sessions": n,
                        "credits_used": self.credits.used(t),
                        "p99_ms": self.tenant_latency_ms(t)}
                    for t, n in sorted(tenants.items())},
            }

    def session_view(self, sid: str) -> dict:
        with self._lock:
            v = self._session(sid).view()
            if self._shard_d > 1 and v.get("slot") is not None:
                # the (device, lane) pair this session's slot addresses
                # under the slot-axis sharding — evict/readmit stay
                # slot-addressed, this is the mesh-side identity
                dev, lane = self.slot_device(v["slot"])
                v["device"], v["device_lane"] = dev, lane
        t = v["tenant"]
        v["tenant_p50_ms"] = self.tenant_latency_ms(t, 0.5)
        v["tenant_p99_ms"] = self.tenant_latency_ms(t, 0.99)
        return v


# ---------------------------------------------------------------------------
# SIGTERM drain hook (rolling restarts)
# ---------------------------------------------------------------------------

_sigterm_installed = False
_sigterm_lock = threading.Lock()


def drain_all_apps(timeout: float = 30.0) -> Dict[str, dict]:
    """Drain every registered serving app (refuse admissions, finish
    in-flight groups, persist all lanes). The SIGTERM hook's body; callable
    directly from an app's own shutdown path."""
    from . import api as _api
    out: Dict[str, dict] = {}
    for name, eng in _api.apps().items():
        try:
            out[name] = eng.drain(timeout=timeout)
        except Exception as e:                         # noqa: BLE001 — one
            out[name] = {"app": name, "error": repr(e)}    # bad app must not
            log.error("drain of %s failed: %r", name, e)   # block the rest
    return out


def install_sigterm_drain(timeout: float = 30.0) -> bool:
    """Install a SIGTERM handler that gracefully drains every registered
    serving app (docs/robustness.md "Serving-plane recovery"): the
    orchestrator's rolling-restart contract is SIGTERM → readyz goes
    unready (draining) → in-flight groups finish → all lanes persist →
    process exit. The drain runs on a background thread (a signal handler
    must not take engine locks); the previous handler is chained after the
    drain completes. Idempotent; returns False when not on the main thread
    (signals uninstallable) — auto-installed by ``register_app`` when
    config ``serve_drain_on_sigterm`` is set."""
    global _sigterm_installed
    import signal
    with _sigterm_lock:
        if _sigterm_installed:
            return True
        try:
            prev = signal.getsignal(signal.SIGTERM)

            def on_term(signum, frame):
                def run():
                    drain_all_apps(timeout=timeout)
                    if callable(prev):
                        try:
                            prev(signum, frame)
                        except Exception:              # noqa: BLE001
                            pass
                    elif prev == signal.SIG_DFL:
                        # restore + re-raise so the process still dies the
                        # default way once the drain landed
                        try:
                            signal.signal(signal.SIGTERM, signal.SIG_DFL)
                            os.kill(os.getpid(), signal.SIGTERM)
                        except Exception:              # noqa: BLE001
                            pass

                threading.Thread(target=run, name="fsdr-serve-drain",
                                 daemon=True).start()

            signal.signal(signal.SIGTERM, on_term)
        except ValueError:
            # not the main thread: the caller owns its signal story
            return False
        _sigterm_installed = True
        return True
