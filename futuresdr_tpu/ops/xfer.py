"""Host↔device transfer shim: complex streams ride as float32 pairs.

Every host→device crossing of a complex array ships the interleaved re/im float32
pairs (a zero-copy ``view`` on the host) and forms the complex array with one jitted
``lax.complex`` on device; device→host splits ``.real``/``.imag`` on device and joins on
the host. Cost: one trivially fused kernel per transfer, on every non-CPU platform.

Why pairs: they are the wire layout the quantizing codecs need (``ops/wire.py`` — sc16 /
sc8 / bf16 payloads are real planes; a complex dtype has no narrow form), so the streamed
path's default wire on an accelerator already ships planes, and the f32 wire is the same
layout unquantized. It mirrors how the reference treats its interleaved-IQ DMA formats
(seify streams are f32-pair interleaved on the wire, ``src/blocks/seify/source.rs``):
pairs are the portable wire layout; the "complex" view is formed device-side.

A raw ``device_put`` of complex64 is exact in both directions on a locally attached
v5e (``chip_smoke.py``'s ``device`` phase measures it on every run), so for the
non-quantizing paths the shim is a deletion candidate (ROADMAP queue 3) once a benchmark
exists to measure the hot path it sits on.
"""

from __future__ import annotations

import queue as _queue
import random as _random
import threading
import time
from typing import Optional

import numpy as np

from ..log import logger
from ..telemetry import prom as _prom
from ..telemetry.spans import recorder as _trace_recorder

__all__ = ["to_device", "to_host", "start_host_transfer", "start_device_transfer",
           "start_device_transfer_parts", "start_host_transfer_parts",
           "split_complex_platform", "set_fake_link", "fake_link",
           "TransferError", "FakeLinkFault", "classify_transfer_error",
           "PackedLayout", "watch", "H2DGroup", "wire_part", "join_parts"]

log = logger("ops.xfer")
_trace = _trace_recorder()
# link-plane metrics (always on; updates are per-frame, not per-sample)
_XFER_BYTES = _prom.counter(
    "fsdr_xfer_bytes_total", "bytes started on the host-device link",
    ("direction",))
_XFER_TRANSFERS = _prom.counter(
    "fsdr_xfer_transfers_total", "transfers started on the host-device link",
    ("direction",))
# physical per-buffer starts: how many device_put/fetch calls actually hit
# the link. A coalesced (packed) frame counts ONE h2d start; the per-part
# path counts len(parts). The transfers counter above stays frame-granular —
# starts/transfers is the coalescing ratio the uplink gate reads.
_XFER_STARTS = _prom.counter(
    "fsdr_xfer_starts_total",
    "physical per-buffer put/fetch starts on the host-device link",
    ("direction",))
# per-transfer duration histogram (telemetry/hist.py log2 buckets) — always
# on like the counters. Under the fake link the observed duration clamps to
# the modeled wire window (true occupancy); on real backends it is the
# stage→finish() DWELL as the drain loop experiences it, which includes any
# read-ahead queue wait — a latency signal, not a wire-time measurement. The
# H2D/D2H trace spans ARE the wire time (the readiness watcher below stamps
# their ends); the histogram cannot be, because it is observed with the
# recorder off too, when no thread waits for a transfer
_XFER_HIST = _prom.histogram(
    "fsdr_xfer_seconds",
    "host-device transfer duration, start to landing (fake link: modeled "
    "wire window)", ("direction",))
_H2D_HIST = _XFER_HIST.labels(direction="h2d")
_D2H_HIST = _XFER_HIST.labels(direction="d2h")
# transient-retry billing (docs/robustness.md): one tick per retried attempt,
# so a seeded fault campaign's retry count is auditable from /metrics
_RETRIES = _prom.counter(
    "fsdr_retries_total", "transient host-device transfer retries",
    ("direction",))
_RETRY_H2D = _RETRIES.labels(direction="h2d")
_RETRY_D2H = _RETRIES.labels(direction="d2h")


# ---------------------------------------------------------------------------
# transfer retry: transient-vs-fatal classification + backoff under deadline
# ---------------------------------------------------------------------------

class TransferError(RuntimeError):
    """Fatal transfer failure: non-transient cause, retry budget exhausted,
    or the per-transfer deadline (``xfer_deadline``) blown."""


class FakeLinkFault(RuntimeError):
    """Transient fault injected by the seeded fake link (CI retry testing)."""


#: lowercase substrings marking a backend/driver error as WORTH retrying —
#: the runtime's retryable status codes plus classic socket transients
_TRANSIENT_MARKERS = ("unavailable", "resource_exhausted", "deadline_exceeded",
                      "aborted", "connection reset", "temporarily",
                      "try again", "timed out")


def classify_transfer_error(e: BaseException) -> bool:
    """True when ``e`` is transient (worth a retry): injected link faults
    (``FakeLinkFault``, transient ``runtime/faults.py`` injections) and
    backend errors matching :data:`_TRANSIENT_MARKERS`. A ``TransferError``
    is always fatal (it already wraps an exhausted retry loop)."""
    if isinstance(e, FakeLinkFault):
        return True
    if isinstance(e, TransferError):
        return False
    transient = getattr(e, "transient", None)     # InjectedFault carries it
    if transient is not None:
        return bool(transient)
    msg = str(e).lower()
    return any(m in msg for m in _TRANSIENT_MARKERS)


#: jitter source for retry backoff — deliberately NOT the fault-injection rng:
#: jitter shifts retry *timing*, never the retry *count*, so seeded campaigns
#: stay deterministic in their observable outcome
_jitter_rng = _random.Random(0x5FDB7)


def _with_retry(direction: str, attempt_fn):
    """Run one transfer attempt with transient-classified retries: jittered
    exponential backoff (``xfer_backoff`` base) under the retry budget
    (``xfer_retries``) and the per-transfer deadline (``xfer_deadline``).
    ``attempt_fn`` must be idempotent — H2D re-puts the host STAGING copies
    (the non-aliasing encode path makes the frames immutable by contract) and
    D2H re-reads the still-resident device array, so a retried frame is
    bit-identical to an unfaulted one."""
    from ..config import config
    c = config()
    retries = int(c.get("xfer_retries", 3))
    backoff = float(c.get("xfer_backoff", 0.005))
    deadline_s = float(c.get("xfer_deadline", 30.0))
    t0 = time.perf_counter()
    attempt = 0
    ctr = _RETRY_H2D if direction == "h2d" else _RETRY_D2H
    while True:
        try:
            return attempt_fn()
        except Exception as e:
            attempt += 1
            if not classify_transfer_error(e):
                raise
            pause = min(backoff * (1 << (attempt - 1)), 1.0)
            pause *= 0.5 + _jitter_rng.random()
            out_of_budget = attempt > retries
            past_deadline = deadline_s > 0 and \
                time.perf_counter() - t0 + pause > deadline_s
            if out_of_budget or past_deadline:
                raise TransferError(
                    f"{direction} transfer failed after {attempt} attempt(s) "
                    f"({'retry budget' if out_of_budget else 'deadline'} "
                    f"exhausted): {e!r}") from e
            ctr.inc()
            log.warning("%s transfer attempt %d failed transiently (%r): "
                        "retrying in %.1f ms", direction, attempt, e,
                        pause * 1e3)
            time.sleep(pause)


_faults_mod = None


def _check_injected(direction: str) -> None:
    """Raise any armed injected fault for this crossing: the fake link's own
    seeded fault model plus the ``h2d``/``d2h``/``link`` sites of
    ``runtime/faults.py`` (imported lazily — ops must not import runtime at
    module level)."""
    link = _fake_link
    if link is not None:
        link.maybe_fault(direction)
    global _faults_mod
    if _faults_mod is None:
        from ..runtime import faults as _fm
        _faults_mod = _fm
    p = _faults_mod.plan()
    if p.armed():
        p.maybe(direction)
        p.maybe("link")


def _span_bounds_ns(t0_ns: int, service: float, deadline: float) -> tuple:
    """``(start_ns, end_ns)`` of a transfer span, clamped to the fake link's
    modeled wire occupancy when one exists: the span STARTS when the wire
    begins servicing these bytes (not when they were queued behind an earlier
    frame — same-lane queue wait double-counted into span sums would inflate
    the overlap ratio) and ENDS at the landing deadline (a finish() called
    late must not inflate the lane's busy interval either)."""
    end = time.perf_counter_ns()
    if deadline:
        dl = int(deadline * 1e9)       # perf_counter and perf_counter_ns share
        if t0_ns < dl < end:           # one epoch (time module contract)
            end = dl
    start = t0_ns
    if service:
        sv = int(service * 1e9)
        if t0_ns < sv:
            start = min(sv, end)
    return start, end


# ---------------------------------------------------------------------------
# the readiness watcher: span ends that the launching thread cannot stamp
# ---------------------------------------------------------------------------
# "The arrays are resident" (H2D) and "the outputs are ready" (program, the
# start of D2H) are instants only ``block_until_ready`` reveals, and a
# launching thread that blocked on it would change the schedule it measures.
# A daemon thread blocks instead, one per LANE: a watcher is a FIFO, and is
# exact only where things become ready in the order they were queued. Uploads
# land in order and outputs become ready in order, but an upload staged ahead
# lands while an older program still runs: one FIFO for both would stamp that
# upload late by the program's remaining time. A watcher exists only while
# the recorder is enabled: ``watch`` starts it, it ends itself once the
# recorder is off and its queue is empty, and between items it holds no
# reference to an array.

_watch_lock = threading.Lock()
_watchers: dict = {}                        # lane -> queue, while one runs
_WATCH_POLL_S = 0.2                         # how soon it notices "off"


def watch(arrays, name: Optional[str], t0_ns: int,
          args: Optional[dict] = None, then=None, lane: str = "out") -> None:
    """Complete the ``cat="tpu"`` span ``name`` from ``t0_ns`` to the instant
    every array of ``arrays`` is ready, stamped on the lane's watcher thread
    (``lane``: ``"out"`` for program outputs, ``"h2d"`` for uploaded parts);
    ``then(ready_ns)`` (optional) runs there right after. Call only under
    ``if _trace.enabled``. Never watch a donated argument: an array deleted
    before the watcher reaches it yields no span, raises nothing and counts
    in ``SpanRecorder.unwatched``."""
    with _watch_lock:
        q = _watchers.get(lane)
        if q is None:
            q = _watchers[lane] = _queue.SimpleQueue()
            threading.Thread(target=_watch_loop, args=(lane, q),
                             name=f"fsdr-xfer-watch-{lane}",
                             daemon=True).start()
        q.put((tuple(arrays), name, t0_ns, args, then))


def _watch_loop(lane: str, q) -> None:
    while True:
        try:
            arrays, name, t0_ns, args, then = q.get(timeout=_WATCH_POLL_S)
        except _queue.Empty:
            with _watch_lock:       # puts hold it too: no item is stranded
                if not _trace.enabled and q.empty():
                    del _watchers[lane]
                    return
            continue
        try:
            for a in arrays:
                wait = getattr(a, "block_until_ready", None)
                if wait is not None:        # host data is ready as it is
                    wait()
        except Exception:           # donated or deleted before we got here
            _trace.unwatched += 1
        else:
            ready = time.perf_counter_ns()
            if name is not None:
                _trace.complete("tpu", name, t0_ns, end_ns=ready, args=args)
            if then is not None:
                then(ready)
        arrays = then = None        # hold nothing while waiting for the next


class _Landing:
    """A real link's ``D2H`` span: outputs ready → bytes on the host. The two
    ends are seen by two threads (the watcher; whoever calls ``finish()``),
    in either order; the second one to stamp completes the span."""

    __slots__ = ("args", "ready_ns", "landed_ns", "_open")

    def __init__(self, arrays, args: dict):
        self.args = args
        self.ready_ns = self.landed_ns = 0
        self._open = [True]
        watch(arrays, None, 0, then=self.ready)

    def ready(self, t_ns: int) -> None:
        self.ready_ns = t_ns
        self._complete()

    def landed(self) -> None:
        self.landed_ns = time.perf_counter_ns()
        self._complete()

    def _complete(self) -> None:
        if self.ready_ns and self.landed_ns:
            try:
                self._open.pop()    # atomic: exactly one side wins
            except IndexError:
                return
            # bytes cannot land before they are ready: a watcher that woke
            # after the finishing thread stamped late, not the device
            _trace.complete("tpu", "D2H", min(self.ready_ns, self.landed_ns),
                            end_ns=self.landed_ns, args=self.args)


def _span_args(nbytes: int, seq) -> dict:
    return {"bytes": nbytes} if seq is None else {"bytes": nbytes, "seq": seq}


def _d2h_landing(arrays, nbytes: int, seq, deadline: float):
    """The ``D2H`` span of a transfer just started on a real link, or None:
    recorder off, or a fake link (``_d2h_observe`` records its window)."""
    if deadline or not _trace.enabled:
        return None
    return _Landing(arrays, _span_args(nbytes, seq))


def _d2h_observe(t0: int, service: float, deadline: float, nbytes: int,
                 seq) -> None:
    """A finished D2H's histogram sample and, under a fake link, its span
    (the modelled wire window)."""
    s, e = _span_bounds_ns(t0, service, deadline)
    _D2H_HIST.observe((e - s) * 1e-9)
    if deadline and _trace.enabled:
        _trace.complete("tpu", "D2H", s, end_ns=e,
                        args=_span_args(nbytes, seq))


class H2DGroup:
    """ONE ``h2d_put`` + ``H2D`` span pair for several transfer starts (a
    serving dispatch group's lane groups and its three small vectors): create
    it right before the first start, pass it as ``group=`` to each, then
    :meth:`close` once they have all returned; ``bytes`` is what the starts
    really put on the link, ``extra`` rides both spans' args. Create only
    under ``if _trace.enabled``. Under a fake link each start keeps its own
    modelled ``H2D`` window and the group records only ``h2d_put``."""

    __slots__ = ("seq", "t0_ns", "arrays", "nbytes", "extra")

    def __init__(self, seq=None, **extra):
        self.seq = seq
        self.t0_ns = time.perf_counter_ns()
        self.arrays: list = []
        self.nbytes = 0
        self.extra = extra

    def add(self, arrays, nbytes: int) -> None:
        self.arrays.extend(arrays)
        self.nbytes += nbytes

    def close(self) -> None:
        args = {**_span_args(self.nbytes, self.seq), **self.extra}
        _trace.complete("tpu", "h2d_put", self.t0_ns, args=args)
        if self.arrays:
            watch(self.arrays, "H2D", self.t0_ns, args, lane="h2d")
            self.arrays = []


_join_jit = None
_split_jit = None


def _jits():
    global _join_jit, _split_jit
    if _join_jit is None:
        import jax

        _join_jit = jax.jit(lambda p: jax.lax.complex(p[..., 0], p[..., 1]))
        _split_jit = jax.jit(lambda x: (x.real, x.imag))
    return _join_jit, _split_jit


_join_parts_jits: dict = {}


def _ships_pairs(dtype, device=None) -> bool:
    """Does an array of ``dtype`` cross to ``device`` as float32 pairs?"""
    return np.issubdtype(np.dtype(dtype), np.complexfloating) and \
        split_complex_platform(_device_platform(device))


def wire_part(arr: np.ndarray, device=None) -> np.ndarray:
    """One host array as it crosses the link, for
    :func:`start_device_transfer_parts`: a complex array's float32 pairs (a
    zero-copy view) where pairs are shipped, else the array itself (a
    serving wire's ``uint32`` words, a complex sample a word, cross as the
    words they are). The device side of several such parts is
    :func:`join_parts`."""
    if _ships_pairs(arr.dtype, device):
        from .wire import _pairs_view
        return _pairs_view(arr)
    return arr


def join_parts(parts, dtype, device=None):
    """The device array whose leading axis is cut into ``parts`` (device
    arrays put as :func:`wire_part` of ``dtype`` arrays): ONE jitted program
    concatenates them and forms the complex values from the pairs — the
    several-part form of :func:`start_device_transfer`'s join. Parts of a
    dtype that ships as it is (``uint32`` words) are concatenated and no
    pairs are formed: whoever decodes them does it in its own program. Which parts
    were just uploaded and which were resident changes no shape, so it
    compiles once per part count and shape."""
    pairs = _ships_pairs(dtype, device)
    if len(parts) == 1 and not pairs:
        return parts[0]
    join = _join_parts_jits.get(pairs)
    if join is None:
        import jax
        import jax.numpy as jnp

        def join(ps):
            x = ps[0] if len(ps) == 1 else jnp.concatenate(ps, axis=0)
            return jax.lax.complex(x[..., 0], x[..., 1]) if pairs else x

        join = _join_parts_jits[pairs] = jax.jit(join)
    return join(tuple(parts))


class _FakeLink:
    """Rate-throttled fake link for deterministic CI pipelining tests.

    Models each direction as a serial wire: a transfer of ``nbytes`` occupies
    the direction for ``nbytes/rate`` seconds starting when the wire frees up.
    ``reserve`` is called at transfer START and returns the wall-clock deadline
    the bytes land at; ``finish()`` sleeps out the remainder. No threads — the
    timeline alone decides whether a drain loop overlapped its transfers:
    serialized loops pay Σ(h2d+compute+d2h), pipelined ones pay ≈ the max.

    ``fault_rate``/``fault_seed`` add a seeded fault model: each transfer
    START draws from a per-direction ``random.Random(f"{seed}:{dir}")``
    stream and raises a transient :class:`FakeLinkFault` on a hit — so the
    retry path is CI-testable deterministically (same seed + same transfer
    sequence → same faults → same retry count, billed on
    ``fsdr_retries_total{direction}``). Per-direction streams keep the draw
    order independent of h2d/d2h thread interleaving."""

    def __init__(self, h2d_bps: Optional[float], d2h_bps: Optional[float],
                 fault_rate: float = 0.0, fault_seed: int = 0):
        self.h2d_bps = h2d_bps
        self.d2h_bps = d2h_bps
        self._lock = threading.Lock()
        self._busy = {"h2d": 0.0, "d2h": 0.0}
        self.fault_rate = float(fault_rate or 0.0)
        self.fault_seed = int(fault_seed)
        # the draw machinery IS runtime/faults.py's SiteInjector (one seeded
        # Bernoulli implementation in the codebase, billed on
        # fsdr_faults_injected_total{site="link:<dir>"}); this class only
        # wraps the fire into its own FakeLinkFault surface
        from ..runtime.faults import SiteInjector
        self._injectors = {
            d: SiteInjector(f"link:{d}", self.fault_rate, self.fault_seed,
                            max_faults=None, transient=True)
            for d in ("h2d", "d2h")}

    @property
    def faults(self):
        """``{direction: fired}`` — campaign introspection."""
        return {d: inj.fired for d, inj in self._injectors.items()}

    def maybe_fault(self, direction: str) -> None:
        """One seeded per-direction draw at transfer start; raises on a hit."""
        if not self.fault_rate:
            return
        from ..runtime.faults import InjectedFault
        try:
            self._injectors[direction].check()
        except InjectedFault as e:
            raise FakeLinkFault(
                f"injected fake-link fault on {direction} (#{e.seq}, "
                f"seed {self.fault_seed})") from e

    def reserve(self, direction: str, nbytes: int) -> tuple:
        """Returns ``(service_start, deadline)``: the wire begins moving these
        bytes at ``service_start`` (after any queued predecessor) and lands
        them at ``deadline`` — both wall-clock ``perf_counter`` values."""
        rate = self.h2d_bps if direction == "h2d" else self.d2h_bps
        if not rate:
            return (0.0, 0.0)
        with self._lock:
            start = max(time.perf_counter(), self._busy[direction])
            self._busy[direction] = start + nbytes / rate
            return (start, self._busy[direction])


_fake_link: Optional[_FakeLink] = None


def set_fake_link(h2d_bps: Optional[float] = None,
                  d2h_bps: Optional[float] = None,
                  fault_rate: float = 0.0, fault_seed: int = 0):
    """Install (or with no args remove) a throttled fake link on every transfer
    started through this module; returns the previous link for restoration.
    CI/testing only — lets the CPU backend reproduce a link-bound streamed
    regime deterministically. ``fault_rate``/``fault_seed`` arm the
    link's seeded fault model (see :class:`_FakeLink`) so the transfer-retry
    path is exercised deterministically too."""
    global _fake_link
    prev = _fake_link
    _fake_link = _FakeLink(h2d_bps, d2h_bps, fault_rate, fault_seed) \
        if (h2d_bps or d2h_bps or fault_rate) else None
    return prev


def fake_link() -> Optional[_FakeLink]:
    return _fake_link


def _reserve(direction: str, nbytes: int) -> tuple:
    """``(service_start, deadline)`` of the modeled wire; zeros without a link."""
    return _fake_link.reserve(direction, nbytes) if _fake_link else (0.0, 0.0)


def _wait_deadline(deadline: float) -> None:
    """Wait out a fake-link deadline PRECISELY: plain ``time.sleep`` overshoots
    by 1-4 ms on Linux, a proportionally larger tax on short (small-frame /
    compact-wire) transfers — enough to skew A/B wire-format ratios. Sleep to
    ~1.5 ms short of the deadline, then yield-spin the remainder."""
    if not deadline:
        return
    while True:
        d = deadline - time.perf_counter()
        if d <= 0:
            return
        time.sleep(d - 0.0015 if d > 0.0015 else 0.0)


def _start_fetch(part):
    """Begin the D2H of one device array NOW (``copy_to_host_async``); returns
    ``thunk() -> np.ndarray``.

    The thunk RETRIES transient materialization failures: on a flaky link the
    error surfaces when the bytes land (inside ``finish()``), not at start —
    the device array stays resident, so re-reading it is idempotent and the
    retried frame is bit-identical."""
    part.copy_to_host_async()
    # the FIRST _with_retry attempt is the original materialization, so the
    # budget/billing contract matches the transfer-start paths exactly:
    # xfer_retries retries, each billed once
    return lambda p=part: _with_retry("d2h", lambda: np.asarray(p))


def split_complex_platform(platform: str) -> bool:
    """Pair-shipping applies on every accelerator platform (cpu transfers are sane)."""
    return platform != "cpu"


def h2d_needs_staging(platform: str) -> bool:
    """Must a ring-buffer view be copied out before being handed to
    ``device_put`` (and the ring position consumed)? ALWAYS — on every
    platform. Single source of truth for TpuKernel/PpKernel.

    On accelerators the H2D is async and reads the source buffer later. The
    CPU backend is the trap: ``device_put`` of a numpy view usually copies
    eagerly, but a 64-BYTE-ALIGNED view is zero-copy BORROWED
    (``unsafe_buffer_pointer() == view.ctypes.data``) — and ring buffers are
    page-aligned memfd mappings, so frame-sized slices are almost always
    aligned. A borrowed frame aliases ring memory the upstream writer then
    overwrites → flaky corruption of in-flight frames (round-5 regression:
    ``test_tpu_kernel_block_in_flowgraph`` failed ~50% after the copy was
    elided on "cpu"; probes with ``np.zeros`` buffers missed it because the
    allocator happened to return misaligned bases). Forcing misalignment
    would just move the same copy inside jax, so the explicit staging copy
    stays."""
    return True


def _device_platform(device=None) -> str:
    import jax

    if device is None:
        return jax.default_backend()
    if hasattr(device, "platform"):          # a Device
        return device.platform
    try:                                      # a Sharding
        devs = list(device.device_set)
        if devs:
            return devs[0].platform
    except AttributeError:
        pass
    return jax.default_backend()


def start_device_transfer_parts(parts, device=None, seq=None, group=None):
    """Begin a NON-blocking H2D of pre-encoded wire parts (``ops/wire.py``
    layouts — plain real/int numpy arrays, never complex); returns a zero-arg
    ``finish()`` that blocks until the payload is device-resident and yields
    the tuple of device arrays.

    Spans (recorder on): ``h2d_put`` brackets the ``device_put`` calls on this
    thread; ``H2D`` runs from the first of them to the instant the arrays are
    resident, stamped by the watcher. ``seq`` (the dispatch group's sequence
    number) rides both; ``group`` (an :class:`H2DGroup`) folds this start into
    the group's one pair instead. Under a fake link ``H2D`` is the modelled
    wire window, completed by ``finish()``.

    This is the H2D symmetric of :func:`start_host_transfer` — the primitive
    that lets a drain loop keep H2D(t+1) on the wire while frame t computes
    (``device_put`` is async on accelerator backends; the fake link models the
    wire time for deterministic CPU-backend tests). ``device`` may be a Device
    or a Sharding."""
    import jax

    host = [np.asarray(p) for p in parts]
    nbytes = sum(p.nbytes for p in host)
    _XFER_BYTES.inc(nbytes, direction="h2d")
    _XFER_TRANSFERS.inc(direction="h2d")
    _XFER_STARTS.inc(len(host), direction="h2d")

    def attempt():
        # idempotent: re-puts the immutable host STAGING copies — a retried
        # frame lands bit-identical to an unfaulted one
        _check_injected("h2d")
        return tuple(jax.device_put(p, device) for p in host)

    t_put = time.perf_counter_ns() if _trace.enabled and group is None else 0
    devs = _with_retry("h2d", attempt)
    # the wire is reserved AFTER the attempt succeeds: faulted attempts spend
    # backoff wall-clock, not modeled wire occupancy
    service, deadline = _reserve("h2d", nbytes)
    t0 = time.perf_counter_ns()
    if t_put:
        args = _span_args(nbytes, seq)
        _trace.complete("tpu", "h2d_put", t_put, end_ns=t0, args=args)
        if not deadline:
            watch(devs, "H2D", t_put, args, lane="h2d")
    elif group is not None and not deadline:
        group.add(devs, nbytes)

    def finish():
        _wait_deadline(deadline)
        s, e = _span_bounds_ns(t0, service, deadline)
        _H2D_HIST.observe((e - s) * 1e-9)
        if deadline and _trace.enabled:
            _trace.complete("tpu", "H2D", s, end_ns=e,
                            args=_span_args(nbytes, seq))
        return devs

    # modeled wire window (service start, landing deadline) — zeros without a
    # fake link. The streamed credit controller (tpu/kernel_block.py) reads
    # consecutive windows to detect up-link idle gaps; symmetric with the
    # D2H finishes' _wire attribute below.
    finish._wire = (service, deadline)
    return finish


class PackedLayout:
    """Offset table of ONE dispatch group's coalesced H2D transfer buffer.

    The uplink coalescing plane: a quantizing wire ships several parts per
    frame (int payload + scale; a megabatch K-stack per part), and each part
    is a separate ``device_put`` — a separate link start. ``PackedLayout``
    fixes the byte layout that packs every part of a dispatch group into one
    contiguous buffer: slot ``i`` holds part ``i``'s bytes at a
    64-byte-aligned offset, so every slot starts on a 32-bit word and
    ``nbytes`` is a whole number of words. The host side writes payloads in
    place through byte views (``ops/arena.PackedAlloc``); what crosses the
    link, and what the program takes, is that buffer as ``uint32[nbytes /
    4]`` (:meth:`pack` returns the view: same bytes, no copy). The device
    side recovers the parts with :meth:`unpack_jax`, fused into the wired
    program by ``Pipeline.compile_wired(packed=...)``: one slice per slot
    and at most one bitcast. Words, because of what a byte buffer costs on
    the TPU: ``u8[n, 2]`` → int16 and ``int16[n, 2]`` → I, Q are arrays
    whose minor dimension is 2, padded to 128 lanes and relaid, 1.0 ms of
    the spectrum program's 1.3 (``docs/tpu_notes.md``, "pair formats cross
    as words").

    The layout is a pure function of the wire codec + frame shape (probed
    from an encode of zeros), so host packer and device unpacker can never
    disagree, and a replayed frame re-ships the EXACT packed words the first
    attempt shipped (the replay log retains the shipped view of the packed
    buffer, not the parts).
    """

    ALIGN = 64
    __slots__ = ("slots", "nbytes")

    def __init__(self, slots, nbytes):
        self.slots = tuple(slots)     # (shape, dtype, offset, nbytes) each
        self.nbytes = int(nbytes)

    @classmethod
    def from_parts(cls, parts) -> "PackedLayout":
        """Layout for a concrete part tuple (shapes/dtypes as shipped)."""
        slots, off = [], 0
        for p in parts:
            p = np.asarray(p)
            slots.append((tuple(p.shape), np.dtype(p.dtype), off,
                          int(p.nbytes)))
            off += -(-max(p.nbytes, 1) // cls.ALIGN) * cls.ALIGN
        return cls(slots, off)

    @classmethod
    def probe(cls, wire, frame_size: int, in_dtype, k: int = 1):
        """Layout for ``wire``'s encode of a ``frame_size`` frame (``k > 1``:
        the megabatch stack — every part gains a leading ``[k]`` axis), or
        ``None`` when the wire ships a single part (nothing to coalesce —
        packing a lone payload would only add a copy)."""
        parts = wire.encode_host(np.zeros(frame_size, dtype=in_dtype))
        parts = [np.asarray(p) for p in parts]
        if len(parts) < 2:
            return None
        if k > 1:
            parts = [np.broadcast_to(p, (int(k),) + p.shape) for p in parts]
        return cls.from_parts(parts)

    @property
    def key(self):
        """Hashable identity (the wired-program cache key extension)."""
        return self.slots

    def matches(self, parts) -> bool:
        """Do ``parts`` fit this layout slot-for-slot (shape and dtype)?"""
        if len(parts) != len(self.slots):
            return False
        return all(tuple(np.shape(p)) == sh and np.dtype(
            getattr(p, "dtype", type(p))) == dt
            for p, (sh, dt, _o, _n) in zip(parts, self.slots))

    def pack(self, parts, out: np.ndarray) -> np.ndarray:
        """Copy any part not already resident in its slot into ``out`` (a
        ``(nbytes,)`` uint8 buffer) and zero the alignment gaps, so the
        shipped bytes are a deterministic function of the parts. Parts the
        encoder already wrote through a slot view (``PackedAlloc``) are left
        untouched. Returns the buffer in the form it SHIPS in: a
        ``uint32[nbytes / 4]`` view of ``out`` (what ``device_put`` is given
        on the hot path, at warm-up and on every replay)."""
        assert out.nbytes >= self.nbytes, (out.nbytes, self.nbytes)
        end = 0
        for p, (sh, dt, off, nb) in zip(parts, self.slots):
            p = np.asarray(p)
            if end < off:                       # alignment gap before slot
                out[end:off] = 0
            view = out[off:off + nb].view(dt).reshape(sh)
            if not np.shares_memory(view, p):
                view[...] = p
            end = off + nb
        if end < self.nbytes:
            out[end:self.nbytes] = 0
        return out[:self.nbytes].view(np.uint32)

    def unpack_jax(self, words, as_words=()):
        """The device-side slicing prolog: recover the part tuple from the
        packed ``uint32`` words, one slice per slot. A slot flagged in
        ``as_words`` (one flag per slot, from ``Wire.pair_words``: a complex
        pair of int16 IS a word) is handed on as the int32 words themselves,
        shape ``slot shape[:-1]``, for ``decode_words_jax`` to split by two
        shifts. Every other slot is bitcast back to its dtype and shape (a
        4-byte dtype: one bitcast; a narrower one unfolds ``[w, 4 /
        itemsize]``, the general form sc8 and a real int16 payload take)."""
        import jax

        parts = []
        flags = tuple(as_words) or (False,) * len(self.slots)
        for (sh, dt, off, nb), word in zip(self.slots, flags):
            seg = jax.lax.slice(words, (off // 4,), ((off + nb + 3) // 4,))
            if word:
                parts.append(jax.lax.bitcast_convert_type(
                    seg, np.int32).reshape(sh[:-1]))
                continue
            if dt.itemsize > 4:
                seg = seg.reshape(-1, dt.itemsize // 4)
            if dt != np.uint32:
                seg = jax.lax.bitcast_convert_type(seg, dt)
            if nb % 4:                  # a narrow dtype's last, part-filled word
                seg = jax.lax.slice(seg.reshape(-1), (0,),
                                    (nb // dt.itemsize,))
            parts.append(seg.reshape(sh))
        return tuple(parts)


def start_device_transfer(arr, device=None, seq=None, group=None):
    """Begin a NON-blocking H2D of one host array (complex rides the pair shim);
    returns ``finish() -> device array``. :func:`to_device` is this with an
    immediate finish. ``seq``/``group``: the span arguments of
    :func:`start_device_transfer_parts`."""
    import jax

    if isinstance(arr, jax.Array):
        # already device-resident: device_put is a same-device no-op (or a safe D2D
        # move); forcing it through np.asarray would be a blocking D2H round-trip
        x = jax.device_put(arr, device) if device is not None else arr
        return lambda: x
    a = np.asarray(arr)
    if _ships_pairs(a.dtype, device):
        # wire_part: the ONE copy of the regression-locked pairs-view trick
        put = start_device_transfer_parts((wire_part(a, device),), device,
                                          seq, group)
        join, _ = _jits()

        def finish():
            (p,) = put()
            return join(p)

        finish._wire = getattr(put, "_wire", None)
        return finish
    put = start_device_transfer_parts((a,), device, seq, group)

    def finish():
        (x,) = put()
        return x

    finish._wire = getattr(put, "_wire", None)
    return finish


def to_device(arr, device=None):
    """``jax.device_put`` through the pair shim: complex arrays ship as float32 pairs."""
    return start_device_transfer(arr, device)()


def to_host(arr) -> np.ndarray:
    """``np.asarray`` that reads complex device arrays back as two float transfers."""
    return start_host_transfer(arr)()


def start_host_transfer(arr, _instrument: bool = True, seq=None):
    """Begin a NON-blocking D2H of ``arr``; returns a zero-arg ``finish()`` that
    blocks until the copy lands and yields the numpy array.
    ``_instrument=False`` (module-private) suppresses the per-call telemetry so
    :func:`start_host_transfer_parts` can bill one frame's parts as ONE
    transfer — symmetric with the H2D side, which reserves per frame.

    The ``D2H`` span (recorder on) runs from the instant ``arr`` is READY on
    the device, stamped by the watcher, to the instant its bytes are on the
    host, stamped here in ``finish()`` — not from ``copy_to_host_async``,
    which is called before the program has run. ``seq`` rides its args. Under
    a fake link it is the modelled wire window.

    This is how a drain loop overlaps transfers: start transfers for every
    completed frame first, then finish them oldest-first — frame t+1's D2H rides
    the wire while the caller is still consuming frame t (the role of the
    reference's circulating empty/full staging buffers, ``buffer/vulkan/d2h.rs``).
    :func:`to_host` is this with an immediate finish; all complex-pair-shim and
    platform logic lives here, once."""
    import jax

    if not isinstance(arr, jax.Array):
        # host data: nothing to fetch (and the jitted split() would device_put
        # it first)
        return lambda: np.asarray(arr)
    dt = np.dtype(getattr(arr, "dtype", np.float32))
    if np.issubdtype(dt, np.complexfloating):
        try:
            devs = list(arr.devices())
            platform = devs[0].platform if devs else _device_platform()
        except Exception:
            platform = _device_platform()
        if split_complex_platform(platform):
            _, split = _jits()
            r, i = split(arr)                    # async device-side split
            nbytes = r.nbytes + i.nbytes
            # physical starts bill regardless of _instrument (parts-path
            # callers suppress the per-frame counters, not the start count)
            _XFER_STARTS.inc(2, direction="d2h")
            if _instrument:
                _XFER_BYTES.inc(nbytes, direction="d2h")
                _XFER_TRANSFERS.inc(direction="d2h")

            def attempt():
                # idempotent: the split halves stay device-resident, so a
                # retried fetch re-reads the same bits
                _check_injected("d2h")
                # both halves start NOW (async copy, or eager pool fetch when
                # the array type has no copy_to_host_async) — never serially
                # in finish
                return _start_fetch(r), _start_fetch(i)

            fr, fi = _with_retry("d2h", attempt)
            service, deadline = _reserve("d2h", nbytes)
            t0 = time.perf_counter_ns() if _instrument else 0
            landing = _d2h_landing((r, i), nbytes, seq, deadline) \
                if t0 else None

            def finish():
                out = np.empty(r.shape, dtype=dt)
                out.real = fr()
                out.imag = fi()
                if landing is not None:
                    landing.landed()
                _wait_deadline(deadline)
                if t0:
                    _d2h_observe(t0, service, deadline, nbytes, seq)
                return out

            finish._wire = (service, deadline)
            return finish
    nbytes = int(getattr(arr, "nbytes", 0))
    _XFER_STARTS.inc(direction="d2h")
    if _instrument:
        _XFER_BYTES.inc(nbytes, direction="d2h")
        _XFER_TRANSFERS.inc(direction="d2h")

    def attempt():
        _check_injected("d2h")
        return _start_fetch(arr)

    fetch = _with_retry("d2h", attempt)
    service, deadline = _reserve("d2h", nbytes)
    t0 = time.perf_counter_ns() if _instrument else 0
    landing = _d2h_landing((arr,), nbytes, seq, deadline) if t0 else None

    def finish():
        out = fetch()
        if landing is not None:
            landing.landed()
        _wait_deadline(deadline)
        if t0:
            _d2h_observe(t0, service, deadline, nbytes, seq)
        return out

    finish._wire = (service, deadline)
    return finish


def start_host_transfer_parts(parts, seq=None):
    """Begin a NON-blocking D2H of a tuple of wire parts (a jitted epilog's
    output, ``ops/wire.py``); returns ``finish() -> tuple of np arrays``.
    Every part's transfer starts immediately, so in-flight frames' payloads
    ride the wire together (per-direction fake-link accounting included).

    Telemetry bills the WHOLE frame as one D2H transfer/span (symmetric with
    :func:`start_device_transfer_parts`): per-part billing would make the
    d2h counters and lane span counts scale with the wire's part count
    instead of the frame count. The ``D2H`` span is
    :func:`start_host_transfer`'s: parts ready → every part on the host."""
    fins = [start_host_transfer(p, _instrument=False) for p in parts]
    nbytes = sum(int(getattr(p, "nbytes", 0)) for p in parts)
    _XFER_BYTES.inc(nbytes, direction="d2h")
    _XFER_TRANSFERS.inc(direction="d2h")
    t0 = time.perf_counter_ns()
    wires = [getattr(f, "_wire", (0.0, 0.0)) for f in fins]
    service = min((s for s, _ in wires if s), default=0.0)
    deadline = max((d for _, d in wires), default=0.0)
    landing = _d2h_landing(parts, nbytes, seq, deadline)

    def finish():
        out = tuple(f() for f in fins)
        if landing is not None:
            landing.landed()
        _d2h_observe(t0, service, deadline, nbytes, seq)
        return out

    return finish
