"""Host staging arena: a size-classed pool of recycled host buffers.

The streamed path's steady state used to allocate fresh numpy buffers every
frame — the ring-exit staging copy, the quantizing wire-encode outputs, the
megabatch pad frames. At MB-scale frames every one of those allocations is an
mmap'd region whose pages fault in on first write, so the allocator taxes the
drain loop with work the wire could have been riding under (the host-transfer
bottleneck of arXiv:1810.09868 §4 — once device compute is fused, the input
pipeline's residual cost IS the host plane). The arena replaces them with
recycled buffers: after the first lap of the in-flight window every ``take``
is a pop from a free list of warm, already-faulted pages.

Ownership is explicit, because recycling under fault tolerance is the
dangerous part: a buffer whose frame may be RE-SHIPPED — by the transfer
plane's idempotent re-put (``ops/xfer.py``) or by the checkpoint replay log
(``tpu/kernel_block.py``) — must not be recycled into a newer frame, or the
retry would upload aliased garbage bit-for-bit confidently. So every consumer
holds its own reference: :meth:`ArenaBuffer.retain` / :meth:`release`, and a
buffer returns to its size-class free list only at refcount zero. The kernel
releases a dispatch group's buffers when its outputs drain; the replay log
holds an additional retain until a committed checkpoint covers the group.

Size classes are powers of two (min 4 KiB), so a frame-size change mid-run
cannot fragment the pool; the pool is bounded (``host_arena_mb`` config) —
past the cap a released buffer is dropped to the allocator instead of pooled.

Telemetry (always on, docs/observability.md): ``fsdr_arena_hits_total`` /
``fsdr_arena_misses_total`` (takes served from the pool vs fresh
allocations), ``fsdr_arena_pinned_bytes`` / ``fsdr_arena_pooled_bytes``
gauges, and a ``doctor.report()["arena"]`` snapshot.

Config: ``host_arena_mb``, the pool's byte cap.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..log import logger
from ..telemetry import prom as _prom

__all__ = ["ArenaBuffer", "StagingArena", "arena", "reset_arena",
           "arena_stats"]

log = logger("ops.arena")

_HITS = _prom.counter(
    "fsdr_arena_hits_total", "arena takes served from a recycled buffer")
_MISSES = _prom.counter(
    "fsdr_arena_misses_total", "arena takes that allocated a fresh buffer")
_PINNED = _prom.gauge(
    "fsdr_arena_pinned_bytes", "bytes of arena buffers currently checked out")
_POOLED = _prom.gauge(
    "fsdr_arena_pooled_bytes", "bytes of arena buffers idle in the pool")

_MIN_CLASS = 12                       # 4 KiB floor: below it pooling is noise


def _class_of(nbytes: int) -> int:
    """Size-class exponent: smallest power of two ≥ nbytes (≥ 4 KiB)."""
    return max(_MIN_CLASS, int(nbytes - 1).bit_length()) if nbytes > 1 \
        else _MIN_CLASS


class ArenaBuffer:
    """One pooled buffer: a flat byte array plus an explicit refcount.

    Created at refcount 1 (the taker owns that reference). Additional
    consumers — the replay log, a retry-window holder — call
    :meth:`retain` and balance it with :meth:`release`; the buffer returns
    to its arena's free list only when the count reaches zero. ``release``
    past zero is a no-op (a defensive contract: a double release must never
    recycle a buffer some other holder still pins)."""

    __slots__ = ("base", "_arena", "_cls", "_rc", "_lock")

    def __init__(self, arena: "StagingArena", cls: int):
        self.base = np.empty(1 << cls, dtype=np.uint8)
        self._arena = arena
        self._cls = cls
        self._rc = 1
        self._lock = threading.Lock()

    @property
    def nbytes(self) -> int:
        return self.base.nbytes

    def array(self, shape, dtype) -> np.ndarray:
        """A leading view of the buffer as ``shape``/``dtype`` (must fit)."""
        dt = np.dtype(dtype)
        n = int(np.prod(shape)) * dt.itemsize
        assert n <= self.base.nbytes, (shape, dt, self.base.nbytes)
        return self.base[:n].view(dt).reshape(shape)

    def retain(self) -> "ArenaBuffer":
        with self._lock:
            assert self._rc > 0, "retain() of an already-recycled buffer"
            self._rc += 1
        return self

    def release(self) -> None:
        with self._lock:
            if self._rc <= 0:
                return
            self._rc -= 1
            if self._rc:
                return
        self._arena._recycle(self)


class StagingArena:
    """The pool: per-size-class free lists, bounded by ``max_bytes``."""

    def __init__(self, max_bytes: int = 256 << 20):
        self.max_bytes = int(max_bytes)
        self._free: Dict[int, List[ArenaBuffer]] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.pinned_bytes = 0
        self.pooled_bytes = 0

    # -- take -----------------------------------------------------------------
    def take(self, nbytes: int) -> ArenaBuffer:
        """Check out a buffer of capacity ≥ nbytes (refcount 1)."""
        cls = _class_of(int(nbytes))
        with self._lock:
            lst = self._free.get(cls)
            if lst:
                buf = lst.pop()
                self.pooled_bytes -= buf.nbytes
                self.pinned_bytes += buf.nbytes
                self.hits += 1
                hit = True
            else:
                self.misses += 1
                hit = False
                buf = None
        if buf is None:
            buf = ArenaBuffer(self, cls)
            with self._lock:
                self.pinned_bytes += buf.nbytes
        else:
            buf._rc = 1
        (_HITS if hit else _MISSES).inc()
        _PINNED.set(self.pinned_bytes)
        _POOLED.set(self.pooled_bytes)
        return buf

    def take_array(self, shape, dtype) -> Tuple[np.ndarray, ArenaBuffer]:
        """``(array view, owning buffer)`` for a fresh-content buffer."""
        dt = np.dtype(dtype)
        buf = self.take(int(np.prod(shape)) * dt.itemsize)
        return buf.array(shape, dt), buf

    def copy_in(self, a: np.ndarray) -> Tuple[np.ndarray, ArenaBuffer]:
        """Copy ``a`` into an arena buffer — the ring-exit staging copy of
        the drain loops (``TpuKernel._stage_available_input``): the frame
        leaves the live ring before ``consume()``, into recycled pages
        instead of a fresh allocation."""
        v, buf = self.take_array(a.shape, a.dtype)
        np.copyto(v, a)
        return v, buf

    # -- recycle --------------------------------------------------------------
    def _recycle(self, buf: ArenaBuffer) -> None:
        with self._lock:
            self.pinned_bytes -= buf.nbytes
            if self.pooled_bytes + buf.nbytes <= self.max_bytes:
                self._free.setdefault(buf._cls, []).append(buf)
                self.pooled_bytes += buf.nbytes
            # else: past the cap — drop to the allocator
        _PINNED.set(self.pinned_bytes)
        _POOLED.set(self.pooled_bytes)

    # -- introspection --------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "pinned_bytes": self.pinned_bytes,
                "pooled_bytes": self.pooled_bytes,
                "classes": {1 << c: len(l)
                            for c, l in sorted(self._free.items()) if l},
            }


_arena: Optional[StagingArena] = None
_arena_lock = threading.Lock()


def arena() -> StagingArena:
    """The process-global arena, sized by ``host_arena_mb`` on first use."""
    global _arena
    if _arena is None:
        with _arena_lock:
            if _arena is None:
                from ..config import config
                _arena = StagingArena(
                    int(config().get("host_arena_mb", 256)) << 20)
    return _arena


def reset_arena() -> None:
    """Drop the process arena (tests / config re-reads); the next
    :func:`arena` call re-resolves config."""
    global _arena
    with _arena_lock:
        _arena = None


def arena_stats() -> Optional[dict]:
    """Snapshot for ``doctor.report()`` (None when the arena was never
    used)."""
    a = _arena
    return a.stats() if a is not None else None


class GroupAlloc:
    """Per-dispatch-group allocator handed to ``Wire.encode_into``: records
    every buffer it hands out so the caller can pin the whole group's
    staging set in one list (the replay-log / drain release contract of
    ``tpu/kernel_block.py``). ``temp()`` buffers are scratch the encode
    itself drops via :meth:`drop_temps` — they never outlive the encode."""

    __slots__ = ("arena", "handles", "_temps")

    def __init__(self, arena: StagingArena):
        self.arena = arena
        self.handles: List[ArenaBuffer] = []
        self._temps: List[ArenaBuffer] = []

    def __call__(self, shape, dtype) -> np.ndarray:
        v, buf = self.arena.take_array(shape, dtype)
        self.handles.append(buf)
        return v

    def temp(self, shape, dtype) -> np.ndarray:
        v, buf = self.arena.take_array(shape, dtype)
        self._temps.append(buf)
        return v

    def drop_temps(self) -> None:
        for b in self._temps:
            b.release()
        self._temps.clear()

    def temps_only(self) -> "_TempsOnly":
        """An alloc view whose ``__call__`` also lands in the temp set — for
        intermediates (per-frame encodes before a megabatch stack) that must
        not pin past the encode."""
        return _TempsOnly(self)


class _TempsOnly:
    """See :meth:`GroupAlloc.temps_only` — everything is scratch, owned (and
    dropped) by the parent alloc."""

    __slots__ = ("_parent",)

    def __init__(self, parent: GroupAlloc):
        self._parent = parent

    def __call__(self, shape, dtype) -> np.ndarray:
        return self._parent.temp(shape, dtype)

    def temp(self, shape, dtype) -> np.ndarray:
        return self._parent.temp(shape, dtype)

    def drop_temps(self) -> None:
        pass                                # the parent owns the temp set


class PackedAlloc(GroupAlloc):
    """A :class:`GroupAlloc` whose payload allocations are VIEWS into ONE
    contiguous packed transfer buffer (the H2D coalescing plane,
    ``ops/xfer.PackedLayout``): ``__call__`` hands out the next unfilled
    layout slot matching the requested shape/dtype, so a quantizing encode's
    int payload is written directly at its packed offset — the coalesce
    costs zero extra payload copies. A request no slot matches falls back to
    a plain arena take (``PackedLayout.pack`` copies those, plus bare parts
    like the quantizer's scale scalar, into their slots afterwards).
    ``handles[0]`` pins the packed buffer itself; the whole-group pinning /
    replay-retention contract is the parent's, unchanged."""

    __slots__ = ("layout", "packed", "_filled")

    def __init__(self, arena: StagingArena, layout):
        super().__init__(arena)
        self.layout = layout
        self.packed, buf = arena.take_array((layout.nbytes,), np.uint8)
        self.handles.append(buf)
        self._filled = [False] * len(layout.slots)

    def __call__(self, shape, dtype) -> np.ndarray:
        sh = ((int(shape),) if isinstance(shape, (int, np.integer))
              else tuple(shape))
        dt = np.dtype(dtype)
        for i, (ssh, sdt, off, nb) in enumerate(self.layout.slots):
            if not self._filled[i] and ssh == sh and sdt == dt:
                self._filled[i] = True
                return self.packed[off:off + nb].view(dt).reshape(sh)
        return super().__call__(shape, dtype)

    def finish(self, parts) -> np.ndarray:
        """Settle the packed buffer for shipping: copy in every part the
        encode did not write through a slot view, zero alignment gaps, and
        return the buffer as it ships — the ``uint32`` word view of the
        packed bytes (same memory, backed by ``handles[0]``)."""
        return self.layout.pack(parts, self.packed)
