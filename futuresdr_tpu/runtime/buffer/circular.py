"""Default CPU buffer: C++ double-mapped circular buffer with lock-free SPSC indices.

Re-design of the reference's default buffer (``src/runtime/buffer/circular.rs`` over the
``vmcircbuffer`` crate): a memfd-backed region mapped twice back-to-back so every read/write
window is contiguous regardless of the wrap position — work windows are never split, unlike the
portable :mod:`.ring` fallback. Index arithmetic (produce/consume/space) lives in C++ atomics
(``native/ringbuf.cpp``), so the data-plane accounting is lock-free exactly as in the reference.

The library is built from ``native/*.cpp`` on the host that runs it (:func:`load_native`);
``FSDR_NO_NATIVE=1`` selects the portable :mod:`.ring` instead, and :func:`available` reports
which of the two the flowgraph default buffer was set to at import (``runtime/__init__``).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

from ...log import logger
from ..inbox import BlockInbox, StreamInputDone, StreamOutputDone
from ..tag import ItemTag
from . import BufferReader, BufferWriter

__all__ = ["CircularWriter", "CircularReader", "available", "load_native"]

log = logger("buffer.circular")

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), os.pardir, "native")
_NATIVE_DIR = os.path.normpath(_NATIVE_DIR)

_lib = None


def load_native() -> Optional[ctypes.CDLL]:
    """Build (``make -C native``) and load the native library.

    The portable Python ring is chosen by ``FSDR_NO_NATIVE=1`` — the only way
    this returns None — or not at all: a failed build or load RAISES, because a
    checkout without a toolchain would otherwise run a different, slower host
    path that nothing reports. ``make`` always runs: a no-op when up to date,
    and the Makefile's build stamp (compiler + flags + the ISA ``-march=native``
    resolves to on THIS host) forces a rebuild of objects carried over from
    another machine, whose instructions this CPU may not have."""
    global _lib
    if _lib is not None:
        return _lib
    if os.environ.get("FSDR_NO_NATIVE"):
        return None
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                       capture_output=True, text=True, timeout=300)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"native build failed (make -C {_NATIVE_DIR}, exit {e.returncode}); "
            f"set FSDR_NO_NATIVE=1 to run on the portable Python ring:\n"
            f"{(e.stderr or e.stdout or '').strip()[-2000:]}") from e
    lib = ctypes.CDLL(os.path.join(_NATIVE_DIR, "libfsdr_native.so"))
    lib.fsdr_dbuf_create.restype = ctypes.c_void_p
    lib.fsdr_dbuf_create.argtypes = [ctypes.c_size_t]
    lib.fsdr_dbuf_destroy.argtypes = [ctypes.c_void_p]
    lib.fsdr_dbuf_ptr.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.fsdr_dbuf_ptr.argtypes = [ctypes.c_void_p]
    lib.fsdr_dbuf_size.restype = ctypes.c_size_t
    lib.fsdr_dbuf_size.argtypes = [ctypes.c_void_p]
    lib.fsdr_ring_create.restype = ctypes.c_void_p
    lib.fsdr_ring_create.argtypes = [ctypes.c_uint64]
    lib.fsdr_ring_destroy.argtypes = [ctypes.c_void_p]
    lib.fsdr_ring_add_reader.restype = ctypes.c_int
    lib.fsdr_ring_add_reader.argtypes = [ctypes.c_void_p]
    lib.fsdr_ring_remove_reader.argtypes = [ctypes.c_void_p, ctypes.c_int]
    for f in ("fsdr_ring_wpos", "fsdr_ring_space"):
        getattr(lib, f).restype = ctypes.c_uint64
        getattr(lib, f).argtypes = [ctypes.c_void_p]
    for f in ("fsdr_ring_rpos", "fsdr_ring_available"):
        getattr(lib, f).restype = ctypes.c_uint64
        getattr(lib, f).argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.fsdr_ring_produce.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.fsdr_ring_consume.argtypes = [ctypes.c_void_p, ctypes.c_int, ctypes.c_uint64]
    _lib = lib
    return _lib


def available() -> bool:
    return load_native() is not None


def probe_native(symbol: str, restype, argtypes) -> Optional[ctypes.CDLL]:
    """Shared native-kernel probe: honors the ``FSDR_NO_NATIVE=1`` escape hatch
    (forces every portable fallback — rule out the C++ toolchain when debugging
    or benchmarking the pure-Python/XLA paths), loads the library, checks the
    symbol, binds its signature, and returns the CDLL (or None). Every native
    kernel (MM clock recovery, Viterbi, …) routes through here so the fallback
    convention cannot silently diverge per call site."""
    if os.environ.get("FSDR_NO_NATIVE"):
        return None
    lib = load_native()
    if lib is None or not hasattr(lib, symbol):
        return None
    fn = getattr(lib, symbol)
    fn.restype = restype
    fn.argtypes = argtypes
    return lib


class CircularWriter(BufferWriter):
    """1 writer → N broadcast readers over a double-mapped region."""

    def __init__(self, dtype, capacity: int, writer_inbox: BlockInbox,
                 writer_port_index: int = 0):
        lib = load_native()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.dtype = np.dtype(dtype)
        want_bytes = int(capacity) * self.dtype.itemsize
        self._dbuf = lib.fsdr_dbuf_create(want_bytes)
        if not self._dbuf:
            raise MemoryError("fsdr_dbuf_create failed")
        size_bytes = lib.fsdr_dbuf_size(self._dbuf)
        self.capacity = size_bytes // self.dtype.itemsize
        ptr = lib.fsdr_dbuf_ptr(self._dbuf)
        # View over BOTH mappings: 2×capacity items, [i] and [i+capacity] alias.
        raw = np.ctypeslib.as_array(ptr, shape=(2 * size_bytes,))[:2 * size_bytes]
        n_items = (2 * size_bytes) // self.dtype.itemsize
        self._data = raw.view(self.dtype)[:n_items]
        self._ring = lib.fsdr_ring_create(self.capacity)
        self._readers: List["CircularReader"] = []
        self._inbox = writer_inbox
        self._port_index = writer_port_index
        self._finished = False
        # tag lists are per-reader, python-side (control plane, low rate)
        self._tag_lock = threading.Lock()

    def __del__(self):
        try:
            if getattr(self, "_ring", None):
                self._lib.fsdr_ring_destroy(self._ring)
                self._ring = None
            if getattr(self, "_dbuf", None):
                self._lib.fsdr_dbuf_destroy(self._dbuf)
                self._dbuf = None
        except Exception:
            pass

    # -- connect ---------------------------------------------------------------
    def add_reader(self, reader_inbox: BlockInbox, port_index: int,
                   min_items: int = 1) -> "CircularReader":
        idx = self._lib.fsdr_ring_add_reader(self._ring)
        if idx < 0:
            raise RuntimeError(
                "too many readers on one circular buffer (native cap: 16, "
                "FSDR_MAX_READERS in native/ringbuf.cpp). For wider broadcast "
                "fan-out use the portable ring buffer (buffer='ring', unbounded "
                "readers) on this edge.")
        r = CircularReader(self, idx, reader_inbox, port_index)
        self._readers.append(r)
        return r

    # -- writer side -----------------------------------------------------------
    def slice(self) -> np.ndarray:
        space = self._lib.fsdr_ring_space(self._ring)
        off = self._lib.fsdr_ring_wpos(self._ring) % self.capacity
        return self._data[off:off + space]   # contiguous thanks to double mapping

    def space_available(self) -> int:
        return int(self._lib.fsdr_ring_space(self._ring))

    def produce(self, n: int, tags: Sequence[ItemTag] = ()) -> None:
        if n == 0:
            return
        if tags:
            base = self._lib.fsdr_ring_wpos(self._ring)
            with self._tag_lock:
                for r in self._readers:
                    if not r._detached:
                        r._tags.extend(ItemTag(base + t.index, t.tag) for t in tags)
        self._lib.fsdr_ring_produce(self._ring, n)
        for r in self._readers:
            if not r._detached:
                r._inbox.notify()

    def notify_finished(self) -> None:
        if self._finished:
            return
        self._finished = True
        for r in self._readers:
            if not r._detached:
                r._inbox.send(StreamInputDone(r.port_index))


class CircularReader(BufferReader):
    def __init__(self, writer: CircularWriter, ring_idx: int,
                 inbox: BlockInbox, port_index: int):
        self._w = writer
        self._idx = ring_idx
        self._inbox = inbox
        self.port_index = port_index
        self._tags: List[ItemTag] = []
        self._detached = False

    def slice(self) -> np.ndarray:
        w = self._w
        avail = w._lib.fsdr_ring_available(w._ring, self._idx)
        off = w._lib.fsdr_ring_rpos(w._ring, self._idx) % w.capacity
        return w._data[off:off + avail]

    def items_available(self) -> int:
        return int(self._w._lib.fsdr_ring_available(self._w._ring, self._idx))

    def tags(self) -> List[ItemTag]:
        w = self._w
        pos = w._lib.fsdr_ring_rpos(w._ring, self._idx)
        with w._tag_lock:
            return [ItemTag(t.index - pos, t.tag) for t in self._tags if t.index >= pos]

    def consume(self, n: int) -> None:
        if n == 0:
            return
        w = self._w
        w._lib.fsdr_ring_consume(w._ring, self._idx, n)
        if self._tags:
            pos = w._lib.fsdr_ring_rpos(w._ring, self._idx)
            with w._tag_lock:
                self._tags = [t for t in self._tags if t.index >= pos]
        w._inbox.notify()   # space freed → wake writer block

    def notify_finished(self) -> None:
        if self._detached:
            return
        self._detached = True
        self._w._lib.fsdr_ring_remove_reader(self._w._ring, self._idx)
        self._w._inbox.send(StreamOutputDone(self._w._port_index))
