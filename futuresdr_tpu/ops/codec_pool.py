"""Codec worker pool: host-side wire encode/decode off the drain thread.

The streamed drain loop used to run the wire codec inline: encode on the way
into ``start_device_transfer_parts``, decode after the D2H lands — both on
the one BLOCKING kernel thread, serializing host codec time against dispatch
and against each other. numpy releases the GIL on large-array ops, so a small
thread pool turns the three-lane overlap (H2D ∥ compute ∥ D2H) into five:

    encode(t+1) ∥ H2D(t) ∥ compute(t) ∥ D2H(t−1) ∥ decode(t−2)

Two separate lanes, deliberately: DECODE tasks block on the D2H landing
(under a fake link that is a modeled wire-time sleep), so sharing one
executor would let parked decodes starve encodes and idle the up-link.
Workers are process-global and live for the process; threads are named
``fsdr-codec-enc*`` / ``fsdr-codec-dec*``.

ORDER is the caller's contract, not the pool's: the kernel drains its staged
and in-flight deques oldest-first and joins each future in sequence, so
emission order is preserved no matter how workers interleave. The telemetry
spans a task emits (encode/decode, ``telemetry/spans.py``) land in the
worker thread's own ring — the doctor's interval-union lanes therefore stay
honest, and ``doctor.report()["host_codec_overlap_frac"]`` measures how much
of the wall the codec lanes actually covered.

Config: ``host_codec_workers``, the thread count of each lane (default 2;
below 1 is a configuration error).
"""

from __future__ import annotations

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Optional

from ..log import logger

__all__ = ["CodecPool", "pool", "reset_pool"]

log = logger("ops.codec_pool")


class CodecPool:
    """One encode executor + one decode executor of ``workers`` threads each."""

    def __init__(self, workers: int):
        self.workers = int(workers)
        self._enc = ThreadPoolExecutor(
            max_workers=self.workers, thread_name_prefix="fsdr-codec-enc")
        self._dec = ThreadPoolExecutor(
            max_workers=self.workers, thread_name_prefix="fsdr-codec-dec")

    def submit_encode(self, fn, *args) -> Future:
        return self._enc.submit(fn, *args)

    def submit_decode(self, fn, *args) -> Future:
        return self._dec.submit(fn, *args)

    def shutdown(self) -> None:
        self._enc.shutdown(wait=True)
        self._dec.shutdown(wait=True)


_pool: Optional[CodecPool] = None
_pool_lock = threading.Lock()


def pool() -> CodecPool:
    """The process-global pool, ``host_codec_workers`` threads per lane."""
    global _pool
    if _pool is None:
        with _pool_lock:
            if _pool is None:
                from ..config import config
                n = int(config().get("host_codec_workers", 2))
                if n < 1:
                    raise ValueError(
                        f"host_codec_workers must be at least 1 (the codec "
                        f"threads per lane), got {n}")
                _pool = CodecPool(n)
                log.info("codec pool: %d encode + %d decode worker(s)", n, n)
    return _pool


def reset_pool() -> None:
    """Shut down and drop the process pool (tests / config re-reads)."""
    global _pool
    with _pool_lock:
        if _pool is not None:
            _pool.shutdown()
        _pool = None
