"""TpuInstance: the device broker of the TPU compute plane.

Role analog of the reference's accelerator ``Instance`` brokers (``buffer/vulkan/mod.rs:46-127``,
``buffer/wgpu/mod.rs:78-127``): owns the jax device (or mesh), hands out compiled stage
programs, and tracks frame-size / in-flight-depth defaults from config.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from ..config import config
from ..log import logger

__all__ = ["TpuInstance", "instance", "ensure_compile_cache"]

log = logger("tpu.instance")


#: the persistent XLA compile cache when ``JAX_COMPILATION_CACHE_DIR`` does not
#: place it: ONE fixed directory inside the checkout, derived from the package's
#: own location — the path is part of the cache key, so a directory that moves
#: (tempfile, pid, time) never hits. Git-ignored.
_DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def ensure_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory before the
    first compile of a device path; returns that directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this function
    sets NOTHING. Unset: the cache goes to ``<checkout>/.jax_cache``. Called by
    every entry to the device (:class:`TpuInstance`, which ``TpuKernel``, the
    frame plane and ``ServeEngine`` pass through, and ``parallel.make_mesh``,
    which ``parallel/`` and ``shard/`` pass through). Idempotent."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_DEFAULT_CACHE_DIR)
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class TpuInstance:
    def __init__(self, device=None, platform: Optional[str] = None):
        ensure_compile_cache()
        if device is None:
            devs = jax.devices(platform) if platform else jax.devices()
            device = devs[0]
        self.device = device
        self.frame_size = config().tpu_frame_size
        self.frames_in_flight = config().tpu_frames_in_flight
        log.info("TpuInstance on %s (frame=%d, in-flight=%d)",
                 self.device, self.frame_size, self.frames_in_flight)

    @property
    def platform(self) -> str:
        return self.device.platform

    def put(self, arr: np.ndarray):
        """H2D that is safe for complex dtypes (pair shim, see ops/xfer.py)."""
        from ..ops.xfer import to_device
        return to_device(arr, self.device)

    def get(self, arr) -> np.ndarray:
        """D2H that is safe for complex dtypes (pair shim, see ops/xfer.py)."""
        from ..ops.xfer import to_host
        return to_host(arr)

    def get_async(self, arr):
        """Start a non-blocking D2H; returns ``finish() -> np.ndarray`` (see
        ``ops/xfer.start_host_transfer`` — lets drains overlap transfers)."""
        from ..ops.xfer import start_host_transfer
        return start_host_transfer(arr)


_instance: Optional[TpuInstance] = None
_lock = threading.Lock()


def instance() -> TpuInstance:
    """Process-global default broker (like the reference's lazy `vulkan::Instance`)."""
    global _instance
    with _lock:
        if _instance is None:
            _instance = TpuInstance()
        return _instance
