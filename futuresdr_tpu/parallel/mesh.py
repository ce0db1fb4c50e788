"""Device mesh helpers for multi-chip scaling.

The reference is single-process shared-memory (SURVEY §2.7); its scale-out story is
transport blocks between hosts. The TPU-native scale-out is SPMD over an ICI mesh:
``jax.sharding.Mesh`` + shardings, XLA inserting the collectives.
"""

from __future__ import annotations

import os
import re
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_mesh", "factor_devices", "shard_params", "virtual_cpu_mesh",
           "P", "NamedSharding"]


def virtual_cpu_mesh(n_devices: int) -> None:
    """Opt this process into ``n_devices`` VIRTUAL CPU devices instead of the
    attached accelerator — for scripts whose point is a mesh wider than the
    hardware (sharding dry runs, CI). Must run before jax's first backend use:
    the device-count flag and the platform choice only act at init. Timings
    taken on such a mesh are not device metrics."""
    flags = os.environ.get("XLA_FLAGS", "")
    want = f"--xla_force_host_platform_device_count={int(n_devices)}"
    if "xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--?xla_force_host_platform_device_count=\d+", want, flags)
    else:
        flags = (flags + " " + want).strip()
    os.environ["XLA_FLAGS"] = flags
    jax.config.update("jax_platforms", "cpu")


def factor_devices(n: int, n_axes: int = 2) -> Tuple[int, ...]:
    """Factor n devices into a near-balanced axis tuple (largest axes first).

    The product ALWAYS equals ``n`` and the tuple always has ``n_axes``
    entries — prime counts on deep meshes land the whole prime on one axis
    with 1s elsewhere (``factor_devices(7, 3) == (7, 1, 1)``), never a
    truncated or padded factorization. Degenerate inputs are refused
    loudly instead of returning a shape whose product is wrong."""
    n, n_axes = int(n), int(n_axes)
    if n < 1:
        raise ValueError(f"cannot factor {n} devices (need >= 1)")
    if n_axes < 1:
        raise ValueError(f"need >= 1 mesh axis, got {n_axes}")
    dims = [1] * n_axes
    rem = n
    # peel off prime factors, assigning each to the currently-smallest axis
    f = 2
    factors = []
    while rem > 1 and f * f <= rem:
        while rem % f == 0:
            factors.append(f)
            rem //= f
        f += 1
    if rem > 1:
        factors.append(rem)
    for f in sorted(factors, reverse=True):
        i = int(np.argmin(dims))
        dims[i] *= f
    assert int(np.prod(dims)) == n, (n, n_axes, dims)
    return tuple(sorted(dims, reverse=True))


def make_mesh(axis_names: Sequence[str], shape: Optional[Sequence[int]] = None,
              devices=None) -> Mesh:
    """Mesh over all (or given) devices; shape auto-factored when omitted.

    A ``shape`` needing MORE devices than exist is refused with a clear
    error (previously a cryptic numpy reshape failure): a silently
    truncated or short mesh would change the program's sharding semantics.
    A shape covering FEWER devices than exist stays valid — an explicit
    sub-mesh (e.g. a 1-device reference mesh next to the full one) is a
    deliberate, documented pattern (``__graft_entry__.dryrun_multichip``).
    """
    from ..tpu.instance import ensure_compile_cache
    ensure_compile_cache()      # parallel/ and shard/ reach the device here
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = factor_devices(len(devices), len(axis_names))
    shape = tuple(int(s) for s in shape)
    if len(shape) != len(axis_names):
        raise ValueError(
            f"mesh shape {shape} has {len(shape)} axes but "
            f"{len(axis_names)} axis names {tuple(axis_names)}")
    need = int(np.prod(shape))
    if need > len(devices):
        raise ValueError(
            f"mesh shape {shape} needs {need} devices but only "
            f"{len(devices)} exist — refusing to build a short mesh "
            f"(shrink the shape or grow the slice)")
    arr = np.array(devices[:need]).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def shard_params(params, mesh: Mesh, axis: str = "mp"):
    """FSDP-style weight sharding: for each parameter leaf, shard its largest
    evenly-divisible axis over ``axis``; replicate the rest.

    Returns (sharded_params, shardings_pytree) — pass the shardings as jit
    in_shardings/out_shardings so the train step runs fully SPMD.
    """
    n = mesh.shape[axis]

    def spec_for(leaf):
        if not hasattr(leaf, "shape") or leaf.ndim == 0:
            return P()
        sizes = list(leaf.shape)
        order = np.argsort(sizes)[::-1]
        for ax in order:
            if sizes[ax] % n == 0 and sizes[ax] >= n:
                spec = [None] * leaf.ndim
                spec[ax] = axis
                return P(*spec)
        return P()

    specs = jax.tree_util.tree_map(spec_for, params)
    shardings = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs)
    sharded = jax.device_put(params, shardings)
    return sharded, shardings
