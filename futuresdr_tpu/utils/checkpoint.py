"""Checkpoint / resume.

The reference has NO checkpointing (SURVEY §5: closest analog is that finished blocks are
restored into the Flowgraph). This framework goes further: block state and jax pytrees
(model params / optimizer state) can be saved and restored — training jobs in the
flowgraph (modrec) resume across process restarts via orbax.
"""

from __future__ import annotations

import base64
import json
import os
from typing import Any, Dict, Optional

import numpy as np

from ..log import logger

__all__ = ["save_pytree", "load_pytree", "save_flowgraph_state", "load_flowgraph_state"]

log = logger("checkpoint")


# ---------------------------------------------------------------------------
# data-only block-state serialization (no pickle: a checkpoint file must never
# be able to execute code on restore)
# ---------------------------------------------------------------------------

def _flatten(obj: Any, path: str, arrays: Dict[str, np.ndarray]) -> Any:
    """Encode ``obj`` as a JSON-able spec; ndarrays go to ``arrays`` by key."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    if isinstance(obj, bytes):
        return {"__t__": "bytes", "v": base64.b64encode(obj).decode()}
    if isinstance(obj, complex):
        return {"__t__": "complex", "re": obj.real, "im": obj.imag}
    if isinstance(obj, np.generic):                       # numpy scalar
        return _flatten(obj.item(), path, arrays)
    if hasattr(obj, "__array__"):                         # ndarray / jax array
        a = np.asarray(obj)
        if a.dtype == object:
            # would save fine but np.load(allow_pickle=False) can never restore it
            raise TypeError(f"state_dict entry {path!r} is an object-dtype array; "
                            f"only numeric/bool dtypes are checkpointable")
        key = f"a{len(arrays)}"
        arrays[key] = a
        return {"__t__": "nd", "k": key}
    if isinstance(obj, (list, tuple)):
        items = [_flatten(v, f"{path}[{i}]", arrays) for i, v in enumerate(obj)]
        return {"__t__": "tuple" if isinstance(obj, tuple) else "list", "v": items}
    if isinstance(obj, dict):
        return {"__t__": "dict",
                "v": [[_flatten(k, path, arrays), _flatten(v, f"{path}.{k}", arrays)]
                      for k, v in obj.items()]}
    raise TypeError(f"state_dict entry {path!r} has unserializable type "
                    f"{type(obj).__name__}; use scalars/ndarrays/containers")


def _unflatten(spec: Any, arrays) -> Any:
    if not isinstance(spec, dict):
        return spec
    t = spec["__t__"]
    if t == "bytes":
        return base64.b64decode(spec["v"])
    if t == "complex":
        return complex(spec["re"], spec["im"])
    if t == "nd":
        return arrays[spec["k"]]
    if t == "list":
        return [_unflatten(v, arrays) for v in spec["v"]]
    if t == "tuple":
        return tuple(_unflatten(v, arrays) for v in spec["v"])
    if t == "dict":
        return {_unflatten(k, arrays): _unflatten(v, arrays) for k, v in spec["v"]}
    raise ValueError(f"unknown spec tag {t!r}")


def save_pytree(path: str, tree: Any) -> None:
    """Persist a jax pytree (params/opt state) with orbax."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path, tree, force=True)
    ckptr.wait_until_finished()


def load_pytree(path: str, like: Optional[Any] = None) -> Any:
    """Restore a pytree. With ``like``, leaves are restored HOST-side (numpy)
    and re-placed onto ``like``'s devices through the transfer pair shim
    (``ops/xfer.to_device``), the same upload path every fresh carry takes, so
    a restored device-pipeline carry (e.g. a FIR stage's frequency-domain
    taps) lands exactly like a newly initialised one."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    ckptr = ocp.StandardCheckpointer()
    if like is not None:
        import jax
        import numpy as np

        from ..ops.xfer import to_device

        def host_struct(a):
            if hasattr(a, "shape") and hasattr(a, "dtype"):
                return np.zeros(a.shape, a.dtype)
            return a

        def place(restored, ref):
            if isinstance(ref, jax.Array):
                devs = list(ref.devices())
                if len(devs) > 1:
                    # multi-device leaf: restore the reference's SHARDING (a
                    # single-device put would concentrate the carry on one chip
                    # and break the next sharded dispatch). Sharded complex on a
                    # split-complex backend cannot transfer either way — let
                    # device_put raise loudly rather than mis-place silently.
                    return jax.device_put(np.asarray(restored), ref.sharding)
                return to_device(np.asarray(restored),
                                 devs[0] if devs else None)
            return restored

        try:
            host = ckptr.restore(
                path, jax.tree_util.tree_map(host_struct, like))
            return jax.tree_util.tree_map(place, host, like)
        except Exception as e:
            # falling back means orbax's own RAW device_puts; the swallowed
            # reason must not vanish with it
            log.warning("host-side checkpoint restore failed (%r); falling "
                        "back to direct orbax restore", e)
    return ckptr.restore(path)


def save_flowgraph_state(fg, path: str) -> None:
    """Snapshot every block exposing ``state_dict()`` (plus Vector-style sinks)."""
    states: Dict[str, Any] = {}
    for bid in range(len(fg)):
        try:
            blk = fg.wrapped(bid)
        except Exception:
            continue
        k = blk.kernel
        if hasattr(k, "state_dict"):
            states[blk.instance_name] = k.state_dict()
    arrays: Dict[str, np.ndarray] = {}
    spec = _flatten(states, "$", arrays)
    with open(path, "wb") as f:           # file object: no .npz suffix munging
        np.savez(f, __spec__=np.frombuffer(
            json.dumps(spec).encode(), dtype=np.uint8), **arrays)
    log.info("saved %d block states to %s", len(states), path)


def load_flowgraph_state(fg, path: str) -> int:
    with open(path, "rb") as f:
        magic = f.read(1)
    if magic == b"\x80":                                  # pickle protocol header
        raise ValueError(
            f"{path} is a legacy pickle-format checkpoint; the format changed to "
            f"data-only npz (arbitrary-code-execution hardening). Re-create it with "
            f"save_flowgraph_state from this version.")
    with np.load(path, allow_pickle=False) as z:
        spec = json.loads(bytes(z["__spec__"]).decode())
        states = _unflatten(spec, z)
    n = 0
    for bid in range(len(fg)):
        try:
            blk = fg.wrapped(bid)
        except Exception:
            continue
        k = blk.kernel
        if blk.instance_name in states and hasattr(k, "load_state_dict"):
            k.load_state_dict(states[blk.instance_name])
            n += 1
    return n
