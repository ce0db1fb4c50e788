"""PpKernel: a flowgraph block whose per-frame compute is a GPipe pipeline
across the mesh's ``pp`` axis.

The sibling of :class:`SpKernel` for PIPELINE parallelism: SpKernel time-shards
each frame over every device (sequence parallelism); PpKernel shards a MODEL —
each device on the ``pp`` axis owns one stage's weights, frames are split into
microbatches that stream through the stages with ``ppermute`` hops between
devices (:func:`futuresdr_tpu.parallel.make_pp_pipeline` — one jitted shard_map,
so the whole schedule is a single XLA program per frame).

This closes the runtime-integration loop for the last parallelism axis: data
(multi-pipe), tensor (shard_params), sequence (SpKernel), and now pipeline
parallelism all run through the SAME actor runtime and stream buffers
(SURVEY §2.7 — the reference pipelines blocks over CPU threads; the TPU-native
form pipelines a model over the mesh and feeds it from a flowgraph).
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Deque, Optional, Sequence

import numpy as np

from ..ops import xfer
from ..runtime.kernel import Kernel
from ..telemetry.spans import recorder as _trace_recorder

__all__ = ["PpKernel"]

_trace = _trace_recorder()


def _check_stage_leading(stage_params, n_stages: int) -> None:
    """Every leaf must lead with exactly n_stages: a larger multiple shards
    without error but each device then uses only its FIRST stage — half the
    model silently ignored."""
    import jax
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if np.ndim(leaf) < 1 or np.shape(leaf)[0] != n_stages:
            raise ValueError(
                f"stage_params leaves must lead with n_stages={n_stages}; "
                f"got leaf shape {np.shape(leaf)}")


class PpKernel(Kernel):
    """Stream → microbatched pipeline over ``mesh[axis]`` → stream.

    - ``apply_stage(params_one_stage, x) -> y``: one stage's computation;
      input/output share shape+dtype (activations ride one ppermute channel).
    - ``stage_params``: pytree with a leading ``n_stages`` axis on every leaf,
      placed one-stage-per-device along ``axis``.
    - ``micro_shape``: shape of ONE microbatch (e.g. ``(batch, features)``);
      each frame carries ``n_micro`` of them, so
      ``frame_size = n_micro * prod(micro_shape)`` items.

    Frames are independent (stateless model application); ``frames_in_flight``
    overlaps H2D/compute/D2H via XLA async dispatch like TpuKernel.
    """

    BLOCKING = True

    def __init__(self, apply_stage: Callable, stage_params, mesh, in_dtype,
                 out_dtype, micro_shape: Sequence[int], n_micro: int,
                 axis: str = "pp", frames_in_flight: int = 2, wire=None):
        super().__init__()
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..ops.wire import resolve_wire
        from ..parallel import make_pp_pipeline

        self.mesh = mesh
        self.axis = axis
        n_stages = mesh.shape[axis]
        self.micro_shape = tuple(int(m) for m in micro_shape)
        self.n_micro = int(n_micro)
        self.frame_size = self.n_micro * int(np.prod(self.micro_shape))
        platform = next(iter(np.asarray(mesh.devices).flat)).platform
        self._platform = platform
        self.wire = resolve_wire(wire, platform)
        self._in_dt = np.dtype(in_dtype)
        self._out_dt = np.dtype(out_dtype)
        # wire codec prolog/epilog fused around the pipeline program: the frame
        # crosses the link in wire parts both ways, dequantized only in-trace
        inner = make_pp_pipeline(apply_stage, n_stages, self.n_micro, mesh, axis)
        w, in_dt, mshape = self.wire, self._in_dt, \
            (self.n_micro,) + self.micro_shape

        def wired(W, *parts):
            x = w.decode_jax(parts, in_dt).reshape(mshape)
            return w.encode_jax(inner(W, x).reshape(-1))

        self._fn = jax.jit(wired)
        _check_stage_leading(stage_params, n_stages)
        self._W = jax.device_put(stage_params, NamedSharding(mesh, P(axis)))
        self._x_shard = NamedSharding(mesh, P())        # microbatches replicated
        self.depth = int(frames_in_flight)
        # H2D staging read-ahead beyond the in-flight budget (TpuKernel
        # contract, kernel_block.py): keeps the next frame's wire time riding
        # under the current frame's compute at steady state
        self.stage_ahead = 1 if self.depth > 1 else 0
        self._needs_staging = xfer.h2d_needs_staging(platform)
        # ring-exit staging copies ride the arena (ops/arena.py): a frame's
        # buffer is released after its pipeline dispatch consumed the parts
        from ..ops import arena as _arena_mod
        self._arena = _arena_mod.arena()
        self._staged: Deque = deque()           # (h2d_finish, valid, handle)
        self._inflight: Deque = deque()                 # (d2h_finish, valid)
        self._pending: Optional[np.ndarray] = None
        self.input = self.add_stream_input("in", in_dtype,
                                           min_items=self.frame_size)
        self.output = self.add_stream_output(
            "out", out_dtype, min_items=self.frame_size,
            min_buffer_size=(self.depth + 1) * self.frame_size
            * np.dtype(out_dtype).itemsize)

    def update_params(self, stage_params) -> None:
        """Swap the pipeline weights between frames (same pytree structure;
        frames already dispatched finish with the old weights)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        _check_stage_leading(stage_params, self.mesh.shape[self.axis])
        self._W = jax.device_put(stage_params,
                                 NamedSharding(self.mesh, P(self.axis)))

    def warmup(self) -> None:
        """Compile the pipeline outside any timed region by dispatching one
        zero frame through the REAL dispatch path (same shapes, same sharded
        placement — warming a hand-built input can compile a different
        executable). Raw device_put, not the staged transfer path: the fake
        link must not bill warmup bytes (TpuKernel.init contract)."""
        import jax
        parts = self.wire.encode_host(
            np.zeros(self.frame_size, dtype=self.input.dtype))
        dev = tuple(jax.device_put(np.asarray(p), self._x_shard)
                    for p in parts)
        y_parts = self._fn(self._W, *dev)
        jax.block_until_ready(y_parts)
        self.wire.decode_host(tuple(np.asarray(p) for p in y_parts),
                              self._out_dt)

    def _stage(self, frame: np.ndarray, valid: Optional[int] = None,
               handle=None) -> None:
        # wire-encoded parts are plain reals/ints (the pair layout of
        # ops/xfer.py by construction); the complex frame is formed in-trace
        # by the wired prolog
        t0 = _trace.now() if _trace.enabled else 0
        parts = self.wire.encode_host(frame)
        if t0:
            _trace.complete("tpu", "encode", t0,
                            args={"wire": self.wire.name, "items": len(frame)})
        h2d = xfer.start_device_transfer_parts(parts, self._x_shard)
        self._staged.append((h2d, self.frame_size if valid is None else valid,
                             handle))

    def _launch_staged(self) -> None:
        """Dispatch the pipeline on staged frames (oldest first) and start
        each result's D2H — H2D(t+1) ∥ pipeline(t) ∥ D2H(t−1), like TpuKernel."""
        while self._staged and len(self._inflight) < self.depth:
            h2d, valid, handle = self._staged.popleft()
            x_parts = h2d()
            t0 = _trace.now() if _trace.enabled else 0
            y_parts = self._fn(self._W, *x_parts)
            if t0:
                _trace.complete("tpu", "compute", t0,
                                args={"frame": self.frame_size})
            if handle is not None:
                # the staging copy is dead once nothing device-side still
                # reads it: accelerators — wait for the async PUT itself to
                # materialize (x_parts; the pipeline dispatch stays async);
                # CPU client — the borrow means the consuming computation
                # must materialize first (free: CPU jit is synchronous)
                import jax
                jax.block_until_ready(
                    y_parts if self._platform == "cpu" else x_parts)
                handle.release()
            self._inflight.append((xfer.start_host_transfer_parts(y_parts),
                                   valid))

    async def work(self, io, mio, meta):
        if self._pending is not None:
            out = self.output.slice()
            k = min(len(out), len(self._pending))
            out[:k] = self._pending[:k]
            self.output.produce(k)
            self._pending = self._pending[k:] if k < len(self._pending) else None
            if self._pending is not None:
                return
        inp = self.input.slice()
        # stage: start every allowed frame's H2D before dispatching any compute
        budget = self.depth + self.stage_ahead
        while len(self._staged) + len(self._inflight) < budget and \
                len(inp) >= self.frame_size:
            frame = np.asarray(inp[:self.frame_size])
            handle = None
            if self._needs_staging and self.wire.encode_may_alias(frame.dtype):
                # async H2D must leave the ring first (quantizing wires
                # materialize fresh arrays in encode_host)
                frame, handle = self._arena.copy_in(frame)
            self._stage(frame, handle=handle)
            self.input.consume(self.frame_size)
            inp = self.input.slice()
        eos = self.input.finished()
        if eos and 0 < len(inp) < self.frame_size and \
                len(self._staged) + len(self._inflight) < budget:
            # final partial frame: zero-pad and emit only the valid prefix —
            # the TpuKernel tail contract (`kernel_block.py:155-165`); the
            # siblings previously disagreed (round-4 advisory: PpKernel
            # silently dropped up to frame_size-1 items at EOS)
            frame = np.zeros(self.frame_size, dtype=self.input.dtype)
            frame[:len(inp)] = inp
            self._stage(frame, valid=len(inp))
            self.input.consume(len(inp))
            inp = self.input.slice()
        self._launch_staged()
        if self._inflight and (len(self._inflight) >= self.depth or eos
                               or len(inp) < self.frame_size):
            finish, valid = self._inflight.popleft()
            raw = finish()
            t0 = _trace.now() if _trace.enabled else 0
            result = self.wire.decode_host(raw, self._out_dt
                                           ).reshape(-1)[:valid]
            if t0:
                _trace.complete("tpu", "decode", t0,
                                args={"wire": self.wire.name, "items": valid})
            out = self.output.slice()
            k = min(len(out), len(result))
            out[:k] = result[:k]
            self.output.produce(k)
            if k < len(result):
                self._pending = result[k:].copy()
            io.call_again = True
            return
        if eos and not self._inflight and not self._staged \
                and self._pending is None and not self.input.available():
            io.finished = True
