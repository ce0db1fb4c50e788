"""Pipeline parallelism over a device mesh axis — GPipe-style microbatching.

The frame plane (``tpu/frames.py``) pipelines *whole flowgraph stages* across
time on one chip; this module pipelines a *single model* across CHIPS: each
device on the ``pp`` axis owns one stage's weights, activations hop stage→stage
over ICI with ``ppermute``, and microbatches stream through so all stages work
concurrently after the fill phase (the standard bubble of (S-1)/(S-1+M)).

Everything is a single jitted ``shard_map``: the schedule is a ``lax.scan`` over
``n_micro + n_stages - 1`` static steps, so XLA sees one compiled program with
collective permutes — no host round-trips between pipeline ticks.

Reference role: SURVEY §2.7 "pipeline parallel". The reference pipelines blocks
over CPU threads; the TPU-native form pipelines over the mesh.
"""

from __future__ import annotations

from typing import Callable

__all__ = ["make_pp_pipeline"]


def make_pp_pipeline(apply_stage: Callable, n_stages: int, n_micro: int,
                     mesh, axis: str = "pp"):
    """Build ``fn(stage_params, micro_x) -> micro_y`` running a ``n_stages``-deep
    pipeline over ``mesh[axis]``.

    - ``apply_stage(params_one_stage, x) -> y``: one stage's computation; input
      and output must share shape/dtype (activations ride one ppermute channel).
    - ``stage_params``: any pytree whose leaves have a leading ``n_stages`` axis
      — sharded one-stage-per-device along ``axis``.
    - ``micro_x``: ``[n_micro, ...]`` microbatches (replicated); returns
      ``[n_micro, ...]`` outputs of the final stage (replicated).
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    assert mesh.shape[axis] == n_stages, \
        f"mesh axis {axis} has {mesh.shape[axis]} devices, need {n_stages}"
    n_steps = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(my_params, micro_x):
        # my_params leaves arrive as [1, ...] — this device's stage
        my_params = jax.tree_util.tree_map(lambda a: a[0], my_params)
        s = jax.lax.axis_index(axis)
        zero = jnp.zeros_like(micro_x[0])

        def step(carry, t):
            recv, outs = carry
            m_in = jnp.clip(t, 0, n_micro - 1)
            feed = jnp.where(t < n_micro, micro_x[m_in], zero)
            xin = jnp.where(s == 0, feed, recv)
            y = apply_stage(my_params, xin)
            recv_next = jax.lax.ppermute(y, axis, perm)
            # the LAST stage's step-t output is microbatch t-(n_stages-1); a
            # single dynamic-index add (fill/drain steps and non-final stages
            # contribute zeros at the clamped row)
            m_out = t - (n_stages - 1)
            outs = outs.at[jnp.clip(m_out, 0, n_micro - 1)].add(
                jnp.where((m_out >= 0) & (s == n_stages - 1), y, zero))
            return (recv_next, outs), None

        outs0 = jnp.zeros((n_micro,) + micro_x.shape[1:], micro_x.dtype)
        (_, outs), _ = jax.lax.scan(step, (zero, outs0),
                                    jnp.arange(n_steps))
        # only the last stage holds real outputs; psum replicates them to all
        return jax.lax.psum(outs, axis)

    return shard_map(body, mesh=mesh, in_specs=(P(axis), P()),
                     out_specs=P(), check_vma=False)
