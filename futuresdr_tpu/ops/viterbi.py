"""Viterbi add-compare-select as a jitted lax.scan — the TPU/XLA decode path.

The reference decodes Viterbi in a scalar Rust loop (``examples/wlan/src/
viterbi_decoder.rs``); here the per-step ACS is vectorized over all trellis states and the
time recursion is a ``lax.scan``, jit-compiled once per (n_states, bucket-length) and
reused — frame lengths are padded up to power-of-two buckets. Traceback stays on host
(cheap, sequential).
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np

__all__ = ["scan_viterbi", "scan_viterbi_batch"]


@lru_cache(maxsize=None)
def _compiled(n_states: int, bucket: int, tables_key):
    import jax
    import jax.numpy as jnp

    prev_s, prev_b, bm0, bm1 = [np.asarray(t) for t in tables_key_store[tables_key]]
    ps = jnp.asarray(prev_s)
    b0 = jnp.asarray(bm0)
    b1 = jnp.asarray(bm1)

    def step(metrics, lam):
        cand = metrics[ps] + b0 * lam[0] + b1 * lam[1]       # [S, 2]
        pick = jnp.argmax(cand, axis=1)
        new = jnp.take_along_axis(cand, pick[:, None], axis=1)[:, 0]
        return new, pick.astype(jnp.uint8)

    @jax.jit
    def run(lams):                                            # [bucket, 2]
        init = jnp.full((n_states,), -1e18).at[0].set(0.0)
        _, picks = jax.lax.scan(step, init, lams)
        return picks                                          # [bucket, S]

    return run


tables_key_store: dict = {}


@lru_cache(maxsize=None)
def _compiled_batch(n_states: int, bucket: int, batch: int, tables_key):
    import jax
    import jax.numpy as jnp

    prev_s, prev_b, bm0, bm1 = [np.asarray(t) for t in tables_key_store[tables_key]]
    ps = jnp.asarray(prev_s)
    b0 = jnp.asarray(bm0)
    b1 = jnp.asarray(bm1)

    def step(metrics, lam):                                   # metrics [B, S]
        cand = metrics[:, ps] + b0[None] * lam[:, None, None, 0] \
            + b1[None] * lam[:, None, None, 1]                # [B, S, 2]
        pick = jnp.argmax(cand, axis=2)
        new = jnp.take_along_axis(cand, pick[..., None], axis=2)[..., 0]
        return new, pick.astype(jnp.uint8)

    @jax.jit
    def run(lams):                                            # [B, bucket, 2]
        init = jnp.full((batch, n_states), -1e18).at[:, 0].set(0.0)
        _, picks = jax.lax.scan(step, init, jnp.swapaxes(lams, 0, 1))
        return picks                                          # [bucket, B, S]

    return run


def scan_viterbi_batch(llrs_list, n_bits_list, prev_s, prev_b, bm0, bm1):
    """Decode a batch of frames in one scan: the TPU-idiomatic burst decoder.

    ``llrs_list``: per-frame soft arrays (2 per step); returns list of bit arrays.
    Frames are padded to a common power-of-two step bucket and the batch to a power of
    two, so distinct shapes stay few and jit-cached.
    """
    n_states = prev_s.shape[0]
    steps = [min(len(l) // 2, n) for l, n in zip(llrs_list, n_bits_list)]
    max_steps = max(steps)
    bucket = max(8, 1 << int(np.ceil(np.log2(max_steps))))
    b_real = len(llrs_list)
    batch = max(1, 1 << int(np.ceil(np.log2(b_real))))
    lams = np.zeros((batch, bucket, 2), dtype=np.float32)
    for i, (l, t) in enumerate(zip(llrs_list, steps)):
        lams[i, :t] = np.asarray(l[:2 * t], np.float32).reshape(t, 2)
    key = (n_states, prev_s.tobytes(), prev_b.tobytes(), bm0.tobytes(), bm1.tobytes())
    hkey = hash(key)
    tables_key_store.setdefault(hkey, (prev_s, prev_b, bm0, bm1))
    run = _compiled_batch(n_states, bucket, batch, hkey)
    picks = np.asarray(run(lams))                             # [bucket, B, S]
    # vectorized traceback over the whole batch: one loop over time, [B] states;
    # frames shorter than the bucket stay parked at state 0 until their own end
    steps_arr = np.asarray(steps + [0] * (batch - b_real))
    states = np.zeros(batch, dtype=np.int64)
    bits_all = np.zeros((bucket, batch), dtype=np.uint8)
    rows = np.arange(batch)
    for tt in range(bucket - 1, -1, -1):
        active = tt < steps_arr
        b = picks[tt, rows, states]
        bits_all[tt, active] = prev_b[states, b][active]
        states = np.where(active, prev_s[states, b], states)
    return [bits_all[:steps[i], i][:n_bits_list[i]] for i in range(b_real)]


def scan_viterbi(llrs: np.ndarray, n_bits: int, prev_s: np.ndarray, prev_b: np.ndarray,
                 bm0: np.ndarray, bm1: np.ndarray) -> np.ndarray:
    """Decode ``n_bits`` from soft ``llrs`` (2 per step) given trellis tables.

    ``prev_s/prev_b``: [S, 2] predecessor state/input per next-state; ``bm0/bm1``: the
    corresponding branch output bits in ±1. Terminated trellis (traceback from state 0).
    """
    n_states = prev_s.shape[0]
    n_steps = min(len(llrs) // 2, n_bits)
    lam = np.zeros((max(8, 1 << int(np.ceil(np.log2(max(n_steps, 1))))), 2),
                   dtype=np.float32)
    lam[:n_steps] = llrs[:2 * n_steps].reshape(n_steps, 2)
    key = (n_states, prev_s.tobytes(), prev_b.tobytes(), bm0.tobytes(), bm1.tobytes())
    hkey = hash(key)
    tables_key_store.setdefault(hkey, (prev_s, prev_b, bm0, bm1))
    run = _compiled(n_states, lam.shape[0], hkey)
    picks = np.asarray(run(lam))                              # [bucket, S]
    # traceback over the real steps only (padding never enters)
    state = 0
    out = np.empty(n_steps, dtype=np.uint8)
    for t in range(n_steps - 1, -1, -1):
        b = picks[t, state]
        out[t] = prev_b[state, b]
        state = prev_s[state, b]
    return out[:n_bits]
