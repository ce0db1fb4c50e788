"""Viterbi decoding as ONE traceable core: add-compare-select over many
packets at once, per-packet lengths, traceback on the device.

The reference decodes Viterbi in a scalar Rust loop (``examples/wlan/src/
viterbi_decoder.rs``). Here the trellis states lie on the major axis and the
packets on the minor one (``[S, L]``: on a TPU the packets fill the vector
lanes), the time recursion is a ``lax.fori_loop`` whose trip count follows the
longest live packet, the survivor decisions stay on the device and the
traceback is a second loop over them. :func:`viterbi_core` is that recursion
as XLA ops, whatever the code's tables: the fused receiver's SIGNAL field
(``models/wlan/rx_stages.py``) and the host-callable :func:`scan_viterbi` and
:func:`scan_viterbi_batch` (jit-compiled once per tables, step bucket and
batch) run it. :func:`viterbi_blocks` is the same recursion, term for term,
with time cut into overlapping pieces and the pieces of all packets decoded
side by side in two Pallas kernels (:func:`_decode_pieces`): what the
receiver's data trellis runs. A test holds the two bit-equal.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np

__all__ = ["viterbi_core", "viterbi_blocks", "piece_slots", "scan_viterbi",
           "scan_viterbi_batch"]

#: metric of a state no path has reached yet (float32 absorbs any sum of
#: branch metrics into it, as the float64 reference's -1e18 does)
_UNREACHED = -1e18
#: trellis steps a loop trip makes (v5e, ACS + traceback of 12 294 steps on
#: 64 lanes: 26.1 to 26.9 ms at 1, 14.0 to 14.4 at 8, 13.3 to 14.2 at 4, 16
#: and 32); a trellis of up to 32 steps (the SIGNAL field's 24) is one trip
UNROLL = 8
#: :func:`viterbi_blocks`: steps kept of a piece, steps of run-in and run-out
#: on either side, most pieces a pass (eight rows of 128: the kernel's tiles
#: are whole vector registers). ``perf/wlan.py --viterbi-core`` sweeps the
#: first and the last by patching them
BLOCK, OVERLAP, CHUNK = 1024, 128, 1024


def piece_slots(n: int) -> int:
    """The ``n_blocks`` of :func:`viterbi_blocks` that holds ``n`` pieces: a
    multiple of 128 up to ``CHUNK`` (one pass), beyond it of ``CHUNK``."""
    unit = 128 if n <= CHUNK else CHUNK
    return -(-max(n, 1) // unit) * unit


def viterbi_core(llr, n_steps, prev_s, prev_b, bm0, bm1):
    """Decode ``L`` terminated packets in one pass. Traceable; static shapes.

    ``llr``: ``[T, 2, L]`` float32 soft values of the mother code, positive ⇒
    bit 1, two per trellis step; ``n_steps``: ``[L]`` int32, the steps of each
    packet (0 = unused lane; values beyond ``T`` are cut to ``T``). The tables
    are host arrays, constants of the trace: ``prev_s/prev_b`` ``[S, 2]``
    predecessor state and input bit per next-state, ``bm0/bm1`` the two branch
    outputs in ±1. Returns ``[T, L]`` uint8 decoded bits, 0 beyond a packet's
    own steps. Each packet is traced back from state 0 at ITS last step.

    The loops run ``ceil(max(n_steps) / UNROLL)`` times, ``UNROLL`` steps a
    trip: a batch of short packets does not pay for ``T``. A packet's metrics
    keep running past its own end; nothing reads them there. Metrics are
    re-based on state 0 once a trip, so float32 keeps its resolution over
    tens of thousands of steps.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    prev_s = np.asarray(prev_s)
    S = prev_s.shape[0]
    T, _, L = llr.shape
    U = T if T <= 32 else UNROLL
    Tp = -(-T // U) * U
    if Tp != T:
        llr = jnp.pad(llr, ((0, Tp - T), (0, 0), (0, 0)))
    n_steps = jnp.minimum(n_steps.astype(jnp.int32), T)
    trips = (jnp.max(n_steps) + U - 1) // U

    ps = [jnp.asarray(prev_s[:, j], jnp.int32) for j in (0, 1)]
    b0 = [jnp.asarray(np.asarray(bm0)[:, j, None], jnp.float32) for j in (0, 1)]
    b1 = [jnp.asarray(np.asarray(bm1)[:, j, None], jnp.float32) for j in (0, 1)]
    # what the traceback reads per (state, decision): predecessor and bit
    code = [jnp.asarray((prev_s[:, j] * 2 + np.asarray(prev_b)[:, j])[:, None],
                        jnp.int32) for j in (0, 1)]
    half = np.arange(S) % (S // 2)
    butterfly = all(np.array_equal(prev_s[:, j], 2 * half + j) for j in (0, 1))

    def predecessors(m, j):
        if butterfly:       # shift-register codes: rows 2i+j, read twice
            h = m.reshape(S // 2, 2, L)[:, j]
            return jnp.concatenate([h, h], axis=0)
        return jnp.take(m, ps[j], axis=0)

    def forward(i, state):
        m, dec = state
        blk = lax.dynamic_slice(llr, (i * U, 0, 0), (U, 2, L))
        picks = []
        for u in range(U):
            l0, l1 = blk[u, 0][None, :], blk[u, 1][None, :]
            ca = predecessors(m, 0) + b0[0] * l0 + b1[0] * l1
            cb = predecessors(m, 1) + b0[1] * l0 + b1[1] * l1
            picks.append(cb > ca)          # a tie keeps candidate 0 (argmax)
            m = jnp.maximum(ca, cb)
        m = m - m[0:1]
        dec = lax.dynamic_update_slice(
            dec, jnp.stack(picks).astype(jnp.uint8), (i * U, 0, 0))
        return m, dec

    with jax.named_scope("viterbi_acs"):
        m0 = jnp.full((S, L), _UNREACHED, jnp.float32).at[0].set(0.0)
        _, dec = lax.fori_loop(0, trips, forward,
                               (m0, jnp.zeros((Tp, S, L), jnp.uint8)))

    rows = jnp.arange(S, dtype=jnp.int32)[:, None]

    def backward(k, state):
        at, bits = state
        i = trips - 1 - k
        blk = lax.dynamic_slice(dec, (i * U, 0, 0), (U, S, L))
        out = [None] * U
        for u in reversed(range(U)):
            live = (i * U + u) < n_steps
            hit = jnp.where(blk[u] != 0, code[1], code[0])
            r = jnp.sum(jnp.where(rows == at[None, :], hit, 0), axis=0)
            at = jnp.where(live, r >> 1, at)
            out[u] = jnp.where(live, r & 1, 0)
        bits = lax.dynamic_update_slice(
            bits, jnp.stack(out).astype(jnp.uint8), (i * U, 0))
        return at, bits

    with jax.named_scope("traceback"):
        _, bits = lax.fori_loop(0, trips, backward,
                                (jnp.zeros((L,), jnp.int32),
                                 jnp.zeros((Tp, L), jnp.uint8)))
    return bits[:T]


def _interpret() -> bool:
    """Mosaic on a TPU backend, the interpreter elsewhere (the convention of
    ``ops/pallas_kernels.py``; the tool that compiles for a described chip
    replaces this)."""
    import jax
    return jax.default_backend() != "tpu"


def _decode_pieces(llr, prev_s, prev_b, bm0, bm1):
    """One pass of :func:`viterbi_blocks`: ``S·128`` open-ended pieces of
    ``Bt`` steps each as two Pallas kernels, add-compare-select and traceback.

    ``llr``: ``[2, Bt, S, 128]`` float32 (the two soft values of a step; a
    piece is one element of the last two axes). Returns ``[Bt, S, 128]`` int32
    decoded bits. The 64 states are the kernel's UNROLLED axis, a state's
    metrics over the pieces are one ``[S, 128]`` tile (a whole vector
    register at ``S`` = 8), so the butterfly is plain loads and stores of
    tiles and every operation is elementwise: no gather, no shuffle, no
    reduction. The survivor decisions of a step are packed into two int32
    tiles (bit ``n % 32`` of word ``n // 32``) and go to HBM a block of steps
    at a time; the traceback reads them back in reverse. The arithmetic is
    that of :func:`viterbi_core` term for term (metrics re-based on state 0
    every ``UNROLL`` steps, a tie keeps candidate 0, the first best state
    starts the traceback), so the two agree bit for bit.

    As XLA ops (a ``fori_loop`` of five small fusions a step on ``[64, 128]``
    operands) a pass of 128 pieces took 2.05 ms on the v5e and left 10 000
    events in a device trace; this form decodes 1024 pieces in under 1.6 ms,
    the gathers around it included, and leaves two (``PERF.md`` section 6).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    prev_s, prev_b = np.asarray(prev_s), np.asarray(prev_b)
    bm0, bm1 = np.asarray(bm0), np.asarray(bm1)
    S_ = prev_s.shape[0]
    half = S_ // 2
    n = np.arange(S_)
    assert S_ == 64 and all(
        np.array_equal(prev_s[:, j], 2 * (n % half) + j)
        and np.array_equal(prev_b[:, j], n // half) for j in (0, 1)) \
        and set(np.unique(np.concatenate([bm0, bm1]))) <= {-1.0, 1.0}, \
        "the kernel is written for a 64-state shift-register code with +-1 outputs"
    _, Bt, S, lanes = llr.shape
    TB = 128                                         # steps a grid step holds
    assert lanes == 128 and Bt % TB == 0 and TB % UNROLL == 0 and UNROLL % 2 == 0
    nT = Bt // TB
    bit = [np.int32(1 << i) if i < 31 else np.int32(-2 ** 31) for i in range(32)]

    def acs(llr_ref, dec_ref, at_ref, m_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            m_ref[0] = jnp.zeros((S_, S, lanes), jnp.float32)

        def two_steps(i, _):
            for src in (0, 1):                       # 0 -> 1 -> 0
                t = 2 * i + src
                l0, l1 = llr_ref[0, t], llr_ref[1, t]
                soft0, soft1 = {1.0: l0, -1.0: -l0}, {1.0: l1, -1.0: -l1}
                words = [jnp.zeros((S, lanes), jnp.int32)] * 2
                for k in range(half):
                    e, o = m_ref[src, 2 * k], m_ref[src, 2 * k + 1]
                    for hi in (0, 1):
                        s = k + half * hi
                        ca = e + soft0[bm0[s, 0]] + soft1[bm1[s, 0]]
                        cb = o + soft0[bm0[s, 1]] + soft1[bm1[s, 1]]
                        m_ref[1 - src, s] = jnp.maximum(ca, cb)
                        words[hi] = words[hi] | jnp.where(cb > ca, bit[k], 0)
                dec_ref[t, 0] = words[0]
                dec_ref[t, 1] = words[1]

            @pl.when(i % (UNROLL // 2) == UNROLL // 2 - 1)
            def _():
                base = m_ref[0, 0]
                for s in range(S_):
                    m_ref[0, s] = m_ref[0, s] - base
            return 0

        lax.fori_loop(0, TB // 2, two_steps, 0)

        @pl.when(pl.program_id(0) == nT - 1)
        def _():
            best, arg = m_ref[0, 0], jnp.zeros((S, lanes), jnp.int32)
            for s in range(1, S_):
                better = m_ref[0, s] > best
                arg = jnp.where(better, s, arg)
                best = jnp.where(better, m_ref[0, s], best)
            at_ref[...] = arg

    def traceback(dec_ref, at0_ref, bits_ref, at_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            at_ref[...] = at0_ref[...]

        def step(k, at):
            t = TB - 1 - k
            word = jnp.where(at < half, dec_ref[t, 0], dec_ref[t, 1])
            pick = lax.shift_right_logical(word, at & (half - 1)) & 1
            bits_ref[t] = at // half
            return ((at & (half - 1)) << 1) | pick

        at_ref[...] = lax.fori_loop(0, TB, step, at_ref[...])

    params = dict(
        grid=(nT,), interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)))
    with jax.named_scope("viterbi_acs"):
        dec, at0 = pl.pallas_call(
            acs, in_specs=[pl.BlockSpec((2, TB, S, lanes), lambda g: (0, g, 0, 0))],
            out_specs=[pl.BlockSpec((TB, 2, S, lanes), lambda g: (g, 0, 0, 0)),
                       pl.BlockSpec((S, lanes), lambda g: (0, 0))],
            out_shape=[jax.ShapeDtypeStruct((Bt, 2, S, lanes), jnp.int32),
                       jax.ShapeDtypeStruct((S, lanes), jnp.int32)],
            scratch_shapes=[pltpu.VMEM((2, S_, S, lanes), jnp.float32)],
            **params)(llr)
    with jax.named_scope("traceback"):
        return pl.pallas_call(
            traceback,
            in_specs=[pl.BlockSpec((TB, 2, S, lanes), lambda g: (nT - 1 - g, 0, 0, 0)),
                      pl.BlockSpec((S, lanes), lambda g: (0, 0))],
            out_specs=pl.BlockSpec((TB, S, lanes), lambda g: (nT - 1 - g, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((Bt, S, lanes), jnp.int32),
            scratch_shapes=[pltpu.VMEM((S, lanes), jnp.int32)],
            **params)(dec, at0)


#: LLR that stands for a coded bit KNOWN to be 0 (before a packet's first step
#: the encoder holds zeros; after its tail it is taken to go on with zeros):
#: ten such bits, the code's free distance, outweigh any run of real LLRs
_KNOWN_ZERO = -32.0


def viterbi_blocks(stream, n_steps, prev_s, prev_b, bm0, bm1, n_blocks: int):
    """:func:`viterbi_core` with time cut into overlapping blocks, so that a
    frame costs a block's steps in series and not its longest packet's.

    ``stream``: ``[2, L, T]`` float32 mother-code LLRs (the two outputs of a
    step as planes), one packet a row, from its first step; ``n_steps`` ``[L]``. Packet ``l`` becomes
    ``ceil(n_steps[l] / BLOCK)`` pieces of ``OVERLAP + BLOCK + OVERLAP`` steps,
    piece ``k`` starting ``OVERLAP`` steps before step ``k·BLOCK``; the pieces
    of all packets, in order, are decoded ``CHUNK`` a pass
    (:func:`_decode_pieces`: every state starts at metric 0, each piece is
    traced back from its best state), as many passes as the live pieces fill
    (``n_blocks`` piece slots, static, from :func:`piece_slots`: the caller
    bounds the sum; the time follows the pieces there are, not the slots),
    and of each piece only the middle ``BLOCK`` decisions are kept. The overlap is warm-up on
    one side and traceback depth on the other: 128 steps, 18 constraint
    lengths, against the 5 to 10 a truncated decoder is built with. Outside a packet the LLRs
    say "coded zeros" (``_KNOWN_ZERO``), which ties the first piece to the
    encoder's zero state and the last to the terminated tail exactly as the
    uncut decoder is tied. ``tests/test_wlan_rx_stages.py`` holds it bit-equal
    to the uncut core on the benchmark cell's mix. Returns ``[L, T]`` uint8, 0
    beyond a packet's own steps.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    _, L, T = stream.shape
    B, V, C = BLOCK, OVERLAP, min(CHUNK, n_blocks)
    Bt = V + B + V
    assert n_blocks == piece_slots(n_blocks), "n_blocks: see piece_slots"
    K = -(-T // B)                                   # pieces of one packet
    n_steps = jnp.minimum(n_steps.astype(jnp.int32), T)
    inside = jnp.arange(T, dtype=jnp.int32)[None, :] < n_steps[:, None]
    ext = jnp.concatenate(
        [jnp.full((2, L, V), _KNOWN_ZERO, jnp.float32),
         jnp.where(inside[None], stream, _KNOWN_ZERO),
         jnp.full((2, L, K * B + V - T), _KNOWN_ZERO, jnp.float32)], axis=2)
    pieces_to = jnp.cumsum((n_steps + B - 1) // B)                  # [L]
    b = jnp.arange(n_blocks, dtype=jnp.int32)
    b_lane = jnp.minimum(jnp.searchsorted(pieces_to, b, side="right",
                                          method="compare_all"), L - 1)
    live = b < pieces_to[-1]
    first = jnp.take(pieces_to, b_lane) - jnp.take((n_steps + B - 1) // B, b_lane)
    # a piece starts on a multiple of B steps and holds Bt: whole rows of 128,
    # gathered (a batch of dynamic_slice is a loop, one trip a piece)
    R = ext.shape[2] // 128
    assert B % 128 == 0 and Bt % 128 == 0 and ext.shape[2] % 128 == 0
    row = b_lane.astype(jnp.int32) * R + jnp.where(live, B * (b - first), 0) // 128
    llr = jnp.take(
        ext.reshape(-1, 128),
        (jnp.arange(2, dtype=jnp.int32) * (L * R))[:, None, None] + row[None, :, None]
        + jnp.arange(Bt // 128, dtype=jnp.int32)[None, None, :], axis=0)
    llr = jnp.transpose(llr.reshape(2, n_blocks // C, C, Bt), (1, 0, 3, 2)) \
        .reshape(n_blocks // C, 2, Bt, C // 128, 128)

    def one_pass(c, out):
        bits = _decode_pieces(lax.dynamic_index_in_dim(llr, c, keepdims=False),
                              prev_s, prev_b, bm0, bm1)[V:V + B]
        return lax.dynamic_update_slice(
            out, bits.reshape(B, C).T.astype(jnp.uint8)[None], (c, 0, 0))

    rows = lax.fori_loop(0, (pieces_to[-1] + C - 1) // C, one_pass,
                         jnp.zeros((n_blocks // C, C, B), jnp.uint8))
    rows = jnp.concatenate([rows.reshape(n_blocks, B),
                            jnp.zeros((K, B), jnp.uint8)])          # no clamping
    mine = jax.vmap(lambda r: lax.dynamic_slice(rows, (r, 0), (K, B)))(
        pieces_to - (n_steps + B - 1) // B)                         # [L, K, B]
    return jnp.where(inside, mine.reshape(L, K * B)[:, :T], 0)


_TABLES: dict = {}


@lru_cache(maxsize=None)
def _compiled(bucket: int, batch: int, tables_key):
    import jax
    import jax.numpy as jnp

    tables = _TABLES[tables_key]

    @jax.jit
    def run(lams, steps):                                     # [B, bucket, 2]
        return viterbi_core(jnp.transpose(lams, (1, 2, 0)), steps, *tables).T

    return run


def scan_viterbi_batch(llrs_list, n_bits_list, prev_s, prev_b, bm0, bm1):
    """Decode a batch of frames in one call of the core.

    ``llrs_list``: per-frame soft arrays (2 per step); returns list of bit arrays.
    Frames are padded to a common power-of-two step bucket and the batch to a power of
    two, so distinct shapes stay few and jit-cached.
    """
    steps = [min(len(l) // 2, n) for l, n in zip(llrs_list, n_bits_list)]
    bucket = max(8, 1 << int(np.ceil(np.log2(max(max(steps), 1)))))
    b_real = len(llrs_list)
    batch = max(1, 1 << int(np.ceil(np.log2(b_real))))
    lams = np.zeros((batch, bucket, 2), dtype=np.float32)
    for i, (l, t) in enumerate(zip(llrs_list, steps)):
        lams[i, :t] = np.asarray(l[:2 * t], np.float32).reshape(t, 2)
    key = hash((prev_s.tobytes(), prev_b.tobytes(), bm0.tobytes(), bm1.tobytes()))
    _TABLES.setdefault(key, (prev_s, prev_b, bm0, bm1))
    bits = np.asarray(_compiled(bucket, batch, key)(
        lams, np.asarray(steps + [0] * (batch - b_real), np.int32)))
    return [bits[i, :steps[i]][:n_bits_list[i]] for i in range(b_real)]


def scan_viterbi(llrs: np.ndarray, n_bits: int, prev_s: np.ndarray, prev_b: np.ndarray,
                 bm0: np.ndarray, bm1: np.ndarray) -> np.ndarray:
    """Decode ``n_bits`` from soft ``llrs`` (2 per step) given trellis tables.

    ``prev_s/prev_b``: [S, 2] predecessor state/input per next-state; ``bm0/bm1``: the
    corresponding branch output bits in ±1. Terminated trellis (traceback from state 0).
    """
    return scan_viterbi_batch([llrs], [n_bits], prev_s, prev_b, bm0, bm1)[0]
