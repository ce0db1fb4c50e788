"""Sequence parallelism for sample streams: shard the time axis over the mesh with
halo exchange.

This is the SDR analog of ring attention / context parallelism (SURVEY §2.7 row
"Sequence parallelism"): a long frame is split into contiguous time shards, one per
device; streaming operators that need history (FIR overlap, `fir.rs:49` ``min_items``)
get their left halo from the previous device via a single ``ppermute`` over ICI, then
compute purely locally. One collective per frame, O(taps) bytes — the collective rides
ICI, not HBM.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["sp_fir", "sp_fir_fft_mag2", "sp_fir_stream", "sp_fir_fft_mag2_stream",
           "sp_channelizer", "sp_channelizer_a2a", "sp_dechirp_scan"]


def _axis_size(axis_name: str) -> int:
    """Static mapped-axis size. ``jax.lax.axis_size`` where it exists (jax ≥
    0.4.38-ish); older jax exposes the same trace-time axis env through
    ``jax.core.axis_frame``."""
    try:
        return jax.lax.axis_size(axis_name)
    except AttributeError:             # pragma: no cover - version-dependent
        return jax.core.axis_frame(axis_name)


def _halo_from_left(local: jnp.ndarray, halo: int, axis_name: str,
                    carry: jnp.ndarray = None) -> jnp.ndarray:
    """Prepend the previous shard's tail — the halo exchange.

    Shard 0's left context is ``carry`` (the previous FRAME's global tail) when given,
    zeros otherwise; so the stateful variants make sharded streaming bit-match a
    single-device streaming stage across frame boundaries (the cross-frame carry the
    reference keeps implicitly in its ring buffers, `fir.rs:49` min_items)."""
    if halo <= 0:
        return local                    # 1-tap FIR: no history needed
    n = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    tail = local[-halo:]
    perm = [(i, (i + 1) % n) for i in range(n)]
    left_tail = jax.lax.ppermute(tail, axis_name, perm)  # shard i gets shard i-1's tail
    fill = jnp.zeros_like(left_tail) if carry is None else carry.astype(local.dtype)
    left_tail = jnp.where(idx == 0, fill, left_tail)
    return jnp.concatenate([left_tail, local])


def _conv_valid(ext: jnp.ndarray, tj: jnp.ndarray) -> jnp.ndarray:
    """Valid-mode FIR of the halo-extended shard (complex as two real passes)."""
    if jnp.iscomplexobj(ext):
        re = jnp.convolve(ext.real, tj, mode="valid", precision="highest")
        im = jnp.convolve(ext.imag, tj, mode="valid", precision="highest")
        return re + 1j * im
    return jnp.convolve(ext, tj, mode="valid", precision="highest")


def sp_fir(taps: np.ndarray, mesh: Mesh, axis: str = "sp") -> Callable:
    """Time-sharded FIR: input [n] sharded over ``axis``; output identically sharded.

    y = conv_valid(halo ++ local) per shard == the global FIR, exactly.
    Requires local shard length ≥ len(taps)-1 (the halo must fit in one neighbour).
    """
    nt = len(taps)
    tj = jnp.asarray(np.asarray(taps))

    def local_fir(x_local):
        ext = _halo_from_left(x_local, nt - 1, axis)
        return _conv_valid(ext, tj).astype(x_local.dtype)

    return shard_map(local_fir, mesh=mesh, in_specs=P(axis), out_specs=P(axis))


def sp_fir_fft_mag2(taps: np.ndarray, fft_size: int, mesh: Mesh,
                    axis: str = "sp") -> Callable:
    """The fused north-star chain, time-sharded: FIR (halo exchange) → per-shard batched
    FFT → |x|². Local shard length must be a multiple of ``fft_size``."""
    nt = len(taps)
    tj = jnp.asarray(np.asarray(taps, dtype=np.float32))

    def local(x_local):
        ext = _halo_from_left(x_local, nt - 1, axis)
        y = _conv_valid(ext, tj)
        spec = jnp.fft.fft(y.reshape(-1, fft_size), axis=1)
        return (spec.real**2 + spec.imag**2).astype(jnp.float32).reshape(-1)

    return shard_map(local, mesh=mesh, in_specs=P(axis), out_specs=P(axis))


def _make_stream(local: Callable, nt: int, mesh: Mesh, axis: str):
    """Wrap a carry-taking local kernel into ``fn(carry, x) -> (carry, y)`` +
    ``init_carry``: the carry is the previous frame's global tail (``nt-1`` samples,
    replicated), consumed by shard 0 as left context. jit ``fn`` with
    ``donate_argnums=(0,)`` to chain carries on-device."""
    inner = shard_map(local, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis))
    n_dev = mesh.shape[axis]

    def fn(carry, x):
        if x.shape[0] // n_dev < nt - 1:     # trace-time: clear error, not a deep
            raise ValueError(                # shard_map broadcast failure
                f"per-shard length {x.shape[0] // n_dev} < halo {nt - 1}: "
                f"grow the frame or reduce taps/devices")
        # fn is jitted by its consumers (SpKernel), so this body only runs at
        # TRACE time — mark each (re)trace in the span stream: silent retraces
        # (shape drift, carry dtype churn) are the classic sharded-pipeline
        # stall and otherwise invisible from the host
        from ..telemetry.spans import recorder
        rec = recorder()
        if rec.enabled and isinstance(x, jax.core.Tracer):
            rec.instant("jit", "sp_trace",
                        args={"frame": int(x.shape[0]),
                              "devices": int(n_dev), "halo": int(nt - 1)})
        y = inner(x, carry)
        # new carry: global frame tail (x[-0:] would be the WHOLE frame at nt=1)
        return x[x.shape[0] - (nt - 1):], y

    def init_carry(dtype):
        from jax.sharding import NamedSharding

        from ..ops.xfer import to_device
        return to_device(np.zeros(nt - 1, dtype=np.dtype(dtype)),
                         NamedSharding(mesh, P()))

    return fn, init_carry


def sp_fir_stream(taps: np.ndarray, mesh: Mesh, axis: str = "sp"):
    """Cross-frame-stateful time-sharded FIR: ``fn(carry, x) -> (carry, y)``.

    Streaming N frames through the sharded fn bit-matches the single-device streaming
    ``fir_stage`` (see :func:`_make_stream` for the carry contract)."""
    nt = len(taps)
    tj = jnp.asarray(np.asarray(taps))

    def local_fir(x_local, carry):
        ext = _halo_from_left(x_local, nt - 1, axis, carry)
        return _conv_valid(ext, tj).astype(x_local.dtype)

    return _make_stream(local_fir, nt, mesh, axis)


def sp_fir_fft_mag2_stream(taps: np.ndarray, fft_size: int, mesh: Mesh,
                           axis: str = "sp"):
    """Cross-frame-stateful fused north-star chain (see :func:`sp_fir_stream`):
    FIR with frame-carry halo → per-shard batched FFT → |x|²."""
    nt = len(taps)
    tj = jnp.asarray(np.asarray(taps, dtype=np.float32))

    def local(x_local, carry):
        ext = _halo_from_left(x_local, nt - 1, axis, carry)
        y = _conv_valid(ext, tj)
        spec = jnp.fft.fft(y.reshape(-1, fft_size), axis=1)
        return (spec.real**2 + spec.imag**2).astype(jnp.float32).reshape(-1)

    return _make_stream(local, nt, mesh, axis)


def sp_channelizer(n_channels: int, taps: np.ndarray, mesh: Mesh,
                   axis: str = "sp") -> Callable:
    """Critically-sampled PFB channelizer, time-sharded: input [n] complex sharded over
    ``axis`` (n/shards must be a multiple of n_channels); output [n_channels, n/N] with
    the channel axis replicated and time sharded.

    Each branch filter needs K-1 blocks of history → halo = (K-1)·N input samples from
    the left neighbour; the IFFT across channels is purely local. This is the reference's
    ``PfbChannelizer`` (`pfb/channelizer.rs`) scaled across chips.
    """
    N = n_channels
    taps = np.asarray(taps, dtype=np.float32)
    K = -(-len(taps) // N)
    padded = np.zeros(K * N, dtype=np.float32)
    padded[:len(taps)] = taps
    branch = jnp.asarray(padded.reshape(K, N).T)          # [N, K]

    def local(x_local):
        halo = (K - 1) * N
        ext = _halo_from_left(x_local, halo, axis)        # [(S + K-1)·N]
        blocks = ext.reshape(-1, N)[:, ::-1].T            # [N, S + K-1] commutated
        # batched branch FIR via valid correlation against each branch's taps
        def one_branch(u, h):
            return jnp.convolve(u, h[::-1], mode="valid", precision="highest")
        v = jax.vmap(one_branch)(blocks, branch)          # [N, S]
        return (jnp.fft.ifft(v, axis=0) * N).astype(jnp.complex64)

    return shard_map(local, mesh=mesh, in_specs=P(axis),
                     out_specs=P(None, axis))


def _halo_from_right(local: jnp.ndarray, halo: int, axis_name: str) -> jnp.ndarray:
    """Append the NEXT shard's head — the mirror of :func:`_halo_from_left`, for
    operators whose windows extend rightward past the shard boundary. The last
    shard pads with zeros (stream edge)."""
    if halo <= 0:
        return local
    n = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    head = local[:halo]
    perm = [(i, (i - 1) % n) for i in range(n)]
    right_head = jax.lax.ppermute(head, axis_name, perm)  # shard i gets i+1's head
    right_head = jnp.where(idx == n - 1, jnp.zeros_like(right_head), right_head)
    return jnp.concatenate([local, right_head])


def sp_dechirp_scan(sf: int, mesh: Mesh, hop: int = None, axis: str = "sp"):
    """LoRa preamble-scan primitive, time-sharded: dechirp every ``hop``-spaced
    window of a long capture and return each window's peak FFT bin and energy
    concentration — the hot loop of `frame_sync.rs` (and this framework's
    ``detect_frames``) scaled across chips.

    Input [n] complex64 sharded over ``axis`` (per-shard length must be a
    multiple of ``hop``); windows anchored near a shard's end extend into the
    next shard, so each device fetches a window-length right halo with one
    ``ppermute`` — O(2^sf) bytes over ICI per frame — then computes purely
    locally. Output: (bins [n/hop], conc [n/hop]), identically time-sharded.
    Windows whose span crosses the stream end are reported from zero-padding
    (conc ≈ 0), matching how the host scan bounds its probe count.
    """
    n = 1 << sf
    hop = hop or n // 4
    if n % hop != 0:
        raise ValueError(f"window length {n} must be a multiple of hop {hop}")
    from ..models.lora.phy import _downchirp     # the host scan's exact chirp
    down = jnp.asarray(_downchirp(n).astype(np.complex64))

    def local(x_local):
        if x_local.shape[0] < n:                 # trace-time: a truncated halo
            raise ValueError(                    # would silently garble windows
                f"per-shard length {x_local.shape[0]} < window {n}: "
                f"grow the capture or reduce sf/devices")
        if x_local.shape[0] % hop:               # trace-time: a non-multiple would
            raise ValueError(                    # drop scan windows at shard seams
                f"per-shard length {x_local.shape[0]} must be a multiple of "
                f"hop {hop}")
        ext = _halo_from_right(x_local, n, axis)
        idx = jnp.arange(x_local.shape[0] // hop)[:, None] * hop + jnp.arange(n)
        spec = jnp.fft.fft(ext[idx] * down[None, :], axis=1)
        pw = spec.real ** 2 + spec.imag ** 2     # |X|^2: argmax and conc need no sqrt
        peak = jnp.argmax(pw, axis=1)
        p2 = jnp.take_along_axis(pw, peak[:, None], axis=1)[:, 0]
        conc = p2 / jnp.maximum(jnp.sum(pw, axis=1), 1e-12)
        return peak.astype(jnp.int32), conc.astype(jnp.float32)

    return shard_map(local, mesh=mesh, in_specs=P(axis),
                     out_specs=(P(axis), P(axis)))


def sp_channelizer_a2a(n_channels: int, taps: np.ndarray, mesh: Mesh,
                       axis: str = "sp") -> Callable:
    """All-to-all (Ulysses-style) sequence parallelism for the channelizer: input is
    time-sharded; each device channelizes its own time shard locally (halo from the left
    neighbour), then one ``all_to_all`` over ICI re-shards from time-split to
    CHANNEL-split — output [n_channels/n_dev local channels, full time] per device,
    i.e. [n_channels, n/N] sharded over the channel axis.

    Complements :func:`sp_channelizer` (which keeps time sharding): choose a2a when the
    downstream consumer is per-channel (demodulators, per-channel decoders), so each
    device owns whole channels and no further collectives are needed.
    """
    N = n_channels
    n_dev = mesh.shape[axis]
    assert N % n_dev == 0, "n_channels must divide the mesh axis"
    taps = np.asarray(taps, dtype=np.float32)
    K = -(-len(taps) // N)
    padded = np.zeros(K * N, dtype=np.float32)
    padded[:len(taps)] = taps
    branch = jnp.asarray(padded.reshape(K, N).T)          # [N, K]

    def local(x_local):
        halo = (K - 1) * N
        ext = _halo_from_left(x_local, halo, axis)
        blocks = ext.reshape(-1, N)[:, ::-1].T            # [N, S + K-1]

        def one_branch(u, h):
            return jnp.convolve(u, h[::-1], mode="valid", precision="highest")

        v = jax.vmap(one_branch)(blocks, branch)          # [N, S_local]
        y = (jnp.fft.ifft(v, axis=0) * N).astype(jnp.complex64)
        # re-shard: split channel axis into n_dev groups, swap with the time axis
        y = y.reshape(n_dev, N // n_dev, -1)              # [n_dev, N/n_dev, S_local]
        g = jax.lax.all_to_all(y, axis, split_axis=0, concat_axis=1, tiled=False)
        # g: [N/n_dev, n_dev, S_local] — device-major time; flatten to full time
        return g.reshape(N // n_dev, -1)

    return shard_map(local, mesh=mesh, in_specs=P(axis),
                     out_specs=P(axis, None))
