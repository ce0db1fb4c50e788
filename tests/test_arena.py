"""Host data path (ISSUE 10): staging arena, codec pool, credit controller,
and cross-process checkpoint persistence."""

import asyncio
import time

import numpy as np
import pytest

from futuresdr_tpu.ops.arena import ArenaBuffer, GroupAlloc, StagingArena


# ---------------------------------------------------------------------------
# staging arena
# ---------------------------------------------------------------------------

def test_arena_size_classes_and_recycle():
    a = StagingArena(max_bytes=64 << 20)
    b1 = a.take(100_000)                 # -> 128 KiB class
    assert b1.nbytes == 1 << 17
    b1.release()
    b2 = a.take(120_000)                 # same class: served from the pool
    assert b2 is b1
    assert a.hits == 1 and a.misses == 1
    # a different class allocates fresh
    b3 = a.take(1 << 20)
    assert b3 is not b1 and b3.nbytes == 1 << 20
    assert a.misses == 2
    b2.release()
    b3.release()
    st = a.stats()
    assert st["pinned_bytes"] == 0
    assert st["pooled_bytes"] == (1 << 17) + (1 << 20)


def test_arena_pinning_blocks_recycle():
    """A retained buffer (the replay log's reference) survives the taker's
    release — recycling only happens at refcount zero, and over-releasing is
    a no-op rather than a double-free."""
    a = StagingArena()
    b = a.take(4096)
    b.retain()                           # second holder (e.g. the rlog)
    b.release()                          # taker done
    assert a.stats()["pooled_bytes"] == 0    # still pinned
    b2 = a.take(4096)
    assert b2 is not b                   # must NOT recycle the pinned buffer
    b.release()                          # rlog pruned
    assert a.stats()["pooled_bytes"] == b.nbytes
    b.release()                          # over-release: defensive no-op
    assert a.stats()["pooled_bytes"] == b.nbytes
    b2.release()


def test_arena_pool_cap_drops():
    a = StagingArena(max_bytes=1 << 17)  # cap: one 128 KiB buffer
    b1, b2 = a.take(1 << 17), a.take(1 << 17)
    b1.release()
    b2.release()                         # past the cap: dropped, not pooled
    assert a.stats()["pooled_bytes"] == 1 << 17
    assert len(a._free[17]) == 1


def test_arena_copy_in_and_array_view():
    a = StagingArena()
    src = np.arange(1000, dtype=np.complex64)
    v, h = a.copy_in(src)
    np.testing.assert_array_equal(v, src)
    assert v.dtype == src.dtype and v.base is h.base
    h.release()


def test_encode_into_bit_identical_to_encode_host():
    """Arena-path encodes must produce bit-identical wire parts (the replay
    and retry planes re-ship them; any difference would break the
    bit-equality contracts) for every wire format, float and passthrough
    payloads alike."""
    from futuresdr_tpu.ops.wire import WIRE_FORMATS
    rng = np.random.default_rng(3)
    payloads = [
        ((rng.standard_normal(4096) + 1j * rng.standard_normal(4096))
         .astype(np.complex64)),
        rng.standard_normal(4096).astype(np.float32),
        rng.integers(-100, 100, 4096).astype(np.int32),
    ]
    # non-finite samples: the int wires' zeroing contract must match exactly
    # (float wires carry NaN through, and NaN-equality on the custom
    # bfloat16 dtype is unreliable in assert_array_equal — quant-only here)
    bad = payloads[0].copy()
    bad[7] = np.inf + 1j * np.nan
    a = StagingArena()
    for wire in WIRE_FORMATS.values():
        cases = payloads + ([bad] if wire.name in ("sc16", "sc8") else [])
        for x in cases:
            alloc = GroupAlloc(a)
            ref = wire.encode_host(x)
            got = wire.encode_into(x, alloc)
            assert len(ref) == len(got), wire.name
            for r, g in zip(ref, got):
                assert np.asarray(r).dtype == np.asarray(g).dtype, wire.name
                np.testing.assert_array_equal(np.asarray(r), np.asarray(g),
                                              err_msg=wire.name)
            for h in alloc.handles:
                h.release()
            assert not alloc._temps, f"{wire.name} leaked temps"


def test_group_alloc_temps_only():
    a = StagingArena()
    alloc = GroupAlloc(a)
    sub = alloc.temps_only()
    sub(np.array([16]), np.float32)      # lands in the PARENT temp set
    assert not alloc.handles and len(alloc._temps) == 1
    alloc.drop_temps()
    assert a.stats()["pinned_bytes"] == 0


# ---------------------------------------------------------------------------
# codec pool
# ---------------------------------------------------------------------------

def test_codec_pool_preserves_join_order():
    from futuresdr_tpu.ops.codec_pool import CodecPool
    pool = CodecPool(2)
    try:
        def task(i):
            time.sleep(0.01 if i % 2 else 0.001)   # out-of-order completion
            return i
        futs = [pool.submit_encode(task, i) for i in range(12)]
        assert [f.result() for f in futs] == list(range(12))
    finally:
        pool.shutdown()


@pytest.mark.parametrize("workers", [0, -1])
def test_codec_pool_rejects_worker_count_below_one(monkeypatch, workers):
    """``host_codec_workers`` is a thread count: below 1 is a configuration
    error that names the field, never a mode."""
    from futuresdr_tpu.config import config
    from futuresdr_tpu.ops import codec_pool
    monkeypatch.setattr(config(), "host_codec_workers", workers)
    codec_pool.reset_pool()
    try:
        with pytest.raises(ValueError, match="host_codec_workers"):
            codec_pool.pool()
    finally:
        monkeypatch.undo()
        codec_pool.reset_pool()


def test_unknown_config_key_lands_in_misc():
    """A config file or FUTURESDR_TPU_* variable naming a key that is not a
    typed field (a switch an earlier release had, a typo) lands in ``misc``,
    which no code path reads, and moves no typed field."""
    from futuresdr_tpu.config import Config
    c = Config()
    c._apply({"host_path_baseline": "0"})                # config.toml
    c._apply({"uplink_baseline": "0"}, env=True)         # FUTURESDR_TPU_…
    assert c.misc == {"host_path_baseline": "0", "uplink_baseline": "0"}
    assert c.get("uplink_baseline") == "0"
    assert c == Config(misc=dict(c.misc))


@pytest.mark.parametrize("wire", ["f32", "sc16"])
def test_arena_misses_flat_over_sustained_window(wire):
    """The staging arena's steady-state allocation count is O(1) per frame
    class: once a short run has warmed the in-flight window's buffers, a
    sustained window of the streamed kernel is served from recycled buffers
    (misses flat; the slack covers one window's worth of buffers for a class
    the warm-up's shorter window never reached — credit growth mid-run). On
    a quantizing wire the class is the packed transfer buffer
    (``ops/arena.PackedAlloc``) and the kernel reports the coalesced
    single-start layout."""
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import Head, NullSink, NullSource
    from futuresdr_tpu.ops import arena as arena_mod
    from futuresdr_tpu.ops import mag2_stage, rotator_stage
    from futuresdr_tpu.tpu import TpuKernel
    frame = 1 << 14

    def run(n_frames):
        fg = Flowgraph()
        tk = TpuKernel([rotator_stage(0.05), mag2_stage()], np.complex64,
                       frame_size=frame, wire=wire)
        snk = NullSink(np.float32)
        fg.connect(NullSource(np.complex64),
                   Head(np.complex64, n_frames * frame), tk, snk)
        Runtime().run(fg)
        assert snk.n_received == n_frames * frame
        return tk

    arena_mod.reset_arena()
    try:
        run(8)                                   # compile + warm the classes
        ar = arena_mod.arena()
        before = ar.stats()
        frames = 96
        tk = run(frames)
        st = ar.stats()
        assert st["misses"] - before["misses"] <= 8, (before, st)
        assert st["hits"] - before["hits"] >= frames, (before, st)
        em = tk.extra_metrics()
        assert em["uplink_coalesced"] == int(wire == "sc16"), em
        assert em["h2d_starts_per_frame"] == 1, em
    finally:
        arena_mod.reset_arena()


# ---------------------------------------------------------------------------
# credit controller
# ---------------------------------------------------------------------------

def _window(cc, count=8, idle=0.0, limited=False, max_seen=0, span=1.0):
    """Feed one synthetic observation window and tick (white-box: the
    controller's signals are wall-clock derived, so unit tests drive the
    accumulators directly for determinism)."""
    cc._count = count
    cc._idle_s = idle
    cc._limited = limited
    cc._max_seen = max_seen
    cc._t0 = time.perf_counter() - span
    cc._tick()


def test_credit_controller_grow_needs_two_windows_and_keeps_on_improvement():
    from futuresdr_tpu.tpu.kernel_block import CreditController
    cc = CreditController(4, adaptive=True)
    _window(cc, count=8, idle=0.5, limited=True)
    assert cc.credits == 4               # one window is not a signal
    _window(cc, count=8, idle=0.5, limited=True)
    assert cc.credits == 5 and cc._probe == (4, pytest.approx(8.0, rel=0.2))
    _window(cc, count=12, idle=0.5, limited=True)   # rate improved: keep
    assert cc.credits == 5 and cc._probe is None


def test_credit_controller_rolls_back_unproductive_grow():
    from futuresdr_tpu.tpu.kernel_block import CreditController
    cc = CreditController(4, adaptive=True)
    _window(cc, count=8, idle=0.5, limited=True)
    _window(cc, count=8, idle=0.5, limited=True)
    assert cc.credits == 5
    _window(cc, count=8, idle=0.5, limited=True)    # no improvement
    # reverted, and growth backs off (the rollback window consumes one of
    # the four hold windows itself)
    assert cc.credits == 4 and cc._hold == 3
    for _ in range(4):                              # hold: no growth
        _window(cc, count=8, idle=0.5, limited=True)
        assert cc.credits == 4


def test_credit_controller_shrinks_on_slack():
    from futuresdr_tpu.tpu.kernel_block import CreditController
    cc = CreditController(6, adaptive=True)
    _window(cc, max_seen=2)
    assert cc.credits == 6               # hysteresis: one slack window
    _window(cc, max_seen=2)
    assert cc.credits == 5
    for _ in range(10):
        _window(cc, max_seen=1)
    assert cc.credits == cc.lo           # bounded below


def test_credit_controller_pinned_when_not_adaptive():
    from futuresdr_tpu.tpu.kernel_block import CreditController
    cc = CreditController(4, adaptive=False)
    cc.note_limited()
    for _ in range(64):
        cc.note_dispatch((0.0, 1.0), 4)
    assert cc.credits == 4 and cc.hi == 4
    # depth=1 serial baselines stay strictly serial
    cc1 = CreditController(1, adaptive=True)
    assert not cc1.adaptive and cc1.credits == 1


def test_credit_controller_idle_detection():
    from futuresdr_tpu.tpu.kernel_block import CreditController
    cc = CreditController(4, adaptive=True, window=64)
    cc.note_dispatch((10.0, 10.5), 1)
    cc.note_dispatch((11.5, 12.0), 2)    # service 1.0s after prev deadline
    assert cc._idle_s == pytest.approx(1.0)
    cc.note_dispatch((11.9, 12.4), 2)    # overlapping window: no new idle
    assert cc._idle_s == pytest.approx(1.0)


def test_kernel_seeds_credits_from_cached_pick(monkeypatch):
    """With no explicit depth and ``tpu_inflight`` at auto, TpuKernel seeds
    its credit budget from the cached autotune_streamed pick's winning
    depth; an explicit depth or pinned config wins over the cache."""
    from futuresdr_tpu.config import config
    from futuresdr_tpu.ops import rotator_stage
    from futuresdr_tpu.tpu import TpuKernel
    from futuresdr_tpu.tpu.autotune import _streamed_cache, \
        record_streamed_pick
    monkeypatch.setattr(config(), "tpu_inflight", 0)
    stages = [rotator_stage(0.037)]
    try:
        record_streamed_pick(stages, np.complex64, "cpu", 1, inflight=6)
        tk = TpuKernel(stages, np.complex64, frame_size=4096)
        assert tk.depth == 6 and tk._credits.credits == 6
        assert tk._credits.adaptive
        # explicit per-kernel depth pins
        tk2 = TpuKernel(stages, np.complex64, frame_size=4096,
                        frames_in_flight=3)
        assert tk2.depth == 3 and not tk2._credits.adaptive
        # pinned config wins over the cache
        monkeypatch.setattr(config(), "tpu_inflight", 2)
        tk3 = TpuKernel(stages, np.complex64, frame_size=4096)
        assert tk3.depth == 2 and not tk3._credits.adaptive
    finally:
        _streamed_cache.clear()


def test_stage_copy_megabatch_always_leaves_ring():
    """A megabatch frame sits in ``_accum`` across work cycles AFTER its
    ring space was consumed — it must leave the ring at stage time even for
    quantizing wires (whose k==1 path legitimately encodes the live view
    pre-consume)."""
    from futuresdr_tpu.ops import rotator_stage
    from futuresdr_tpu.tpu import TpuKernel
    view = np.zeros(4096, np.complex64)
    tk1 = TpuKernel([rotator_stage(0.01)], np.complex64, frame_size=4096,
                    frames_in_flight=2, wire="sc16")
    f1, h1 = tk1._stage_copy(view)
    assert f1 is view and h1 is None     # k==1 quantizing: encode pre-consume
    tk4 = TpuKernel([rotator_stage(0.01)], np.complex64, frame_size=4096,
                    frames_in_flight=2, wire="sc16", frames_per_dispatch=4)
    f4, _h4 = tk4._stage_copy(view)
    assert f4 is not view                # k>1: retention outlives the ring


def test_adopt_credit_mode_honors_config_pin(monkeypatch):
    """Fusion must not un-pin a budget: a config ``tpu_inflight`` pin wins
    over the devchain builders' member-explicitness vote."""
    from futuresdr_tpu.config import config
    from futuresdr_tpu.ops import rotator_stage
    from futuresdr_tpu.tpu import TpuKernel
    monkeypatch.setattr(config(), "tpu_inflight", 3)
    tk = TpuKernel([rotator_stage(0.01)], np.complex64, frame_size=4096)
    assert tk.depth == 3 and not tk._credits.adaptive
    tk._adopt_credit_mode(True)          # the builders' "members adaptive"
    assert not tk._credits.adaptive      # ... loses to the config pin


# ---------------------------------------------------------------------------
# cross-process checkpoint persistence (config `checkpoint_dir`)
# ---------------------------------------------------------------------------

_FRAME = 1 << 11


def _ckpt_stages():
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage, rotator_stage
    taps = firdes.lowpass(0.2, 31).astype(np.float32)
    return [fir_stage(taps, fft_len=256), rotator_stage(0.05)]


def _make_kernel(ck=1):
    from futuresdr_tpu.tpu import TpuKernel
    tk = TpuKernel(_ckpt_stages(), np.complex64, frame_size=_FRAME,
                   frames_in_flight=2, checkpoint_every=ck)
    asyncio.run(tk.init(None, None))
    return tk


def _drive(tk, frames):
    """Push frames through the kernel's internal staged→launch→drain surface
    (one at a time: outputs land in order)."""
    outs = []
    for f in frames:
        tk._stage(f.copy(), len(f), ())
        tk._launch_staged()
        r = tk._drain_one()
        if r is not None:
            outs.append(r[0])
    return np.concatenate(outs)


def _frames(n, seed=5):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal(_FRAME) + 1j * rng.standard_normal(_FRAME))
            .astype(np.complex64) for _ in range(n)]


def _wait_for(cond, timeout=5.0):
    """Snapshot writes/purges ride the codec executor (off the drain
    thread) — poll for their filesystem effect."""
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.01)
    return True


def _drain_persist_queue():
    """Barrier on the single-thread persistence executor: every queued
    snapshot write/purge submitted before this call has completed after."""
    from futuresdr_tpu.tpu.kernel_block import _persist_executor
    _persist_executor().submit(lambda: None).result()


def test_checkpoint_persists_and_recovers_across_processes(tmp_path,
                                                           monkeypatch):
    """ISSUE 10 satellite (ROADMAP robustness follow-up): committed carry
    checkpoints serialize under ``checkpoint_dir`` (atomic rename, CRC
    integrity) and a NEW process's kernel — same name, same pipeline —
    restores the carry from disk in ``recover()``: the stream continues
    bit-identical to an uninterrupted run from the snapshot point on."""
    import os
    from futuresdr_tpu.config import config
    frames = _frames(10)
    # reference: uninterrupted run, persistence off
    monkeypatch.setattr(config(), "checkpoint_dir", "")
    ref = _drive(_make_kernel(ck=0), frames)

    monkeypatch.setattr(config(), "checkpoint_dir", str(tmp_path))
    tk1 = _make_kernel()
    out1 = _drive(tk1, frames[:6])
    path = tk1._ckpt_file()
    assert path and _wait_for(lambda: os.path.exists(path)), \
        "commit did not persist"
    _drain_persist_queue()

    # "process restart": a fresh kernel object, nothing in-kernel to restore
    tk2 = _make_kernel()
    assert asyncio.run(tk2.recover(RuntimeError("process restart"))) is True
    out2 = _drive(tk2, frames[6:])
    got = np.concatenate([out1, out2])
    np.testing.assert_array_equal(got, ref)


def test_checkpoint_disk_corruption_rejected(tmp_path, monkeypatch):
    from futuresdr_tpu.config import config
    monkeypatch.setattr(config(), "checkpoint_dir", str(tmp_path))
    tk1 = _make_kernel()
    _drive(tk1, _frames(4))
    path = tk1._ckpt_file()
    assert _wait_for(lambda: __import__("os").path.exists(path))
    _drain_persist_queue()
    data = bytearray(open(path, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(path, "wb").write(bytes(data))
    tk2 = _make_kernel()
    assert tk2._load_disk_ckpt() is None      # CRC/parse rejects it
    # recover falls through to the fresh-init sentinel instead of crashing
    assert asyncio.run(tk2.recover(RuntimeError("restart"))) is True
    # and the restored carry is the FRESH one, not the corrupted snapshot
    import jax
    _, fresh = tk2.pipeline.compile_wired(tk2.frame_size, tk2.wire,
                                          device=tk2.inst.device,
                                          k=tk2.k_batch, donate=tk2._donate)
    for a, b in zip(jax.tree_util.tree_leaves(tk2._carry),
                    jax.tree_util.tree_leaves(fresh)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_dir_key_collisions(tmp_path, monkeypatch):
    """ISSUE 14 satellite: instance names are per-FLOWGRAPH, so two kernels
    in different flowgraphs can carry the SAME name. The snapshot filename
    is keyed by name + pipeline-signature hash (utils/snapshot.py
    ``snapshot_signature``): different pipelines under one reused name map
    to DIFFERENT files — neither can ever read the other's carry — and the
    true worst case (same name AND same pipeline) shares one file but still
    restores bit-consistently because the signature IS the carry contract."""
    import os
    from futuresdr_tpu.config import config
    from futuresdr_tpu.ops import rotator_stage
    from futuresdr_tpu.tpu import TpuKernel
    from futuresdr_tpu.utils import snapshot as snap
    monkeypatch.setattr(config(), "checkpoint_dir", str(tmp_path))

    tk_fir = _make_kernel()                      # fir+rotator chain
    tk_rot = TpuKernel([rotator_stage(0.05)], np.complex64,
                       frame_size=_FRAME, frames_in_flight=2,
                       checkpoint_every=1)
    asyncio.run(tk_rot.init(None, None))
    # same instance name, different pipelines
    tk_rot.meta.instance_name = tk_fir.meta.instance_name
    p_fir, p_rot = tk_fir._ckpt_file(), tk_rot._ckpt_file()
    assert p_fir != p_rot, "signature hash failed to separate the files"
    # the signature term is the pipeline (stage names + in dtype), pinned
    # at the shared-helper level too
    assert snap.snapshot_signature(tk_fir.pipeline,
                                   tk_fir.meta.instance_name) != \
        snap.snapshot_signature(tk_rot.pipeline, tk_rot.meta.instance_name)

    # drive both; each persists under its own file
    frames = _frames(4)
    _drive(tk_fir, frames)
    _drive(tk_rot, frames)
    assert _wait_for(lambda: os.path.exists(p_fir) and os.path.exists(p_rot))
    _drain_persist_queue()

    # a fresh incarnation of EACH kernel loads only its own snapshot: the
    # rotator kernel (same name!) never sees the FIR chain's carry
    tk_fir2 = _make_kernel()
    got = tk_fir2._load_disk_ckpt()
    assert got is not None
    _, leaves = got
    import jax
    _, fresh = tk_fir2.pipeline.compile_wired(
        tk_fir2.frame_size, tk_fir2.wire, device=tk_fir2.inst.device,
        k=tk_fir2.k_batch, donate=tk_fir2._donate)
    treedef = jax.tree_util.tree_flatten(fresh)[1]
    assert tk_fir2.pipeline.carry_matches(leaves, treedef, fresh)
    tk_rot2 = TpuKernel([rotator_stage(0.05)], np.complex64,
                        frame_size=_FRAME, frames_in_flight=2,
                        checkpoint_every=1)
    asyncio.run(tk_rot2.init(None, None))
    tk_rot2.meta.instance_name = tk_fir.meta.instance_name
    got2 = tk_rot2._load_disk_ckpt()
    assert got2 is not None
    assert len(got2[1]) != len(leaves), \
        "rotator kernel read the FIR chain's snapshot"


def test_checkpoint_clean_eos_purges_snapshot(tmp_path, monkeypatch):
    """A cleanly finished stream's state is complete — the persisted
    snapshot is removed so a later process starts fresh (the in-kernel
    clean-EOS reset contract, extended to disk)."""
    import os
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.config import config
    from futuresdr_tpu.tpu import TpuKernel
    monkeypatch.setattr(config(), "checkpoint_dir", str(tmp_path))
    rng = np.random.default_rng(1)
    n = _FRAME * 5
    data = (rng.standard_normal(n) + 1j * rng.standard_normal(n)) \
        .astype(np.complex64)
    fg = Flowgraph()
    tk = TpuKernel(_ckpt_stages(), np.complex64, frame_size=_FRAME,
                   frames_in_flight=2, checkpoint_every=1)
    snk = VectorSink(np.complex64)
    fg.connect(VectorSource(data), tk, snk)
    Runtime().run(fg, timeout=60.0)
    assert snk.items() is not None
    path = tk._ckpt_file()
    assert path and _wait_for(lambda: not os.path.exists(path)), \
        "clean EOS left a persisted snapshot behind"
