"""Single-shot uplink plane (ISSUE 18): coalesced H2D transfers, zero-copy
ingest, deferred-consume staging, and mid-stream adaptive wire switching.

Acceptance contracts exercised here:
* packed-path output BIT-IDENTICAL to the per-part path across wire formats
  x K in {1, 4} x linear / fan-out kernels, with ``h2d_starts_per_frame==1``
  and ONE billed transfer start per dispatch group;
* fault-injected replay re-ships the EXACT packed bytes (bit-identical
  output through a recovery mid-stream);
* dlpack/registered-buffer ingest frames stay pinned until drain AND a
  covering checkpoint (the owner's ``pinned`` flag honors fault replay);
* an adaptive wire switch lands only at a quiescent dispatch boundary, is
  bit-exact from the switch group on, and survives recovery (the wire-switch
  log replays like the retune log).
"""

import asyncio

import numpy as np
import pytest

from futuresdr_tpu import Mocker
from futuresdr_tpu.config import config
from futuresdr_tpu.dsp import firdes
from futuresdr_tpu.ops import (FanoutPipeline, fir_stage, mag2_stage,
                               rotator_stage)
from futuresdr_tpu.ops import ingest, xfer
from futuresdr_tpu.ops.arena import PackedAlloc, StagingArena
from futuresdr_tpu.ops.wire import WIRE_FORMATS, get_wire
from futuresdr_tpu.tpu import TpuKernel
from futuresdr_tpu.tpu.kernel_block import TpuFanoutKernel, WireController

FS = 2048


@pytest.fixture(autouse=True)
def _uplink_defaults():
    """Every test starts from the shipped uplink defaults and leaves no
    ingest registrations behind."""
    c = config()
    saved = c.tpu_adaptive_wire
    ingest.reset()
    yield
    c.tpu_adaptive_wire = saved
    ingest.reset()


@pytest.fixture
def per_part(monkeypatch):
    """Calling it makes every kernel built afterwards ship its wire parts
    one transfer each: the coalescing probe answers None, which is how a
    single-part wire reaches the per-part form. The packed tests use that
    form as their REFERENCE."""
    def arm():
        monkeypatch.setattr(xfer.PackedLayout, "probe",
                            classmethod(lambda cls, *a, **k: None))
    yield arm
    monkeypatch.undo()


def _taps():
    return firdes.lowpass(0.2, 31).astype(np.float32)


def _data(n_frames, seed=7):
    rng = np.random.default_rng(seed)
    n = FS * n_frames
    return (rng.standard_normal(n) + 1j * rng.standard_normal(n)) \
        .astype(np.complex64)


def _kernel(wire="sc16", k=1, ck=None):
    return TpuKernel([fir_stage(_taps(), fft_len=256, name="f"),
                      rotator_stage(0.05, name="rot")],
                     np.complex64, frame_size=FS, frames_in_flight=2,
                     wire=wire, frames_per_dispatch=k,
                     checkpoint_every=ck)


def _drive(mk, data, out_scale=2):
    m = Mocker(mk)
    m.input("in", data)
    m.init_output("out", len(data) * out_scale)
    m.init()
    m.run()
    return m.output("out").copy()


# ---------------------------------------------------------------------------
# coalescing: layout + alloc units
# ---------------------------------------------------------------------------

def test_packed_layout_probe_gates():
    """Single-part wires never pack (coalescing is moot at one H2D start);
    quantizers pack payload+scale."""
    assert xfer.PackedLayout.probe(get_wire("f32"), FS, np.complex64,
                                   k=1) is None
    lay = xfer.PackedLayout.probe(get_wire("sc16"), FS, np.complex64, k=1)
    assert lay is not None and len(lay.slots) == 2
    assert lay.nbytes % xfer.PackedLayout.ALIGN == 0
    # every slot offset is aligned
    for _, _, off, _ in lay.slots:
        assert off % xfer.PackedLayout.ALIGN == 0


def test_packed_layout_roundtrip_bit_exact():
    """pack → device unpack prolog → bitcast views reproduce every part
    bit-for-bit, gaps zeroed (deterministic replay bytes)."""
    import jax
    for wname in ("sc16", "sc8"):
        for k in (1, 4):
            w = get_wire(wname)
            lay = xfer.PackedLayout.probe(w, FS, np.complex64, k=k)
            rng = np.random.default_rng(3)
            frames = [(rng.standard_normal(FS) + 1j
                       * rng.standard_normal(FS)).astype(np.complex64)
                      for _ in range(k)]
            encs = [w.encode_host(f) for f in frames]
            parts = [np.stack([np.asarray(e[i]) for e in encs])
                     if k > 1 else np.asarray(encs[0][i])
                     for i in range(len(encs[0]))]
            buf = lay.pack(parts, np.empty(lay.nbytes, np.uint8))
            out = jax.jit(lay.unpack_jax)(buf)
            assert len(out) == len(parts)
            for a, b in zip(parts, out):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b), err_msg=wname)


def test_packed_alloc_writes_through_slots():
    """A PackedAlloc encode writes int payloads at their packed offsets —
    pack() then skips the copy (np.shares_memory) and only settles bare
    parts (the quantizer's scale scalar) and gap bytes."""
    w = get_wire("sc16")
    lay = xfer.PackedLayout.probe(w, FS, np.complex64, k=1)
    a = StagingArena()
    alloc = PackedAlloc(a, lay)
    x = _data(1)
    parts = w.encode_into(x, alloc)
    assert np.shares_memory(np.asarray(parts[0]), alloc.packed)
    packed = alloc.finish(parts)
    ref = [np.asarray(p) for p in w.encode_host(x)]
    got = lay.unpack_host(packed) if hasattr(lay, "unpack_host") else None
    # settle through the slot table directly
    for (sh, dt, off, nb), r in zip(lay.slots, ref):
        np.testing.assert_array_equal(
            packed[off:off + nb].view(dt).reshape(sh), r)
    for h in alloc.handles:
        h.release()


# ---------------------------------------------------------------------------
# coalescing: end-to-end bit-equality + starts billing
# ---------------------------------------------------------------------------

def _run_chain(wire, k, n_frames=8, seed=7):
    data = _data(n_frames, seed)
    mk = _kernel(wire=wire, k=k)
    m = Mocker(mk)
    m.input("in", data)
    m.init_output("out", len(data) * 2)
    m.init()                 # compile + warmup + cost probes bill separately
    starts0 = xfer._XFER_STARTS.get(direction="h2d")
    m.run()
    starts = xfer._XFER_STARTS.get(direction="h2d") - starts0
    return m.output("out").copy(), starts, mk.extra_metrics()


@pytest.mark.parametrize("wire", ["sc16", "sc8"])
@pytest.mark.parametrize("k", [1, 4])
def test_packed_bit_identical_and_single_start(wire, k, per_part):
    a, sa, ema = _run_chain(wire, k)
    per_part()
    b, sb, emb = _run_chain(wire, k)
    np.testing.assert_array_equal(a, b)
    assert ema["uplink_coalesced"] == 1 and emb["uplink_coalesced"] == 0
    assert ema["h2d_starts_per_frame"] == 1
    assert emb["h2d_starts_per_frame"] == 2      # payload + scale
    groups = 8 // k
    # ONE billed transfer start per packed group; per-part pays one per
    # wire part (quantizer payload + scale)
    assert sa == groups, (sa, groups)
    assert sb == 2 * groups, (sb, groups)


def test_packed_single_part_wires_stay_per_part():
    out, _, em = _run_chain("f32", 1)
    assert em["uplink_coalesced"] == 0
    assert em["h2d_starts_per_frame"] == 1       # already single-start


def test_packed_fanout_bit_identical(per_part):
    """Fan-out kernels ride the same packed upload (one input crossing)."""
    def mk_fan():
        return TpuFanoutKernel(
            FanoutPipeline([fir_stage(_taps(), fft_len=256, name="p")],
                           [[mag2_stage()], [rotator_stage(0.1)]],
                           np.complex64),
            frame_size=FS, frames_in_flight=2, wire="sc16")
    data = _data(6)
    outs = {}
    for coalesce in (True, False):
        if not coalesce:
            per_part()
        mk = mk_fan()
        m = Mocker(mk)
        m.input("in", data)
        m.init_output("out0", len(data) * 2)
        m.init_output("out1", len(data) * 2)
        m.init()
        m.run()
        outs[coalesce] = (m.output("out0").copy(), m.output("out1").copy())
        assert mk.extra_metrics()["uplink_coalesced"] == int(coalesce)
    np.testing.assert_array_equal(outs[True][0], outs[False][0])
    np.testing.assert_array_equal(outs[True][1], outs[False][1])


@pytest.mark.parametrize("k", [1, 4])
def test_packed_replay_bit_identical(k):
    """A recovery mid-stream re-ships the logged PACKED buffers untouched:
    the full output matches the unfailed run bit-for-bit."""
    data = _data(8, seed=11)
    want = _drive(_kernel(wire="sc16", k=k, ck=2), data)

    mk = _kernel(wire="sc16", k=k, ck=2)
    m = Mocker(mk)
    m.init_output("out", len(data) * 2)
    m.init()
    m.input("in", data[:FS * 4])
    m.run()
    assert mk._packed is not None
    assert asyncio.run(mk.recover(RuntimeError("injected test fault")))
    m.input("in", data[FS * 4:])
    m.run()
    np.testing.assert_array_equal(m.output("out"), want)


def test_packed_survives_fake_link_faults():
    """Transient H2D faults under the seeded fake link retry the SAME packed
    buffer — output equals the clean run exactly."""
    data = _data(8, seed=5)
    want = _drive(_kernel(wire="sc16", k=1), data)
    old_backoff = config().xfer_backoff
    config().xfer_backoff = 0.0005
    try:
        xfer.set_fake_link(fault_rate=0.2, fault_seed=3)
        r0 = xfer._RETRIES.get(direction="h2d")
        got = _drive(_kernel(wire="sc16", k=1), data)
        assert xfer._RETRIES.get(direction="h2d") > r0   # faults actually hit
    finally:
        xfer.set_fake_link()
        config().xfer_backoff = old_backoff
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# zero-copy ingest
# ---------------------------------------------------------------------------

def test_ingest_registry_lookup_and_writable_fallback():
    a = np.arange(4096, dtype=np.complex64)
    h = ingest.register(a, name="t")
    assert not a.flags.writeable                 # tripwire armed
    assert ingest.lookup(a[10:100]) is h         # views resolve to the root
    assert ingest.register(a) is h               # idempotent per root
    w = np.arange(64, dtype=np.complex64)
    assert ingest.lookup(w) is None              # writable → copy path
    ingest.unregister(h)
    assert ingest.lookup(a) is None


def test_ingest_refcount_idle_callback():
    idled = []
    a = np.zeros(1024, np.float32)
    h = ingest.register(a, on_idle=idled.append)
    assert not h.pinned
    h.retain()
    assert h.pinned and not idled
    h.release()
    assert not h.pinned and idled == [h]


@pytest.mark.parametrize("k", [1, 4])
def test_ingest_zero_copy_frames_on_aliasing_wire(k):
    """A registered read-only buffer skips EVERY ring-exit copy on the f32
    wire (frac == 1.0, also for frames that wait in a megabatch group);
    output is bit-identical to the copying run and the buffer is unpinned
    once everything drained."""
    data = _data(8, seed=9)
    want = _drive(_kernel(wire="f32", k=k), data)
    h = ingest.register(data, name="capture")
    mk = _kernel(wire="f32", k=k)
    got = _drive(mk, data)
    em = mk.extra_metrics()
    assert em["ingest_zero_copy_frac"] == 1.0, em
    assert not h.pinned                          # drained + pruned
    np.testing.assert_array_equal(got, want)


def test_ingest_pinned_through_checkpoint_replay():
    """The ingest pin rides the replay log: after a recovery the re-staged
    frames come from the STILL-PINNED registered buffer and the output stays
    bit-exact; only when replay coverage commits does the pin drop."""
    data = _data(8, seed=13)
    want = _drive(_kernel(wire="f32", k=1, ck=2), data)
    h = ingest.register(data, name="capture")
    mk = _kernel(wire="f32", k=1, ck=2)
    m = Mocker(mk)
    m.init_output("out", len(data) * 2)
    m.init()
    m.input("in", data[:FS * 4])
    m.run()
    assert asyncio.run(mk.recover(RuntimeError("injected test fault")))
    m.input("in", data[FS * 4:])
    m.run()
    np.testing.assert_array_equal(m.output("out"), want)
    assert mk.extra_metrics()["ingest_zero_copy_frac"] > 0
    # sparse cadence: the replay log still covers the tail groups (the
    # committed floor is the OLDER of the two retained checkpoints), so the
    # owner must keep the buffer alive — pinned stays True at EOS...
    assert h.pinned
    # ...and drops only when the kernel's retention actually ends
    mk._recovery_reset()
    assert not h.pinned


def test_ingest_disabled_on_quant_wire():
    """Quantizing wires materialize fresh int payloads — no copy to skip, so
    the fast path must not engage (deferred consume covers that case)."""
    data = _data(4)
    ingest.register(data)
    mk = _kernel(wire="sc16", k=1)
    assert not mk._ingest_enabled
    _drive(mk, data)
    assert mk.extra_metrics()["ingest_zero_copy_frac"] == 0.0


def test_ingest_from_dlpack():
    import jax
    x = jax.numpy.arange(256, dtype=jax.numpy.float32)
    arr = ingest.from_dlpack(x)
    assert ingest.lookup(arr) is not None
    np.testing.assert_array_equal(np.asarray(x), arr)


# ---------------------------------------------------------------------------
# deferred-consume staging (quantizing wires, K=1)
# ---------------------------------------------------------------------------

def test_deferred_consume_engages_and_matches():
    data = _data(8)
    mk = _kernel(wire="sc16", k=1)
    got = _drive(mk, data)
    assert mk.extra_metrics()["deferred_consume"] == 1
    assert mk._pending_consume is None           # fully settled at EOS
    # the reference: the same kernel encoding on the staging thread before
    # consume() (a test fake: the instance attribute, not a setting)
    ref = _kernel(wire="sc16", k=1)
    ref._deferred_consume = False
    off = _drive(ref, data)
    assert ref.extra_metrics()["deferred_consume"] == 0
    np.testing.assert_array_equal(got, off)


@pytest.mark.parametrize("wire,k,want", [("sc16", 4, (0, 0, 0)),
                                         ("sc8", 1, (0, 0, 1)),
                                         ("f32", 1, (1, 1, 0)),
                                         ("f32", 4, (1, 1, 0))])
def test_uplink_modes_follow_wire_and_k(wire, k, want):
    """``_resolve_uplink`` derives (encode offload, zero-copy ingest,
    deferred consume) from what the kernel can observe: whether the wire's
    encode aliases its input, and K."""
    mk = _kernel(wire=wire, k=k)
    assert (int(mk._encode_offload), int(mk._ingest_enabled),
            int(mk._deferred_consume)) == want


@pytest.mark.parametrize("wire", ["sc16", "sc8"])
def test_one_physical_h2d_start_per_group_sustained(wire):
    """A quantizing-wire streamed chain bills exactly ONE physical H2D start
    per dispatch group over a sustained window, each of the packed layout's
    byte count (payload + scale ride one buffer)."""
    groups = 48
    data = _data(groups, seed=21)
    mk = _kernel(wire=wire, k=1)
    m = Mocker(mk)
    m.input("in", data)
    m.init_output("out", len(data) * 2)
    m.init()                 # compile + warm-up bill separately
    s0 = xfer._XFER_STARTS.get(direction="h2d")
    b0 = xfer._XFER_BYTES.get(direction="h2d")
    m.run()
    assert xfer._XFER_STARTS.get(direction="h2d") - s0 == groups
    assert xfer._XFER_BYTES.get(direction="h2d") - b0 == \
        groups * mk._packed.nbytes
    em = mk.extra_metrics()
    assert em["uplink_coalesced"] == 1 and em["h2d_starts_per_frame"] == 1


# ---------------------------------------------------------------------------
# adaptive wire switching
# ---------------------------------------------------------------------------

def _feed(ctl, frames, wire_s=0.0, n=16):
    """Feed n dispatch groups' worth of signal + wire windows."""
    for _ in range(n):
        for f in frames:
            ctl.observe_frame(f)
        ctl.note_dispatch((0.0, wire_s) if wire_s else None)


def test_wire_controller_widens_on_low_snr():
    """A high crest-factor signal (one huge spike over a quiet floor)
    predicts sub-budget sc8 SNR → two agreeing windows propose widening."""
    ctl = WireController(budget_db=40.0, window=4)
    quiet = np.full(512, 1e-4, np.complex64)
    quiet[0] = 1.0 + 0j                          # crest: peak >> rms
    assert ctl.predicted_snr_db("f32") == float("inf")
    _feed(ctl, [quiet], n=4)
    assert ctl.propose("sc8") is None            # first agreeing window
    _feed(ctl, [quiet], n=4)
    assert ctl.propose("sc8") == "sc16"          # second → widen one step
    # holdoff mutes the next windows
    _feed(ctl, [quiet], n=4)
    assert ctl.propose("sc16") is None


def test_wire_controller_narrows_only_when_link_busy():
    """A well-conditioned signal clears the sc16 budget+margin, but the
    narrow proposal needs measured H2D occupancy ≥ the bar."""
    sig = (np.ones(512) * 0.5).astype(np.complex64)
    idle = WireController(budget_db=40.0, window=4)
    _feed(idle, [sig], wire_s=0.0, n=8)
    assert idle.propose("f32") is None           # idle link: stay exact
    busy = WireController(budget_db=40.0, window=4)
    # occupancy ≈ busy_s/span ≥ bar: claim 10 s of wire time per window
    _feed(busy, [sig], wire_s=10.0, n=4)
    assert busy.propose("f32") is None
    _feed(busy, [sig], wire_s=10.0, n=4)
    assert busy.propose("f32") == "sc16"


def test_apply_wire_retune_switches_at_quiescent_boundary():
    """Manual wire surgery mid-stream: the switch lands between dispatch
    groups and the tail is bit-identical to a run built on the new wire."""
    data = _data(8, seed=13)
    mk = _kernel(wire="sc16", k=1, ck=2)
    m = Mocker(mk)
    m.init_output("out", len(data) * 2)
    m.init()
    m.input("in", data[:FS * 4])
    m.run()
    mk.apply_wire_retune("f32")
    m.input("in", data[FS * 4:])
    m.run()
    assert mk.wire.name == "f32"
    assert mk.extra_metrics()["wire_switches"] == 1
    want_tail = _drive(_kernel(wire="f32", k=1, ck=2), data)[FS * 8:]
    np.testing.assert_array_equal(m.output("out")[FS * 8:], want_tail)


def test_wire_switch_survives_recovery():
    """The wire-switch log replays like the retune log: a restore point
    after the switch recovers INTO the switched format."""
    data = _data(8, seed=13)
    mk = _kernel(wire="sc16", k=1, ck=2)
    m = Mocker(mk)
    m.init_output("out", len(data) * 2)
    m.init()
    m.input("in", data[:FS * 4])
    m.run()
    mk.apply_wire_retune("sc8")
    m.input("in", data[FS * 4:FS * 6])
    m.run()
    assert mk.wire.name == "sc8"
    assert asyncio.run(mk.recover(RuntimeError("injected test fault")))
    assert mk.wire.name == "sc8"                 # restored from the log
    m.input("in", data[FS * 6:])
    m.run()
    assert mk.wire.name == "sc8"


def test_wire_retune_rejects_unknown_format():
    mk = _kernel(wire="sc16", k=1)
    with pytest.raises(Exception):
        mk.apply_wire_retune("nope")


# ---------------------------------------------------------------------------
# autotune wire axis
# ---------------------------------------------------------------------------

def test_autotune_wire_axis_roundtrip(tmp_path, monkeypatch):
    import sys
    at = sys.modules["futuresdr_tpu.tpu.autotune"]
    monkeypatch.setattr(config(), "autotune_cache_dir", str(tmp_path))
    at._streamed_cache.clear()
    at._disk_memo.clear()
    stages = [fir_stage(_taps(), fft_len=256, name="f")]
    at.record_streamed_pick(stages, np.complex64, "cpu", 4, inflight=2)
    at.record_wire_start(stages, np.complex64, "cpu", "sc16")
    # a later K re-record preserves the orthogonal wire axis
    at.record_streamed_pick(stages, np.complex64, "cpu", 1, inflight=4)
    assert at.cached_wire_start(stages, np.complex64, "cpu") == "sc16"
    # disk round-trip through _norm_entry
    at._streamed_cache.clear()
    at._disk_memo.clear()
    e = at.cached_streamed_pick(stages, np.complex64, "cpu")
    assert e == {"k": 1, "inflight": 4, "wire": "sc16"}
    # unknown formats are dropped, not stored
    at.record_wire_start(stages, np.complex64, "cpu", "bogus")
    assert at.cached_wire_start(stages, np.complex64, "cpu") == "sc16"
    at._streamed_cache.clear()
    at._disk_memo.clear()


def test_adaptive_kernel_starts_from_cached_pick(tmp_path, monkeypatch):
    """Arming tpu_adaptive_wire adopts the cached autotune_streamed wire as
    the policy's start point (the build-time wire is just the fallback)."""
    import sys
    at = sys.modules["futuresdr_tpu.tpu.autotune"]
    monkeypatch.setattr(config(), "autotune_cache_dir", str(tmp_path))
    monkeypatch.setattr(config(), "tpu_adaptive_wire", True)
    at._streamed_cache.clear()
    at._disk_memo.clear()
    stages = [fir_stage(_taps(), fft_len=256, name="f"),
              rotator_stage(0.05, name="rot")]
    at.record_wire_start(stages, np.complex64, "cpu", "sc16")
    mk = TpuKernel(stages, np.complex64, frame_size=FS,
                   frames_in_flight=2, wire="f32")
    assert mk.wire.name == "sc16" and mk._wire0 == "sc16"
    assert mk._wirectl is not None
    assert mk._packed is not None                # re-derived for the start
    at._streamed_cache.clear()
    at._disk_memo.clear()
