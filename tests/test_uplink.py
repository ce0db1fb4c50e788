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

from _ship_log import ShipLog

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


def _special_frame(kind, in_dtype, rng, n=FS):
    """One frame of ``in_dtype``: Gaussian, all zero, or Gaussian with
    non-finite samples in it (the quantizer zeroes them on encode)."""
    cplx = np.issubdtype(np.dtype(in_dtype), np.complexfloating)
    if kind == "zeros":
        return np.zeros(n, in_dtype)
    x = rng.standard_normal(n)
    if cplx:
        x = x + 1j * rng.standard_normal(n)
    x = x.astype(in_dtype)
    if kind == "nonfinite":
        x[3] = np.nan
        x[n // 2] = np.inf
        x[-1] = -np.inf if not cplx else complex(1.0, -np.inf)
    return x


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("in_dtype", [np.complex64, np.float32],
                         ids=["c64", "f32"])
@pytest.mark.parametrize("wname", ["sc16", "sc8"])
def test_packed_layout_roundtrip_bit_exact(wname, in_dtype, k):
    """pack → the packed program's prolog (``unpack`` + ``wire_decode``, as
    ``packed_wired_fn`` composes them: words in, a word a sample where the
    slot allows) decodes every frame BIT FOR BIT as ``decode_jax`` does from
    the separate parts — Gaussian, all-zero and non-finite frames alike; the
    plain unpack reproduces every part, gaps zeroed (deterministic replay
    bytes), and the buffer ships as uint32 words."""
    import jax
    from futuresdr_tpu.ops import Pipeline
    w = get_wire(wname)
    lay = xfer.PackedLayout.probe(w, FS, in_dtype, k=k)
    pipe = Pipeline([mag2_stage()], in_dtype)
    as_words = pipe.pair_word_slots(w, lay)
    want_words = wname == "sc16" and in_dtype is np.complex64
    assert as_words == (want_words, False)
    rng = np.random.default_rng(3)
    kinds = ["gauss", "zeros", "nonfinite", "gauss"]
    for rot in range(3):
        frames = [_special_frame(kinds[(i + rot) % 4], in_dtype, rng)
                  for i in range(k)]
        encs = [w.encode_host(f) for f in frames]
        parts = [np.stack([np.asarray(e[i]) for e in encs])
                 if k > 1 else np.asarray(encs[0][i])
                 for i in range(len(encs[0]))]
        buf = lay.pack(parts, np.full(lay.nbytes, 0xA5, np.uint8))
        assert buf.dtype == np.uint32 and buf.shape == (lay.nbytes // 4,)
        # the plain unpack: every part back, bit for bit
        out = jax.jit(lay.unpack_jax)(buf)
        assert len(out) == len(parts)
        for a, b in zip(parts, out):
            assert np.asarray(b).dtype == np.asarray(a).dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=wname)
        # alignment gaps are zeroed whatever the buffer held
        bytes_ = buf.view(np.uint8)
        end = 0
        for _sh, _dt, off, nb in lay.slots:
            assert not bytes_[end:off].any()
            end = off + nb
        assert not bytes_[end:].any()

        # the prolog the packed program runs, frame by frame
        def prolog(words):
            ps = lay.unpack_jax(words, as_words)
            dec = w.decode_words_jax if any(as_words) else w.decode_jax
            if k == 1:
                return dec(ps, in_dtype)
            return jax.lax.map(lambda p: dec(p, in_dtype), ps)

        got = np.asarray(jax.jit(prolog)(buf))
        ref = jax.jit(lambda *ps: w.decode_jax(ps, in_dtype))
        want = np.stack([np.asarray(ref(*e)) for e in encs]) if k > 1 \
            else np.asarray(ref(*encs[0]))
        assert got.dtype == want.dtype and got.shape == want.shape
        bits = np.uint32 if got.dtype == np.float32 else np.uint64
        np.testing.assert_array_equal(got.view(bits), want.view(bits))
        assert np.isfinite(got).all()


def _lowered_scope_types(text, scopes=("unpack", "wire_decode")):
    """Every tensor type on a line of the lowered text that carries one of
    ``scopes`` in its location (debug info on), as lists of dimensions."""
    import re
    locs = {m.group(1) for m in re.finditer(
        r'^(#loc\d+) = loc\("[^"]*/(?:%s)/' % "|".join(scopes), text,
        re.M)}
    assert locs, "no op of the lowered text carries the prolog's scopes"
    dims = []
    for line in text.splitlines():
        m = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if m and m.group(1) in locs:
            for t in re.findall(r"tensor<([0-9x]*)x?[a-z]+[0-9]*>", line):
                dims.append([int(d) for d in t.split("x") if d])
    assert dims
    return dims


@pytest.mark.parametrize("k", [1, 4])
def test_packed_prolog_has_no_narrow_minor_dimension(k):
    """The structural guard (ISSUE 32): at the cells' size, sc16 / complex64
    / 262144, no tensor under the scopes ``unpack`` and ``wire_decode`` of
    the lowered packed program has a minor dimension of 2 or 4 (such an
    array is padded to 128 lanes on the TPU and relaid: 1.0 ms of a 1.3 ms
    program), and the kernel's counter says the word arm engaged — 1 there,
    0 for sc8 and f32."""
    import jax
    from futuresdr_tpu.ops import Pipeline
    fs = 262144
    w = get_wire("sc16")
    pipe = Pipeline([mag2_stage()], np.complex64)
    lay = xfer.PackedLayout.probe(w, fs, np.complex64, k=k)
    fn = jax.jit(pipe.packed_wired_fn(w, k, lay))
    text = fn.lower(pipe.init_carry(),
                    jax.ShapeDtypeStruct((lay.nbytes // 4,), np.uint32)) \
        .as_text(debug_info=True)
    # (rank 1 is exempt: at k = 4 the scale slot IS four words, f32[4])
    narrow = [d for d in _lowered_scope_types(text)
              if len(d) >= 2 and d[-1] in (2, 4)]
    assert not narrow, narrow
    # the guard can tell: the byte-and-pair form it replaces trips it
    def old_prolog(buf):
        with jax.named_scope("unpack"):
            q = jax.lax.bitcast_convert_type(
                buf[:fs * 4].reshape(-1, 2), np.int16).reshape(fs, 2)
        with jax.named_scope("wire_decode"):
            return w.decode_jax((q, np.float32(1.0)), np.complex64)
    old = jax.jit(old_prolog).lower(
        jax.ShapeDtypeStruct((lay.nbytes,), np.uint8)) \
        .as_text(debug_info=True)
    assert any(len(d) >= 2 and d[-1] == 2
               for d in _lowered_scope_types(old))

    for wname, want in (("sc16", 1), ("sc8", 0), ("f32", 0)):
        mk = TpuKernel([mag2_stage()], np.complex64, frame_size=FS,
                       wire=wname, frames_per_dispatch=k)
        em = mk.extra_metrics()
        assert em["uplink_word_slots"] == want, (wname, em)
        assert em["uplink_coalesced"] == int(wname != "f32")
    # a real-float input on sc16 ships int16[n]: two samples a word, no
    # word-a-sample form — chosen from the slot, not from the wire's name
    mk = TpuKernel([mag2_stage()], np.float32, frame_size=FS, wire="sc16")
    assert mk.extra_metrics()["uplink_word_slots"] == 0


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
    # what ships is the SAME buffer seen as 32-bit words: a view, no copy
    assert packed.dtype == np.uint32 and packed.nbytes == lay.nbytes
    assert np.shares_memory(packed, alloc.packed)
    ref = [np.asarray(p) for p in w.encode_host(x)]
    # settle through the slot table directly
    raw = packed.view(np.uint8)
    for (sh, dt, off, nb), r in zip(lay.slots, ref):
        np.testing.assert_array_equal(
            raw[off:off + nb].view(dt).reshape(sh), r)
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
def test_packed_replay_bit_identical(k, monkeypatch):
    """A recovery mid-stream re-ships the logged PACKED buffers untouched:
    the full output matches the unfailed run bit-for-bit, and every replayed
    group crosses as the same uint32 words, dtype and bytes, as its first
    attempt (the program takes words: ISSUE 32)."""
    data = _data(8, seed=11)
    want = _drive(_kernel(wire="sc16", k=k, ck=2), data)

    log = ShipLog(monkeypatch)
    mk = _kernel(wire="sc16", k=k, ck=2)
    m = Mocker(mk)
    m.init_output("out", len(data) * 2)
    m.init()
    # k = 1: five frames, so that group 4 lies past the checkpoint @3 and
    # is re-shipped from the log (k = 4: group 0 replays from the sentinel)
    cut = FS * (5 if k == 1 else 4)
    m.input("in", data[:cut])
    m.run()
    assert mk._packed is not None
    assert asyncio.run(mk.recover(RuntimeError("injected test fault")))
    m.input("in", data[cut:])
    m.run()
    np.testing.assert_array_equal(m.output("out"), want)
    assert log.assert_reships_identical(np.uint32) >= 1
    for attempts in log.ships.values():          # first attempts too
        assert [p[0] for p in attempts[0]] == [np.uint32]
        assert attempts[0][0][1] == (mk._packed.nbytes // 4,)


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


# ---------------------------------------------------------------------------
# a sample engine's program never meets the uplink prolog (ISSUE 32, satellite
# 2; since ISSUE 36 an engine built on a wire decodes words, and only it)
# ---------------------------------------------------------------------------

def test_serving_program_has_no_uplink_prolog():
    """A ``ServeEngine`` built without a wire compiles ``Pipeline.fn()`` under
    its slot program and ships raw complex64 through ``xfer.wire_part`` /
    ``join_parts``: what the slot program lowers to for the FM front end at
    [64, 65536] (the ``fm_serve_sat`` / ``fm_serve_paced`` cells' shape) names
    neither ``unpack`` nor ``wire_decode``, and no source file under
    ``futuresdr_tpu/serve/`` reaches for the streamed path's wired program or
    its layout — so a change to the coalesced uplink cannot move those
    cells. The one thing serving shares with it since ISSUE 36 is
    ``Sc16Wire.decode_words_jax``, reached only by ``build_slot_program``'s
    ``wire`` argument (``tests/test_serve_sc16.py``)."""
    import pathlib
    import re

    import jax
    from futuresdr_tpu.apps.fm_receiver import front_end_stages
    from futuresdr_tpu.ops import Pipeline
    from futuresdr_tpu.serve.engine import build_slot_program
    pipe = Pipeline(front_end_stages(), np.complex64)
    # frame_size 65536 as the cells give it: the engine rides the largest
    # multiple of the chain's frame multiple below it (65500)
    cap = 64
    fs = 65536 // pipe.frame_multiple * pipe.frame_multiple
    prog = build_slot_program(pipe, cap)
    pages = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((cap,) + np.shape(l),
                                       np.asarray(l).dtype),
        pipe.init_carry())
    text = prog.lower(pages,
                      jax.ShapeDtypeStruct((cap,), np.int32),
                      jax.ShapeDtypeStruct((cap,), np.bool_),
                      jax.ShapeDtypeStruct((cap, fs), np.complex64),
                      jax.ShapeDtypeStruct((cap,), np.bool_)) \
        .as_text(debug_info=True)
    assert "serve_gather" in text                  # the scopes ARE in the text
    assert not re.search(r"/(unpack|wire_decode|wire_encode)/", text)
    serve = pathlib.Path(xfer.__file__).resolve().parents[1] / "serve"
    files = sorted(serve.glob("*.py"))
    assert files
    for f in files:
        src = f.read_text()
        hit = re.search(r"compile_wired|wired_fn|PackedLayout|PackedAlloc|"
                        r"unpack_jax|decode_jax|encode_jax|encode_host", src)
        assert hit is None, (f.name, hit.group(0))
        # the word decode: one call site, in the slot program's builder
        assert len(re.findall(r"decode_words_jax\(", src)) == \
            (1 if f.name == "engine.py" else 0), f.name
