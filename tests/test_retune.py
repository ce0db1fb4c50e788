"""Runtime retune/tap-swap on the device path (VERDICT r2 item 5).

Carry-resident parameters (FIR spectra/taps, rotator increment) are swapped by
host-side carry surgery between dispatches — no recompile, frames in flight
keep the old values. Reference workflow: the fm-receiver's retune-while-running
(``examples/fm-receiver/src/main.rs:83-155``), here reaching the DEVICE segment.
"""

import numpy as np
import pytest

from futuresdr_tpu.dsp import firdes
from futuresdr_tpu.ops import (Pipeline, fir_stage, mag2_stage, rotator_stage)


def _stream(pipe, fn, carry, x, frame):
    outs = []
    for i in range(0, len(x), frame):
        carry, y = fn(carry, x[i:i + frame])
        outs.append(np.asarray(y))
    return carry, np.concatenate(outs)


@pytest.mark.parametrize("impl", ["os", "pallas", "poly"])
def test_fir_tap_swap_streaming(impl):
    """Swap taps mid-stream on each FIR implementation; after the nt-1 sample
    transient the output exactly matches a filter built with the new taps."""
    rng = np.random.default_rng(0)
    nt, frame, decim = 24, 4096, (2 if impl == "poly" else 1)
    t1 = firdes.kaiser_lowpass(0.1, 0.05)[:nt].astype(np.float32)
    t2 = -firdes.kaiser_lowpass(0.22, 0.05)[:nt].astype(np.float32)
    x = rng.standard_normal(8 * frame).astype(np.float32)

    st = fir_stage(t1, decim=decim, impl=impl)
    pipe = Pipeline([st], np.float32, optimize=False)
    fn = pipe.fn()
    carry = pipe.init_carry()

    half = 4 * frame
    carry, y_a = _stream(pipe, fn, carry, x[:half], frame)
    carry = pipe.update_stage(carry, "fir", taps=t2)
    carry, y_b = _stream(pipe, fn, carry, x[half:], frame)

    ref1 = np.convolve(x, t1)[:half][::decim]
    np.testing.assert_allclose(y_a, ref1.astype(np.float32), atol=2e-3)

    # post-swap steady state: filter t2 continuing with the REAL history of x
    ref2_full = np.convolve(x, t2)[half:half + half]
    ref2 = ref2_full[::decim] if decim > 1 else ref2_full
    settle = nt  # transient: old history filtered by new taps
    np.testing.assert_allclose(y_b[settle:], ref2.astype(np.float32)[settle:],
                               atol=2e-3)
    # and it genuinely changed the response
    assert np.abs(y_b[settle:] - (np.convolve(x, t1)[half:half + half][::decim]
                                  ).astype(np.float32)[settle:]).max() > 1e-2


def test_fir_tap_swap_rejects_length_change():
    st = fir_stage(np.ones(16, np.float32))
    pipe = Pipeline([st], np.float32, optimize=False)
    carry = pipe.init_carry()
    with pytest.raises(ValueError, match="tap count"):
        pipe.update_stage(carry, 0, taps=np.ones(17, np.float32))
    with pytest.raises(KeyError):
        pipe.update_stage(carry, "nope", taps=np.ones(16, np.float32))


def test_fir_tap_swap_rejects_complex_on_real_built():
    """Realness is baked at trace time (pallas / half-spectrum branches): a
    complex swap on a real-built stage must be rejected, not silently truncated."""
    for build in (lambda t: fir_stage(t),
                  lambda t: fir_stage(t, decim=2, impl="poly")):
        st = build(np.ones(16, np.float32))
        pipe = Pipeline([st], np.complex64, optimize=False)
        carry = pipe.init_carry()
        with pytest.raises(ValueError, match="complex"):
            pipe.update_stage(carry, 0, taps=np.ones(16, np.complex64) * 1j)


def test_ctrl_port_accepts_plain_list_taps():
    """Pmt.map wraps Python-list elements as Pmt (VecPmt); the ctrl handler must
    unwrap them — a retune with taps=[...] as a plain list has to work."""
    import asyncio
    from futuresdr_tpu.tpu import TpuKernel
    from futuresdr_tpu.types import Pmt

    taps = firdes.kaiser_lowpass(0.1, 0.05)[:16].astype(np.float32)
    tk = TpuKernel([fir_stage(taps, name="f")], np.float32, frame_size=8192)

    async def drive():
        await tk.init(None, None)
        new = (-taps).tolist()                       # plain Python list of floats
        r = await tk.ctrl_handler(None, None, None,
                                  Pmt.map({"stage": "f", "taps": new}))
        assert r == Pmt.ok(), "list taps rejected"
        # carried spectrum actually changed sign
        Hc = np.asarray(tk._carry[0][0])
        ref = np.fft.rfft(np.concatenate([-taps, np.zeros(tk.pipeline.stages[0].lti[2] - 16)]))
        np.testing.assert_allclose(Hc, ref.astype(np.complex64), atol=1e-5)

    asyncio.run(drive())


def test_rotator_retune_phase_continuous():
    """Retuning the rotator keeps phase continuity — no discontinuity click."""
    fs, frame = 1e6, 4096
    inc1, inc2 = 0.1, -0.3
    x = np.ones(4 * frame, np.complex64)
    st = rotator_stage(inc1)
    pipe = Pipeline([st], np.complex64, optimize=False)
    fn, carry = pipe.fn(), pipe.init_carry()
    carry, y_a = _stream(pipe, fn, carry, x[:2 * frame], frame)
    carry = pipe.update_stage(carry, "rotator", phase_inc=inc2)
    carry, y_b = _stream(pipe, fn, carry, x[2 * frame:], frame)
    y = np.concatenate([y_a, y_b])
    # per-sample phase increments: inc1 for the first half, inc2 after — and the
    # sample AT the boundary continues from the accumulated phase (no reset)
    dphi = np.angle(y[1:] * np.conj(y[:-1]))
    np.testing.assert_allclose(dphi[:2 * frame - 1], inc1, atol=1e-3)
    np.testing.assert_allclose(dphi[2 * frame:], inc2, atol=1e-3)
    # the step INTO the first new-segment sample continues from the accumulated
    # phase (old increment) — that IS the continuity property: no reset, no click
    assert abs(dphi[2 * frame - 1] - inc1) < 1e-3


def test_tpu_kernel_ctrl_port_retune():
    """End-to-end FM-style retune through a running TpuKernel: two stations, the
    device chain's rotator+lowpass selects one; a ctrl message switches to the
    other while frames are in flight."""
    import time
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import Throttle, VectorSink, VectorSource
    from futuresdr_tpu.tpu import TpuKernel
    from futuresdr_tpu.types import Pmt

    fs = 256_000.0
    f_a, f_b = 60_000.0, -90_000.0           # two "stations", distinct amplitudes
    amp_b = 0.25                             # |.|^2: A -> ~1.0, B -> ~0.0625
    n = 1 << 18
    t = np.arange(n) / fs
    x = (np.exp(2j * np.pi * f_a * t) +
         amp_b * np.exp(2j * np.pi * f_b * t)).astype(np.complex64)

    taps = firdes.kaiser_lowpass(0.05, 0.02).astype(np.float32)
    stages = [rotator_stage(-2 * np.pi * f_a / fs, name="tuner"),
              fir_stage(taps, name="chan"),
              mag2_stage()]

    fg = Flowgraph()
    src = VectorSource(x)
    # pace the stream so the mid-flight retune lands before the tail is
    # processed — without this, a loaded machine can drain all frames first
    thr = Throttle(np.complex64, rate=250_000.0)
    tk = TpuKernel(stages, np.complex64, frame_size=16384, frames_in_flight=2)
    snk = VectorSink(np.float32)
    fg.connect(src, thr, tk, snk)
    rt = Runtime()
    running = rt.start(fg)

    # wait until a good chunk has streamed with station A selected
    t0 = time.perf_counter()
    while len(snk.items()) < n // 4 and time.perf_counter() - t0 < 30:
        time.sleep(0.02)
    n_before = len(snk.items())
    assert n_before >= n // 4, n_before

    # retune to station B through the ctrl port, mid-flight
    r = rt.scheduler.run_coro_sync(running.handle.call(
        tk, "ctrl", Pmt.map({"stage": "tuner",
                             "phase_inc": -2 * np.pi * f_b / fs})))
    assert r == Pmt.ok()
    running.wait_sync()
    got = snk.items()
    assert len(got) == n

    # |lowpass(shifted)|^2: station A in band → ~1.0; station B → ~0.0625.
    # The head must show A, the tail must show B — frames in flight at retune
    # time keep A, so only judge well clear of the switchover region.
    head = got[len(taps) * 2:max(n_before - 4 * 16384, len(taps) * 4)]
    tail = got[-(n - n_before) // 4:]
    assert np.median(head) > 0.5, "station A not selected before retune"
    assert np.median(tail) < 0.2, "retune did not take effect on the device path"
    assert np.median(tail) > 0.01, "station B vanished (filter broken post-swap)"


def test_ctrl_port_rejects_garbage():
    from futuresdr_tpu.tpu import TpuKernel
    from futuresdr_tpu.types import Pmt
    import asyncio

    tk = TpuKernel([rotator_stage(0.1, name="r")], np.complex64,
                   frame_size=4096)

    async def call(p):
        return await tk.ctrl_handler(None, None, None, p)

    # unknown stage name → InvalidValue, not a crash (queued pre-init path)
    assert asyncio.run(call(Pmt.f64(1.0))) == Pmt.invalid_value()


def test_tpu_stage_ctrl_port_retune():
    """The frame-plane TpuStage exposes the same ctrl retune contract: a tap
    swap lands mid-stream through the inplace pipeline."""
    import time
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import VectorSink, VectorSource, Throttle
    from futuresdr_tpu.tpu import TpuH2D, TpuStage, TpuD2H
    from futuresdr_tpu.types import Pmt

    nt, frame = 24, 16384
    t1 = firdes.kaiser_lowpass(0.1, 0.05)[:nt].astype(np.float32)
    t2 = -firdes.kaiser_lowpass(0.22, 0.05)[:nt].astype(np.float32)
    n = 16 * frame
    rng = np.random.default_rng(2)
    x = rng.standard_normal(n).astype(np.float32)

    fg = Flowgraph()
    src = VectorSource(x)
    thr = Throttle(np.float32, rate=250_000.0)     # pace so the retune lands mid-run
    h2d = TpuH2D(np.float32, frame_size=frame)
    st = TpuStage([fir_stage(t1, name="f")], np.float32)
    d2h = TpuD2H(np.float32)
    snk = VectorSink(np.float32)
    fg.connect(src, thr, h2d, st, d2h, snk)
    rt = Runtime()
    running = rt.start(fg)
    t0 = time.perf_counter()
    while len(snk.items()) < n // 4 and time.perf_counter() - t0 < 30:
        time.sleep(0.01)
    n_before = len(snk.items())
    assert n_before >= n // 4
    r = running.handle.call_sync(st, "ctrl",
                                 Pmt.map({"stage": "f", "taps": t2.tolist()}))
    assert r == Pmt.ok()
    running.wait_sync()
    got = snk.items()
    assert len(got) == n
    # well before the switch: filter t1; well after: filter t2
    ref1 = np.convolve(x, t1)[:n].astype(np.float32)
    ref2 = np.convolve(x, t2)[:n].astype(np.float32)
    head = slice(nt, max(n_before - 2 * frame, nt + 1))
    np.testing.assert_allclose(got[head], ref1[head], atol=2e-3)
    tail = slice(n - 2 * frame, n)
    np.testing.assert_allclose(got[tail], ref2[tail], atol=2e-3)


def test_tpu_stage_ctrl_before_first_frame():
    """A retune posted before the first frame reaches TpuStage (whose carry
    compiles lazily) must be QUEUED and applied, not silently dropped — the
    whole output then reflects the swapped taps."""
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.tpu import TpuH2D, TpuStage, TpuD2H
    from futuresdr_tpu.types import Pmt

    nt, frame = 16, 16384
    t1 = firdes.kaiser_lowpass(0.1, 0.05)[:nt].astype(np.float32)
    t2 = -firdes.kaiser_lowpass(0.22, 0.05)[:nt].astype(np.float32)
    n = 4 * frame
    x = np.random.default_rng(3).standard_normal(n).astype(np.float32)

    st = TpuStage([fir_stage(t1, name="f")], np.float32)
    # handler fires before any frame: carry is None -> queued
    import asyncio
    r = asyncio.run(st.ctrl_handler(None, None, None,
                                    Pmt.map({"stage": "f", "taps": t2.tolist()})))
    assert r == Pmt.ok()
    assert st._pending_ctrl, "early ctrl was not queued"

    fg = Flowgraph()
    fg.connect(VectorSource(x), TpuH2D(np.float32, frame_size=frame), st,
               TpuD2H(np.float32), (snk := VectorSink(np.float32)))
    Runtime().run(fg)
    got = snk.items()
    assert len(got) == n
    ref2 = np.convolve(x, t2)[:n].astype(np.float32)
    np.testing.assert_allclose(got[nt:], ref2[nt:], atol=2e-3)


def test_tpu_stage_early_ctrl_rejects_bad_stage():
    """An early (pre-carry) ctrl with a bad stage name must reply InvalidValue
    immediately — not ok-then-silently-dropped at first-frame compile."""
    import asyncio
    from futuresdr_tpu.tpu import TpuStage
    from futuresdr_tpu.types import Pmt

    st = TpuStage([fir_stage(np.ones(8, np.float32), name="f")], np.float32)
    r = asyncio.run(st.ctrl_handler(None, None, None,
                                    Pmt.map({"stage": "nope", "taps": [1.0] * 8})))
    assert r == Pmt.invalid_value()
    assert not st._pending_ctrl


def test_xlating_fir_stage_matches_unfolded_chain():
    """The folded tuner (complex taps + decimated-rate residual rotator,
    `xlating_fir_stage`) must match rotator → decimating FIR within f32
    phase-accumulation noise, across frames (carry) and through a retune."""
    import jax

    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage, rotator_stage, xlating_fir_stage
    from futuresdr_tpu.ops.stages import Pipeline

    theta = -2 * np.pi * 100e3 / 1e6
    taps = firdes.lowpass(0.5 / 16 * 0.8, 128).astype(np.float32)
    rng = np.random.default_rng(5)
    n = 1 << 15
    frames = [(rng.standard_normal(n) + 1j * rng.standard_normal(n))
              .astype(np.complex64) for _ in range(3)]

    pA = Pipeline([rotator_stage(theta, name="tuner"),
                   fir_stage(taps, decim=16, fft_len=4096, name="chan")],
                  np.complex64)
    pB = Pipeline([xlating_fir_stage(taps, theta, 16, name="tuner")],
                  np.complex64)
    fa, fb = jax.jit(pA.fn()), jax.jit(pB.fn())
    ca, cb = pA.init_carry(), pB.init_carry()
    for x in frames:
        ca, ya = fa(ca, x)
        cb, yb = fb(cb, x)
        # tolerance dominated by the UNFOLDED path's full-rate f32 phase ramp
        np.testing.assert_allclose(np.asarray(yb), np.asarray(ya), atol=5e-3)

    theta2 = -2 * np.pi * 250e3 / 1e6
    ca = pA.update_stage(ca, "tuner", phase_inc=theta2)
    cb = pB.update_stage(cb, "tuner", phase_inc=theta2)
    ca, ya = fa(ca, frames[0])
    cb, yb = fb(cb, frames[0])
    np.testing.assert_allclose(np.asarray(yb)[32:], np.asarray(ya)[32:],
                               atol=8e-3)
    # base-lowpass swap keeps the translation frequency
    t2 = firdes.lowpass(0.5 / 16 * 0.5, 128).astype(np.float32)
    cb = pB.update_stage(cb, "tuner", taps=t2)
    ca2 = pA.update_stage(pA.init_carry(), "tuner", phase_inc=theta2)
    pA2 = Pipeline([rotator_stage(theta2, name="tuner"),
                    fir_stage(t2, decim=16, fft_len=4096, name="chan")],
                   np.complex64)
    # run both fresh with the new taps at theta2; ignore carried-history transient
    cb2 = pB.update_stage(pB.init_carry(), "tuner", phase_inc=theta2)
    cb2 = pB.update_stage(cb2, "tuner", taps=t2)
    fa2 = jax.jit(pA2.fn())
    ca2, ya = fa2(pA2.init_carry(), frames[1])
    cb2, yb = fb(cb2, frames[1])
    np.testing.assert_allclose(np.asarray(yb), np.asarray(ya), atol=5e-3)
    import pytest
    with pytest.raises(ValueError, match="REAL base"):
        pB.update_stage(cb, "tuner", taps=t2.astype(np.complex64) * 1j)
    with pytest.raises(ValueError, match="tap count"):
        pB.update_stage(cb, "tuner", taps=t2[:64])


def test_complex_retune_compiles_nothing_under_the_pair_shim(monkeypatch):
    """On an accelerator complex parameters ride the pair shim, whose join is a
    jitted program. A retune must reuse the program init_carry compiled — the
    first chip run caught ``update`` handing its committed device to
    ``to_device``, which re-lowered the join inside the ctrl handler. The shim
    is forced on here, as it is off-CPU."""
    import jax

    from futuresdr_tpu.ops import xfer
    from futuresdr_tpu.ops.stages import xlating_fir_stage

    monkeypatch.setattr(xfer, "split_complex_platform", lambda platform: True)
    taps = firdes.lowpass(0.1, 128).astype(np.float32)
    pipe = Pipeline([xlating_fir_stage(taps, -0.3, 4, name="tuner"),
                     fir_stage(taps[:32] * (1 + 0.5j), name="cfir")],
                    np.complex64)
    carry = jax.device_put(pipe.init_carry(), jax.devices()[0])   # committed
    join, _ = xfer._jits()
    before = join._cache_size()
    carry = pipe.update_stage(carry, "tuner", phase_inc=-0.9)
    carry = pipe.update_stage(carry, "tuner", taps=taps[::-1].copy())
    carry = pipe.update_stage(carry, "cfir", taps=taps[:32] * (1 - 0.5j))
    assert join._cache_size() == before


def test_xlating_taps_update_preserves_exact_theta():
    """Round-4 advisory: update(taps=...) without phase_inc must rebuild the
    complex weights with the EXACT translation theta, not a value re-derived
    from the carried float32 increment — the weights must be bit-identical to
    a fresh stage built at the same theta."""
    import jax
    import numpy as np

    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import xlating_fir_stage
    from futuresdr_tpu.ops.stages import Pipeline

    theta = -2 * np.pi * 0.1234567891234  # poorly representable in float32
    taps = firdes.lowpass(0.1, 64).astype(np.float32)
    t2 = firdes.lowpass(0.05, 64).astype(np.float32)

    pipe = Pipeline([xlating_fir_stage(taps, theta, 4, name="x")], np.complex64)
    c = pipe.init_carry()
    c = pipe.update_stage(c, "x", taps=t2)
    fresh = Pipeline([xlating_fir_stage(t2, theta, 4, name="x")],
                     np.complex64).init_carry()
    got_W = np.asarray(jax.device_get(c[0][0]))
    want_W = np.asarray(jax.device_get(fresh[0][0]))
    np.testing.assert_array_equal(got_W, want_W)


# ---------------------------------------------------------------------------
# replay-aware retunes (ISSUE 11 satellite, docs/robustness.md)
# ---------------------------------------------------------------------------

def _mocked_kernel(ck=10):
    """A stateful TpuKernel driven by the Mocker: sparse checkpoint cadence
    so a recovery's restore point predates recent dispatch groups — the
    regime where a logged retune must be RE-APPLIED during replay."""
    from futuresdr_tpu.tpu import TpuKernel
    taps = firdes.lowpass(0.2, 31).astype(np.float32)
    return TpuKernel([fir_stage(taps, fft_len=256, name="f"),
                      rotator_stage(0.05, name="rot")],
                     np.complex64, frame_size=2048, frames_in_flight=2,
                     checkpoint_every=ck)


def _retune_data(n_frames=9):
    rng = np.random.default_rng(21)
    n = 2048 * n_frames
    return (rng.standard_normal(n) + 1j * rng.standard_normal(n)) \
        .astype(np.complex64)


def _drive(m, data, lo, hi):
    """Feed frames [lo, hi) through the mocked kernel and drain."""
    m.input("in", data[lo * 2048:hi * 2048])
    m.run()


def test_replayed_retune_lands_on_exactly_the_original_frame():
    """Acceptance (replay-aware ctrl retunes): with a sparse checkpoint
    cadence, a recovery whose restore point PRECEDES a logged retune
    re-applies the carry surgery at exactly its original dispatch boundary
    during replay — the full output is BIT-IDENTICAL to the unfailed run
    with the same retune timing. (Before this PR the restored carry simply
    lost the surgery: the replayed and subsequent frames recomputed with
    the OLD parameters.)"""
    import asyncio

    from futuresdr_tpu import Mocker
    from futuresdr_tpu.types import Pmt
    data = _retune_data()
    pmt = Pmt.map({"stage": "rot", "phase_inc": -0.11})

    # unfailed reference: 3 frames, retune, 6 more frames
    mk_ref = _mocked_kernel()
    ref = Mocker(mk_ref)
    ref.init_output("out", len(data) * 2)
    ref.init()
    _drive(ref, data, 0, 3)
    assert ref.post("ctrl", pmt) == Pmt.ok()
    _drive(ref, data, 3, 9)
    expected = ref.output("out").copy()

    # faulted run: same timing, then a recovery AFTER the retune whose
    # restore point (the fresh-init sentinel — no commit yet at cadence 10)
    # precedes it: every group replays, the retune must re-land at group 3
    mk = _mocked_kernel()
    m = Mocker(mk)
    m.init_output("out", len(data) * 2)
    m.init()
    _drive(m, data, 0, 3)
    assert m.post("ctrl", pmt) == Pmt.ok()
    _drive(m, data, 3, 6)
    assert mk._retune_log and mk._retune_log[0][0] == 3
    assert asyncio.run(mk.recover(RuntimeError("injected test fault")))
    assert mk._replay_retunes and mk._replay_retunes[0][0] == 3
    _drive(m, data, 6, 9)
    got = m.output("out")
    np.testing.assert_array_equal(got, expected)
    assert not mk._replay_retunes        # consumed at its boundary


def test_retune_during_replay_rejects_bad_params_at_call_site():
    """A retune landing mid-replay with a valid stage but invalid params
    must reject at the call site (InvalidValue), exactly like the same
    retune outside a replay window — NOT return ok and then silently drop
    at the deferred boundary (the deferral branch validates the FULL
    surgery against the current carry, discarding the result)."""
    import asyncio

    from futuresdr_tpu import Mocker
    from futuresdr_tpu.types import Pmt

    data = _retune_data(6)
    mk = _mocked_kernel()
    m = Mocker(mk)
    m.init_output("out", len(data) * 2)
    m.init()
    _drive(m, data, 0, 3)
    assert asyncio.run(mk.recover(RuntimeError("injected test fault")))
    assert mk._replay_queue              # replay window armed, not drained
    assert m.post("ctrl", Pmt.map({"stage": "rot", "bogus_param": 1.0})) \
        == Pmt.invalid_value()
    assert not mk._replay_retunes        # nothing queued for the boundary


def test_retune_with_staged_backlog_logs_the_oldest_unlaunched_group():
    """A retune arriving while dispatch groups are STAGED but not yet
    launched (the credit budget holding them back) mutates the carry those
    groups will dispatch with — so the replay log must record the OLDEST
    unlaunched group's boundary, not the next group to be staged. Logging
    ``self._seq`` there would make a later replay re-dispatch the staged
    groups with the pre-retune parameters."""
    import asyncio

    mk = _mocked_kernel()
    asyncio.run(mk.init(None, None))

    # drained kernel: the boundary IS the next staged seq
    mk._seq = 4
    mk.apply_retune("rot", {"phase_inc": -0.07})
    assert mk._retune_log[-1][0] == 4

    # staged backlog: groups 5 and 6 are staged awaiting credits — the new
    # parameters are visible from group 5 onward
    mk._seq = 7
    mk._staged.append((None, [], 5, False))
    mk._staged.append((None, [], 6, False))
    try:
        mk.apply_retune("rot", {"phase_inc": 0.19})
    finally:
        mk._staged.clear()
    assert mk._retune_log[-1][0] == 5


def test_retune_during_replay_defers_to_post_window_boundary(caplog):
    """A NEW retune arriving while the replay window is still in flight is
    deferred to the post-replay boundary (structured warning upgraded from
    the PR 8 divergence note): replayed frames keep their original
    parameters and the final output is bit-identical to an unfailed run
    where the retune lands at that same frame."""
    import asyncio
    import logging

    from futuresdr_tpu import Mocker
    from futuresdr_tpu.types import Pmt
    data = _retune_data()
    pmt = Pmt.map({"stage": "rot", "phase_inc": 0.21})

    # unfailed reference: retune lands after frame 6
    mk_ref = _mocked_kernel()
    ref = Mocker(mk_ref)
    ref.init_output("out", len(data) * 2)
    ref.init()
    _drive(ref, data, 0, 6)
    assert ref.post("ctrl", pmt) == Pmt.ok()
    _drive(ref, data, 6, 9)
    expected = ref.output("out").copy()

    mk = _mocked_kernel()
    m = Mocker(mk)
    m.init_output("out", len(data) * 2)
    m.init()
    _drive(m, data, 0, 6)
    assert asyncio.run(mk.recover(RuntimeError("injected test fault")))
    assert mk._replay_queue              # replay window armed, not drained
    with caplog.at_level(logging.WARNING, logger="futuresdr_tpu.tpu.kernel"):
        assert m.post("ctrl", pmt) == Pmt.ok()
    msgs = [r.getMessage() for r in caplog.records
            if "replay window" in r.getMessage()]
    assert msgs and "deferred to the post-replay boundary" in msgs[0]
    assert mk._replay_retunes and mk._replay_retunes[0][0] == 6
    _drive(m, data, 6, 9)
    got = m.output("out")
    np.testing.assert_array_equal(got, expected)
