"""Interior-precision lowering correctness (ops/precision.py) + the Pallas
PFB/FIR hot kernels (ops/pallas_kernels.py) + the per-call-site ``impl=``
plumbing and per-dtype chip peaks that ride the same PR.

The contract under test (docs/tpu_notes.md "Interior precision"):

* ``interior_precision="off"`` is BIT-identical to an unlowered build — the
  planner returns the SAME pipeline object.
* ``"auto"`` lowers only where the MEASURED per-edge SNR vs the f32 reference
  clears the budget; refusals carry machine-readable reasons; the end-to-end
  composition guard rolls the whole plan back when the sink SNR blows the
  incoherent-sum allowance.
* Lowered programs keep the full streaming contract: carry checkpoint/replay
  round-trips bf16 leaves bit-exactly, fan-out/DAG shapes lower per node,
  merges decline.
* The Pallas kernels are tolerance-pinned against the matmul paths they
  replace, including ragged tails that exercise the block padding.
"""

import json
from fractions import Fraction

import numpy as np
import pytest

import jax.numpy as jnp

from futuresdr_tpu.ops import precision as P
from futuresdr_tpu.ops.stages import (DagPipeline, FanoutPipeline, MergeStage,
                                      Pipeline, Stage, channelizer_stage,
                                      fft_stage, fir_stage, mag2_stage)


def _run(pipe, x, frame=None):
    """Compile + run one frame through a pipeline, return host output."""
    fn, c = pipe.compile(len(x) if frame is None else frame, donate=False)
    _c, y = fn(c, jnp.asarray(x))
    return np.asarray(y)


def _stream(pipe, x, frame):
    """Run ``x`` through ``pipe`` frame by frame (carry chained); returns the
    concatenated output and the final carry."""
    fn, c = pipe.compile(frame, donate=False)
    outs = []
    for i in range(0, len(x), frame):
        c, y = fn(c, jnp.asarray(x[i:i + frame]))
        outs.append(np.asarray(y))
    return np.concatenate(outs), c


def _frames(n, dtype=np.complex64, seed=0):
    rng = np.random.default_rng(seed)
    if np.issubdtype(np.dtype(dtype), np.complexfloating):
        return ((rng.standard_normal(n) + 1j * rng.standard_normal(n))
                / np.sqrt(2)).astype(dtype)
    return rng.standard_normal(n).astype(dtype)


def _chain():
    taps = np.hanning(64).astype(np.float32)
    taps /= taps.sum()
    return [fir_stage(taps, fft_len=2048, name="fir"), fft_stage(2048)]


# ---------------------------------------------------------------------------
# planner: off / auto / bf16 / overrides / declines
# ---------------------------------------------------------------------------

def test_off_returns_same_object():
    p = Pipeline(_chain(), np.complex64)
    low, plan = P.plan_interior_precision(p, mode="off")
    assert low is p                     # bit-identical BY CONSTRUCTION
    assert plan.mode == "off" and plan.lowered == 0
    # config default is off: the no-arg form is also the same object
    low2, _ = P.plan_interior_precision(p)
    assert low2 is p


def test_auto_lowers_fir_fft_within_budget():
    p = Pipeline(_chain(), np.complex64)
    low, plan = P.plan_interior_precision(p, mode="auto", budget_db=40.0)
    assert low is not p
    assert plan.lowered == 2            # fir accum+edge, fft accum
    assert plan.declined_e2e is False
    # every accepted lowering carries a measured SNR ≥ budget (inf = exact)
    for e in plan.edges:
        if e.edge == "bf16" and e.edge_snr_db is not None:
            assert e.edge_snr_db >= 40.0
    # the sink SNR the guard measured clears the incoherent-sum floor
    assert plan.e2e_snr_db >= 40.0 - 10 * np.log10(plan.lowered)
    # and the pinned floor exists and sits in the bf16 band
    assert plan.min_snr_db is not None and plan.min_snr_db >= 40.0
    # tolerance pin vs the f32 reference on fresh data
    x = _frames(1 << 14, seed=3)
    yr, yl = _run(p, x), _run(low, x)
    err = float(np.mean(np.abs(yl - yr) ** 2))
    sig = float(np.mean(np.abs(yr) ** 2))
    assert 10 * np.log10(sig / max(err, 1e-30)) >= 37.0


def test_tight_budget_declines_everything():
    """Stages whose lowering has REAL cost on this backend (bf16-cast carried
    weights — the OS-FIR/FFT accum knob is an MXU precision flag that is
    exact on CPU, so those measure inf and rightly pass any budget) must all
    decline under an unmeetable budget and return the original object."""
    taps = np.hanning(128).astype(np.float32)
    taps /= taps.sum()
    p = Pipeline([fir_stage(taps, decim=16, name="dec"),
                  _noise_stage("nz", 50.0)], np.complex64)
    low, plan = P.plan_interior_precision(p, mode="auto", budget_db=200.0)
    assert low is p                     # nothing lowered → original object
    assert plan.lowered == 0
    # refusals are recorded with reasons, not silently dropped
    reasons = [e.declined for e in plan.edges]
    assert any(r and "snr<" in r for r in reasons)


def test_bf16_mode_force_lowers_and_still_measures():
    p = Pipeline(_chain(), np.complex64)
    low, plan = P.plan_interior_precision(p, mode="bf16", budget_db=200.0)
    assert plan.mode == "bf16"
    assert plan.lowered == 2            # budget ignored
    # SNR is still MEASURED and reported (the honest-force contract)
    assert plan.e2e_snr_db is not None
    assert plan.declined_e2e is False   # the e2e guard is auto-only


def test_override_off_pins_stage_f32():
    p = Pipeline(_chain(), np.complex64)
    _low, plan = P.plan_interior_precision(
        p, mode="bf16", overrides={"fir": "off"})
    d = {e.stage: e for e in plan.edges}
    assert d["fir"].accum == "f32" and d["fir"].edge == "f32"
    assert d["fir"].declined == "override"
    assert d["fft2048"].accum == "bf16"


def test_override_string_form_and_bad_value():
    assert P.parse_overrides("fir=off;fft2048=bf16") == {
        "fir": "off", "fft2048": "bf16"}
    assert P.parse_overrides("") == {}
    with pytest.raises(ValueError):
        P.parse_overrides("fir=fp8")


def test_bad_mode_raises():
    p = Pipeline(_chain(), np.complex64)
    with pytest.raises(ValueError):
        P.plan_interior_precision(p, mode="int4")


def test_non_float_edges_decline():
    """An integer-valued edge (symbol stream) must pass through untouched."""
    sym = Stage(lambda c, x: (c, (jnp.abs(x) > 0.5).astype(jnp.int32)),
                lambda d: jnp.zeros(()), Fraction(1, 1), np.int32, 1, "slice")
    widen = Stage(lambda c, x: (c, x.astype(jnp.float32) * 2.0),
                  lambda d: jnp.zeros(()), Fraction(1, 1), np.float32, 1,
                  "widen")
    p = Pipeline([sym, widen], np.float32)
    _low, plan = P.plan_interior_precision(p, mode="bf16")
    d = {e.stage: e for e in plan.edges}
    assert d["slice"].declined == "non-float"
    assert d["slice"].accum == "f32" and d["slice"].edge == "f32"


def test_int8_ladder_reaches_declaring_stage():
    """The int8 rung is tried first wherever a stage's ``lower`` hook accepts
    it — the mechanism pinned with a synthetic declaring stage (scale-by-2
    rebuilt at int8 as an exact int op), independent of the FIR family's
    real int8 forms (tested below)."""
    def lower(prec):
        if prec not in ("int8", "bf16"):
            return None
        return Stage(lambda c, x: (c, (x.astype(jnp.int8) * 2)
                                   .astype(jnp.float32)),
                     lambda d: jnp.zeros(()), Fraction(1, 1), np.float32, 1,
                     "dbl", compute_dtype="bf16")

    dbl = Stage(lambda c, x: (c, x * 2.0), lambda d: jnp.zeros(()),
                Fraction(1, 1), np.float32, 1, "dbl", lower=lower)
    sink = Stage(lambda c, x: (c, x + 0.0), lambda d: jnp.zeros(()),
                 Fraction(1, 1), np.float32, 1, "sink")
    p = Pipeline([dbl, sink], np.float32)

    # int8-exact inputs: the int8 candidate is bit-exact → SNR inf → accepted
    # at the FIRST (most-compressed) rung
    def frames(in_dtype, frame, n, seed):
        rng = np.random.default_rng(seed)
        return [rng.integers(-50, 50, frame).astype(np.float32)
                for _ in range(n)]
    orig = P._calib_frames
    P._calib_frames = frames
    try:
        _low, plan = P.plan_interior_precision(p, mode="auto", budget_db=40.0)
    finally:
        P._calib_frames = orig
    d = {e.stage: e for e in plan.edges}
    assert d["dbl"].accum == "int8"


def test_int8_mode_forces_fir_rung_and_carry_compat():
    """mode="int8" walks the FIR family down to the quantized int8 matmul
    form (edges stay bf16 — forced modes never widen the wire), mode="bf16"
    must NOT force-accept the deeper rung, and the int8-lowered carries
    stay treedef/shape-compatible with the f32 chain's (the serve brownout
    leafwise-conversion contract: int8 stages carry FLOAT weights and
    quantize in-trace)."""
    import jax
    p = Pipeline(_chain() + [mag2_stage()], np.complex64)
    low, plan = P.plan_interior_precision(p, mode="int8")
    d = {e.stage: e for e in plan.edges}
    assert d["fir"].accum == "int8"
    cd = {s.name: s.compute_dtype for s in low.stages}
    assert cd["fir"] == "int8"
    for e in plan.edges:
        assert e.edge in ("bf16", "f32")        # int8 never hits the wire

    # forced bf16 stays bf16 — the deeper rung needs mode="int8"
    _lb, plan_b = P.plan_interior_precision(p, mode="bf16")
    db = {e.stage: e for e in plan_b.edges}
    assert db["fir"].accum == "bf16"

    # carry compatibility: same treedefs, same leaf shapes (dtype may
    # narrow — the brownout converts leafwise)
    a_l, a_def = jax.tree_util.tree_flatten(p.init_carry())
    b_l, b_def = jax.tree_util.tree_flatten(low.init_carry())
    assert a_def == b_def
    assert [np.shape(a) for a in a_l] == [np.shape(b) for b in b_l]

    # numerics: the quantization band, not garbage — and decim paths too
    x = _frames(4 * 4096, seed=31)
    ref, _ = _stream(p, x, 4096)
    got, _ = _stream(low, x, 4096)
    err = float(np.mean(np.abs(got - ref) ** 2))
    sig = float(np.mean(np.abs(ref) ** 2))
    assert 10 * np.log10(sig / max(err, 1e-30)) >= 25.0

    taps = np.hanning(96).astype(np.float32)
    taps /= taps.sum()
    pd = Pipeline([fir_stage(taps, decim=8, impl="poly", name="dfir")],
                  np.complex64)
    lowd, pland = P.plan_interior_precision(pd, mode="int8")
    assert {e.stage: e.accum for e in pland.edges}["dfir"] == "int8"
    refd, _ = _stream(pd, x, 4096)
    gotd, _ = _stream(lowd, x, 4096)
    errd = float(np.mean(np.abs(gotd - refd) ** 2))
    sigd = float(np.mean(np.abs(refd) ** 2))
    assert 10 * np.log10(sigd / max(errd, 1e-30)) >= 25.0

    # int8 routes never count as Pallas stages (they lower to quantized
    # XLA matmuls, not hand-written kernels)
    assert P.pallas_stage_count(lowd) == 0


def _noise_stage(name, snr_target_db, phase=0.0):
    """Identity stage whose bf16-lowering candidate adds a DETERMINISTIC
    noise vector at exactly ``snr_target_db`` below unit power — the e2e
    guard's test vehicle (same ``phase`` → coherent noise across stages)."""
    eps = 10.0 ** (-snr_target_db / 20.0)

    def fn(c, x):
        return c, x

    def lower(prec):
        if prec != "bf16":
            return None

        def lfn(c, x):
            i = jnp.arange(x.shape[0], dtype=jnp.float32)
            n = jnp.sin(12.9898 * i + phase)
            n = n / jnp.sqrt(jnp.mean(n * n))      # exactly unit power
            return c, x + eps * n.astype(x.dtype)

        return Stage(lfn, lambda d: jnp.zeros(()), Fraction(1, 1), None, 1,
                     name, compute_dtype="bf16")

    return Stage(fn, lambda d: jnp.zeros(()), Fraction(1, 1), None, 1, name,
                 lower=lower)


def test_e2e_guard_rolls_back_coherent_composition():
    """Four stages whose per-edge SNR each clears the budget but whose noise
    adds COHERENTLY compose to 20·log10(4) = 12 dB worse — past the
    incoherent-sum allowance (10·log10(4) ≈ 6 dB), so the auto plan must
    decline as a whole and return the original pipeline."""
    budget = 60.0
    stages = [_noise_stage(f"n{i}", budget + 3.0, phase=1.0)
              for i in range(4)]
    p = Pipeline(stages, np.float32)
    low, plan = P.plan_interior_precision(p, mode="auto", budget_db=budget)
    assert plan.declined_e2e is True
    assert low is p
    assert plan.lowered == 0            # verdicts rolled back
    assert all(e.declined and e.declined.startswith("e2e-snr<")
               for e in plan.edges)


def test_e2e_guard_keeps_incoherent_composition():
    """Two stages with INDEPENDENT noise at budget+3 compose ~3 dB worse —
    inside the allowance, so the plan stands."""
    budget = 60.0
    stages = [_noise_stage("na", budget + 3.0, phase=1.0),
              _noise_stage("nb", budget + 3.0, phase=40.7)]
    p = Pipeline(stages, np.float32)
    low, plan = P.plan_interior_precision(p, mode="auto", budget_db=budget)
    assert plan.declined_e2e is False
    assert low is not p
    assert plan.lowered == 2


# ---------------------------------------------------------------------------
# graph shapes: fan-out, DAG, merge declines
# ---------------------------------------------------------------------------

def test_fanout_pipeline_lowers_per_node():
    taps = np.hanning(32).astype(np.float32)
    taps /= taps.sum()
    fan = FanoutPipeline([fir_stage(taps, name="prod")],
                         [[fft_stage(256)], [mag2_stage()]], np.complex64)
    low, plan = P.plan_interior_precision(fan, mode="auto", budget_db=40.0)
    assert isinstance(low, FanoutPipeline)
    assert plan.lowered >= 1
    x = _frames(4096, seed=5)
    fn_r, c_r = fan.compile(4096, donate=False)
    fn_l, c_l = low.compile(4096, donate=False)
    _c, ys_r = fn_r(c_r, jnp.asarray(x))
    _c, ys_l = fn_l(c_l, jnp.asarray(x))
    for yr, yl in zip(ys_r, ys_l):
        yr, yl = np.asarray(yr), np.asarray(yl)
        err = float(np.mean(np.abs(yl - yr) ** 2))
        sig = float(np.mean(np.abs(yr) ** 2))
        assert 10 * np.log10(sig / max(err, 1e-30)) >= 37.0


def test_dag_merge_declines_and_dag_lowers():
    taps = np.hanning(16).astype(np.float32)
    taps /= taps.sum()
    merge = MergeStage(lambda c, xs: (c, xs[0] + xs[1]),
                       lambda d: jnp.zeros(()), k=2, name="sum")
    dag = DagPipeline([
        ([fir_stage(taps, name="prod")], []),
        ([fft_stage(256)], [0]),
        ([fft_stage(256, direction="inverse")], [0]),
        ([merge], [1, 2]),
    ], np.complex64)
    low, plan = P.plan_interior_precision(dag, mode="bf16")
    d = {e.stage: e for e in plan.edges}
    assert d["sum"].declined == "merge"
    assert plan.lowered >= 2
    x = _frames(4096, seed=6)
    yr = _run(dag, x)
    yl = _run(low, x)
    err = float(np.mean(np.abs(yl - yr) ** 2))
    sig = float(np.mean(np.abs(yr) ** 2))
    assert 10 * np.log10(sig / max(err, 1e-30)) >= 37.0


# ---------------------------------------------------------------------------
# streaming contract: carry dtypes, checkpoint/replay round trip
# ---------------------------------------------------------------------------

def test_lowered_poly_fir_carries_bf16_weights():
    taps = np.hanning(128).astype(np.float32)
    taps /= taps.sum()
    p = Pipeline([fir_stage(taps, decim=16, name="dec")], np.complex64)
    low, plan = P.plan_interior_precision(p, mode="bf16")
    assert plan.lowered == 1
    carry = low.init_carry()
    import jax
    leaves = jax.tree_util.tree_flatten(carry)[0]
    dts = {str(np.asarray(l).dtype) for l in leaves}
    assert "bfloat16" in dts            # the carried weight matrix halved


def test_lowered_checkpoint_replay_bit_identical():
    """snapshot_carry → restore_carry of a LOWERED pipeline reproduces the
    unfailed run bit-for-bit (bf16 leaves round-trip the host hop)."""
    taps = np.hanning(128).astype(np.float32)
    taps /= taps.sum()
    frame = 8192
    x = _frames(4 * frame, seed=9)
    p = Pipeline([fir_stage(taps, decim=16, name="dec"), fft_stage(256)],
                 np.complex64)
    low, _plan = P.plan_interior_precision(p, mode="bf16")

    ref, _c = _stream(low, x, frame)

    # run 2 frames, checkpoint, restore into a FRESH compile, run the rest
    fn, c = low.compile(frame, donate=False)
    outs = []
    for i in range(0, 2 * frame, frame):
        c, y = fn(c, jnp.asarray(x[i:i + frame]))
        outs.append(np.asarray(y))
    fins, treedef = low.snapshot_carry(c)
    leaves = [np.asarray(f()) for f in fins]
    assert low.carry_matches(leaves, treedef, low.init_carry())
    c2 = low.restore_carry(leaves, treedef)
    fn2, _fresh = low.compile(frame, donate=False)
    for i in range(2 * frame, 4 * frame, frame):
        c2, y = fn2(c2, jnp.asarray(x[i:i + frame]))
        outs.append(np.asarray(y))
    got = np.concatenate(outs)
    np.testing.assert_array_equal(got, ref)


def test_mismatched_dtype_checkpoint_rejected():
    """A checkpoint taken from the f32 build must FAIL the lowered build's
    carry integrity check (the dtype contract the restore path enforces)."""
    taps = np.hanning(128).astype(np.float32)
    taps /= taps.sum()
    p = Pipeline([fir_stage(taps, decim=16, name="dec")], np.complex64)
    low, _plan = P.plan_interior_precision(p, mode="bf16")
    fn, c = p.compile(8192, donate=False)
    c, _y = fn(c, jnp.asarray(_frames(8192)))
    fins, treedef = p.snapshot_carry(c)
    leaves = [np.asarray(f()) for f in fins]
    assert p.carry_matches(leaves, treedef, p.init_carry())
    assert not low.carry_matches(leaves, treedef, low.init_carry())


# ---------------------------------------------------------------------------
# kernel plane: off bit-identity, pre-init retune scoping, plan publication
# ---------------------------------------------------------------------------

def _kernel_run(x, frame, **kw):
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.tpu import TpuKernel
    fg = Flowgraph()
    src = VectorSource(x)
    tk = TpuKernel(_chain(), np.complex64, frame_size=frame, **kw)
    snk = VectorSink(np.complex64)
    fg.connect(src, tk, snk)
    Runtime().run(fg)
    return np.asarray(snk.items()), tk


def test_kernel_off_bit_identical_and_auto_within_budget():
    x = _frames(1 << 15, seed=11)
    y_default, _ = _kernel_run(x, 8192)
    y_off, tk_off = _kernel_run(x, 8192, interior_precision="off")
    np.testing.assert_array_equal(y_default, y_off)
    assert tk_off._precision_plan is None
    assert tk_off.extra_metrics()["interior_precision"] == "off"

    y_auto, tk = _kernel_run(x, 8192, interior_precision="auto")
    assert tk._precision_plan is not None and tk._precision_plan.lowered == 2
    assert tk.extra_metrics()["interior_lowered"] == 2
    err = float(np.mean(np.abs(y_auto - y_off) ** 2))
    sig = float(np.mean(np.abs(y_off) ** 2))
    assert 10 * np.log10(sig / max(err, 1e-30)) >= 37.0
    # the applied plan is published under the kernel's program name for
    # doctor.report()["precision"] and the REST profile view
    plans = P.plans_report()
    hit = [v for v in plans.values() if v["mode"] == "auto"]
    assert hit and hit[-1]["lowered"] == 2


def test_precision_retune_preinit_scopes_to_named_stage():
    """A single-stage retune on an 'off' kernel lowers ONLY that stage —
    entering auto mode must not drag the rest of the chain with it."""
    from futuresdr_tpu.tpu import TpuKernel
    tk = TpuKernel(_chain(), np.complex64, frame_size=8192,
                   interior_precision="off")
    tk.apply_precision_retune("fft2048", "bf16")
    plan = tk._precision_plan
    d = {e.stage: e for e in plan.edges}
    assert d["fft2048"].accum == "bf16"
    assert d["fir"].accum == "f32" and d["fir"].edge == "f32"
    assert d["fir"].declined == "override"
    with pytest.raises(ValueError):
        tk.apply_precision_retune("fir", "fp8")
    with pytest.raises(KeyError):
        tk.apply_precision_retune("nope", "bf16")


def test_widening_retune_restores_pristine_parameters():
    """Retuning bf16 → off must take WIDENED parameter leaves from the
    pristine template, not upcast the quantized bf16 values — an 'f32'
    program carrying frozen bf16 quantization would be a silent lie."""
    import jax
    import time
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import Throttle, VectorSink, VectorSource
    from futuresdr_tpu.tpu import TpuKernel
    from futuresdr_tpu.types import Pmt

    taps = np.hanning(128).astype(np.float32)
    taps /= taps.sum()
    n = 1 << 16
    x = _frames(n, seed=41)
    fg = Flowgraph()
    src = VectorSource(x)
    thr = Throttle(np.complex64, rate=300_000.0)
    tk = TpuKernel([fir_stage(taps, decim=16, name="dec")], np.complex64,
                   frame_size=8192, frames_in_flight=2,
                   interior_precision="bf16")
    snk = VectorSink(np.complex64)
    fg.connect(src, thr, tk, snk)
    rt = Runtime()
    running = rt.start(fg)
    t0 = time.perf_counter()
    while len(snk.items()) < n // 64 and time.perf_counter() - t0 < 30:
        time.sleep(0.02)
    r = rt.scheduler.run_coro_sync(running.handle.call(
        tk, "ctrl", Pmt.map({"stage": "dec", "interior_precision": "off"})))
    assert r == Pmt.ok()
    running.wait_sync()
    assert len(snk.items()) == (n // 8192) * 8192 // 16
    # the widened W leaf is BIT-equal to the pristine f32 build's parameter
    # (inspected AFTER the drain — mid-stream the carry buffers are donated;
    # dispatches thread W through unchanged, so the pin holds at the end)
    ref = {a.tobytes() for a in
           (np.asarray(l) for l in jax.tree_util.tree_flatten(
               tk._base_pipeline.init_carry())[0])
           if a.dtype == np.float32 and a.ndim == 2}
    got = [np.asarray(l) for l in jax.tree_util.tree_flatten(tk._carry)[0]
           if np.asarray(l).dtype == np.float32 and np.asarray(l).ndim == 2]
    assert got and all(w.tobytes() in ref for w in got)


def test_noop_retune_keeps_off_mode_and_program():
    """Pinning 'off' on an already-off kernel must not recompile or flip the
    reported mode to 'auto' — the program is unchanged."""
    from futuresdr_tpu.tpu import TpuKernel
    tk = TpuKernel(_chain(), np.complex64, frame_size=8192,
                   interior_precision="off")
    pipe = tk.pipeline
    tk.apply_precision_retune("fir", "off")
    assert tk.pipeline is pipe
    assert tk._precision_mode == "off"
    assert tk.extra_metrics()["interior_precision"] == "off"
    # the pin is still remembered for later retunes of OTHER stages
    assert tk._precision_overrides["fir"] == "off"


def test_kernel_init_corrects_stale_precision_axis():
    """An off-mode kernel's init must overwrite a stale lowering stamp in
    the streamed-pick cache (a cached K measured under bf16 must not claim
    to describe an f32 rebuild) — and must NOT create entries for chains
    that were never tuned or lowered."""
    from futuresdr_tpu.tpu.autotune import (cached_interior_precision,
                                            record_interior_precision)
    x = _frames(1 << 14, seed=43)
    stages = _chain()
    record_interior_precision(stages, np.complex64, "cpu", "bf16")
    _y, tk = _kernel_run(x, 8192, interior_precision="off")
    assert cached_interior_precision(
        stages, np.complex64, tk.inst.platform) == "off"
    # a DIFFERENT never-stamped chain gains no entry from an off-mode init
    other = [fir_stage(np.hanning(32).astype(np.float32), name="solo")]
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.tpu import TpuKernel
    fg = Flowgraph()
    tk2 = TpuKernel(other, np.complex64, frame_size=8192,
                    interior_precision="off")
    fg.connect(VectorSource(x), tk2, VectorSink(np.complex64))
    Runtime().run(fg)
    assert cached_interior_precision(
        other, np.complex64, tk2.inst.platform) is None


def test_doctor_and_profile_report_carry_plans():
    from futuresdr_tpu.telemetry import doctor as doc
    from futuresdr_tpu.telemetry import profile as prof
    p = Pipeline(_chain(), np.complex64)
    _low, plan = P.plan_interior_precision(p, mode="auto", budget_db=40.0)
    P.note_plan("t-precision-prog", plan)
    try:
        snap = prof.plane().snapshot()
        assert snap["precision"]["t-precision-prog"]["lowered"] == 2
        rep = doc.report([])
        assert rep["precision"]["t-precision-prog"]["mode"] == "auto"
        # the view is JSON-clean (REST body)
        json.dumps(snap["precision"])
    finally:
        P.clear_plans()


# ---------------------------------------------------------------------------
# Pallas kernels: PFB + fused FIR→decimate vs the matmul paths
# ---------------------------------------------------------------------------

def _pfb_matmul_ref(rows, taps_kn):
    """Reference: the channelizer matmul path's branch MAC + ifft·N."""
    K, N = taps_kn.shape
    t = rows.shape[0] - (K - 1)
    windows = np.stack([rows[(K - 1) - k:(K - 1) - k + t] for k in range(K)],
                       axis=1)                       # [t, K, N]
    v = np.einsum("tkc,kc->tc", windows, taps_kn)
    return np.fft.ifft(v, axis=1) * N


@pytest.mark.parametrize("t,block", [(37, 8), (64, 64), (200, 256), (1, 4)])
def test_pallas_pfb_matches_matmul_ragged(t, block):
    """Tolerance pin vs the matmul path, incl. ragged tails where t is not a
    block multiple (the EOS-tail shape after frame padding)."""
    from futuresdr_tpu.ops.pallas_kernels import pallas_pfb
    rng = np.random.default_rng(t)
    K, N = 4, 16
    taps = rng.standard_normal((K, N)).astype(np.float32)
    rows = (rng.standard_normal((t + K - 1, N))
            + 1j * rng.standard_normal((t + K - 1, N))).astype(np.complex64)
    got = np.asarray(pallas_pfb(jnp.asarray(rows), jnp.asarray(taps),
                                block=block))
    ref = _pfb_matmul_ref(rows, taps)
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)


def test_pallas_pfb_bf16_band():
    from futuresdr_tpu.ops.pallas_kernels import pallas_pfb
    rng = np.random.default_rng(2)
    K, N = 4, 32
    taps = (rng.standard_normal((K, N)) / K).astype(np.float32)
    rows = (rng.standard_normal((512 + K - 1, N))
            + 1j * rng.standard_normal((512 + K - 1, N))).astype(np.complex64)
    ref = np.asarray(pallas_pfb(jnp.asarray(rows), jnp.asarray(taps)))
    got = np.asarray(pallas_pfb(jnp.asarray(rows), jnp.asarray(taps),
                                precision="bf16"))
    err = float(np.mean(np.abs(got - ref) ** 2))
    sig = float(np.mean(np.abs(ref) ** 2))
    snr = 10 * np.log10(sig / max(err, 1e-30))
    assert 35.0 <= snr                      # bf16 band, far above sc8


def test_channelizer_pallas_impl_matches_matmul():
    x = _frames(8192, seed=13)
    ym = _run(Pipeline([channelizer_stage(16, impl="matmul")], np.complex64), x)
    yp = _run(Pipeline([channelizer_stage(16, impl="pallas")], np.complex64), x)
    err = float(np.mean(np.abs(yp - ym) ** 2))
    sig = float(np.mean(np.abs(ym) ** 2))
    assert 10 * np.log10(sig / max(err, 1e-30)) >= 80.0


def test_channelizer_lower_hook_roundtrip():
    st = channelizer_stage(16, impl="matmul")
    low = st.lower("bf16")
    assert low is not None and low.compute_dtype == "bf16"
    assert st.lower("int8") is None


@pytest.mark.parametrize("nq,m,block", [(1, 3, 4), (100, 7, 16), (513, 1, 256)])
def test_pallas_poly_fir_matches_matvec_ragged(nq, m, block):
    from futuresdr_tpu.ops.pallas_kernels import pallas_poly_fir
    rng = np.random.default_rng(nq)
    D = 8
    W = rng.standard_normal((m + 1, D)).astype(np.float32)
    rows = rng.standard_normal((nq + m, D)).astype(np.float32)
    got = np.asarray(pallas_poly_fir(jnp.asarray(rows), jnp.asarray(W),
                                     block=block))
    ref = np.zeros(nq, np.float32)
    for a in range(m + 1):
        ref += rows[m - a:m - a + nq] @ W[a]
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_fir_stage_pallas_impl_matches_poly_decim():
    taps = np.hanning(128).astype(np.float32)
    taps /= taps.sum()
    x = _frames(8192, seed=17)
    ya = _run(Pipeline([fir_stage(taps, decim=16, impl="poly")], np.complex64), x)
    yb = _run(Pipeline([fir_stage(taps, decim=16, impl="pallas")], np.complex64), x)
    np.testing.assert_allclose(yb, ya, rtol=1e-4, atol=1e-5)


def test_fir_stage_pallas_decim_streaming_matches_poly():
    """Streaming (carry-chained) equality across frames — the history rows
    crossing dispatch boundaries are the part the fused kernel must get
    right."""
    taps = np.hanning(96).astype(np.float32)
    taps /= taps.sum()
    x = _frames(4 * 4096, seed=19)
    ya, _ = _stream(Pipeline([fir_stage(taps, decim=8, impl="poly")],
                             np.complex64), x, 4096)
    yb, _ = _stream(Pipeline([fir_stage(taps, decim=8, impl="pallas")],
                             np.complex64), x, 4096)
    np.testing.assert_allclose(yb, ya, rtol=1e-4, atol=1e-5)


def test_lowered_pallas_poly_fir_bf16_band():
    taps = np.hanning(128).astype(np.float32)
    taps /= taps.sum()
    x = _frames(8192, seed=23)
    p = Pipeline([fir_stage(taps, decim=16, impl="pallas")], np.complex64)
    ref = _run(p, x)
    low, plan = P.plan_interior_precision(p, mode="bf16")
    assert plan.lowered == 1
    got = _run(low, x)
    err = float(np.mean(np.abs(got - ref) ** 2))
    sig = float(np.mean(np.abs(ref) ** 2))
    assert 10 * np.log10(sig / max(err, 1e-30)) >= 40.0


def test_pallas_stage_count():
    taps = np.hanning(32).astype(np.float32)
    p = Pipeline([fir_stage(taps, decim=16, impl="pallas", name="d"),
                  fft_stage(256)], np.complex64)
    assert P.pallas_stage_count(p) == 1


def test_lti_merge_preserves_matching_pins_refuses_mixed():
    """Adjacent pinned FIRs merge only when their (fft_impl, precision) pins
    AGREE — and the merged stage keeps them; mixed pins refuse to merge (a
    pin must never silently revert to module policy / f32)."""
    t1 = np.hanning(16).astype(np.float32)
    t2 = np.hanning(8).astype(np.float32)
    same = Pipeline([fir_stage(t1, name="a", precision="bf16"),
                     fir_stage(t2, name="b", precision="bf16")], np.complex64)
    assert len(same.stages) == 1
    assert same.stages[0].compute_dtype == "bf16"
    assert same.stages[0].route[2] == "bf16"
    mixed = Pipeline([fir_stage(t1, name="a", precision="bf16"),
                      fir_stage(t2, name="b")], np.complex64)
    assert len(mixed.stages) == 2
    # unpinned firs keep merging exactly as before
    plain = Pipeline([fir_stage(t1, name="a"), fir_stage(t2, name="b")],
                     np.complex64)
    assert len(plain.stages) == 1


def test_precision_retune_rejects_ambiguous_name():
    """Overrides are name-keyed, so a retune addressing one of two
    same-named stages (by name OR by index) must be rejected, not silently
    lower both."""
    from futuresdr_tpu.tpu import TpuKernel
    taps = np.hanning(16).astype(np.float32)
    tk = TpuKernel([fir_stage(taps, fft_len=256),
                    fft_stage(256),
                    fir_stage(taps, fft_len=256)],
                   np.complex64, frame_size=4096, interior_precision="off")
    with pytest.raises(KeyError, match="ambiguous"):
        tk.apply_precision_retune("fir", "bf16")
    with pytest.raises(KeyError, match="ambiguous"):
        tk.apply_precision_retune(2, "bf16")


def test_pallas_stage_count_respects_pins_and_dtype():
    taps = np.hanning(32).astype(np.float32)
    # explicit matmul pin never counts, forced pallas counts on any backend
    assert P.pallas_stage_count(Pipeline(
        [channelizer_stage(16, impl="matmul")], np.complex64)) == 0
    assert P.pallas_stage_count(Pipeline(
        [channelizer_stage(16, impl="pallas")], np.complex64)) == 1
    assert P.pallas_stage_count(Pipeline(
        [fir_stage(taps, decim=16, impl="pallas")], np.complex64)) == 1
    # auto short-real-taps FIR only counts on TPU, and never on a complex
    # stream (_pallas_fir_wins) — on the CPU test backend both are 0
    assert P.pallas_stage_count(Pipeline(
        [fir_stage(taps[:16])], np.float32)) == 0


def test_partial_lowering_not_reported_declined():
    """A stage whose accum refuses but whose edge lowers IS lowered — the
    plan must not show a decline reason on it (the accum refusal stays
    readable as accum='f32' + its measured SNR)."""
    budget = 52.0          # between the 48 dB accum target and ~55 dB edge
    sink = Stage(lambda c, x: (c, x * 2.0), lambda d: jnp.zeros(()),
                 Fraction(1, 1), None, 1, "gain")
    p = Pipeline([_noise_stage("nz", 48.0), sink], np.float32)
    _low, plan = P.plan_interior_precision(p, mode="auto", budget_db=budget)
    nz = {e.stage: e for e in plan.edges}["nz"]
    assert nz.edge == "bf16"            # edge accepted (~55 ≥ 52)
    assert nz.accum == "f32"            # accum refused (48 < 52)
    assert nz.accum_snr_db == pytest.approx(48.0, abs=1.5)
    assert nz.declined is None          # partially lowered ≠ declined


# ---------------------------------------------------------------------------
# per-call-site impl= plumbing (the ops/mxu_fft.py header promise)
# ---------------------------------------------------------------------------

def test_fft_stage_impl_pins_route_per_call_site():
    """Two fft stages with DIFFERENT impl= in one process keep their own
    routes: the forced-mxu stage runs the direct-DFT matmul (different
    rounding than jnp.fft), the forced-xla stage runs jnp.fft — regardless
    of the module set_impl policy at trace time."""
    from futuresdr_tpu.ops import mxu_fft
    x = _frames(2048, seed=29)
    y_xla = _run(Pipeline([fft_stage(512, impl="xla")], np.complex64), x)
    old = mxu_fft._impl
    mxu_fft.set_impl("xla")             # module policy says xla...
    try:
        y_mxu = _run(Pipeline([fft_stage(512, impl="mxu")], np.complex64), x)
    finally:
        mxu_fft.set_impl(old)
    # ...but the per-call-site pin wins: matmul DFT, not jnp.fft
    assert not np.array_equal(y_mxu, y_xla)
    np.testing.assert_allclose(y_mxu, y_xla, rtol=2e-3, atol=2e-3)


def test_fir_stage_fft_impl_pins_os_core():
    taps = np.hanning(64).astype(np.float32)
    taps /= taps.sum()
    x = _frames(4096, seed=31)
    y_def = _run(Pipeline([fir_stage(taps, fft_len=512)], np.complex64), x)
    y_mxu = _run(Pipeline([fir_stage(taps, fft_len=512, fft_impl="mxu")],
                          np.complex64), x)
    assert not np.array_equal(y_mxu, y_def)     # different FFT route engaged
    np.testing.assert_allclose(y_mxu, y_def, rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# per-dtype chip peaks (utils/roofline + telemetry/profile)
# ---------------------------------------------------------------------------

def test_detect_peaks_dtype_keying(monkeypatch):
    from futuresdr_tpu.config import config
    from futuresdr_tpu.utils.roofline import detect_peaks, dtype_peak_flops
    monkeypatch.setattr(config(), "peak_flops", 200e12)
    monkeypatch.setattr(config(), "peak_hbm_gbps", 800.0)
    base = detect_peaks()
    assert base["flops"] == 200e12              # back-compat: tabled bf16 peak
    f32 = detect_peaks(dtype="f32")
    assert f32["flops"] == 100e12 and f32["dtype"] == "f32"
    bf16 = detect_peaks(dtype="bf16")
    assert bf16["flops"] == 200e12
    assert dtype_peak_flops(base, "f32") == 100e12
    assert dtype_peak_flops(base, None) == 200e12


def test_dominant_dtype_of_lowered_chain():
    from futuresdr_tpu.utils.roofline import dominant_dtype
    p = Pipeline(_chain(), np.complex64)
    assert dominant_dtype(p.stages) == "f32"
    low, _ = P.plan_interior_precision(p, mode="bf16")
    assert dominant_dtype(low.stages) == "bf16"
    assert P.dominant_compute_dtype(low) == "bf16"


# ---------------------------------------------------------------------------
# autotune precision axis
# ---------------------------------------------------------------------------

def test_autotune_norm_entry_precision_axis():
    from futuresdr_tpu.tpu.autotune import _norm_entry
    good = _norm_entry({"k": 4, "inflight": 2, "interior_precision": "bf16"})
    assert good["interior_precision"] == "bf16"
    # a malformed precision field loses ONLY its axis, never (k, inflight,
    # serve_buckets)
    bad = _norm_entry({"k": 4, "inflight": 2, "serve_buckets": [2, 8],
                       "interior_precision": {"mode": "bf16"}})
    assert bad == {"k": 4, "inflight": 2, "serve_buckets": [2, 8]}
    typo = _norm_entry({"k": 4, "inflight": None,
                        "interior_precision": "fp8"})
    assert "interior_precision" not in typo and typo["k"] == 4
    assert _norm_entry("garbage") is None


def test_autotune_precision_axis_roundtrip_and_preservation():
    from futuresdr_tpu.tpu.autotune import (cached_interior_precision,
                                            cached_streamed_pick,
                                            record_interior_precision,
                                            record_streamed_pick)
    st = _chain()
    record_streamed_pick(st, np.complex64, "t-prec-plat", 8, inflight=4)
    record_interior_precision(st, np.complex64, "t-prec-plat", "auto")
    assert cached_interior_precision(st, np.complex64, "t-prec-plat") == "auto"
    entry = cached_streamed_pick(st, np.complex64, "t-prec-plat")
    assert entry["k"] == 8 and entry["inflight"] == 4
    # a later streamed re-tune must NOT wipe the precision axis
    record_streamed_pick(st, np.complex64, "t-prec-plat", 16, inflight=2)
    entry = cached_streamed_pick(st, np.complex64, "t-prec-plat")
    assert entry["k"] == 16
    assert entry["interior_precision"] == "auto"
    # unknown modes are dropped at record time, not stored-then-stripped
    record_interior_precision(st, np.complex64, "t-prec-plat", "fp8")
    assert cached_interior_precision(st, np.complex64, "t-prec-plat") == "auto"
