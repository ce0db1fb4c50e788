"""The shifted-row polyphase factorization over MXU-sized rows (ops/stages.py).

``_shifted_matvec`` contracts over rows of width ``R = g·D`` (``_row_width``) with band
matrices built in the trace from the carried D-wide weights. Every case here holds the
re-blocked stage to a float64 direct-form reference AND to the D-wide form it replaced,
and the last tests pin what must not move: the carry tree (pages of ServeEngine's pool,
persisted carries) and the shape of the FM front end's matmuls (what stops the 99
four-wide multiply-reduces of the v5e trace from coming back).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from futuresdr_tpu.apps.fm_receiver import front_end_stages
from futuresdr_tpu.dsp import firdes
from futuresdr_tpu.ops.stages import (Pipeline, _row_width, _shifted_matvec, fir_stage,
                                      resample_stage, xlating_fir_stage)

HI = jax.lax.Precision.HIGHEST


def _noise(rng, n, dtype=np.complex64):
    x = rng.standard_normal(n)
    if np.issubdtype(dtype, np.complexfloating):
        x = x + 1j * rng.standard_normal(n)
    return x.astype(dtype)


def _taps(nt, D, complex_taps, rng):
    t = firdes.lowpass(0.4 / max(D, 2), nt).astype(np.float32)
    if complex_taps:
        t = (t * np.exp(1j * rng.uniform(-1, 1) * np.arange(nt))).astype(np.complex64)
    return t


def _direct64(taps, x, D):
    """y[q] = Σ_t taps[t]·x[qD − t] in float64/complex128, zero history."""
    y = np.convolve(np.asarray(x, np.complex128), np.asarray(taps, np.complex128))
    return y[:len(x):D]


def _d_wide(ext, W, m, nq):
    """The form this factorization replaced: m+1 matvecs over rows of width D."""
    rows = ext.reshape(-1, W.shape[1])
    y = jnp.matmul(rows[m:m + nq], W[0], precision=HI)
    for r in range(1, m + 1):
        y = y + jnp.matmul(rows[m - r:m - r + nq], W[r], precision=HI)
    return y


def _run(stage, frames, dtype=np.complex64):
    f = jax.jit(stage.fn)
    carry, out = stage.init_carry(dtype), []
    for x in frames:
        carry, y = f(carry, x)
        out.append(np.asarray(y))
    return carry, out


# frames per D: one that R = _row_width(D) divides, one that it does not (65500 is the
# served frame: 4·5³·131, which no 128-wide row divides)
FRAMES = {2: (1024, 65500), 4: (4096, 65500), 16: (4096, 65504), 125: (1000, 65500)}


@pytest.mark.parametrize("n_idx", [0, 1])
@pytest.mark.parametrize("complex_taps", [False, True])
@pytest.mark.parametrize("nt", [17, 128])
@pytest.mark.parametrize("D", [2, 4, 16, 125])
def test_decimating_fir_matches_direct_form_and_d_wide_rows(D, nt, complex_taps, n_idx):
    """fir_stage's polyphase route, two consecutive frames (history carry), against the
    float64 direct form and against the D-wide rows computed from the same carry."""
    n = FRAMES[D][n_idx]
    R = _row_width(D)
    assert (n % R == 0) == (n_idx == 0 or R == D)
    rng = np.random.default_rng(D * 1000 + nt + n_idx)
    taps = _taps(nt, D, complex_taps, rng)
    x = _noise(rng, 2 * n)
    st = fir_stage(taps, decim=D, impl="poly")
    assert st.route == (f"rows{R}", None, None)
    carry0 = st.init_carry(np.complex64)
    m = carry0[0].shape[0] - 1
    _, (y1, y2) = _run(st, [x[:n], x[n:]])
    got = np.concatenate([y1, y2])

    want = _direct64(taps, x, D)
    scale = np.max(np.abs(want))
    np.testing.assert_allclose(got, want, atol=5e-6 * scale, rtol=0)

    ext = jnp.concatenate([jnp.zeros(m * D, jnp.complex64), jnp.asarray(x)])
    old = np.asarray(_d_wide(ext, carry0[0], m, 2 * n // D))
    np.testing.assert_allclose(got, old, atol=3e-6 * scale, rtol=0)


@pytest.mark.parametrize("n", [4096, 65500])
@pytest.mark.parametrize("nt", [17, 128])
@pytest.mark.parametrize("D", [2, 4, 16])
def test_xlating_fir_matches_direct_form_and_d_wide_rows(D, nt, n):
    """The tuner: folded complex taps, residual rotator at the decimated rate, two frames.
    The float64 reference rotates at the decimated rate along the stage's own float32
    phase ramp (that ramp's rounding is the parent's too, and is not under test)."""
    n -= n % D
    rng = np.random.default_rng(D * 77 + nt)
    theta = -15 / 32 / D        # θ·D·k is exact in float32: the ramp rounds the same
    #                             way here as in the stage, fused multiply-add or not
    base = _taps(nt, D, False, rng)
    x = _noise(rng, 2 * n)
    st = xlating_fir_stage(base, theta, D, name="tuner")
    assert st.route == (f"rows{_row_width(D)}", None, None)
    carry0 = st.init_carry(np.complex64)
    W, m, nq = carry0[0], carry0[0].shape[0] - 1, n // D
    _, (y1, y2) = _run(st, [x[:n], x[n:]])

    ct = base.astype(np.float64) * np.exp(-1j * theta * np.arange(nt))
    inc = np.float32(theta * D)
    ph1 = np.float32(0) + inc * np.arange(nq, dtype=np.float32)
    ph0_2 = np.mod(np.float32(0) + inc * np.float32(nq), np.float32(2 * np.pi))
    ph2 = ph0_2 + inc * np.arange(nq, dtype=np.float32)
    core = _direct64(ct, x, D)
    want = core * np.exp(1j * np.concatenate([ph1, ph2]).astype(np.float64))
    scale = np.max(np.abs(want))
    np.testing.assert_allclose(np.concatenate([y1, y2]), want, atol=2e-5 * scale, rtol=0)

    ext = jnp.concatenate([jnp.zeros(m * D, jnp.complex64), jnp.asarray(x)])
    old = np.asarray(_d_wide(ext, W, m, 2 * nq))
    rot = np.exp(1j * np.concatenate([ph1, ph2])).astype(np.complex64)
    np.testing.assert_allclose(np.concatenate([y1, y2]), old * rot,
                               atol=3e-6 * scale, rtol=0)


@pytest.mark.parametrize("I,D", [(3, 2), (7, 4), (24, 125)])
def test_resampler_matches_d_wide_rows(I, D):
    """The resampler's 3-D phase weights ride the same helper: D < 64 is re-blocked,
    the FM audio resampler (D = 125) keeps its rows."""
    rng = np.random.default_rng(I * 10 + D)
    taps = (firdes.lowpass(0.4 / max(I, D), 12 * max(I, D) + 1) * I).astype(np.float32)
    n = 40 * D
    x = _noise(rng, 2 * n, np.float32)
    st = resample_stage(I, D, taps)
    _, (y1, y2) = _run(st, [x[:n], x[n:]], np.float32)
    up = np.zeros(2 * n * I)
    up[::I] = x
    want = np.convolve(up, taps.astype(np.float64))[:2 * n * I:D]
    np.testing.assert_allclose(np.concatenate([y1, y2]), want,
                               atol=5e-6 * np.max(np.abs(want)), rtol=0)


@pytest.mark.parametrize("precision", ["bf16", "int8"])
def test_lowered_rungs_keep_their_numerics_over_wide_rows(precision):
    """The bf16 and int8 rungs share the helper. int8 is integer arithmetic, so the
    re-blocked sum equals the D-wide one exactly; bf16 keeps f32 accumulation."""
    from futuresdr_tpu.ops.stages import _int8_shifted_matvec
    D, nt, n = 4, 64, 4000
    rng = np.random.default_rng(9)
    taps = _taps(nt, D, False, rng)
    x = _noise(rng, n)
    st = fir_stage(taps, decim=D, impl="poly", precision=precision)
    carry0 = st.init_carry(np.complex64)
    _, (got,) = _run(st, [x])
    W, m = carry0[0], carry0[0].shape[0] - 1
    ext = jnp.concatenate([jnp.zeros(m * D, jnp.complex64), jnp.asarray(x)])
    rows = ext.reshape(-1, D)
    if precision == "int8":
        old = jax.jit(lambda r, w: jax.lax.complex(
            _int8_shifted_matvec(r.real, w, m, n // D),
            _int8_shifted_matvec(r.imag, w, m, n // D)))(rows, W)
        np.testing.assert_array_equal(got, np.asarray(old))
    else:
        # the carried weights are bf16; a complex stream keeps f32 operands on the CPU
        assert W.dtype == jnp.bfloat16
        ref = _direct64(np.asarray(jnp.asarray(taps).astype(jnp.bfloat16).astype(jnp.float32)),
                        x, D)
        np.testing.assert_allclose(got, ref, atol=5e-6 * np.max(np.abs(ref)), rtol=0)


def test_vmap_over_lanes_with_their_own_phase_inc_equals_solo_runs():
    """ServeEngine's form: one program, 8 lanes, each with its own translation (so its
    own band weights, built per lane in the trace)."""
    D, nt, n, lanes = 4, 128, 65500, 8
    rng = np.random.default_rng(3)
    base = _taps(nt, D, False, rng)
    thetas = np.linspace(-0.9, 0.9, lanes)
    stages = [xlating_fir_stage(base, th, D) for th in thetas]
    xs = np.stack([_noise(rng, n) for _ in range(lanes)])
    carries = [s.init_carry(np.complex64) for s in stages]
    stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *carries)
    fb = jax.jit(jax.vmap(stages[0].fn))
    cb, yb = fb(stacked, xs)
    cb, yb2 = fb(cb, xs[::-1])
    for i, st in enumerate(stages):
        _, (y1, y2) = _run(st, [xs[i], xs[lanes - 1 - i]])
        scale = np.max(np.abs(y1))
        np.testing.assert_allclose(np.asarray(yb[i]), y1, atol=2e-6 * scale, rtol=0)
        np.testing.assert_allclose(np.asarray(yb2[i]), y2, atol=2e-6 * scale, rtol=0)


@pytest.mark.parametrize("what", ["phase_inc", "taps"])
def test_update_mid_stream_equals_a_fresh_stage(what):
    """A retune is carry surgery on the D-wide weights; the band matrices follow because
    they are rebuilt from the carry in every dispatch. After update() the next frame
    equals a fresh stage built with the new parameter and given the running history."""
    D, nt, n = 4, 128, 8000
    rng = np.random.default_rng(11)
    base, base2 = _taps(nt, D, False, rng), firdes.lowpass(0.05, nt).astype(np.float32)
    th, th2 = -0.31, 0.47
    x = _noise(rng, 2 * n)
    st = xlating_fir_stage(base, th, D)
    f = jax.jit(st.fn)
    carry, _ = f(st.init_carry(np.complex64), x[:n])
    if what == "phase_inc":
        carry = st.update(carry, phase_inc=th2)
        fresh = xlating_fir_stage(base, th2, D)
    else:
        carry = st.update(carry, taps=base2)
        fresh = xlating_fir_stage(base2, th, D)
    fc = fresh.init_carry(np.complex64)
    for got, want in zip(carry[:2] + carry[3:6], fc[:2] + fc[3:6]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    _, y = f(carry, x[n:])
    _, y_fresh = jax.jit(fresh.fn)(fc[:2] + (carry[2],) + fc[3:6] + (carry[6],), x[n:])
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_fresh))
    # and the weights that were swapped in are the ones that filter: direct form
    ct = (base2 if what == "taps" else base).astype(np.float64) * np.exp(
        -1j * (th2 if what == "phase_inc" else th) * np.arange(nt))
    core = _direct64(ct, x, D)[n // D:]
    np.testing.assert_allclose(np.abs(np.asarray(y)), np.abs(core),
                               atol=5e-6 * np.max(np.abs(core)), rtol=0)


def _leaves(tree):
    return [(tuple(leaf.shape), str(leaf.dtype)) for leaf in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("build,structure,leaves", [
    (lambda: Pipeline(front_end_stages(), np.complex64).init_carry(),
     "PyTreeDef(((*, *, *, *, *, *, *), *, *))",
     [((33, 4), "complex64"), ((128,), "float32"), ((), "float32"), ((), "float32"),
      ((), "float32"), ((), "float32"), ((128,), "complex64"), ((), "complex64"),
      ((250,), "float32")]),
    (lambda: fir_stage(firdes.lowpass(0.1, 128).astype(np.float32), decim=4)
     .init_carry(np.complex64),
     "PyTreeDef((*, *))", [((33, 4), "float32"), ((128,), "complex64")]),
    (lambda: fir_stage(firdes.lowpass(0.1, 128) * (1 + 0.5j), decim=4)
     .init_carry(np.complex64),
     "PyTreeDef((*, *))", [((33, 4), "complex64"), ((128,), "complex64")]),
], ids=["fm_front_end", "poly_decim_real_taps", "poly_decim_complex_taps"])
def test_carry_tree_is_the_d_wide_one(build, structure, leaves):
    """Leaf for leaf what the D-wide form carried (literals from the parent commit): each
    leaf is a page of ServeEngine's pool, persisted by serve/persist.py and checkpointed."""
    carry = build()
    assert str(jax.tree_util.tree_structure(carry)) == structure
    assert _leaves(carry) == leaves


def _dots_by_scope(jaxpr, out):
    for e in jaxpr.eqns:
        if e.primitive.name == "dot_general":
            (lc, _rc), _batch = e.params["dimension_numbers"]
            lhs = e.invars[0].aval.shape
            out.append((str(e.source_info.name_stack), lhs, e.invars[1].aval.shape,
                        int(np.prod([lhs[d] for d in lc]))))
        for v in e.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                _dots_by_scope(sub, out)
    return out


def test_fm_front_end_matmuls_are_mxu_shaped_at_the_served_shape():
    """``jax.vmap(Pipeline(front_end_stages()).fn())`` at [64, 65500], from the jaxpr: the
    tuner holds at most 4 dot_generals and none contracts over fewer than 64; the
    resampler's are the three [131, 125]·[125, 24] they were. No chip needed."""
    pipe = Pipeline(front_end_stages(), np.complex64)
    carry = jax.tree_util.tree_map(
        lambda leaf: jax.ShapeDtypeStruct((64,) + leaf.shape, leaf.dtype), pipe.init_carry())
    x = jax.ShapeDtypeStruct((64, 65500), jnp.complex64)
    dots = _dots_by_scope(jax.make_jaxpr(jax.vmap(pipe.fn()))(carry, x).jaxpr, [])
    tuner = [d for d in dots if "tuner" in d[0]]
    resample = [d for d in dots if "resample" in d[0]]
    assert len(tuner) + len(resample) == len(dots)
    assert 1 <= len(tuner) <= 4, tuner
    assert all(k >= 64 for *_, k in tuner), tuner
    assert [d[1:] for d in tuner] == [((64, 512, 256), (64, 256, 32), 256)]
    assert [d[1:] for d in resample] == [((64, 131, 125), (125, 24), 125)] * 3


def test_row_width_rule():
    """D >= 64 keeps its rows (so the resampler compiles to the program it did); smaller
    D takes the smallest multiple of D that is >= 128."""
    assert [_row_width(D) for D in (1, 2, 3, 4, 16, 48, 63, 64, 125, 200)] == \
        [128, 128, 129, 128, 128, 144, 189, 64, 125, 200]
    # a helper call with D >= 64 never pads or re-blocks: same jaxpr as the plain loop
    W = jnp.ones((2, 125, 24), jnp.float32)
    ext = jnp.ones((125 * 9,), jnp.float32)
    a = jax.make_jaxpr(lambda e, w: _shifted_matvec(e, w, 1, 8))(ext, W)
    b = jax.make_jaxpr(lambda e, w: _d_wide(e, w, 1, 8))(ext, W)
    assert str(a) == str(b)
