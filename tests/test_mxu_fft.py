"""MXU four-step FFT: correctness of the matmul decomposition vs numpy's FFT.

CI runs on the CPU backend where the `auto` policy picks jnp.fft; these tests force the
MXU (matmul) implementation so the four-step math itself is validated everywhere. On a
real TPU the same code runs on the systolic array (measured in docs/tpu_notes.md).
"""
import numpy as np
import pytest

from futuresdr_tpu.ops import mxu_fft


@pytest.fixture
def force_mxu():
    mxu_fft.set_impl("mxu")
    yield
    mxu_fft.set_impl("auto")


@pytest.mark.parametrize("n", [256, 1024, 2048, 8192])
def test_fft_matches_numpy(force_mxu, n):
    rng = np.random.default_rng(3)
    x = (rng.standard_normal((4, n)) + 1j * rng.standard_normal((4, n))).astype(np.complex64)
    got = np.asarray(mxu_fft.fft(x))
    ref = np.fft.fft(x, axis=-1)
    assert np.abs(got - ref).max() / np.abs(ref).max() < 1e-4


@pytest.mark.parametrize("n", [256, 2048])
def test_ifft_roundtrip(force_mxu, n):
    rng = np.random.default_rng(4)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(np.complex64)
    y = np.asarray(mxu_fft.ifft(mxu_fft.fft(x)))
    assert np.abs(y - x).max() < 1e-4


def test_auto_policy_on_cpu_uses_xla():
    # on the CPU test backend auto must not take the matmul path (bit-exactness with
    # jnp.fft is part of the CPU contract)
    assert not mxu_fft._use_mxu(2048)


@pytest.mark.parametrize("n", [48, 100, 320])
def test_direct_dft_non_pow2(force_mxu, n):
    # small / non-pow2 sizes run as a direct [n, n] DFT matmul
    rng = np.random.default_rng(8)
    x = (rng.standard_normal((3, n)) + 1j * rng.standard_normal((3, n))).astype(np.complex64)
    got = np.asarray(mxu_fft.fft(x))
    ref = np.fft.fft(x, axis=-1)
    assert np.abs(got - ref).max() / np.abs(ref).max() < 1e-4


def test_fir_stage_mxu_matches_xla():
    """Overlap-save FIR must produce the same stream on the MXU-FFT path."""
    from futuresdr_tpu.ops import fir_stage
    rng = np.random.default_rng(5)
    taps = rng.standard_normal(64).astype(np.float32)

    def run(x):
        st = fir_stage(taps)
        carry = st.init_carry(x.dtype)
        outs = []
        frame = 1 << 14
        for i in range(0, len(x), frame):
            carry, y = st.fn(carry, x[i:i + frame])
            outs.append(np.asarray(y))
        return np.concatenate(outs)

    for dtype in (np.float32, np.complex64):
        x = rng.standard_normal(1 << 15).astype(np.float32)
        if dtype == np.complex64:
            x = (x + 1j * rng.standard_normal(len(x))).astype(np.complex64)
        y_xla = run(x)
        mxu_fft.set_impl("mxu")
        try:
            y_mxu = run(x)
        finally:
            mxu_fft.set_impl("auto")
        assert np.abs(y_mxu - y_xla).max() < 2e-3, dtype


def test_fft_stage_mxu_matches_xla():
    from futuresdr_tpu.ops import fft_stage
    rng = np.random.default_rng(6)
    x = (rng.standard_normal(4096) + 1j * rng.standard_normal(4096)).astype(np.complex64)
    st = fft_stage(2048)
    _, y_xla = st.fn(st.init_carry(np.complex64), x)
    mxu_fft.set_impl("mxu")
    try:
        st2 = fft_stage(2048)
        _, y_mxu = st2.fn(st2.init_carry(np.complex64), x)
    finally:
        mxu_fft.set_impl("auto")
    assert np.abs(np.asarray(y_mxu) - np.asarray(y_xla)).max() < 2e-2


def test_fir_stage_pallas_impl_matches_os():
    """fir_stage(impl='pallas') streams identically to the overlap-save path."""
    from futuresdr_tpu.ops import fir_stage
    rng = np.random.default_rng(9)
    taps = rng.standard_normal(32).astype(np.float32)
    for dtype in (np.float32, np.complex64):
        x = rng.standard_normal(1 << 15).astype(np.float32)
        if dtype == np.complex64:
            x = (x + 1j * rng.standard_normal(len(x))).astype(np.complex64)

        def run(impl):
            st = fir_stage(taps, impl=impl)
            carry = st.init_carry(x.dtype)
            outs = []
            for i in range(0, len(x), 1 << 13):
                carry, y = st.fn(carry, x[i:i + (1 << 13)])
                outs.append(np.asarray(y))
            return np.concatenate(outs)

        y_os, y_pl = run("os"), run("pallas")
        assert np.abs(y_os - y_pl).max() < 2e-3, dtype


def test_forced_mxu_huge_nonpow2_falls_back():
    """impl='mxu' must not route a huge non-power-of-two n through a dense [n,n]
    DFT matmul (O(n^2) HBM) — it falls back to jnp.fft above the direct cap."""
    from futuresdr_tpu.ops import mxu_fft
    assert not mxu_fft._use_mxu(100_000, impl="mxu")      # would be ~80 GB dense
    assert mxu_fft._use_mxu(300, impl="mxu")              # small direct: fine
    assert mxu_fft._use_mxu(1 << 16, impl="mxu")          # pow2: four-step, fine
    # per-call override wins over the module global
    mxu_fft.set_impl("mxu")
    try:
        assert not mxu_fft._use_mxu(2048, impl="xla")
    finally:
        mxu_fft.set_impl("auto")


# -- the few-row forms (PR 37): re and im planes as stacked rows, one real matmul
# -- a stage, four-step from 512 points; chosen by ``form(n, rows)`` alone

def _noise(shape, seed):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(np.complex64)


@pytest.mark.parametrize("n", [8, 100, 256, 512, 1024, 2048, 8192])
@pytest.mark.parametrize("rows", [1, 8, 32])
def test_few_rows_match_numpy_float64(rows, n):
    assert mxu_fft.form(n, rows).startswith("planes")
    x = _noise((rows, n), 100 * rows + n)
    ref = np.fft.fft(x.astype(np.complex128), axis=-1)
    got = np.asarray(mxu_fft.fft(x, impl="mxu"))
    assert got.dtype == np.complex64 and got.shape == x.shape
    assert np.abs(got - ref).max() <= 2e-5 * np.abs(ref).max()
    back = np.asarray(mxu_fft.ifft(got, impl="mxu"))
    assert back.dtype == np.complex64
    assert np.abs(back - x).max() <= 2e-5 * np.abs(x).max()
    inv = np.asarray(mxu_fft.ifft(x, impl="mxu"))
    assert np.abs(inv - np.fft.ifft(x.astype(np.complex128), axis=-1)).max() \
        <= 2e-5 * np.abs(inv).max()


def test_few_rows_leading_dimensions_and_a_single_row():
    x = _noise((2, 4, 512), 7)                      # rows = 8, as two leading dimensions
    assert np.abs(np.asarray(mxu_fft.fft(x, impl="mxu")) - np.fft.fft(x, axis=-1)).max() < 1e-3
    v = _noise((1024,), 8)                          # no leading dimension: one row
    assert np.abs(np.asarray(mxu_fft.fft(v, impl="mxu")) - np.fft.fft(v)).max() < 1e-3


@pytest.mark.parametrize("n, rows, name", [
    # the gateway's scan steps: 8 rows, 2 * 2^SF points
    (256, 8, "planes_direct"), (512, 8, "planes_four_step"), (1024, 8, "planes_four_step"),
    (2048, 8, "planes_four_step"), (4096, 8, "planes_four_step"), (8192, 8, "planes_four_step"),
    (8, 1, "planes_direct"), (100, 8, "planes_direct"), (320, 32, "planes_direct"),
    (768, 8, "planes_direct"), (512, 32, "planes_four_step"), (256, 32, "planes_direct"),
    # many rows: today's forms (the spectrum chain's fft2048 runs 128 rows, its FIR 64
    # of 8192 points, the bank's ifft 8 points over thousands, the gateway's detect
    # thousands)
    (2048, 128, "four_step"), (8192, 64, "four_step"), (2048, 2048, "four_step"),
    (256, 2048, "direct"), (512, 33, "direct"), (512, 64, "direct"), (512, 128, "direct"),
    (1024, 33, "four_step"), (8, 40960, "direct"),
    (100, 128, "direct"), (768, 128, "direct"), (256, 5000, "direct"), (8192, 4000, "four_step"),
])
def test_form_table(n, rows, name):
    assert mxu_fft.form(n, rows) == name


def _lowered(f, x):
    import jax
    return jax.jit(f).lower(jax.ShapeDtypeStruct(x, np.complex64)).as_text()


@pytest.mark.parametrize("shape", [(128, 2048), (2048, 256), (64, 8192), (128, 512)])
@pytest.mark.parametrize("op", ["fft", "ifft"])
def test_many_rows_lower_to_the_text_of_mxu_fft_called_directly(shape, op):
    """Many rows take ``_mxu_fft`` as it stood: the same program text."""
    import jax.numpy as jnp
    n = shape[-1]
    if op == "fft":
        new = lambda x: mxu_fft.fft(x, impl="mxu")
        old = lambda x: mxu_fft._mxu_fft(x.astype(jnp.complex64), n, None)
    else:
        new = lambda x: mxu_fft.ifft(x, impl="mxu")
        old = lambda x: jnp.conj(mxu_fft._mxu_fft(jnp.conj(x.astype(jnp.complex64)), n, None)) / n
    strip = lambda t: [l for l in t.splitlines() if "module @" not in l]
    a, b = _lowered(new, shape), _lowered(old, shape)
    assert "dot_general" in a and strip(a) == strip(b)


@pytest.mark.parametrize("shape", [(128, 512), (2048, 256), (128, 100), (128, 1024), (64, 8192)])
def test_form_names_what_mxu_fft_does_with_many_rows(shape):
    """``form``'s many-row names mirror ``_mxu_fft``'s own branch: a direct DFT is
    one complex ``dot_general``, a four-step two."""
    text = _lowered(lambda x: mxu_fft.fft(x, impl="mxu"), shape)
    name = mxu_fft.form(shape[-1], shape[0])
    assert len(_dots(text)) == {"direct": 1, "four_step": 2}[name]
    assert all("complex" in d for d in _dots(text))


def _dots(text):
    return [l for l in text.splitlines() if "dot_general" in l]


@pytest.mark.parametrize("n, stages", [(256, 1), (512, 2), (1024, 2), (8192, 2)])
def test_few_rows_one_real_dot_general_a_stage(n, stages):
    dots = _dots(_lowered(lambda x: mxu_fft.fft(x, impl="mxu"), (8, n)))
    assert len(dots) == stages
    assert all("complex" not in d and "HIGHEST" in d for d in dots)


@pytest.mark.parametrize("n", [256, 512])
def test_few_rows_bf16_lowers_at_default_precision(n):
    dots = _dots(_lowered(lambda x: mxu_fft.fft(x, impl="mxu", precision="bf16"), (8, n)))
    assert dots and all("HIGHEST" not in d for d in dots)
    mxu_fft.set_precision("bf16")                   # the module policy reaches the few-row forms
    try:
        dots = _dots(_lowered(lambda x: mxu_fft.fft(x, impl="mxu"), (8, n)))
    finally:
        mxu_fft.set_precision("f32")
    assert dots and all("HIGHEST" not in d for d in dots)
