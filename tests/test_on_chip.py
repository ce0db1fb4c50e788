"""Curated on-chip validation (``FSDR_TEST_TPU=1`` on a host with a TPU).

The main suite runs on a virtual 8-device CPU mesh (conftest.py). This module
drives the compute plane on the REAL chip with TPU-calibrated tolerances (MXU
f32 accumulates differently than host f64), and is the Pallas kernels'
standing check: one compile-and-match test per kernel at the shapes
``chip_smoke.py`` uses.

Run on the chip: ``FSDR_TEST_TPU=1 python -m pytest tests/test_on_chip.py -q``
(the module is a no-op skip in the normal CPU suite; with ``FSDR_TEST_TPU=1``
and no TPU it FAILS — asking for the chip and not getting it is an error).

These tests exist because the routes that switch on
``jax.default_backend() == "tpu"`` at trace time never run on the CPU mesh:
the MXU matmul FFT, the Pallas FIR/PFB auto routes compiled by Mosaic, the
sc16 default wire and the complex pair shim.
"""

import os

import numpy as np
import pytest

if not os.environ.get("FSDR_TEST_TPU"):
    pytest.skip("FSDR_TEST_TPU not set (suite runs on the virtual CPU mesh)",
                allow_module_level=True)

import jax  # noqa: E402

if jax.default_backend() != "tpu":
    raise RuntimeError(
        f"FSDR_TEST_TPU=1 but jax's backend is {jax.default_backend()!r}: "
        f"these tests only mean something on a TPU")

import chip_smoke  # noqa: E402  (repo root: conftest puts it on sys.path)

from futuresdr_tpu.dsp import firdes  # noqa: E402
from futuresdr_tpu.ops import fft_stage, fir_stage, mag2_stage  # noqa: E402
from futuresdr_tpu.ops.stages import Pipeline, _pallas_fir_wins  # noqa: E402
from futuresdr_tpu.ops.xfer import to_device, to_host  # noqa: E402
from futuresdr_tpu.tpu.instance import instance  # noqa: E402

# MXU f32 (and the bf16x3 matmul decomposition inside the four-step FFT) land
# within ~1e-4 relative of the host-f64 reference at these sizes; 1e-3 is the
# assertion line — loose enough for accumulation-order noise, tight enough
# that a wrong twiddle/layout (the bugs these tests exist for) blows through.
REL_TOL = 1e-3


def _rel_err(got, want):
    scale = max(1e-9, float(np.max(np.abs(want))))
    return float(np.max(np.abs(got - want))) / scale


@pytest.mark.parametrize("case", chip_smoke.pallas_cases(rehearse=False),
                         ids=lambda c: c[0])
def test_pallas_kernel_compiles_and_matches_its_xla_route(case):
    """Mosaic compiles the kernel (the lowered module carries the custom
    call — never the interpreter on a TPU backend) and two frames through the
    ``impl="pallas"`` stage match the same stage on its XLA route."""
    rec = chip_smoke.run_pallas_case(case, seed=17, on_tpu=True)
    assert rec["mosaic"] and rec["rel_err_vs_xla"] <= rec["tol"]


def test_complex_xfer_roundtrip_exact():
    """H2D + D2H of complex64 through the pair shim is bit-exact."""
    rng = np.random.default_rng(1)
    host = (rng.standard_normal(4096)
            + 1j * rng.standard_normal(4096)).astype(np.complex64)
    dev = to_device(host)
    assert dev.dtype == np.complex64
    back = to_host(dev)
    np.testing.assert_array_equal(back, host)


@pytest.mark.parametrize("nt,dtype", [(16, np.float32), (48, np.float32),
                                      (64, np.float32), (16, np.complex64)])
def test_fir_auto_impl_matches_numpy(nt, dtype):
    """fir_stage(impl='auto') across its routing boundaries (pallas for real
    <=48 taps, overlap-save beyond and for complex) against a host f64
    convolution."""
    taps = firdes.lowpass(0.2, nt).astype(np.float32)
    st = fir_stage(taps)
    rng = np.random.default_rng(5)
    n = 8192
    if dtype == np.float32:
        host = rng.standard_normal(n).astype(np.float32)
    else:
        host = (rng.standard_normal(n)
                + 1j * rng.standard_normal(n)).astype(np.complex64)
    carry = jax.device_put(st.init_carry(host.dtype), instance().device)
    fn = jax.jit(st.fn)
    _, y = fn(carry, to_device(host, instance().device))
    got = to_host(y)
    want = np.convolve(np.concatenate([np.zeros(nt - 1, dtype), host]),
                       taps)[nt - 1:nt - 1 + n].astype(dtype)
    assert _rel_err(got, want) < REL_TOL


def test_fir_routing_is_the_measured_crossover():
    assert _pallas_fir_wins(16, False)
    assert _pallas_fir_wins(48, False)
    assert not _pallas_fir_wins(64, False)
    assert not _pallas_fir_wins(16, True)


def test_fir_carry_chunk_invariance_on_chip():
    """One 8192-frame vs two 4096-frames produce identical outputs (the
    carried tail is correct on the device path, not just the CPU mesh)."""
    taps = firdes.lowpass(0.25, 32).astype(np.float32)
    rng = np.random.default_rng(9)
    host = (rng.standard_normal(8192)
            + 1j * rng.standard_normal(8192)).astype(np.complex64)
    st = fir_stage(taps)
    fn = jax.jit(st.fn)

    c = jax.device_put(st.init_carry(host.dtype), instance().device)
    _, y_once = fn(c, to_device(host))

    c = jax.device_put(st.init_carry(host.dtype), instance().device)
    c, y_a = fn(c, to_device(host[:4096]))
    _, y_b = fn(c, to_device(host[4096:]))
    got = np.concatenate([to_host(y_a), to_host(y_b)])
    want = to_host(y_once)
    assert _rel_err(got, want) < 1e-6      # same kernel, same math: ~bit-equal


def test_mxu_fft_matches_numpy():
    """The four-step matmul FFT (auto-engaged on TPU at 2048) vs np.fft."""
    from futuresdr_tpu.ops import mxu_fft
    rng = np.random.default_rng(2)
    x = (rng.standard_normal((8, 2048))
         + 1j * rng.standard_normal((8, 2048))).astype(np.complex64)
    got = to_host(jax.jit(mxu_fft.fft)(to_device(x)))
    want = np.fft.fft(x)
    assert _rel_err(got, want) < REL_TOL


def test_mxu_ifft_roundtrip():
    from futuresdr_tpu.ops import mxu_fft
    rng = np.random.default_rng(3)
    x = (rng.standard_normal((4, 2048))
         + 1j * rng.standard_normal((4, 2048))).astype(np.complex64)
    y = jax.jit(lambda v: mxu_fft.ifft(mxu_fft.fft(v)))(to_device(x))
    assert _rel_err(to_host(y), x) < REL_TOL


def test_headline_pipeline_matches_numpy():
    """The bench chain (fir64 → fft2048 → |x|²) fused, one frame, vs a host
    reference of the same math."""
    taps = firdes.lowpass(0.2, 64).astype(np.float32)
    pipe = Pipeline([fir_stage(taps), fft_stage(2048), mag2_stage()],
                    np.complex64)
    rng = np.random.default_rng(4)
    host = (rng.standard_normal(16384)
            + 1j * rng.standard_normal(16384)).astype(np.complex64)
    carry = jax.device_put(pipe.init_carry(), instance().device)
    _, y = jax.jit(pipe.fn())(carry, to_device(host))
    got = to_host(y)

    fir = np.convolve(np.concatenate([np.zeros(63, np.complex64), host]),
                      taps)[63:63 + 16384]
    spec = np.fft.fft(fir.reshape(-1, 2048), axis=1).reshape(-1)
    want = (spec.real ** 2 + spec.imag ** 2).astype(np.float32)
    assert _rel_err(got, want) < REL_TOL


def test_wlan_demod_body_recovers_bits_on_chip():
    """demod_body_jax (the shim-riding entry point) on a clean constructed
    OFDM symbol: BPSK LLR signs must equal the transmitted bits."""
    from futuresdr_tpu.models.wlan.consts import (CP_LEN, DATA_CARRIERS,
                                                  FFT_SIZE, PILOT_CARRIERS,
                                                  PILOT_VALUES, PILOT_POLARITY)
    from futuresdr_tpu.models.wlan.jax_demod import demod_body_jax

    rng = np.random.default_rng(6)
    bits = rng.integers(0, 2, 48)
    spec = np.zeros(FFT_SIZE, np.complex64)
    spec[DATA_CARRIERS % FFT_SIZE] = 2.0 * bits - 1.0
    spec[PILOT_CARRIERS % FFT_SIZE] = PILOT_VALUES * PILOT_POLARITY[1]
    sym = np.fft.ifft(spec).astype(np.complex64) * FFT_SIZE
    body = np.concatenate([sym[-CP_LEN:], sym])          # one 80-sample symbol
    llrs = demod_body_jax(body, np.ones(64, np.complex64), 1, 1,
                          0.0, 0.0, "bpsk")
    assert llrs.shape == (48,)
    assert np.all((llrs > 0) == (bits == 1))


def test_wlan_demod_head_runs_on_chip():
    """demod_head_jax end to end on the chip (complex in AND complex out —
    the H readback exercises the to_host split)."""
    from futuresdr_tpu.models.wlan.jax_demod import demod_head_jax
    rng = np.random.default_rng(7)
    head = (rng.standard_normal(208)
            + 1j * rng.standard_normal(208)).astype(np.complex64)
    H, llrs = demod_head_jax(head, 1e-4)
    assert H.shape == (64,) and H.dtype == np.complex64
    assert llrs.shape == (48,) and np.all(np.isfinite(llrs))
    assert np.all(np.isfinite(H))


def test_streamed_tpu_kernel_flowgraph():
    """The actor-runtime streamed path (host ring → H2D staging → fused chain
    → D2H → host ring) against the real chip: VectorSource → TpuKernel(fir)
    → VectorSink, output checked vs numpy. Drives h2d_needs_staging and the
    frame-chaining drain loop on real hardware."""
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.tpu import TpuKernel

    taps = firdes.lowpass(0.2, 32).astype(np.float32)
    rng = np.random.default_rng(8)
    n = 4 * 4096
    host = (rng.standard_normal(n)
            + 1j * rng.standard_normal(n)).astype(np.complex64)

    fg = Flowgraph()
    src = VectorSource(host)
    tk = TpuKernel([fir_stage(taps)], np.complex64, frame_size=4096,
                   frames_in_flight=2)
    snk = VectorSink(np.complex64)
    fg.connect(src, tk, snk)
    Runtime().run(fg)

    got = snk.items()
    assert got.shape == (n,)
    want = np.convolve(np.concatenate([np.zeros(31, np.complex64), host]),
                       taps)[31:31 + n].astype(np.complex64)
    assert _rel_err(got, want) < REL_TOL


def test_lora_dechirp_demod_on_chip():
    """lora_demod_stage (BASELINE #5's hot loop) on the real chip: modulated
    symbols round-trip through dechirp → MXU-era FFT → argmax exactly —
    integer symbol recovery leaves no tolerance question."""
    from futuresdr_tpu.models.lora.phy import LoraParams, _upchirp
    from futuresdr_tpu.ops.stages import lora_demod_stage

    sf = 7
    n = 1 << sf
    rng = np.random.default_rng(11)
    syms = rng.integers(0, n, 24)
    chips = np.concatenate([_upchirp(n, int(s)) for s in syms]) \
        .astype(np.complex64)
    st = lora_demod_stage(sf)
    carry = jax.device_put(st.init_carry(np.complex64), instance().device)
    _, got = jax.jit(st.fn)(carry, to_device(chips))
    np.testing.assert_array_equal(np.asarray(to_host(got)), syms)


def test_fm_front_end_on_chip():
    """BASELINE #3's front half (xlating FIR decimator → quadrature demod) on
    the chip vs the numpy twin: a real FM tone demodulates to its frequency."""
    from futuresdr_tpu.ops.stages import quad_demod_stage, xlating_fir_stage

    fs = 256_000.0
    decim = 4
    taps = firdes.lowpass(0.1, 48).astype(np.float32)
    offset = 2 * np.pi * 25_000.0 / fs           # shift the signal to baseband
    n = 16_384
    t = np.arange(n) / fs
    # FM tone at +25 kHz carrier, 1 kHz deviation payload
    dev = np.cumsum(2 * np.pi * 5_000.0 * np.cos(2 * np.pi * 1_000.0 * t) / fs)
    host = np.exp(1j * (2 * np.pi * 25_000.0 * t + dev)).astype(np.complex64)

    pipe = Pipeline([xlating_fir_stage(taps, -offset, decim),
                     quad_demod_stage(gain=1.0)], np.complex64)
    carry = jax.device_put(pipe.init_carry(), instance().device)
    _, y = jax.jit(pipe.fn())(carry, to_device(host))
    got = np.asarray(to_host(y))
    # steady-state demod ≈ instantaneous frequency of the payload: a 1 kHz
    # cosine with ±(2π·5000/fs·decim) swing
    body = got[64:]
    expect_peak = 2 * np.pi * 5_000.0 / fs * decim
    assert abs(float(np.max(body)) - expect_peak) < 0.15 * expect_peak
    assert abs(float(np.min(body)) + expect_peak) < 0.15 * expect_peak


def test_throttleless_tree_shapes_compile_on_chip():
    """A fused-stage pipeline with a rate change (decimating FIR) keeps its
    frame-multiple contract on device: two frames chunk-invariant vs one."""
    taps = firdes.lowpass(0.1, 32).astype(np.float32)
    st = fir_stage(taps, decim=4)
    rng = np.random.default_rng(12)
    host = (rng.standard_normal(8192)
            + 1j * rng.standard_normal(8192)).astype(np.complex64)
    fn = jax.jit(st.fn)
    c = jax.device_put(st.init_carry(host.dtype), instance().device)
    _, y_once = fn(c, to_device(host))
    c = jax.device_put(st.init_carry(host.dtype), instance().device)
    c, y_a = fn(c, to_device(host[:4096]))
    _, y_b = fn(c, to_device(host[4096:]))
    got = np.concatenate([to_host(y_a), to_host(y_b)])
    assert _rel_err(got, to_host(y_once)) < 1e-6


def test_wlan_full_rx_decode_on_chip():
    """The COMPLETE 802.11 RX (sync → equalize → per-axis demap → lax.scan
    Viterbi → descramble) decodes real frames on the chip, bit-matching the
    CPU behavior: clean frames decode perfectly across modulations, and the
    impaired-channel config the CPU suite passes (delay + AWGN + CFO,
    `test_wlan.test_phy_loopback_noise_cfo_delay`) decodes here too.
    FSDR_NO_NATIVE routes the Viterbi to the jitted scan so the trellis
    actually runs on the device."""
    import importlib

    prev = os.environ.get("FSDR_NO_NATIVE")
    os.environ["FSDR_NO_NATIVE"] = "1"
    try:
        from futuresdr_tpu.models.wlan import coding
        importlib.reload(coding)      # drop a cached native-viterbi handle
        from futuresdr_tpu.models.wlan.phy import decode_stream, encode_frame

        rng = np.random.default_rng(6)
        for mcs in ("bpsk_1_2", "qpsk_1_2", "qam16_1_2", "qam64_3_4"):
            psdu = bytes(rng.integers(0, 256, 160).astype(np.uint8))
            dec = decode_stream(encode_frame(psdu, mcs))
            assert len(dec) == 1 and dec[0].psdu == psdu, mcs
            assert dec[0].mcs.name == mcs

        psdu = b"The quick brown fox jumps over the lazy dog" * 4
        frame = encode_frame(psdu, "qpsk_1_2")
        sig = np.concatenate([np.zeros(777, np.complex64), frame,
                              np.zeros(500, np.complex64)])
        n = np.arange(len(sig))
        sig = sig * np.exp(1j * 2 * np.pi * 1e-4 * n)
        sig = sig + (0.02 * (rng.standard_normal(len(sig))
                             + 1j * rng.standard_normal(len(sig))))
        dec = decode_stream(sig.astype(np.complex64))
        assert len(dec) == 1 and dec[0].psdu == psdu
    finally:
        # restore the operator's setting AND drop the fallback-mode cache the
        # reload baked into the module, or every later test in this session
        # would silently run the numpy/scan Viterbi instead of the native one
        if prev is None:
            os.environ.pop("FSDR_NO_NATIVE", None)
        else:
            os.environ["FSDR_NO_NATIVE"] = prev
        importlib.reload(coding)
