"""Wire-format codec layer + overlapped transfer machinery (ops/wire, ops/xfer).

Tier-1 coverage for the streamed-path wire codec PR:

- codec round trips per format (host↔host is direction-symmetric, so it is
  exactly one link crossing's quantization), measured-SNR floors, byte widths,
  non-float passthrough, empty frames;
- ``to_device``/``to_host`` round trips: complex64/complex128, strided and
  non-contiguous inputs, empty frames, and BIT-exactness of the f32-pair path
  (regression-locks the ``ascontiguousarray`` view trick);
- D2H fetches start (``copy_to_host_async``) when the transfer is started,
  not inside ``finish()``;
- streamed smoke over a rate-throttled fake link: a TpuKernel chain through
  every wire format is tolerance-correct, and the pipelined drain loop
  beats the serialized one on wall-clock (transfer/compute overlap).
"""

import time

import numpy as np
import pytest

from futuresdr_tpu.ops import xfer
from futuresdr_tpu.ops.wire import (WIRE_FORMATS, get_wire, measure_snr_db,
                                    resolve_wire, streamed_ceiling_msps,
                                    wire_names)

ALL_WIRES = sorted(wire_names())


@pytest.fixture
def fake_link():
    """Install a throttled fake link for the test; always restore after."""
    installed = []

    def install(h2d_bps, d2h_bps):
        installed.append(xfer.set_fake_link(h2d_bps, d2h_bps))

    yield install
    xfer.set_fake_link()


def _gaussian_c64(n, seed=0):
    rng = np.random.default_rng(seed)
    return ((rng.standard_normal(n) + 1j * rng.standard_normal(n))
            / np.sqrt(2)).astype(np.complex64)


# ---------------------------------------------------------------------------
# codec unit tests
# ---------------------------------------------------------------------------

# measured-SNR floor per format for a unit-power Gaussian c64 frame; nominal
# figures are NOT trusted (the table in ops/wire.py is derived, these are
# asserted)
SNR_FLOORS = {"f32": float("inf"), "bf16": 35.0, "sc16": 80.0, "sc8": 38.0}


@pytest.mark.parametrize("name", ALL_WIRES)
def test_measured_snr_floor(name):
    snr = measure_snr_db(name)
    assert snr >= SNR_FLOORS[name]


@pytest.mark.parametrize("name", ALL_WIRES)
def test_host_round_trip_complex(name):
    w = get_wire(name)
    x = _gaussian_c64(4096, seed=1)
    y = w.decode_host(w.encode_host(x), np.complex64)
    assert y.dtype == np.complex64 and y.shape == x.shape
    tol = 10 ** (-SNR_FLOORS[name] / 20) if name != "f32" else 0.0
    np.testing.assert_allclose(y, x, atol=2 * tol + 1e-12, rtol=0)


@pytest.mark.parametrize("name", ALL_WIRES)
def test_host_round_trip_real(name):
    w = get_wire(name)
    x = np.random.default_rng(2).standard_normal(1024).astype(np.float32)
    y = w.decode_host(w.encode_host(x), np.float32)
    assert y.dtype == np.float32 and y.shape == x.shape
    tol = 10 ** (-SNR_FLOORS[name] / 20) if name != "f32" else 0.0
    np.testing.assert_allclose(y, x, atol=2 * tol + 1e-12, rtol=0)


@pytest.mark.parametrize("name", ALL_WIRES)
def test_jax_decode_matches_host_decode(name):
    """The jitted device prolog and the host decode agree on the same parts —
    the two ends of the link speak the same layout."""
    import jax
    w = get_wire(name)
    x = _gaussian_c64(512, seed=3)
    parts = w.encode_host(x)
    dec = jax.jit(lambda *p: w.decode_jax(p, np.complex64))
    y_dev = np.asarray(dec(*parts))
    y_host = w.decode_host(parts, np.complex64)
    np.testing.assert_allclose(y_dev, y_host, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("name", ALL_WIRES)
def test_jax_encode_round_trip(name):
    """Device epilog encode → host decode: the D2H direction's codec."""
    import jax
    import jax.numpy as jnp
    w = get_wire(name)
    x = _gaussian_c64(512, seed=4)
    enc = jax.jit(lambda v: w.encode_jax(v))
    parts = tuple(np.asarray(p) for p in enc(jnp.asarray(x)))
    y = w.decode_host(parts, np.complex64)
    tol = 10 ** (-SNR_FLOORS[name] / 20) if name != "f32" else 1e-7
    np.testing.assert_allclose(y, x, atol=2 * tol + 1e-12, rtol=0)


def test_bytes_per_sample():
    c, f = np.complex64, np.float32
    assert get_wire("f32").bytes_per_sample(c) == 8
    assert get_wire("bf16").bytes_per_sample(c) == 4
    assert get_wire("sc16").bytes_per_sample(c) == 4
    assert get_wire("sc8").bytes_per_sample(c) == 2
    assert get_wire("f32").bytes_per_sample(f) == 4
    assert get_wire("sc8").bytes_per_sample(f) == 1
    # non-float payloads pass through at their own width
    assert get_wire("sc8").bytes_per_sample(np.int32) == 4


@pytest.mark.parametrize("name", ALL_WIRES)
def test_non_float_passthrough(name):
    """Integer payloads (demod symbol indices) must cross every format
    bit-exact — quantizing indices would corrupt them."""
    w = get_wire(name)
    x = np.arange(-5, 250, dtype=np.int32)
    y = w.decode_host(w.encode_host(x), np.int32)
    np.testing.assert_array_equal(y, x)


@pytest.mark.parametrize("name", ALL_WIRES)
def test_empty_frame(name):
    w = get_wire(name)
    x = np.empty(0, dtype=np.complex64)
    y = w.decode_host(w.encode_host(x), np.complex64)
    assert y.shape == (0,) and y.dtype == np.complex64


def test_quant_constant_and_zero_frames():
    """Block-floating-point: a constant frame uses the full int range (exact
    up to rounding), and an all-zero frame survives (scale guard, no 0/0)."""
    w = get_wire("sc16")
    x = np.full(256, 0.125 + 0.0625j, dtype=np.complex64)
    y = w.decode_host(w.encode_host(x), np.complex64)
    np.testing.assert_allclose(y, x, rtol=1e-4)
    z = np.zeros(256, dtype=np.complex64)
    y = w.decode_host(w.encode_host(z), np.complex64)
    np.testing.assert_array_equal(y, z)


@pytest.mark.parametrize("name", ["sc16", "sc8"])
def test_quant_nonfinite_samples_zeroed_frame_survives(name):
    """One inf/NaN sample must not poison the frame: the quantizer zeroes
    non-finite samples (an int wire cannot carry them) and every finite
    neighbour round-trips at full scale — regression for the scale-fallback
    overflow (scale=1.0 would wrap amplitude-1000 samples to garbage)."""
    import jax.numpy as jnp
    w = get_wire(name)
    x = np.full(256, 1000.0 + 500.0j, dtype=np.complex64)
    x[7] = np.inf + 0j
    x[11] = np.nan * 1j
    tol = 1000.0 / (2 * w.qmax)
    # host-side encode
    y = w.decode_host(w.encode_host(x), np.complex64)
    assert np.isfinite(y).all()
    assert y[7] == 0 and y[11] == 0
    keep = np.ones(256, bool); keep[[7, 11]] = False
    np.testing.assert_allclose(y[keep], x[keep], atol=2 * tol, rtol=0)
    # device-side encode epilog behaves identically
    y = w.decode_host(
        tuple(np.asarray(p) for p in w.jit_encode()(jnp.asarray(x))),
        np.complex64)
    assert np.isfinite(y).all()
    assert y[7] == 0 and y[11] == 0
    np.testing.assert_allclose(y[keep], x[keep], atol=2 * tol, rtol=0)


def test_get_wire_and_resolve():
    with pytest.raises(KeyError, match="unknown wire format"):
        get_wire("sc4")
    assert get_wire(WIRE_FORMATS["sc16"]) is WIRE_FORMATS["sc16"]
    # auto: exact on the CPU backend (the "link" is a memcpy), sc16 elsewhere
    assert resolve_wire("auto", "cpu").name == "f32"
    assert resolve_wire("auto", "tpu").name == "sc16"
    assert resolve_wire("sc8", "cpu").name == "sc8"


def test_streamed_ceiling_msps():
    # 96 MB/s up, 62 MB/s down; c64 in (8 B f32 / 4 B sc16), f32 out (4/2 B)
    f32 = streamed_ceiling_msps("f32", 96e6, 62e6)
    sc16 = streamed_ceiling_msps("sc16", 96e6, 62e6)
    assert f32 == pytest.approx(12.0)        # min(96/8, 62/4)
    assert sc16 == pytest.approx(24.0)       # min(96/4, 62/2) — 2× the bytes win
    assert streamed_ceiling_msps("sc8", 96e6, 62e6) == pytest.approx(48.0)


def test_pick_wire_snr_floor_and_tie_break():
    from futuresdr_tpu.tpu.autotune import pick_wire
    # link-bound: sc16 halves the bytes and clears the 60 dB floor → picked;
    # sc8/bf16 are excluded by the floor despite their higher ceilings
    assert pick_wire(96e6, 62e6, np.complex64, np.float32) == "sc16"
    # compute-bound far below every ceiling: ties go to the exact format
    assert pick_wire(96e6, 62e6, np.complex64, np.float32,
                     compute_msps=1.0) == "f32"
    # floor disabled and link-bound: sc8's 4× byte win takes it
    assert pick_wire(96e6, 62e6, np.complex64, np.float32,
                     min_snr_db=None) == "sc8"


# ---------------------------------------------------------------------------
# xfer round trips (satellite: regression-lock the pair-shim view trick)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [np.complex64, np.complex128])
def test_to_device_to_host_round_trip(dtype):
    x = (_gaussian_c64(2048, seed=5)).astype(dtype)
    y = xfer.to_host(xfer.to_device(x))
    np.testing.assert_allclose(y, x.astype(np.complex64), rtol=1e-6, atol=1e-7)


def test_round_trip_strided_and_noncontiguous():
    base = _gaussian_c64(4096, seed=6)
    strided = base[::3]                          # non-unit stride
    np.testing.assert_allclose(xfer.to_host(xfer.to_device(strided)), strided,
                               rtol=1e-6, atol=0)
    mat = base.reshape(64, 64).T                 # non-contiguous 2-D view
    np.testing.assert_allclose(xfer.to_host(xfer.to_device(mat)), mat,
                               rtol=1e-6, atol=0)


def test_round_trip_empty():
    y = xfer.to_host(xfer.to_device(np.empty(0, np.complex64)))
    assert y.shape == (0,)


def test_pair_path_bit_exact(monkeypatch):
    """The f32-pair shim (forced on, as on every accelerator platform) must be
    BIT-exact: the wire is a reinterpreting view, not an arithmetic cast."""
    monkeypatch.setattr(xfer, "split_complex_platform", lambda p: True)
    x = _gaussian_c64(4096, seed=7)
    x[7] = np.float32(1e-38) + 1j * np.float32(-1e38)    # extreme exponents
    y = xfer.to_host(xfer.to_device(x))
    assert y.dtype == np.complex64
    np.testing.assert_array_equal(y.view(np.uint64), x.view(np.uint64))


def test_host_array_passthrough():
    """start_host_transfer of a plain numpy array must not round-trip it
    through the device."""
    x = _gaussian_c64(64, seed=8)
    np.testing.assert_array_equal(xfer.start_host_transfer(x)(), x)


# ---------------------------------------------------------------------------
# D2H: fetches start at call time, not inside finish()
# ---------------------------------------------------------------------------

class _AsyncStubArray:
    """Array type with copy_to_host_async: records when the copy started."""

    def __init__(self, value):
        self._v = np.asarray(value)
        self.async_started = False

    def __array__(self, dtype=None, copy=None):
        return self._v if dtype is None else self._v.astype(dtype)

    def copy_to_host_async(self):
        self.async_started = True


def test_start_fetch_uses_copy_to_host_async():
    a = _AsyncStubArray(np.ones(4, np.float32))
    fin = xfer._start_fetch(a)
    assert a.async_started            # started at call time, not inside finish
    np.testing.assert_array_equal(fin(), a._v)


# ---------------------------------------------------------------------------
# fake link + streamed smoke (satellite: CI overlap evidence)
# ---------------------------------------------------------------------------

def test_fake_link_throttles_and_restores(fake_link):
    payload = np.zeros(1 << 18, np.float32)      # 1 MiB
    fake_link(h2d_bps=64e6, d2h_bps=64e6)        # → ≥ ~16 ms per crossing
    t0 = time.perf_counter()
    y = xfer.to_device(payload)
    up = time.perf_counter() - t0
    t0 = time.perf_counter()
    xfer.to_host(y)
    down = time.perf_counter() - t0
    assert up >= 0.014 and down >= 0.014
    xfer.set_fake_link()                         # removed → no throttle
    t0 = time.perf_counter()
    xfer.to_host(xfer.to_device(payload))
    assert time.perf_counter() - t0 < 0.014


# per-format output tolerance for the fft+mag2 chain, relative to the spectrum
# peak (quantization noise spreads over the fft; block-fp scales to the peak)
CHAIN_TOL = {"f32": 1e-5, "bf16": 3e-2, "sc16": 1e-3, "sc8": 8e-2}


def _run_wired_kernel(wire, tone, frame, depth):
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.ops import fft_stage, mag2_stage
    from futuresdr_tpu.tpu import TpuKernel
    fg = Flowgraph()
    src = VectorSource(tone)
    tk = TpuKernel([fft_stage(256), mag2_stage()], np.complex64,
                   frame_size=frame, frames_in_flight=depth, wire=wire)
    snk = VectorSink(np.float32)
    fg.connect(src, tk, snk)
    t0 = time.perf_counter()
    Runtime().run(fg)
    return np.asarray(snk.items()), time.perf_counter() - t0


@pytest.mark.parametrize("name", ALL_WIRES)
def test_streamed_kernel_every_wire_format(name, fake_link):
    """TpuKernel chain through each wire format over a throttled fake link:
    output is tolerance-correct for the format's SNR class."""
    fake_link(h2d_bps=400e6, d2h_bps=400e6)
    n, frame = 1 << 16, 1 << 14
    x = (0.8 * np.exp(2j * np.pi * 0.125 * np.arange(n))
         + _gaussian_c64(n, seed=9) * 0.01).astype(np.complex64)
    got, _ = _run_wired_kernel(name, x, frame, depth=4)
    assert len(got) == n
    ref = (np.abs(np.fft.fft(x.reshape(-1, 256), axis=1)) ** 2).reshape(-1)
    peak = float(ref.max())
    np.testing.assert_allclose(got, ref, atol=CHAIN_TOL[name] * peak,
                               rtol=CHAIN_TOL[name] * 10)


def test_streamed_pipelining_overlaps_link(fake_link):
    """Trace-measured evidence of H2D ∥ compute ∥ D2H: the span recorder's
    per-frame lane intervals prove the overlap directly — union(all lanes) <
    Σ(durations) — instead of the old wall-clock `pipelined ≤ 0.75×serialized`
    heuristic (which conflated scheduler noise with overlap and could not say
    WHICH lane hid under which). Serialized (depth=1) must read ≈ 1.0 and the
    pipelined loop ≤ 0.75: with the fake link's per-direction wire occupancy
    deterministically modeled, the ideal pipelined ratio here is ~0.5 (D2H
    fully hidden under H2D, compute ≈ 0) and the serialized one exactly 1.0
    (lanes strictly alternate on one frame in flight)."""
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.ops import mag2_stage
    from futuresdr_tpu.telemetry import spans
    from futuresdr_tpu.tpu import TpuKernel

    n, frame = 1 << 19, 1 << 15
    tone = np.exp(2j * np.pi * 0.2 * np.arange(n)).astype(np.complex64)

    def run(depth):
        fg = Flowgraph()
        src = VectorSource(tone)
        tk = TpuKernel([mag2_stage()], np.complex64, frame_size=frame,
                       frames_in_flight=depth, wire="f32")
        snk = VectorSink(np.float32)
        fg.connect(src, tk, snk)
        spans.drain()                            # fresh ring for this run
        Runtime().run(fg)
        return spans.overlap_report(spans.drain())

    was = spans.enabled()
    spans.enable(True)
    try:
        # f32 wire: 256 KiB/frame up (16 ms at 16 MB/s), 128 KiB down (16 ms
        # at 8 MB/s); 16 frames → ≈512 ms of modeled wire time per run
        fake_link(h2d_bps=16e6, d2h_bps=8e6)
        serial = run(1)
        fake_link(h2d_bps=16e6, d2h_bps=8e6)     # fresh timeline
        pipe = run(4)
    finally:
        spans.enable(was)
    # every lane actually recorded every frame
    for rep in (serial, pipe):
        for lane in ("H2D", "compute", "D2H"):
            assert rep["lanes"][lane]["spans"] == n // frame, (lane, rep)
    # the wire time is real (≈0.13 s per direction at these rates), so the
    # ratio is measuring modeled link occupancy, not noise-scale intervals
    assert pipe["sum_s"] >= 0.2, pipe
    assert serial["ratio"] >= 0.9, \
        f"serialized lanes overlapped: {serial}"
    assert pipe["ratio"] <= 0.75, \
        f"no overlap: pipelined union/sum {pipe['ratio']:.2f} ({pipe})"


def test_frame_plane_wire_round_trip(fake_link):
    """TpuH2D(wire) → TpuStage → TpuD2H(wire): the frame plane speaks the
    codec on both crossings too."""
    from scipy import signal as sps
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage
    from futuresdr_tpu.tpu import TpuD2H, TpuH2D, TpuStage
    fake_link(h2d_bps=400e6, d2h_bps=400e6)
    taps = firdes.lowpass(0.2, 64).astype(np.float32)
    data = np.random.default_rng(10).standard_normal(100_000).astype(np.float32)
    frame = 16384
    fg = Flowgraph()
    src, snk = VectorSource(data), VectorSink(np.float32)
    h2d = TpuH2D(np.float32, frame_size=frame, wire="sc16")
    st = TpuStage([fir_stage(taps, fft_len=1024)], np.float32)
    d2h = TpuD2H(np.float32, wire="sc16")
    fg.connect(src, h2d, st, d2h, snk)
    Runtime().run(fg)
    got = snk.items()
    ref = sps.lfilter(taps, 1.0, data)
    n = (len(data) // frame) * frame
    assert len(got) >= n
    np.testing.assert_allclose(got[:n], ref[:n], rtol=1e-2, atol=2e-3)


def test_wire_config_env_override(monkeypatch):
    """FUTURESDR_TPU_WIRE_FORMAT pins the codec through resolve_wire(None)."""
    monkeypatch.setenv("FUTURESDR_TPU_WIRE_FORMAT", "sc8")
    from futuresdr_tpu.config import reload_config
    reload_config()
    try:
        assert resolve_wire(None, "cpu").name == "sc8"
    finally:
        monkeypatch.delenv("FUTURESDR_TPU_WIRE_FORMAT")
        reload_config()
