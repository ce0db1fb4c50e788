#!/usr/bin/env python
"""perf/precision_ab — interior-precision + Pallas hot-kernel correctness gate
(docs/tpu_notes.md "Interior precision").

``--smoke`` (the check.sh gate, the only mode) runs the hot chains in a small
matrix and asserts numerics only: ``interior_precision="off"`` is
bit-identical (same program object, same bits out), the auto plan lowers the
resident fir64+fft2048+mag2 chain with its measured floor above the
configured budget, the lowered output clears budget − allowance vs f32, the
forced int8 rung stays inside its quantization floor, the fused FIR→FFT stage
matches the composed chain, and both Pallas kernels (``pallas_pfb``,
``pallas_poly_fir``) match their matmul paths. On the CPU backend the Pallas
kernels run in INTERPRET mode. It times nothing: what a precision rung or a
kernel is worth on the chip is a benchmark cell's to say (ROADMAP D9).
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FFT_SIZE = 2048
N_TAPS = 64


def _chains():
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops.stages import (Pipeline, channelizer_stage,
                                          fft_stage, fir_fft_stage,
                                          fir_stage, mag2_stage)
    taps = firdes.lowpass(0.2, N_TAPS).astype(np.float32)
    dtaps = firdes.lowpass(0.04, 128).astype(np.float32)
    return {
        "resident": lambda: Pipeline(
            [fir_stage(taps), fft_stage(FFT_SIZE), mag2_stage()],
            np.complex64),
        # the SAME chain with the filter and transform fused in one Pallas
        # kernel (no HBM round-trip between them) — the fused-vs-composed
        # A/B row; optimize=False keeps the factory's stage split intact
        "fir_fft_fused": lambda: Pipeline(
            [fir_fft_stage(taps, FFT_SIZE), mag2_stage()],
            np.complex64, optimize=False),
        "pfb_matmul": lambda: Pipeline(
            [channelizer_stage(64, impl="matmul")], np.complex64),
        "pfb_pallas": lambda: Pipeline(
            [channelizer_stage(64, impl="pallas")], np.complex64),
        "decim_poly": lambda: Pipeline(
            [fir_stage(dtaps, decim=16, impl="poly")], np.complex64),
        "decim_pallas": lambda: Pipeline(
            [fir_stage(dtaps, decim=16, impl="pallas")], np.complex64),
    }


def _one_frame(pipe, frame: int, seed: int = 3) -> np.ndarray:
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    m = pipe.frame_multiple
    frame = max(m, (frame // m) * m)
    x = (rng.standard_normal(frame)
         + 1j * rng.standard_normal(frame)).astype(np.complex64)
    fn, c = pipe.compile(frame, donate=False)
    _c, y = fn(c, jnp.asarray(x))
    return np.asarray(y)


def _snr_db(ref, got) -> float:
    err = float(np.mean(np.abs(np.asarray(got) - np.asarray(ref)) ** 2))
    sig = float(np.mean(np.abs(np.asarray(ref)) ** 2))
    return 10 * np.log10(sig / max(err, 1e-30))


def smoke(frame: int = 1 << 15) -> None:
    """The check.sh correctness gate."""
    from futuresdr_tpu.ops import precision as P
    chains = {k: build() for k, build in _chains().items()}
    res = chains["resident"]

    # off is bit-identical: the SAME object, so the same program and bits
    off, plan_off = P.plan_interior_precision(res, mode="off")
    assert off is res and plan_off.lowered == 0
    y_ref = _one_frame(res, frame)
    np.testing.assert_array_equal(y_ref, _one_frame(off, frame))

    # auto lowers the resident chain with its measured floor over budget
    budget = 40.0
    lowered, plan = P.plan_interior_precision(res, mode="auto",
                                              budget_db=budget)
    assert plan.lowered >= 1, "auto declined the whole resident chain"
    assert plan.declined_e2e is False
    allowance = 10 * np.log10(max(1, plan.lowered))
    # the budget contract, exactly: every ACCEPTED per-edge measurement
    # clears the budget; the composition clears budget − allowance (the
    # planner's own floors — asserting min_snr_db ≥ budget would be
    # stricter than the semantics it pins, since that floor includes e2e)
    for e in plan.edges:
        for prec, db in ((e.accum, e.accum_snr_db), (e.edge, e.edge_snr_db)):
            if prec != "f32" and db is not None and np.isfinite(db):
                assert db >= budget, f"{e.stage}: accepted at {db:.1f} dB"
    assert plan.e2e_snr_db is None or \
        plan.e2e_snr_db >= budget - allowance
    got = _one_frame(lowered, frame)
    snr = _snr_db(y_ref, got)
    assert snr >= budget - allowance, \
        f"lowered resident chain SNR {snr:.1f} dB under " \
        f"{budget - allowance:.1f} dB floor"
    print(f"# smoke: resident auto-lowered {plan.lowered} stage(s), "
          f"min edge SNR {plan.min_snr_db}, e2e {snr:.1f} dB",
          file=sys.stderr)

    # forced int8 takes the rung on the FIR and stays inside its honest
    # quantization floor (dynamic absmax ≈ 36 dB; edges/FFT stay bf16, so
    # the chain floor is the FIR's)
    int8_pipe, plan8 = P.plan_interior_precision(res, mode="int8")
    assert plan8.lowered >= 1, "mode=int8 declined the resident FIR"
    snr8 = _snr_db(y_ref, _one_frame(int8_pipe, frame))
    assert snr8 >= 25.0, f"int8 resident chain SNR {snr8:.1f} dB"
    print(f"# smoke: resident int8 rung on {plan8.lowered} stage(s), "
          f"e2e {snr8:.1f} dB", file=sys.stderr)

    # the fused FIR→FFT stage matches the composed fir+fft program
    y_fu = _one_frame(chains["fir_fft_fused"], frame)
    snr_fu = _snr_db(y_ref, y_fu)
    assert snr_fu >= 80.0, \
        f"fused FIR→FFT off the composed chain ({snr_fu:.1f} dB)"

    # Pallas kernels match the matmul paths they replace
    y_mm = _one_frame(chains["pfb_matmul"], frame)
    y_pl = _one_frame(chains["pfb_pallas"], frame)
    assert _snr_db(y_mm, y_pl) >= 80.0, "pallas PFB kernel off matmul path"
    y_po = _one_frame(chains["decim_poly"], frame)
    y_pa = _one_frame(chains["decim_pallas"], frame)
    np.testing.assert_allclose(y_pa, y_po, rtol=1e-4, atol=1e-5)
    print("precision_ab smoke OK", file=sys.stderr)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--smoke", action="store_true",
                   help="the correctness gate (the only mode)")
    p.parse_args()
    smoke()


if __name__ == "__main__":
    main()
