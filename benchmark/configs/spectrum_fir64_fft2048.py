"""Builder of ``spectrum_fir64_fft2048``: the system under test through its
public entry points, its seeded input, its plain reference and its costs.
Interface of a configuration that the ``stream`` driver can run:

    make_kernel(cfg, rehearse)            -> the TpuKernel block
    make_input(cfg, seed, n_frames, frame) -> complex64 replay block
    reference(cfg, x, history)            -> float64 output for ``x``
    judge(cfg, got, want, rehearse)       -> (ok, {"snr_db": ...})
    frame_cost(cfg, frame, wire)          -> {"flops", "bytes"} per frame
"""

from __future__ import annotations

import numpy as np

from harness import costs, refs


def _taps(cfg) -> np.ndarray:
    p = cfg["parameters"]
    return refs.lowpass(cfg["assumed"]["lowpass_cutoff"],
                        p["n_taps"]).astype(np.float32)


def make_kernel(cfg: dict, rehearse: bool):
    from futuresdr_tpu.ops import fft_stage, fir_stage, mag2_stage
    from futuresdr_tpu.tpu import TpuKernel

    p = cfg["parameters"]
    frame = cfg["rehearsal"]["frame_size"] if rehearse else p["frame_size"]
    return TpuKernel(
        [fir_stage(_taps(cfg)), fft_stage(p["n_fft"]), mag2_stage()],
        np.dtype(p["in_dtype"]), frame_size=frame,
        frames_in_flight=p["frames_in_flight"], wire=p["wire"])


def make_input(cfg: dict, seed: int, n_frames: int, frame: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    n = n_frames * frame
    x = np.empty(n, np.complex64)
    x.real = rng.standard_normal(n, np.float32) * np.float32(np.sqrt(0.5))
    x.imag = rng.standard_normal(n, np.float32) * np.float32(np.sqrt(0.5))
    return x


def reference(cfg: dict, x: np.ndarray, history=None) -> np.ndarray:
    return refs.ref_spectrum(x, _taps(cfg), cfg["parameters"]["n_fft"], history)


def judge(cfg: dict, got: np.ndarray, want: np.ndarray, rehearse: bool):
    floor = (cfg["rehearsal"] if rehearse else cfg["correctness"])["snr_db_floor"]
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return False, {"snr_db": float("-inf"), "floor": floor}
    snr = refs.snr_db(got, want)
    return bool(snr >= floor), {"snr_db": snr, "floor": floor}


def frame_cost(cfg: dict, frame: int, wire: str) -> dict:
    up, down = cfg["wire_bytes"][wire]
    p = cfg["parameters"]
    return costs.spectrum_frame_cost(frame, p["n_taps"], p["n_fft"], up, down)
