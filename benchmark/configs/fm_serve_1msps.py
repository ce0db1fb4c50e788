"""Builder of ``fm_serve_1msps``. Interface of a configuration that the
``serve`` driver can run:

    make_engine(cfg, rehearse)           -> ServeEngine (not yet registered)
    lane_signal(cfg, seed, lane, frame)  -> complex64 [period_frames, frame]
    reference(cfg, x, retune_at, theta)  -> float64 audio for ``x``
    judge(cfg, got, want)                -> (ok, max_abs_err)
    dispatch_cost(cfg, frame, capacity)  -> {"flops", "bytes"} per dispatch
    retune_body(theta)                   -> JSON body of a lane retune
"""

from __future__ import annotations

import numpy as np

from harness import costs, refs


def _sized(cfg: dict, rehearse: bool) -> dict:
    p = dict(cfg["parameters"])
    if rehearse:
        p.update(frame_size=cfg["rehearsal"]["frame_size"],
                 buckets=cfg["rehearsal"]["buckets"])
    return p


def make_engine(cfg: dict, rehearse: bool):
    from futuresdr_tpu.apps.fm_receiver import front_end_stages
    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.serve.engine import ServeEngine

    p = _sized(cfg, rehearse)
    return ServeEngine(Pipeline(front_end_stages(), np.dtype(p["in_dtype"])),
                       frame_size=p["frame_size"], app=p["app"],
                       buckets=tuple(p["buckets"]))


def lane_signal(cfg: dict, seed: int, lane: int, frame: int) -> np.ndarray:
    """One listener's station: a tone with a whole number of periods in
    ``period_frames`` frames, so the buffer replays without a seam."""
    period = int(cfg["assumed"]["period_frames"])
    n = period * frame
    rng = np.random.default_rng([seed, lane])
    cycles = int(rng.integers(max(2, n // 3300), max(3, n // 330)))  # 0.3-3 kHz
    f_tone = cycles * refs.FM_INPUT_RATE / n
    x = refs.fm_signal(n, f_tone, rate=refs.FM_INPUT_RATE,
                       phase=float(rng.uniform(0, 2 * np.pi)))
    return x.reshape(period, frame)


def reference(cfg: dict, x: np.ndarray, retune_at: int = -1,
              theta: float = 0.0) -> np.ndarray:
    return refs.ref_fm_front_end(x, retune_at=retune_at, theta=theta)


def judge(cfg: dict, got: np.ndarray, want: np.ndarray):
    tol = cfg["correctness"]["abs_tolerance"]
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return False, float("inf")
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    return err <= tol, err


def dispatch_cost(cfg: dict, frame: int, capacity: int) -> dict:
    """Masked lanes compute too: a dispatch costs ``capacity`` lane-frames."""
    interp, decim, taps = refs.fm_resampler()
    lane = costs.fm_front_end_frame_cost(frame, refs.FM_TUNER_TAPS,
                                         refs.FM_DECIM, interp, decim,
                                         len(taps))
    return {k: v * capacity for k, v in lane.items()}


def retune_body(theta: float) -> dict:
    return {"stage": "tuner", "params": {"phase_inc": float(theta)}}
