"""Builder of ``fm_serve_1msps_sc16``: ``fm_serve_1msps`` for listeners whose
radios deliver 16-bit I/Q. The engine is built on the ``sc16`` serving wire:
sessions submit ``uint32[frame]`` words, a complex sample a word (I the low
half, Q the high half, int16 each: the bytes of UHD's ``sc16`` and SoapySDR's
``CS16``), the words cross the link as they are and the step's program
decodes them. Same interface as the sibling's module (the ``serve`` driver's):

    make_engine(cfg, rehearse)           -> ServeEngine (not yet registered)
    lane_signal(cfg, seed, lane, frame)  -> uint32 [period_frames, frame]
    reference(cfg, x, retune_at, theta)  -> float64 audio for the words ``x``
    judge(cfg, got, want)                -> (ok, max_abs_err)
    dispatch_cost(cfg, frame, capacity)  -> {"flops", "bytes"} per dispatch
    retune_body(theta)                   -> JSON body of a lane retune

The reference demodulates the SUBMITTED 16 bits (words -> complex128 at
``full_scale`` -> ``harness.refs.ref_fm_front_end``, float64): this deployment
has no float original, so quantization is not an error of the system.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from harness import cells, refs

#: everything but the frame format is the sibling's, by its own functions
SIB = cells.load_module(Path(__file__).with_name("fm_serve_1msps.py"))
judge, retune_body = SIB.judge, SIB.retune_body


def make_engine(cfg: dict, rehearse: bool):
    from futuresdr_tpu.apps.fm_receiver import front_end_stages
    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.serve.engine import ServeEngine

    p = SIB._sized(cfg, rehearse)
    return ServeEngine(Pipeline(front_end_stages(), np.dtype(p["in_dtype"])),
                       frame_size=p["frame_size"], app=p["app"],
                       buckets=tuple(p["buckets"]), wire=p["wire"])


def to_words(x: np.ndarray, cfg: dict) -> np.ndarray:
    """What the radio's converter hands over for the unit-envelope signal
    ``x``: I and Q at ``amplitude`` of full scale, rounded to int16 once, a
    little-endian pair a ``uint32`` word."""
    a = cfg["assumed"]
    q = np.empty(x.shape + (2,), np.int16)
    peak = float(a["amplitude"]) * float(a["full_scale"])
    q[..., 0] = np.rint(x.real.astype(np.float64) * peak)
    q[..., 1] = np.rint(x.imag.astype(np.float64) * peak)
    return q.view(np.uint32).reshape(x.shape)


def from_words(w: np.ndarray, cfg: dict) -> np.ndarray:
    """The samples a word stands for, complex128: its two int16 halves over
    ``full_scale``, exactly."""
    q = np.ascontiguousarray(w, np.uint32).view(np.int16).reshape(w.shape + (2,))
    fs = float(cfg["assumed"]["full_scale"])
    return q[..., 0] / fs + 1j * (q[..., 1] / fs)


def lane_signal(cfg: dict, seed: int, lane: int, frame: int) -> np.ndarray:
    """One listener's station, the sibling's (a tone with a whole number of
    periods in ``period_frames`` frames, tone and phase from
    ``[seed, lane]``), as the 16-bit words its radio delivers."""
    return to_words(SIB.lane_signal(cfg, seed, lane, frame), cfg)


def reference(cfg: dict, x: np.ndarray, retune_at: int = -1,
              theta: float = 0.0) -> np.ndarray:
    return refs.ref_fm_front_end(from_words(x, cfg), retune_at=retune_at,
                                 theta=theta)


def dispatch_cost(cfg: dict, frame: int, capacity: int) -> dict:
    """The sibling's count less 4 bytes a sample in: ``harness/costs.py``
    counts a complex64 input, 8 bytes a sample; here a sample enters the
    program as one 4-byte word. Masked lanes compute too."""
    cost = SIB.dispatch_cost(cfg, frame, capacity)
    cost["bytes"] -= capacity * frame * 4
    return cost
