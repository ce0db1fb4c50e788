"""Operations and bytes a call NEEDS, from its shapes: the numerator of every
roofline share. Nothing here asks XLA (``cost_analysis`` bytes are not
auditable: PR 21 read an HBM utilization of 2.13 from them).

Conventions, stated once:

* a real multiply-add is 2 operations; a complex x real MAC is 4, a complex x
  complex MAC is 8; a complex multiply alone is 6;
* an FFT of length n needs ``5 n log2 n`` operations (the radix-2 count every
  FFT benchmark uses). The MXU four-step form EXECUTES more
  (``fft_four_step_executed_flops``); executed-but-not-needed work does not
  count toward a roofline share, it is why the share is low;
* ``atan2`` counts as 1 operation (a transcendental unit op);
* bytes are what must cross HBM for a fused program: its input read once and
  its output written once, at the dtypes they have on the device. Interior
  edges of a fused program need no HBM traffic and count 0.
"""

from __future__ import annotations

import math
from typing import Dict


def fir_flops(n_out: int, n_taps: int, complex_in: bool = True,
              complex_taps: bool = False) -> int:
    """Direct-form FIR: ``n_taps`` MACs per output sample."""
    mac = 8 if (complex_in and complex_taps) else 4 if (complex_in or complex_taps) else 2
    return n_out * n_taps * mac


def fft_flops(n_fft: int, batch: int) -> int:
    return int(batch * 5 * n_fft * math.log2(n_fft))


def fft_four_step_executed_flops(n1: int, n2: int, batch: int) -> int:
    """What the matmul form runs for n = n1*n2: a dense DFT_n1 (complex
    matmul, 8 ops per MAC), n twiddle multiplies (6 each), a dense DFT_n2."""
    n = n1 * n2
    return batch * (8 * n * n1 + 6 * n + 8 * n * n2)


def mag2_flops(n: int) -> int:
    return 3 * n                     # re*re + im*im


def xlating_fir_flops(n_in: int, n_taps: int, decim: int) -> int:
    """Complex taps on complex input at the decimated rate, then the residual
    rotator (one complex multiply) per output sample."""
    n_out = n_in // decim
    return n_out * (8 * n_taps + 6)


def discriminator_flops(n: int) -> int:
    """y[n]·conj(y[n-1]) (6), atan2 (1), gain (1)."""
    return 8 * n


def polyphase_resampler_flops(n_in: int, interp: int, decim: int,
                              n_taps: int) -> int:
    """Real input, real taps: each output sample uses one polyphase branch of
    ``ceil(n_taps / interp)`` taps."""
    n_out = n_in * interp // decim
    return n_out * 2 * math.ceil(n_taps / interp)


def spectrum_frame_cost(frame: int, n_taps: int, n_fft: int,
                        in_bytes_per_sample: float,
                        out_bytes_per_item: float) -> Dict[str, float]:
    """One frame of fir → fft → |x|² as one fused program."""
    flops = (fir_flops(frame, n_taps) + fft_flops(n_fft, frame // n_fft)
             + mag2_flops(frame))
    return {"flops": float(flops),
            "bytes": frame * (in_bytes_per_sample + out_bytes_per_item)}


def fm_front_end_frame_cost(frame: int, tuner_taps: int, decim: int,
                            interp: int, rs_decim: int,
                            resampler_taps: int) -> Dict[str, float]:
    """One lane-frame of xlating FIR → discriminator → polyphase resampler,
    complex64 in, float32 out."""
    n_ch = frame // decim
    flops = (xlating_fir_flops(frame, tuner_taps, decim)
             + discriminator_flops(n_ch)
             + polyphase_resampler_flops(n_ch, interp, rs_decim,
                                         resampler_taps))
    return {"flops": float(flops),
            "bytes": float(frame * 8 + (n_ch * interp // rs_decim) * 4)}


def pfb_cost(rows: int, n_channels: int, taps_per_branch: int) -> Dict[str, float]:
    """One call of a critically sampled PFB analysis bank that yields ``rows``
    rows of ``n_channels`` outputs: per row a complex x real MAC for every tap
    of every branch and an FFT across the branches. Bytes: its rows in once
    (the ``taps_per_branch - 1`` rows of history with them) and out once,
    complex as two float32 planes, and its real float32 taps once."""
    n, k = n_channels, taps_per_branch
    flops = fir_flops(rows * n, k) + fft_flops(n, rows)
    return {"flops": float(flops),
            "bytes": float(8 * n * (rows + k - 1) + 8 * n * rows + 4 * n * k)}


def window_fetch_cost(lanes: int, window: int, steps: int) -> Dict[str, float]:
    """``steps`` calls of a fetch that copies one window of ``window`` complex
    samples out of each of ``lanes`` lanes: no operation; each window's
    samples read once and written once, as two float32 planes."""
    return {"flops": 0.0, "bytes": float(steps * lanes * window * 8 * 2)}


def trellis_cost(steps: float, ops_per_step: int) -> Dict[str, float]:
    """A Viterbi trellis over ``steps`` steps of a rate-1/2 mother code, at
    ``ops_per_step`` (add-compare-select of every state and one traceback
    step). Bytes: the step's two float32 LLRs in and its decision out, one
    byte."""
    return {"flops": float(steps * ops_per_step),
            "bytes": float(steps * (2 * 4 + 1))}


def roofline(cost: Dict[str, float], peaks: dict) -> Dict[str, object]:
    """The least time the chip could take for ``cost`` and which peak sets
    it: the larger of operations over peak FLOP/s and bytes over peak B/s."""
    t_flops = cost["flops"] / peaks["flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"min_s": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "t_flops_s": t_flops, "t_bytes_s": t_bytes}
