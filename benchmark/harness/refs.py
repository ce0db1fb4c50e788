"""Plain references, the signal generator and the compile meter, copied from
``chip_smoke.py`` (PR 21) so that no later PR can move them. float64
numpy/scipy, zero initial state, no XLA. The originals are listed in PERF.md's
open questions for a later PR to delete."""

from __future__ import annotations

import time

import numpy as np

# scipy.signal takes ~2 s to import: the references run after the window, so
# it is imported where it is used and stays out of every run's set-up

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_JAX_COMPILE_EVENTS = ("/jax/core/compile/", "/jax/compilation_cache/")

# the FM front end's constants (futuresdr_tpu/apps/fm_receiver.py:19-20 and
# front_end_stages' defaults), restated here so the reference does not import
# the code it checks
FM_INPUT_RATE = 1_000_000
FM_CHANNEL_RATE = 250_000
FM_AUDIO_RATE = 48_000
FM_DECIM = FM_INPUT_RATE // FM_CHANNEL_RATE
FM_TUNER_TAPS = 128
FM_DEVIATION = 75e3


class CompileMeter:
    """Every XLA program build of the process, from jax's own monitoring
    events. A persistent-cache hit still counts as a build (it is one jit
    miss), just a fast one."""

    def __init__(self):
        import jax
        self.events = []          # (perf_counter_ns at end, seconds, name)
        self.cache_hits = 0
        self.stages = []          # (perf_counter_ns at end, stage, seconds, name)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        now = time.perf_counter_ns()
        if event == _BACKEND_COMPILE:
            self.events.append((now, float(seconds),
                                str(kw.get("fun_name", "?"))))
        if event.startswith(_JAX_COMPILE_EVENTS):
            # also what a build is made of: a retrace or a lowering that ends
            # in a cache hit builds nothing, and still holds the GIL
            self.stages.append((now, event.rsplit("/", 1)[-1], float(seconds),
                                str(kw.get("fun_name", "?"))))

    def _event(self, event, **kw):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def between(self, t0_ns: int, t1_ns: int) -> list:
        """Names of the programs whose build ended inside ``[t0, t1]``."""
        return [n for t, _, n in self.events if t0_ns <= t <= t1_ns]

    def stages_between(self, t0_ns: int, t1_ns: int) -> list:
        """``[stage, name, ms, seconds into the window]`` of every tracing,
        lowering, build or cache read that ended inside ``[t0, t1]``."""
        return [[st, n, round(s * 1e3, 1), round((t - t0_ns) * 1e-9, 3)]
                for t, st, s, n in self.stages if t0_ns <= t <= t1_ns]

    def seconds(self) -> float:
        return float(sum(s for _, s, _ in self.events))


def lowpass(cutoff: float, n_taps: int) -> np.ndarray:
    """Hamming-windowed sinc, unity DC gain; cutoff in cycles/sample. The
    formula of ``futuresdr_tpu/dsp/firdes.lowpass`` (window="hamming"),
    restated so the references own their taps."""
    n = np.arange(n_taps) - (n_taps - 1) / 2.0
    taps = 2.0 * cutoff * np.sinc(2.0 * cutoff * n) * np.hamming(n_taps)
    return taps / taps.sum()


def kaiser_lowpass(cutoff: float, transition_width: float,
                   atten_db: float = 60.0) -> np.ndarray:
    """Kaiser-window lowpass from a spec (order and beta by Kaiser's
    formulas, odd length), the formula of ``dsp/firdes.kaiser_lowpass``."""
    beta = 0.1102 * (atten_db - 8.7)          # atten > 50 dB
    n = int(np.ceil((atten_db - 7.95)
                    / (2.285 * 2 * np.pi * transition_width))) + 1
    n += (n % 2 == 0)
    k = np.arange(n) - (n - 1) / 2.0
    taps = 2.0 * cutoff * np.sinc(2.0 * cutoff * k) * np.kaiser(n, beta)
    return taps / taps.sum()


def fm_tuner_taps() -> np.ndarray:
    """The xlating FIR's channel filter (apps/fm_receiver.front_end_stages)."""
    return lowpass(0.5 / FM_DECIM * 0.8, FM_TUNER_TAPS)


def fm_resampler() -> tuple:
    """``(interp, decim, taps)`` of the 250 kHz → 48 kHz polyphase resampler
    (``ops.resample_stage``'s default prototype: Kaiser lowpass at 0.4/r with
    a 0.1/r transition, gain ``interp``)."""
    from math import gcd
    g = gcd(FM_AUDIO_RATE, FM_CHANNEL_RATE)
    interp, decim = FM_AUDIO_RATE // g, FM_CHANNEL_RATE // g
    r = max(interp, decim)
    return interp, decim, kaiser_lowpass(0.5 / r * 0.8, 0.1 / r) * interp


def snr_db(got, want) -> float:
    err = float(np.sum(np.abs(np.asarray(got, np.float64) - want) ** 2))
    return float("inf") if err == 0.0 else \
        10.0 * np.log10(float(np.sum(np.abs(want) ** 2)) / err)


def ref_spectrum(x: np.ndarray, taps: np.ndarray, n_fft: int,
                 history: np.ndarray = None) -> np.ndarray:
    """fir → fft(n_fft) → |x|² in float64. ``history``: the samples that came
    before ``x`` (at least ``len(taps) - 1`` of them) when ``x`` is not the
    start of the stream; None means zero initial state."""
    from scipy import signal
    xd = x.astype(np.complex128)
    h = taps.astype(np.float64)
    if history is None:
        y = signal.lfilter(h, 1.0, xd)
    else:
        k = len(h) - 1
        if len(history) < k:
            raise ValueError(f"history of {len(history)} < {k} samples")
        y = signal.lfilter(
            h, 1.0, np.concatenate([history[-k:].astype(np.complex128), xd]))[k:]
    spec = np.fft.fft(y.reshape(-1, n_fft), axis=1)
    return (spec.real ** 2 + spec.imag ** 2).reshape(-1)


def fm_signal(n: int, f_tone: float, rate: float = 1e6, dev: float = 50e3,
              carrier: float = 0.0, phase: float = 0.0) -> np.ndarray:
    """Constant-envelope FM of one audio tone (float64 phase, then c64). With
    ``carrier`` 0 and ``f_tone * n / rate`` a whole number the signal is
    periodic in ``n``: a session replays it for ever without a seam."""
    t = np.arange(n) / rate
    ph = (dev / f_tone) * np.sin(2 * np.pi * f_tone * t + phase) \
        + 2 * np.pi * carrier * t
    return np.exp(1j * ph).astype(np.complex64)


def ref_fm_front_end(x: np.ndarray, retune_at: int = -1,
                     theta: float = 0.0) -> np.ndarray:
    """The FM front end at its defaults, zero initial state, float64:
    xlating decimating FIR (÷4) → FM discriminator → 24/125 polyphase
    resampler. ``retune_at``/``theta``: from input sample ``retune_at`` on the
    tuner runs at phase increment ``theta`` (phase continuous); before it, at
    0. Taps are rounded to float32 first, as the program holds them."""
    from scipy import signal
    D = FM_DECIM
    h = fm_tuner_taps().astype(np.float32).astype(np.float64)
    xd = x.astype(np.complex128)
    y = signal.upfirdn(h, xd, 1, D)[:len(x) // D]
    if retune_at >= 0:
        rot = np.exp(1j * theta * (np.arange(len(x)) - retune_at))
        y_post = signal.upfirdn(h, xd * rot, 1, D)[:len(x) // D]
        y[retune_at // D:] = y_post[retune_at // D:]
    prev = np.concatenate([[1.0 + 0j], y[:-1]])
    audio = FM_CHANNEL_RATE / (2 * np.pi * FM_DEVIATION) \
        * np.angle(y * np.conj(prev))
    interp, decim, rs = fm_resampler()
    rs = rs.astype(np.float32).astype(np.float64)
    return signal.upfirdn(rs, audio, interp, decim)[:len(audio) * interp // decim]
