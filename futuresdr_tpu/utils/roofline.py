"""Program cost records and chip peaks for the profile plane
(``telemetry/profile.py``).

The numbers come from the compiled program's ``cost_analysis()`` (XLA's
flop/byte counts for exactly the HLO that runs). Caveat: the analysis is
per-backend, and its "bytes accessed" counts VMEM traffic as HBM (ROADMAP
D10); the benchmark's roofline share is computed from shapes instead
(``benchmark/harness/costs.py``).

Peaks: :func:`detect_peaks` resolves the denominator for MFU/HBM-utilization
claims in two layers — explicit config overrides (``peak_flops`` in FLOP/s,
``peak_hbm_gbps`` in GB/s), then the LIVE chip kind from
``jax.devices()[0].device_kind`` against the public per-chip spec table
(:data:`CHIP_PEAKS`, bf16 matmul peaks — the standard MFU convention; there is
no official f32 peak, f32 matmuls lower to multiple bf16 passes so f32 chains
simply show proportionally lower MFU). A CPU host and an UNKNOWN live
accelerator return None: flops/bytes-only output, never an MFU against a
denominator the run did not have — a backend label never stands in for a
live device.

Cost records are cached **by program signature** (:data:`_cost_cache`):
``cost_of`` pays its AOT ``jax.jit(fn).lower().compile()`` once per signature
per process, so the profile plane's program registration does not compile a
second time what another kernel of the same shape already asked about.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

__all__ = ["cost_of", "program_cost", "detect_peaks", "dtype_peak_flops",
           "dominant_dtype", "CHIP_PEAKS"]

# public per-chip specs (per chip, bf16 matmul peak FLOP/s + HBM B/s;
# ``int8_flops`` where the generation publishes a distinct int8 OPS figure —
# v5e/v5p/v6e run int8 matmuls at 2x the bf16 rate, v2–v4 have no int8
# acceleration so the key is absent and int8 grades against the bf16 peak)
CHIP_PEAKS = {
    "v2": {"flops": 45e12, "hbm_bytes": 700e9},
    "v3": {"flops": 123e12, "hbm_bytes": 900e9},
    "v4": {"flops": 275e12, "hbm_bytes": 1228e9},
    "v5e": {"flops": 197e12, "hbm_bytes": 819e9, "int8_flops": 394e12},
    "v5p": {"flops": 459e12, "hbm_bytes": 2765e9, "int8_flops": 918e12},
    "v6e": {"flops": 918e12, "hbm_bytes": 1640e9, "int8_flops": 1836e12},
}


def _kind_to_chip(kind: str) -> Optional[str]:
    """Map a ``device_kind`` string to a :data:`CHIP_PEAKS` key (None =
    unknown). Kind strings vary by runtime version ("TPU v5 lite",
    "TPU v5e", "tpu_v5_lite", …) — match on the version token."""
    k = str(kind).lower().replace("_", " ")
    if "v5p" in k:
        return "v5p"
    if "v5" in k and ("lite" in k or "v5e" in k):
        return "v5e"
    if "v6" in k:
        return "v6e"
    if "v4" in k:
        return "v4"
    if "v3" in k:
        return "v3"
    if "v2" in k:
        return "v2"
    return None


def dtype_peak_flops(peaks: dict, dtype: Optional[str] = None) -> float:
    """The MFU flops denominator for a program whose dominant compute dtype
    is ``dtype``. The tabled peaks (and the config ``peak_flops`` override —
    config.py documents it as the bf16 matmul peak) are BF16 figures; f32
    matmuls lower to multiple bf16 passes on every tabled chip, so the f32
    peak is half. Keying the denominator on the program's dtype stops
    f32-dominant chains from grading themselves against a peak they cannot
    reach (5.6% of bf16-peak is 11.2% of the f32 peak the chain actually
    runs against — the headroom claim changes materially). ``"int8"`` uses
    the chip's published int8 OPS figure (``int8_flops`` in
    :data:`CHIP_PEAKS`) where one exists — the HONEST denominator for an
    int8-accumulating program, typically 2x the bf16 peak — falling back to
    the bf16 figure on generations without int8 acceleration (and on pure
    config-override peaks, which carry no int8 axis)."""
    f = float(peaks["flops"])
    d = str(dtype or "bf16")
    if d == "bf16":
        return f
    if d == "int8":
        return float(peaks.get("int8_flops", f))
    return f / 2.0


def dominant_dtype(stages) -> str:
    """The per-program key for :func:`dtype_peak_flops`: ``"int8"`` when any
    stage of the (possibly lowered) chain accumulates through an int8 MXU
    pass (the deepest ladder rung dominates — its peak is the one the
    program's hot matmuls run against), else ``"bf16"`` when any stage
    accumulates in bf16 or the process-wide MXU FFT precision policy is
    bf16, else ``"f32"``."""
    bf16 = False
    try:
        from ..ops import mxu_fft
        if mxu_fft._precision == "bf16":
            bf16 = True
    except Exception:                                   # noqa: BLE001
        pass
    for s in stages:
        cd = getattr(s, "compute_dtype", "f32")
        if cd == "int8":
            return "int8"
        if cd == "bf16":
            bf16 = True
    return "bf16" if bf16 else "f32"


def detect_peaks(dtype: Optional[str] = None) -> Optional[dict]:
    """Resolve ``{"flops", "hbm_bytes", "chip"}`` for MFU accounting.

    Layering (module docstring): both config overrides set → pure-config
    peaks; else the live device's ``device_kind`` against the public table
    (single-axis overrides still apply). A CPU host or an unknown kind
    returns None, which disables MFU/HBM-util output entirely — pin the
    denominator on an unknown chip with ``peak_flops``/``peak_hbm_gbps``.

    ``dtype`` keys the flops figure on the program's dominant compute dtype
    (:func:`dtype_peak_flops`): ``"f32"`` halves the tabled bf16 peak and
    stamps ``"dtype"`` on the result; ``None``/``"bf16"`` keeps the tabled
    figure (back-compatible)."""
    from ..config import config
    c = config()
    try:
        pf = float(c.get("peak_flops", 0) or 0)
    except (TypeError, ValueError):
        pf = 0.0
    try:
        pb = float(c.get("peak_hbm_gbps", 0) or 0)
    except (TypeError, ValueError):
        pb = 0.0
    def _keyed(out: dict) -> dict:
        # per-dtype denominator: applied LAST so it scales whatever source
        # won (table or the config override — both bf16 figures)
        if dtype is not None:
            out = dict(out)
            out["flops"] = dtype_peak_flops(out, dtype)
            out["dtype"] = str(dtype)
        return out

    if pf > 0 and pb > 0:
        return _keyed({"flops": pf, "hbm_bytes": pb * 1e9, "chip": "config"})

    import jax
    dev = jax.devices()[0]
    chip = None if dev.platform == "cpu" else _kind_to_chip(dev.device_kind)
    if chip is None:
        return None
    out = dict(CHIP_PEAKS[chip], chip=chip)
    if pf > 0:
        out["flops"] = pf
    if pb > 0:
        out["hbm_bytes"] = pb * 1e9
    return _keyed(out)


# ---------------------------------------------------------------------------
# cost analysis (signature-cached)
# ---------------------------------------------------------------------------

#: ``signature -> {"flops", "bytes"}`` — one AOT cost-analysis compile per
#: signature per process (kernel registrations and the profile plane's
#: ensure_costs share it)
_cost_cache: Dict[tuple, dict] = {}


def cost_of(fn, *args, signature: Optional[tuple] = None,
            compiled=None) -> dict:
    """flops + bytes accessed of ``jit(fn)(*args)`` from XLA's cost analysis.

    ``signature`` (hashable) memoizes the record — the second ask for the
    same program is free. ``compiled`` reuses an ALREADY-compiled executable
    (anything with ``cost_analysis()``) instead of paying the AOT
    ``jax.jit(fn).lower().compile()`` second copy. An actual AOT compile is
    billed to the profile plane as ``reason="cost"`` (visible to the
    doctor's "compiling" verdict; excluded from storm detection — each
    signature compiles at most once per process by construction)."""
    if signature is not None:
        hit = _cost_cache.get(signature)
        if hit is not None:
            return dict(hit)
    if compiled is None:
        import jax

        from ..telemetry import profile as _profile
        with _profile.compiling("cost_analysis", "cost",
                                str(signature or "?")):
            compiled = jax.jit(fn).lower(*args).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    out = {"flops": float(ca.get("flops", 0.0)),
           "bytes": float(ca.get("bytes accessed", 0.0))}
    if signature is not None:
        _cost_cache[signature] = dict(out)
    return dict(out)


def _stage_marker(s) -> tuple:
    """A structural fingerprint of one stage for cost-cache keys. Names
    alone are NOT enough — two ``fir_stage``s with different tap counts or
    decimation share ``name="fir"`` but compile to different-cost programs.
    Ratio, out dtype, frame multiple and the LTI config (tap count, decim,
    fft length, impl) disambiguate every structural cost determinant;
    carry-resident parameters (retunable without recompile) by construction
    cannot change the program's cost."""
    lti = getattr(s, "lti", None)
    lti_m = None
    if lti is not None:
        taps, decim, fft_len, impl = lti
        lti_m = (int(np.asarray(taps).size), int(decim), int(fft_len),
                 str(impl))
    return (str(getattr(s, "name", "?")), str(getattr(s, "ratio", "")),
            str(getattr(s, "out_dtype", None)),
            int(getattr(s, "frame_multiple", 1) or 1), lti_m,
            # per-call-site route pins (impl, fft_impl, precision): two
            # same-shape stages on different routes compile different-cost
            # programs and must not share a cost-cache line
            getattr(s, "route", None),
            # MergeStage extras (None for plain stages): input count + mode
            getattr(s, "k", None), getattr(s, "mode", None))


def program_cost(pipeline, frame: int, wire=None, k: int = 1) -> dict:
    """Per-DISPATCH flops/bytes of a pipeline's compiled program FORM.

    ``wire=None`` analyzes the bare ``(carry, frame) -> (carry, out)``
    program; a wire name analyzes the WIRED form (decode prolog + encode
    epilog fused in) and ``k > 1`` the megabatch ``lax.scan`` form — exactly
    the program ``TpuKernel`` dispatches, so the profile plane's live MFU is
    charged for the HLO that actually runs. Cached by signature (pipeline
    shape + topology + dtype + frame + wire + k + backend)."""
    import jax

    from ..ops.stages import DagPipeline, FanoutPipeline
    markers = tuple(_stage_marker(s) for s in pipeline.stages)
    # flat markers alone cannot distinguish two graphs with the same stage
    # multiset (a diamond vs a chain of the same nodes, or a fan-out split
    # at a different producer boundary) — the edge structure changes the
    # compiled program's cost, so it must be part of the cache key. The
    # node lengths partition the MERGED flat ``stages`` list the markers
    # were taken from, so (markers, topo) fully determines the program.
    topo: Optional[tuple] = None
    if isinstance(pipeline, DagPipeline):
        topo = ("dag", tuple((len(sl), tuple(inputs))
                             for sl, inputs, _off in pipeline._nodes))
    elif isinstance(pipeline, FanoutPipeline):
        topo = ("fanout", len(pipeline.producer.stages),
                tuple(len(b.stages) for b in pipeline.branches))
    in_dt = np.dtype(pipeline.in_dtype)
    wire_name = None
    if wire is not None:
        from ..ops.wire import get_wire
        wire = get_wire(wire)
        wire_name = wire.name
    sig = ("program", jax.default_backend(), type(pipeline).__name__,
           str(in_dt), int(frame), wire_name, int(k), markers, topo)
    hit = _cost_cache.get(sig)
    if hit is not None:
        return dict(hit)
    carry = pipeline.init_carry()
    host = np.zeros(frame, dtype=in_dt)
    if wire is None:
        return cost_of(pipeline.fn(), carry, host, signature=sig)
    parts = wire.encode_host(host)
    if k > 1:
        parts = tuple(np.stack([np.asarray(p)] * int(k)) for p in parts)
    return cost_of(pipeline.wired_fn(wire, int(k)), carry,
                   *[np.asarray(p) for p in parts], signature=sig)
