"""Roofline accounting for fused stage pipelines — XLA's own cost model, not hand
math.

VERDICT r3 item 7: a bare "2,944 Msps" is not auditable; ops/sample and
bytes/sample turn it into an efficiency claim. The numbers come from the
compiled program's ``cost_analysis()`` (XLA's flop/byte counts for exactly the
HLO that runs), so they track fusion decisions instead of a paper formula.
Caveat: the analysis is per-backend — a CPU-compiled pipeline fuses differently
than the TPU one, so artifacts must carry the backend they were derived on.

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
per process, so bench roofline accounting and the profile plane's program
registration (``telemetry/profile.py``) stop double-compiling programs the
pipeline's own jit cache already holds.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

__all__ = ["cost_of", "pipeline_roofline", "graph_roofline", "program_cost",
           "detect_peaks", "dtype_peak_flops", "dominant_dtype", "CHIP_PEAKS"]

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
#: signature per process (bench prefix sweeps, kernel registrations and the
#: profile plane's ensure_costs all share it)
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


def _host_frame(in_dtype, frame: int) -> np.ndarray:
    rng = np.random.default_rng(0)
    if np.issubdtype(np.dtype(in_dtype), np.complexfloating):
        return (rng.standard_normal(frame)
                + 1j * rng.standard_normal(frame)).astype(in_dtype)
    return rng.standard_normal(frame).astype(in_dtype)


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


# ---------------------------------------------------------------------------
# per-stage / per-node attribution
# ---------------------------------------------------------------------------

def pipeline_roofline(stages: Sequence, in_dtype, frame: int,
                      rate_sps: Optional[float] = None,
                      backend: str = "cpu") -> dict:
    """Ops/sample + bytes/sample for the FUSED pipeline and per-stage prefixes.

    Per-stage numbers are DIFFERENCES of compiled prefixes (stage k's cost =
    cost(stages[:k+1]) − cost(stages[:k])), so each stage is charged exactly
    what adding it to the fused program costs — fusion across the boundary
    lands on the stage that triggered it. With ``rate_sps`` the achieved
    FLOP/s, bandwidth, and (when :func:`detect_peaks` knows the chip) MFU
    are filled in. Prefix costs are signature-cached, so a repeated bench
    run (or a profile-plane registration of the full chain) compiles each
    prefix once per process."""
    from ..ops.stages import Pipeline

    out = {"frame": frame, "backend": backend, "stages": []}
    prev = {"flops": 0.0, "bytes": 0.0}
    host = _host_frame(in_dtype, frame)
    dt = str(np.dtype(in_dtype))
    markers = tuple(_stage_marker(s) for s in stages)

    for k in range(1, len(stages) + 1):
        pipe = Pipeline(list(stages[:k]), in_dtype)
        carry = pipe.init_carry()
        sig = ("prefix", backend, dt, int(frame), markers[:k])
        cost = cost_of(pipe.fn(), carry, host, signature=sig)
        out["stages"].append({
            "name": stages[k - 1].name,
            "flops_per_sample": (cost["flops"] - prev["flops"]) / frame,
            "bytes_per_sample": (cost["bytes"] - prev["bytes"]) / frame,
        })
        prev = cost
    out["flops_per_sample"] = prev["flops"] / frame
    out["bytes_per_sample"] = prev["bytes"] / frame
    _finish_roofline(out, out["stages"], rate_sps,
                     dominant_dtype(stages))
    return out


def graph_roofline(pipeline, frame: Optional[int] = None,
                   rate_sps: Optional[float] = None,
                   backend: str = "cpu") -> dict:
    """Per-NODE roofline attribution for fan-out / general-DAG pipelines.

    The prefix-difference math of :func:`pipeline_roofline` generalized to
    DAGs: node i's cost = cost(DAG truncated to nodes[:i+1]) − cost(nodes[:i])
    (node lists are topological, so every prefix is a valid sub-DAG; a
    truncated prefix's extra sink materializations mirror the linear prefix
    caveat). Accepts a :class:`~futuresdr_tpu.ops.stages.DagPipeline`, a
    :class:`~futuresdr_tpu.ops.stages.FanoutPipeline` (viewed as producer
    node + one node per branch), or a plain
    :class:`~futuresdr_tpu.ops.stages.Pipeline` (delegates to the per-stage
    form, re-keyed under ``nodes``). Per-sample numbers are per REGION-INPUT
    sample."""
    from ..ops.stages import DagPipeline, FanoutPipeline, Pipeline

    if isinstance(pipeline, Pipeline):
        out = pipeline_roofline(pipeline.stages, pipeline.in_dtype,
                                frame or pipeline.frame_multiple,
                                rate_sps, backend)
        out["nodes"] = [dict(s, inputs=([] if i == 0 else [i - 1]))
                        for i, s in enumerate(out["stages"])]
        return out
    if isinstance(pipeline, FanoutPipeline):
        nodes = [(list(pipeline.producer.stages), [])]
        nodes += [(list(b.stages), [0]) for b in pipeline.branches]
        in_dtype = pipeline.in_dtype
    elif isinstance(pipeline, DagPipeline):
        nodes = [(list(sl), list(inputs))
                 for sl, inputs in pipeline.raw_nodes]
        in_dtype = pipeline.in_dtype
    else:
        raise TypeError(f"graph_roofline: unsupported pipeline type "
                        f"{type(pipeline).__name__}")
    fm = pipeline.frame_multiple
    frame = frame or fm
    frame = max(fm, (int(frame) // fm) * fm)
    host = _host_frame(in_dtype, frame)
    dt = str(np.dtype(in_dtype))
    node_names = tuple(
        ("+".join(str(getattr(s, "name", "?")) for s in sl) or "passthrough",
         tuple(inputs)) for sl, inputs in nodes)
    node_markers = tuple(
        (tuple(_stage_marker(s) for s in sl), tuple(inputs))
        for sl, inputs in nodes)

    out = {"frame": frame, "backend": backend, "nodes": []}
    prev = {"flops": 0.0, "bytes": 0.0}
    for i in range(1, len(nodes) + 1):
        sub = DagPipeline(nodes[:i], in_dtype)
        sig = ("dag-prefix", backend, dt, frame, node_markers[:i])
        cost = cost_of(sub.fn(), sub.init_carry(), host, signature=sig)
        name, inputs = node_names[i - 1]
        out["nodes"].append({
            "name": name,
            "inputs": list(inputs),
            "flops_per_sample": (cost["flops"] - prev["flops"]) / frame,
            "bytes_per_sample": (cost["bytes"] - prev["bytes"]) / frame,
        })
        prev = cost
    out["flops_per_sample"] = prev["flops"] / frame
    out["bytes_per_sample"] = prev["bytes"] / frame
    _finish_roofline(out, out["nodes"], rate_sps,
                     dominant_dtype(pipeline.stages))
    return out


def _finish_roofline(out: dict, entries, rate_sps,
                     dtype: Optional[str] = None) -> None:
    """Shared tail of the per-stage/per-node walks: bound classification
    against the detected chip ridge + achieved-rate fields, with the MFU
    denominator keyed on the chain's dominant compute dtype."""
    peak = detect_peaks(dtype=dtype)
    if dtype is not None:
        out["compute_dtype"] = str(dtype)
    if peak:
        ridge = peak["flops"] / peak["hbm_bytes"]     # flop/byte ridge point
        for s in entries:
            ai = s["flops_per_sample"] / max(s["bytes_per_sample"], 1e-12)
            s["arith_intensity"] = ai
            s["bound"] = "hbm" if ai < ridge else "compute"
    if rate_sps:
        out["achieved_flops"] = rate_sps * out["flops_per_sample"]
        out["achieved_bw_bytes"] = rate_sps * out["bytes_per_sample"]
        if peak:
            out["mfu"] = out["achieved_flops"] / peak["flops"]
            out["hbm_util"] = out["achieved_bw_bytes"] / peak["hbm_bytes"]
