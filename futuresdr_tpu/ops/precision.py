"""Interior precision policy: SNR-budgeted auto-lowering of the fused device plane.

The resident chains are memory-bound by the operations and bytes their
shapes need (``benchmark/harness/costs.py``; ``program_roofline`` in
``PERF_LEDGER.jsonl``); what a lower interior precision buys on the chip is
not measured by any cell yet (ROADMAP queue 1 item 3). The boundary wire
already has a quantified-loss story —
``ops/wire.py`` measures each codec's SNR and ``pick_wire`` refuses formats
under a floor. This module extends that machinery INWARD: interior DAG edges
and stage accumulation lower to bf16 (int8 where a stage declares support)
only when a configured SNR budget allows, with the loss MEASURED against the
f32 reference program, never assumed.

Two lowering mechanisms, per stage:

* **Accumulation lowering** — a stage that offers the ``Stage.lower`` hook
  (``fir_stage``, ``fft_stage``, ``channelizer_stage`` and the polyphase
  decimator behind them) is rebuilt with bf16 operands / f32 accumulation:
  native-speed MXU passes on TPU, carried weight/tap matrices landing in
  bf16 (half the carry's HBM round trip per dispatch). On CPU the same cast
  applies the same quantization, so calibration is honest on every backend.
* **Interior-edge lowering** — any float-valued edge BETWEEN stages (never
  the boundary wire — that belongs to ``ops/wire.py``) is quantized through
  bfloat16 (complex edges per re/im plane). Inside the fused XLA program
  this frees the compiler to keep the edge's materialization (scan
  intermediates, multiply-consumed fence stashes) in half-width form.

Calibration (``mode="auto"``): a seeded Gaussian calibration dispatch runs
the f32 reference program stage by stage, then each candidate lowering is
replayed on the reference inputs at its own edge and its output SNR vs the
reference output is measured — a lowering that blows
``interior_snr_budget_db`` is REFUSED, per edge, with the reason recorded.
An end-to-end check guards the composition: the fully-lowered program's sink
SNR must clear the budget minus the incoherent-sum allowance
(``budget − 10·log10(n_lowered)``), else the whole plan declines.
``mode="bf16"`` force-lowers every supporting stage/edge to bf16 (budget
ignored, SNR still measured and reported); ``mode="int8"`` force-lowers each
supporting stage as deep as its hook goes — int8 where accepted (the FIR
family's quantized matmul rungs), bf16 otherwise — the deepest serve
brownout lever. ``mode="off"`` returns the pipeline object UNCHANGED —
bit-identical by construction.

Declined edges and achieved per-edge SNR are visible in ``doctor.report()``
(key ``"precision"``) and the REST profile view
(``GET /api/fg/{fg}/profile/``) via :func:`plans_report`; the applied mode
also rides the autotune streamed-pick cache
(``tpu/autotune.record_interior_precision``) next to (k, inflight,
serve_buckets).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["EdgeDecision", "PrecisionPlan", "plan_interior_precision",
           "lower_pipeline", "snr_db", "parse_overrides", "note_plan",
           "plans_report", "clear_plans", "pallas_stage_count",
           "dominant_compute_dtype"]

#: precisions tried per stage, most-compressed first (int8 only where the
#: stage's ``lower`` hook accepts it — the FIR family does: ``fir_stage``'s
#: banded int8 matmul and the polyphase decimator's int8 shifted matvec,
#: both real-taps-only; FFT/channelizer stages decline the rung)
LOWER_LADDER = ("int8", "bf16")

#: ``"bf16"`` force-lowers every supporting stage/edge to bf16 exactly;
#: ``"int8"`` force-lowers each supporting stage as DEEP as it goes (int8
#: where the hook accepts it, bf16 fallback, edges bf16) — the serve
#: brownout's deepest precision lever. Forced modes ignore the budget but
#: still measure and report every SNR.
MODES = ("off", "auto", "bf16", "int8")


def snr_db(ref, got) -> float:
    """SNR of ``got`` against reference ``ref`` in dB (inf when exact) — the
    interior-edge counterpart of ``ops/wire.measure_snr_db``."""
    ref = np.asarray(ref).astype(np.complex128)
    got = np.asarray(got).astype(np.complex128)
    err = float(np.mean(np.abs(got - ref) ** 2))
    sig = float(np.mean(np.abs(ref) ** 2))
    if err == 0.0:
        return float("inf")
    if sig == 0.0:
        return float("-inf")
    return 10.0 * float(np.log10(sig / err))


def _edge_cast(y):
    """Quantize one interior edge value through bfloat16 (complex: per
    re/im plane), preserving the stream dtype contract."""
    import jax
    import jax.numpy as jnp
    if jnp.iscomplexobj(y):
        return jax.lax.complex(
            y.real.astype(jnp.bfloat16).astype(jnp.float32),
            y.imag.astype(jnp.bfloat16).astype(jnp.float32)).astype(y.dtype)
    if jnp.issubdtype(y.dtype, jnp.floating):
        return y.astype(jnp.bfloat16).astype(y.dtype)
    return y                      # int payloads (symbols) pass through


@dataclass
class EdgeDecision:
    """One stage's lowering verdict: the accumulation precision applied, the
    output-edge precision applied, the MEASURED SNRs backing both, and —
    only when NO lowering was applied at all — the refusal reason (a
    partially-lowered stage reads its accum refusal from ``accum="f32"`` +
    the measured ``accum_snr_db``, never from ``declined``)."""
    stage: str
    node: int
    index: int                    # flat stage index (update_stage addressing)
    accum: str = "f32"            # "f32" | "bf16" | "int8"
    edge: str = "f32"             # "f32" | "bf16"
    accum_snr_db: Optional[float] = None
    edge_snr_db: Optional[float] = None
    declined: Optional[str] = None

    def as_dict(self) -> dict:
        def _r(v):
            if v is None:
                return None
            return round(v, 1) if np.isfinite(v) else None
        return {"stage": self.stage, "node": self.node, "index": self.index,
                "accum": self.accum, "edge": self.edge,
                "accum_snr_db": _r(self.accum_snr_db),
                "edge_snr_db": _r(self.edge_snr_db),
                "declined": self.declined}


@dataclass
class PrecisionPlan:
    mode: str
    budget_db: float
    edges: List[EdgeDecision] = field(default_factory=list)
    e2e_snr_db: Optional[float] = None     # min across sinks, lowered vs f32
    declined_e2e: bool = False             # auto plan rolled back entirely
    frame: int = 0                         # calibration frame size

    @property
    def lowered(self) -> int:
        """How many stages carry ANY lowering (accum or edge)."""
        return sum(1 for e in self.edges
                   if e.accum != "f32" or e.edge != "f32")

    @property
    def min_snr_db(self) -> Optional[float]:
        """The worst MEASURED SNR among accepted lowerings — the pinned floor
        the planner is held to. None when nothing
        lowered or every measurement was exact (inf)."""
        vals = []
        for e in self.edges:
            if e.accum != "f32" and e.accum_snr_db is not None \
                    and np.isfinite(e.accum_snr_db):
                vals.append(e.accum_snr_db)
            if e.edge != "f32" and e.edge_snr_db is not None \
                    and np.isfinite(e.edge_snr_db):
                vals.append(e.edge_snr_db)
        if self.e2e_snr_db is not None and np.isfinite(self.e2e_snr_db) \
                and self.lowered:
            vals.append(self.e2e_snr_db)
        return min(vals) if vals else None

    def as_dict(self) -> dict:
        mn = self.min_snr_db
        e2e = self.e2e_snr_db
        return {"mode": self.mode, "budget_db": self.budget_db,
                "lowered": self.lowered,
                "declined": sum(1 for e in self.edges if e.declined),
                "min_snr_db": round(mn, 1) if mn is not None else None,
                "e2e_snr_db": (round(e2e, 1)
                               if e2e is not None and np.isfinite(e2e)
                               else None),
                "declined_e2e": self.declined_e2e,
                "frame": self.frame,
                "edges": [e.as_dict() for e in self.edges]}


def parse_overrides(spec) -> Dict[str, str]:
    """``"fir=off;fft2048=bf16"`` (the config string form) or a dict →
    ``{stage_name: "off"|"auto"|"bf16"|"int8"}``. Unknown values raise — a
    typo'd override must not silently lower or pin anything."""
    if not spec:
        return {}
    if isinstance(spec, dict):
        items = spec.items()
    else:
        items = (part.split("=", 1) for part in str(spec).split(";") if part)
    out = {}
    for k, v in items:
        v = str(v).strip()
        if v not in ("off", "auto", "bf16", "int8"):
            raise ValueError(f"interior_precision override {k!r}={v!r}: "
                             f"expected off|auto|bf16|int8")
        out[str(k).strip()] = v
    return out


# ---------------------------------------------------------------------------
# graph normalization: one node/edge view over all three pipeline classes
# ---------------------------------------------------------------------------

def _as_nodes(pipeline) -> Tuple[list, str]:
    """``([(stages, input_node_ids)], kind)`` in topological order — the
    post-LTI-merge stage lists, so the plan addresses exactly the stages
    ``update_stage`` sees."""
    from .stages import DagPipeline, FanoutPipeline
    if isinstance(pipeline, DagPipeline):
        return [(list(sl), list(inputs))
                for sl, inputs, _off in pipeline._nodes], "dag"
    if isinstance(pipeline, FanoutPipeline):
        nodes = [(list(pipeline.producer.stages), [])]
        nodes += [(list(b.stages), [0]) for b in pipeline.branches]
        return nodes, "fanout"
    return [(list(pipeline.stages), [])], "linear"


def _rebuild(pipeline, kind: str, new_nodes: list):
    from .stages import DagPipeline, FanoutPipeline, Pipeline
    if kind == "dag":
        return DagPipeline([(sl, inputs) for sl, inputs in new_nodes],
                           pipeline.in_dtype, optimize=False)
    if kind == "fanout":
        return FanoutPipeline(new_nodes[0][0],
                              [sl for sl, _in in new_nodes[1:]],
                              pipeline.in_dtype, optimize=False)
    return Pipeline(new_nodes[0][0], pipeline.in_dtype, optimize=False)


def _sink_nodes(nodes: list) -> set:
    consumed = set()
    for _sl, inputs in nodes:
        consumed.update(inputs)
    return {i for i in range(len(nodes)) if i not in consumed}


def _calib_frames(in_dtype, frame: int, n: int, seed: int) -> list:
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        if np.issubdtype(np.dtype(in_dtype), np.complexfloating):
            f = ((rng.standard_normal(frame) + 1j * rng.standard_normal(frame))
                 / np.sqrt(2)).astype(in_dtype)
        elif np.issubdtype(np.dtype(in_dtype), np.floating):
            f = rng.standard_normal(frame).astype(in_dtype)
        else:
            f = rng.integers(0, 127, frame).astype(in_dtype)
        out.append(f)
    return out


def _dtype_of(v):
    if isinstance(v, tuple):
        v = v[0]
    return np.dtype(getattr(v, "dtype", np.float32))


def _run_graph(nodes: list, frames: list, io_ins: Optional[dict] = None,
               io_out: Optional[dict] = None) -> list:
    """Run the node graph eagerly over the calibration frames, carry chained
    frame to frame; returns per-sink output of the LAST frame. ``io_ins``
    collects per-(node, stage) input values of EVERY frame (the candidate
    replay feed); ``io_out`` the last frame's output per stage (the per-edge
    reference)."""
    import jax.numpy as jnp
    carries: Dict[tuple, Any] = {}
    sinks = sorted(_sink_nodes(nodes))
    last_out = None
    for fi, x in enumerate(frames):
        vals: list = [None] * len(nodes)
        for ni, (stages, inputs) in enumerate(nodes):
            if not inputs:
                v = jnp.asarray(x)
            elif len(inputs) == 1:
                v = vals[inputs[0]]
            else:
                v = tuple(vals[j] for j in inputs)
            for si, s in enumerate(stages):
                key = (ni, si)
                if key not in carries:
                    carries[key] = s.init_carry(_dtype_of(v))
                if io_ins is not None:
                    io_ins.setdefault(key, []).append(v)
                c, v = s.fn(carries[key], v)
                carries[key] = c
                if io_out is not None and fi == len(frames) - 1:
                    io_out[key] = v
            vals[ni] = v
        last_out = [vals[s] for s in sinks]
    return last_out


def _replay_stage(stage, ref_in_frames: list) -> Any:
    """Run a candidate stage over the reference inputs at its edge (fresh
    carry, carry chained across the calibration frames); returns the last
    frame's output."""
    c = stage.init_carry(_dtype_of(ref_in_frames[0]))
    y = None
    for v in ref_in_frames:
        c, y = stage.fn(c, v)
    return y


def _wrap_edge(s):
    """The (possibly accum-lowered) stage with its OUTPUT edge quantized
    through bf16. ``lti`` is dropped — lowering runs post-merge and a
    re-merge would discard the wrapper."""
    inner = s.fn

    def fn(carry, x):
        carry, y = inner(carry, x)
        return carry, _edge_cast(y)

    return replace(s, fn=fn, lti=None)


def plan_interior_precision(pipeline, mode: Optional[str] = None,
                            budget_db: Optional[float] = None,
                            overrides=None, frame: Optional[int] = None,
                            seed: int = 0):
    """Plan + build the interior-precision-lowered form of ``pipeline``.

    Returns ``(lowered_pipeline, plan)``. ``mode``/``budget_db`` default to
    config ``interior_precision`` / ``interior_snr_budget_db``;
    ``overrides`` (dict or ``"stage=off;…"`` string, default config
    ``interior_precision_overrides``) pins per-stage verdicts. ``mode="off"``
    returns the SAME pipeline object — bit-identical by construction.
    See the module docstring for the calibration contract.
    """
    from ..config import config
    c = config()
    if mode is None:
        mode = str(c.get("interior_precision", "off") or "off")
    if mode in ("", "off", "0", "false", "none"):
        return pipeline, PrecisionPlan("off", 0.0)
    if mode not in MODES:
        raise ValueError(f"interior_precision mode {mode!r}: "
                         f"expected one of {MODES}")
    if budget_db is None:
        budget_db = float(c.get("interior_snr_budget_db", 40.0))
    if overrides is None:
        overrides = c.get("interior_precision_overrides", "")
    overrides = parse_overrides(overrides)

    nodes, kind = _as_nodes(pipeline)
    fm = int(pipeline.frame_multiple)
    if frame is None:
        frame = fm * max(1, -(-8192 // fm))
    else:
        frame = max(fm, (int(frame) // fm) * fm)
    frames = _calib_frames(pipeline.in_dtype, frame, 2, seed)

    # f32 reference trace: per-stage input feed (every frame — the candidate
    # replay input) and last-frame output (the per-edge reference), with warm
    # carries so streaming state is realistic; plus per-sink outputs
    io_all: Dict[tuple, list] = {}
    io_out: Dict[tuple, Any] = {}
    ref_sinks = _run_graph(nodes, frames, io_ins=io_all, io_out=io_out)

    sinks = _sink_nodes(nodes)
    plan = PrecisionPlan(str(mode), float(budget_db), frame=frame)
    new_nodes: list = []
    flat = 0
    from .stages import MergeStage
    for ni, (stages, inputs) in enumerate(nodes):
        new_stages: list = []
        for si, s in enumerate(stages):
            d = EdgeDecision(stage=str(getattr(s, "name", "?")), node=ni,
                             index=flat)
            flat += 1
            cur = s
            ref_out = io_out[(ni, si)]
            ref_ins = io_all[(ni, si)]
            ov = overrides.get(d.stage)
            is_boundary = si == len(stages) - 1 and ni in sinks
            float_out = _is_float_val(ref_out)
            if isinstance(s, MergeStage):
                d.declined = "merge"
            elif ov == "off":
                d.declined = "override"
            elif not float_out:
                d.declined = "non-float"
            else:
                # -- accumulation ladder (stage-declared support only) ------
                if s.lower is not None:
                    if ov in ("bf16", "int8"):
                        ladder = (ov,)
                    elif mode == "bf16":
                        # forced-bf16 must not force-accept a DEEPER rung
                        ladder = ("bf16",)
                    else:
                        ladder = LOWER_LADDER
                    forced = mode in ("bf16", "int8")
                    for prec in ladder:
                        cand = s.lower(prec)
                        if cand is None:
                            if ov == prec:
                                d.declined = f"unsupported:{prec}"
                            continue
                        got = _replay_stage(cand, ref_ins)
                        s_db = snr_db(ref_out, got)
                        if forced or s_db >= budget_db or ov == prec:
                            d.accum = prec
                            d.accum_snr_db = s_db
                            cur = cand
                            # an earlier rung's refusal (int8 SNR, forced-
                            # unsupported) no longer describes this stage —
                            # ``declined`` means NO lowering was applied
                            d.declined = None
                            break
                        d.accum_snr_db = s_db
                        d.declined = f"accum-snr<{budget_db:g}dB"
                elif ov in ("bf16", "int8"):
                    d.declined = "no-lower-hook"
                # -- interior edge (never the boundary wire) ----------------
                if not is_boundary:
                    e_db = snr_db(ref_out, _edge_cast_host(ref_out))
                    d.edge_snr_db = e_db
                    if mode in ("bf16", "int8") or e_db >= budget_db:
                        d.edge = "bf16"
                        cur = _wrap_edge(cur)
                        # a partially-lowered stage is LOWERED: the accum
                        # refusal stays readable as accum="f32" + its
                        # measured accum_snr_db, not as a decline
                        d.declined = None
                    elif d.accum == "f32" and d.declined is None:
                        d.declined = f"edge-snr<{budget_db:g}dB"
            plan.edges.append(d)
            new_stages.append(cur)
        new_nodes.append((new_stages, list(inputs)))

    if plan.lowered == 0:
        return pipeline, plan

    lowered = _rebuild(pipeline, kind, new_nodes)
    # end-to-end guard: the composition must clear the budget minus the
    # incoherent-sum allowance for the accepted lowerings
    low_sinks = _run_graph(_as_nodes(lowered)[0], frames)
    e2e = min(snr_db(r, g) for r, g in zip(ref_sinks, low_sinks))
    plan.e2e_snr_db = e2e
    if mode == "auto":
        floor = budget_db - 10.0 * np.log10(max(1, plan.lowered))
        if e2e < floor:
            plan.declined_e2e = True
            for d in plan.edges:
                if d.accum != "f32" or d.edge != "f32":
                    d.accum = d.edge = "f32"
                    d.declined = f"e2e-snr<{floor:.1f}dB"
            return pipeline, plan
    return lowered, plan


def _is_float_val(v) -> bool:
    dt = _dtype_of(v)
    return (np.issubdtype(dt, np.floating)
            or np.issubdtype(dt, np.complexfloating))


def _edge_cast_host(y):
    """Host-side mirror of :func:`_edge_cast` for SNR measurement (numpy in,
    numpy out — no trace)."""
    import ml_dtypes
    a = np.asarray(y)
    if np.issubdtype(a.dtype, np.complexfloating):
        re = a.real.astype(np.float32).astype(ml_dtypes.bfloat16)
        im = a.imag.astype(np.float32).astype(ml_dtypes.bfloat16)
        return (re.astype(np.float32)
                + 1j * im.astype(np.float32)).astype(a.dtype)
    if np.issubdtype(a.dtype, np.floating):
        return a.astype(ml_dtypes.bfloat16).astype(a.dtype)
    return a


#: back-compat convenience name: most callers want the (pipeline, plan) pair
lower_pipeline = plan_interior_precision


# ---------------------------------------------------------------------------
# plan registry: doctor.report()["precision"] / REST profile view
# ---------------------------------------------------------------------------

_plans_lock = threading.Lock()
_plans: Dict[str, dict] = {}


def note_plan(program: str, plan: PrecisionPlan) -> None:
    """Publish a kernel's applied plan under its program name (the same name
    the profile plane bills compiles/MFU to)."""
    with _plans_lock:
        _plans[str(program)] = plan.as_dict()


def plans_report() -> Dict[str, dict]:
    """Every published plan — the ``doctor.report()["precision"]`` body and
    the REST profile view's ``"precision"`` key."""
    with _plans_lock:
        return {k: dict(v) for k, v in _plans.items()}


def clear_plans() -> None:
    with _plans_lock:
        _plans.clear()


# ---------------------------------------------------------------------------
# attribution helpers
# ---------------------------------------------------------------------------

def dominant_compute_dtype(pipeline) -> str:
    """"bf16" when any stage accumulates in bf16 (a lowered pipeline) or the
    process-wide MXU precision policy is bf16, else "f32" — the per-dtype
    MFU-denominator key (delegates to ``utils/roofline.dominant_dtype``)."""
    from ..utils.roofline import dominant_dtype
    return dominant_dtype(getattr(pipeline, "stages", []))


def pallas_stage_count(pipeline) -> int:
    """How many stages of ``pipeline`` route through a hand-written Pallas
    kernel, mirroring each
    stage's actual trace-time dispatch from its ``Stage.route`` — a forced
    ``impl="pallas"`` counts on every backend (the kernel genuinely runs,
    interpret mode off-TPU); ``"auto"`` counts only where the policy picks
    the kernel on THIS backend (``_pallas_fir_wins`` for FIRs, TPU for the
    channelizer); explicit matmul/os/poly pins never count. The stream
    dtype is walked through the flat stage list (exact for linear chains;
    topological approximation on fan-out/DAG shapes)."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    n = 0
    dt = np.dtype(getattr(pipeline, "in_dtype", np.complex64))
    for s in getattr(pipeline, "stages", []):
        name = str(getattr(s, "name", ""))
        route = getattr(s, "route", None)
        lti = getattr(s, "lti", None)
        is_c = np.issubdtype(dt, np.complexfloating)
        if route is not None and len(route) > 2 and route[2] == "int8":
            # the int8 rung computes through quantized XLA matmuls, not the
            # (f32/bf16-only) Pallas kernels — never counts
            pass
        elif name == "pallas_fir":
            n += 1
        elif lti is not None:
            taps, decim, _fl, lti_impl = lti
            eff = (route[0] if route else None) or lti_impl
            taps = np.asarray(taps)
            nt = int(taps.size)
            if eff == "pallas" and np.isrealobj(taps) and nt >= 2:
                n += 1          # forced: direct FIR (decim=1) or fused
                #                 FIR→decimate kernel, any backend
            elif eff == "auto" and decim == 1 and on_tpu and not is_c \
                    and np.isrealobj(taps) and 2 <= nt <= 48:
                n += 1          # the fn's _pallas_fir_wins branch
        elif route is not None and "channelizer" in name:
            if route[0] == "pallas" or (route[0] == "auto" and on_tpu):
                n += 1
        elif route is not None and route[0] == "pallas":
            # an edge-wrapped lowered FIR (_wrap_edge drops lti so a
            # re-merge can't discard the wrapper) keeps its route: a forced
            # pallas build asserted real taps at construction, so it counts
            # without re-checking them here
            n += 1
        if getattr(s, "out_dtype", None) is not None:
            dt = np.dtype(s.out_dtype)
    return n
