"""Frame-size / depth / wire-format autotuning for TPU stage pipelines.

The throughput of a fused stage chain depends on frame size (dispatch amortization vs
HBM residency), in-flight depth (transfer/compute overlap), and — for the STREAMED
path — the wire format (``ops/wire.py``: bytes/sample vs codec SNR). This sweeps a
small grid with the real pipeline (device dispatch + host staging, as TpuKernel does)
and returns the best configuration — run once at deploy time, feed the result to
``TpuKernel``.

Streamed tuning is two-stage: :func:`measure_link` stamps the link envelope,
:func:`pick_wire` turns it into the analytic format choice (each format's
link-bounded ceiling, filtered by an SNR floor), and :func:`autotune_streamed`
verifies the pick by measuring the REAL wired drain loop over the grid. The
config/env override ``FUTURESDR_TPU_WIRE_FORMAT`` (``config.tpu_wire_format``)
short-circuits all of it.
"""

from __future__ import annotations

import json
import os
import time
from collections import deque
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from ..log import logger
from ..ops import xfer
from ..ops.stages import Pipeline, Stage
from ..telemetry import profile as _profile
from .instance import TpuInstance, instance

__all__ = ["autotune", "autotune_streamed", "autotune_serve",
           "autotune_shard", "default_frames", "measure_link",
           "pick_wire", "StreamedResults", "record_streamed_pick",
           "cached_frames_per_dispatch", "cached_streamed_pick",
           "record_serve_buckets", "cached_serve_buckets",
           "record_serve_pages", "cached_serve_pages",
           "record_interior_precision", "cached_interior_precision",
           "record_shard_devices", "cached_shard_devices",
           "record_pallas_blocks", "cached_pallas_blocks",
           "autotune_pallas_blocks"]

log = logger("tpu.autotune")


def default_frames(platform: str) -> tuple:
    """The frame grid autotune sweeps when the caller doesn't pin one.

    Accelerator platforms extend to 2M samples: per-frame dispatch cost
    (driver/PCIe latency) can move the streamed optimum above the CPU
    backend's. Where it sits on a locally attached chip is not measured."""
    base = (1 << 17, 1 << 18, 1 << 19, 1 << 20)
    return base if platform == "cpu" else base + (1 << 21,)


def _measure(pipe: Pipeline, frame: int, depth: int, inst: TpuInstance,
             min_seconds: float) -> float:
    """Msamples/s through the pipeline incl. H2D staging and D2H sync."""
    fn, carry = pipe.compile(frame, device=inst.device)
    host = np.zeros(frame, dtype=pipe.in_dtype)
    # warmup (compile) — billed reason="autotune" so a tuning sweep's
    # compiles never read as a recompile storm (telemetry/profile.py)
    with _profile.compiling("autotune", "autotune",
                            f"frame={frame},depth={depth}"):
        carry, y = fn(carry, inst.put(host))
        inst.get(y)
    inflight = []
    n_frames = 0
    t0 = time.perf_counter()
    while True:
        carry, y = fn(carry, inst.put(host))
        inflight.append(y)
        n_frames += 1
        if len(inflight) >= depth:
            inst.get(inflight.pop(0))
        if n_frames % 4 == 0 and time.perf_counter() - t0 > min_seconds:
            break
        if n_frames > 10000:
            break
    for y in inflight:
        inst.get(y)
    dt = time.perf_counter() - t0
    return n_frames * frame / dt / 1e6


def autotune(stages: Sequence[Stage], in_dtype,
             frames: Optional[Sequence[int]] = None,
             depths: Sequence[int] = (2, 4, 8),
             min_seconds: float = 0.3,
             inst: Optional[TpuInstance] = None) -> Tuple[int, int, Dict]:
    """Returns (best_frame, best_depth, {(frame, depth): Msps}).

    ``frames=None`` sweeps ``default_frames(platform)`` (see its docstring
    for the measured rationale)."""
    inst = inst or instance()
    if frames is None:
        frames = default_frames(inst.platform)
    pipe = Pipeline(list(stages), in_dtype)
    results: Dict[Tuple[int, int], float] = {}
    best = (0, 0)
    best_rate = -1.0
    for f in frames:
        m = pipe.frame_multiple
        f = max(m, (f // m) * m)
        for d in depths:
            try:
                rate = _measure(Pipeline(list(stages), in_dtype), f, d, inst, min_seconds)
            except Exception as e:   # OOM at large frames, etc.
                log.warning("autotune (%d, %d) failed: %r", f, d, e)
                continue
            results[(f, d)] = round(rate, 1)
            if rate > best_rate:
                best_rate = rate
                best = (f, d)
    log.info("autotune best: frame=%d depth=%d (%.1f Msps)", *best, best_rate)
    return best[0], best[1], results


# ---------------------------------------------------------------------------
# streamed-path tuning: link envelope → wire format → verified grid point
# ---------------------------------------------------------------------------

def measure_link(inst: Optional[TpuInstance] = None, nbytes: int = 4 << 20,
                 repeats: int = 3, dtype=np.float32) -> Tuple[float, float]:
    """Measured (h2d_Bps, d2h_Bps) of the host↔device link, median of
    ``repeats`` payload crossings of ``dtype`` (complex rides the pair shim,
    exactly as streamed frames do; the fake link is honored, so CI can
    exercise the whole tuning path deterministically)."""
    inst = inst or instance()
    dt = np.dtype(dtype)
    payload = np.zeros(max(1, nbytes // dt.itemsize), dt)
    ups, downs = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        y = xfer.to_device(payload, inst.device)
        y.block_until_ready()
        ups.append(payload.nbytes / (time.perf_counter() - t0))
        t0 = time.perf_counter()
        xfer.to_host(y)
        downs.append(payload.nbytes / (time.perf_counter() - t0))
    return sorted(ups)[repeats // 2], sorted(downs)[repeats // 2]


def pick_wire(h2d_Bps: float, d2h_Bps: float, in_dtype, out_dtype,
              out_per_in: float = 1.0, compute_msps: Optional[float] = None,
              min_snr_db: Optional[float] = 60.0,
              wires: Optional[Sequence[str]] = None) -> str:
    """Analytic wire-format choice from a measured link envelope.

    Each format's streamed ceiling is ``min(h2d/up_bytes, d2h/down_bytes,
    compute)`` (:func:`futuresdr_tpu.ops.wire.streamed_ceiling_msps`); formats
    whose MEASURED codec SNR falls below ``min_snr_db`` are excluded (the
    default 60 dB keeps quantization ≥ ~20 dB under a strong RF signal's own
    noise floor — sc16 passes at ~89 dB, sc8/bf16 don't). Ties go to the
    higher-fidelity format, so a compute-bound link never trades SNR for
    nothing."""
    from ..ops.wire import get_wire, measure_snr_db, streamed_ceiling_msps
    cand = []
    for name in (wires or ("f32", "sc16", "sc8", "bf16")):
        w = get_wire(name)
        snr = measure_snr_db(w, in_dtype)
        if min_snr_db is not None and snr < min_snr_db:
            continue
        ceil = streamed_ceiling_msps(w, h2d_Bps, d2h_Bps, in_dtype, out_dtype,
                                     out_per_in)
        if compute_msps:
            ceil = min(ceil, compute_msps)
        cand.append((ceil, snr, w.name))
    if not cand:
        return "f32"
    # sort by ceiling, then SNR: a 1% ceiling edge must not beat 40 dB of SNR
    cand.sort(key=lambda c: (round(c[0], 2), c[1]), reverse=True)
    return cand[0][2]


def _measure_wired(pipe: Pipeline, wire, frame: int, depth: int,
                   inst: TpuInstance, min_seconds: float,
                   k: int = 1) -> float:
    """Msamples/s through the PIPELINED wired drain loop (encode → staged H2D →
    fused decode/compute/encode → read-ahead D2H → decode), the loop TpuKernel
    runs — so the number includes host codec cost and honors any fake link.
    ``k`` is the megabatch frames-per-dispatch (``Pipeline.compile_wired(k=)``):
    each program call scans k frames, so dispatch overhead is paid once per k.

    ``pipe`` may be a :class:`~futuresdr_tpu.ops.stages.FanoutPipeline`: the
    wired fan-out program ships ONE input upload and a flat multi-branch
    output part tuple, decoded per branch here — so a fan-out region tunes
    through exactly the drain loop ``TpuFanoutKernel`` runs."""
    from ..ops.wire import get_wire
    wire = get_wire(wire)
    fn, carry = pipe.compile_wired(frame, wire, device=inst.device, k=k)
    host = np.zeros(frame, dtype=pipe.in_dtype)
    n_branches = getattr(pipe, "n_branches", 0)
    if n_branches:
        branch_counts = pipe.part_counts(wire)

        def decode_frame(raw_parts):
            off = 0
            for j, cnt in enumerate(branch_counts):
                wire.decode_host(raw_parts[off:off + cnt],
                                 pipe.out_dtypes[j])
                off += cnt
    else:
        def decode_frame(raw_parts):
            wire.decode_host(raw_parts, pipe.out_dtype)

    def encode_group():
        if k == 1:
            return wire.encode_host(host)
        groups = [wire.encode_host(host) for _ in range(k)]
        return tuple(np.stack([np.asarray(g[j]) for g in groups])
                     for j in range(len(groups[0])))

    import jax
    dev = tuple(jax.device_put(np.asarray(p), inst.device)
                for p in encode_group())
    # warmup compile off the clock, billed reason="autotune" (never a storm)
    with _profile.compiling("autotune", "autotune",
                            f"wire={wire.name},frame={frame},k={k}"):
        carry, y = fn(carry, *dev)
        jax.block_until_ready(y)
    staged: deque = deque()
    inflight: deque = deque()
    n_frames = 0
    t0 = time.perf_counter()
    while True:
        staged.append(xfer.start_device_transfer_parts(
            encode_group(), inst.device))
        while staged and len(inflight) < depth:
            carry, y_parts = fn(carry, *staged.popleft()())
            inflight.append(xfer.start_host_transfer_parts(y_parts))
            n_frames += k
        if len(inflight) >= depth:
            raw = inflight.popleft()()
            if k == 1:
                decode_frame(raw)
            else:                           # stacked parts decode per frame
                for i in range(k):
                    decode_frame(tuple(p[i] for p in raw))
        if n_frames % 4 == 0 and time.perf_counter() - t0 > min_seconds:
            break
        if n_frames > 10000:
            break
    for fin in inflight:
        fin()                               # land the tail transfers
    dt = time.perf_counter() - t0
    return n_frames * frame / dt / 1e6


# ---------------------------------------------------------------------------
# streamed-pick cache: autotune_streamed results survive for later launches
# ---------------------------------------------------------------------------

#: ``(platform, in_dtype, stage names) -> {"k": …, "inflight": …}`` —
#: recorded by :func:`autotune_streamed`, consumed by the device-graph
#: fusion pass (``runtime/devchain.py``) when config leaves
#: ``tpu_frames_per_dispatch`` unset, and by ``TpuKernel`` construction as
#: the SEED of the adaptive in-flight credit controller when config leaves
#: ``tpu_inflight`` at auto — so a deploy that autotuned once keeps its
#: megabatch K and its in-flight budget on every later launch of the same
#: chain without re-measuring. The in-memory layer is authoritative within
#: a process; picks also persist as JSON under the ``autotune_cache_dir``
#: config knob, so they survive across PROCESSES too (legacy on-disk
#: entries are bare ints — K only — and load with no inflight seed).
_streamed_cache: Dict[tuple, dict] = {}


def _sig_names(stages) -> tuple:
    return tuple(str(getattr(s, "name", "?")) for s in stages
                 if getattr(s, "name", "") != "devchain_boundary")


def _fanout_names(producer_stages, branch_stage_lists) -> tuple:
    """Fan-out SHAPE signature: producer names + per-branch markers, so a
    1→2 region and the linear chain of the same stages never share a pick."""
    names = _sig_names(producer_stages)
    for j, b in enumerate(branch_stage_lists):
        names += (f"fanout[{j}]",) + _sig_names(b)
    return names


def _dag_names(dag) -> tuple:
    """DAG SHAPE signature, CANONICALIZED: linear runs of single-input /
    single-consumer nodes contract into one group before the per-group
    ``dag[i<-inputs]`` markers are emitted — so a devchain-composed region
    (one node per flowgraph MEMBER, plus fence-only endpoint nodes) and a
    hand-built :class:`~futuresdr_tpu.ops.stages.DagPipeline` of the same
    stages map to the SAME streamed pick. Boundary fences are filtered
    exactly as in linear signatures."""
    nodes = [([s for s in sl
               if getattr(s, "name", "") != "devchain_boundary"],
              list(inputs)) for sl, inputs in dag.raw_nodes]
    n = len(nodes)
    n_cons = [0] * n
    for _sl, ins in nodes:
        for j in ins:
            n_cons[j] += 1
    # group assignment in topo (index) order: a node with exactly one input
    # whose producer has exactly one consumer joins the producer's group
    group = [0] * n
    g_stages: Dict[int, list] = {}
    g_inputs: Dict[int, list] = {}
    next_g = 0
    for i, (sl, ins) in enumerate(nodes):
        if len(ins) == 1 and n_cons[ins[0]] == 1:
            g = group[ins[0]]
            group[i] = g
            g_stages[g].extend(sl)
        else:
            g = next_g
            next_g += 1
            group[i] = g
            g_stages[g] = list(sl)
            g_inputs[g] = [group[j] for j in ins]
    names: tuple = ()
    for g in range(next_g):
        names += (f"dag[{g}<-{','.join(map(str, g_inputs[g]))}]",)
        names += _sig_names(g_stages[g])
    return names


def _make_sig(platform: str, in_dtype, names: tuple) -> tuple:
    """THE cache-key layout — every signature (linear, fan-out, raw-list)
    must be assembled here so recorder and lookup can never diverge."""
    return (platform, str(np.dtype(in_dtype)), names)


def _streamed_sig(stages, in_dtype, platform: str) -> tuple:
    """Cache key for one tuned chain: devchain boundary fences are ignored so
    a FUSED composition of the same member stages maps to the same entry.
    A :class:`~futuresdr_tpu.ops.stages.FanoutPipeline` keys on its fan-out
    shape (:func:`_fanout_names`); a
    :class:`~futuresdr_tpu.ops.stages.DagPipeline` on its canonicalized DAG
    shape (:func:`_dag_names`)."""
    from ..ops.stages import DagPipeline, FanoutPipeline
    if isinstance(stages, DagPipeline):
        names = _dag_names(stages)
    elif isinstance(stages, FanoutPipeline):
        names = _fanout_names(stages.producer.stages,
                              [b.stages for b in stages.branches])
    else:
        names = _sig_names(stages)
    return _make_sig(platform, in_dtype, names)


def _cache_file() -> Optional[str]:
    """The persisted streamed-pick store (None = persistence disabled via
    ``autotune_cache_dir`` set to ""/off/none/0)."""
    from ..config import config
    d = str(config().get("autotune_cache_dir", "") or "")
    if not d or d.lower() in ("0", "off", "none", "false"):
        return None
    return os.path.join(os.path.expanduser(d), "streamed_picks.json")


def _sig_str(sig: tuple) -> str:
    platform, dtype, names = sig
    return "|".join((platform, dtype, ",".join(names)))


def _norm_entry(v) -> Optional[dict]:
    """Normalize one cache value to ``{"k": int, "inflight": int|None}``
    plus the optional serving-plane ``"serve_buckets"`` slot-bucket ladder
    (round-15 axis) and the applied ``"interior_precision"`` mode (round-17
    axis — both absent from older entries). Legacy entries (pre-round-14)
    are bare ints carrying only K; a malformed value returns None (skip the
    entry — a bad cache line must never fail a launch)."""
    try:
        if isinstance(v, dict):
            fl = v.get("inflight")
            out = {"k": int(v["k"]),
                   "inflight": int(fl) if fl is not None else None}
            sb = v.get("serve_buckets")
            if sb:
                # parsed in its own guard: a malformed ladder (e.g. the
                # config-style string "1,4,16") must lose only the serving
                # axis, never the entry's valid k/inflight picks
                try:
                    buckets = sorted({int(b) for b in sb if int(b) > 0})
                    if buckets:
                        out["serve_buckets"] = buckets
                except (TypeError, ValueError):
                    pass
            sp = v.get("serve_pages")
            if sp is not None:
                # round-21 axis (paged serving carries): the measured
                # page-pool capacity pick — same per-axis guard, a
                # malformed field loses only this axis
                try:
                    sp = int(sp)
                    if sp >= 1:
                        out["serve_pages"] = sp
                except (TypeError, ValueError):
                    pass
            nd = v.get("n_devices")
            if nd is not None:
                # round-19 axis (mesh-sharded device plane): the measured
                # best shard width — same per-axis guard, a malformed field
                # loses only this axis
                try:
                    nd = int(nd)
                    if nd >= 1:
                        out["n_devices"] = nd
                except (TypeError, ValueError):
                    pass
            ip = v.get("interior_precision")
            if ip is not None:
                # same per-axis guard: a malformed precision field (a list,
                # a typo'd mode) loses only this axis, never the entry's
                # valid (k, inflight, serve_buckets)
                try:
                    mode = str(ip).strip().lower()
                    if mode in ("off", "auto", "bf16", "int8"):
                        out["interior_precision"] = mode
                except (TypeError, ValueError):
                    pass
            pb = v.get("pallas_blocks")
            if pb is not None:
                # round-20 axis (Pallas autotune plane): measured per-chip
                # block shapes as {device_kind: {kernel: block}} — same
                # per-axis guard, a malformed table (wrong nesting, a
                # negative shape, an unknown kernel from a newer revision)
                # loses only this axis, never the entry's valid picks
                try:
                    from ..ops.pallas_kernels import DEFAULT_BLOCKS
                    tbl = {}
                    for dev, blocks in dict(pb).items():
                        good = {}
                        for kn, bv in dict(blocks).items():
                            bv = int(bv)
                            if str(kn) in DEFAULT_BLOCKS and bv > 0:
                                good[str(kn)] = bv
                        if good:
                            tbl[str(dev)] = good
                    if tbl:
                        out["pallas_blocks"] = tbl
                except (TypeError, ValueError, AttributeError):
                    pass
            w = v.get("wire")
            if w is not None:
                # round-22 axis (single-shot uplink plane): the adaptive
                # wire policy's measured start format — same per-axis
                # guard, an unknown format name (a newer revision's codec)
                # loses only this axis, never the entry's valid picks
                try:
                    from ..ops.wire import WIRE_FORMATS
                    w = str(w).strip().lower()
                    if w in WIRE_FORMATS:
                        out["wire"] = w
                except (TypeError, ValueError):
                    pass
            return out
        return {"k": int(v), "inflight": None}
    except (TypeError, ValueError, KeyError):
        return None


#: one disk read per process (keyed by path so a test that repoints
#: ``autotune_cache_dir`` re-reads); the memory layer is authoritative
#: in-process, so stale memo entries only cost a re-measure, never correctness
_disk_memo: Dict[str, Dict[str, dict]] = {}


def _disk_load(refresh: bool = False) -> Dict[str, dict]:
    path = _cache_file()
    if not path:
        return {}
    if not refresh and path in _disk_memo:
        return _disk_memo[path]
    out: Dict[str, dict] = {}
    try:
        with open(path) as f:
            d = json.load(f)
        if isinstance(d, dict):
            for key, v in d.items():
                entry = _norm_entry(v)
                if entry is None:
                    log.warning("streamed-pick cache: ignoring bad value "
                                "%r for %r", v, key)
                else:
                    out[str(key)] = entry
    except (OSError, ValueError):
        pass
    _disk_memo[path] = out
    return out


def _disk_store(sig: tuple, entry: dict) -> None:
    """Best-effort read-modify-write with an atomic rename: concurrent
    processes see the old or the new file, never a torn one (a lost
    concurrent update costs one re-measure, not correctness)."""
    path = _cache_file()
    if not path:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        d = dict(_disk_load(refresh=True))    # fresh read for the RMW
        d[_sig_str(sig)] = entry
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(d, f, sort_keys=True, indent=0)
        os.replace(tmp, path)
        # the memo holds NORMALIZED entries (the freshly-stored value is
        # still in its wire form here)
        _disk_memo[path] = {k2: e for k2, e in
                            ((k2, _norm_entry(v2)) for k2, v2 in d.items())
                            if e is not None}
    except OSError as e:
        log.debug("streamed-pick cache write failed: %r", e)


def _record_sig(sig: tuple, frames_per_dispatch: int,
                inflight: Optional[int] = None) -> None:
    entry = {"k": int(frames_per_dispatch),
             "inflight": int(inflight) if inflight else None}
    # preserve the orthogonal axes a previous record stamped on this chain
    # (the serving-plane bucket ladder, the applied interior-precision
    # mode) — streamed re-tunes must not wipe them
    prev = _streamed_cache.get(sig) or _disk_load().get(_sig_str(sig))
    if prev and prev.get("serve_buckets"):
        entry["serve_buckets"] = list(prev["serve_buckets"])
    if prev and prev.get("serve_pages"):
        entry["serve_pages"] = int(prev["serve_pages"])
    if prev and prev.get("interior_precision"):
        entry["interior_precision"] = prev["interior_precision"]
    if prev and prev.get("n_devices"):
        entry["n_devices"] = int(prev["n_devices"])
    if prev and prev.get("pallas_blocks"):
        entry["pallas_blocks"] = {d: dict(b) for d, b
                                  in prev["pallas_blocks"].items()}
    if prev and prev.get("wire"):
        entry["wire"] = prev["wire"]
    _streamed_cache[sig] = entry
    # K-only records persist in the legacy bare-int form (readable by older
    # processes); the dict form is written only when it carries more
    _disk_store(sig, int(frames_per_dispatch)
                if not inflight and len(entry) == 2 else entry)


def record_streamed_pick(stages, in_dtype, platform: str,
                         frames_per_dispatch: int,
                         inflight: Optional[int] = None) -> None:
    _record_sig(_streamed_sig(stages, in_dtype, platform),
                frames_per_dispatch, inflight)


def cached_streamed_pick(stages, in_dtype, platform: str) -> Optional[dict]:
    """The cached pick of a previously autotuned chain as
    ``{"k": …, "inflight": …}`` — the in-process memory layer first
    (authoritative), then the persisted store; None when never tuned."""
    sig = _streamed_sig(stages, in_dtype, platform)
    entry = _streamed_cache.get(sig)
    if entry is not None:
        return entry
    entry = _disk_load().get(_sig_str(sig))
    if entry is not None:
        _streamed_cache[sig] = entry  # promote: later lookups stay in memory
    return entry


def cached_frames_per_dispatch(stages, in_dtype,
                               platform: str) -> Optional[int]:
    """The cached megabatch K of a previously autotuned chain (see
    :func:`cached_streamed_pick`); None when the chain was never tuned."""
    entry = cached_streamed_pick(stages, in_dtype, platform)
    return entry["k"] if entry is not None else None


# ---------------------------------------------------------------------------
# serving-plane slot buckets (futuresdr_tpu/serve, docs/serving.md)
# ---------------------------------------------------------------------------

def _serve_sig_stages(pipeline):
    """Normalize a pipeline-or-stage-list to what :func:`_streamed_sig`
    keys on (a plain :class:`Pipeline` keys on its stage list; fan-out/DAG
    pipelines key on their shape signatures)."""
    if isinstance(pipeline, Pipeline):
        return pipeline.stages
    return pipeline


def record_serve_buckets(pipeline, in_dtype, platform: str,
                         buckets: Sequence[int]) -> None:
    """Stamp a measured slot-bucket ladder into the streamed-pick cache
    entry of this chain (the serving axis rides NEXT TO the (k, inflight)
    streamed axes — one signature, orthogonal planes)."""
    sig = _streamed_sig(_serve_sig_stages(pipeline), in_dtype, platform)
    cur = _streamed_cache.get(sig) or _disk_load().get(_sig_str(sig)) \
        or {"k": 1, "inflight": None}
    entry = {**cur, "serve_buckets": sorted({int(b) for b in buckets
                                             if int(b) > 0})}
    _streamed_cache[sig] = entry
    _disk_store(sig, entry)


def cached_serve_buckets(pipeline, in_dtype, platform: str) -> Optional[list]:
    """The cached slot-bucket ladder of a previously :func:`autotune_serve`d
    chain; None when never tuned (the engine then uses the configured or
    default ladder)."""
    entry = cached_streamed_pick(_serve_sig_stages(pipeline), in_dtype,
                                 platform)
    if entry is None:
        return None
    return entry.get("serve_buckets")


def record_serve_pages(pipeline, in_dtype, platform: str,
                       pages: int) -> None:
    """Stamp the measured page-pool capacity pick (the largest bucket the
    :func:`autotune_serve` ladder kept) next to the ladder itself — the
    engine seeds its paged carry pool there so a restarted process reaches
    its steady-state capacity with ONE compile instead of walking the
    ladder through churn."""
    pages = int(pages)
    if pages < 1:
        return
    sig = _streamed_sig(_serve_sig_stages(pipeline), in_dtype, platform)
    cur = _streamed_cache.get(sig) or _disk_load().get(_sig_str(sig)) \
        or {"k": 1, "inflight": None}
    entry = {**cur, "serve_pages": pages}
    _streamed_cache[sig] = entry
    _disk_store(sig, entry)


def cached_serve_pages(pipeline, in_dtype, platform: str) -> Optional[int]:
    """The cached page-pool capacity of a previously :func:`autotune_serve`d
    chain; None when never tuned (the engine then starts at the smallest
    bucket and grows the pool on demand)."""
    entry = cached_streamed_pick(_serve_sig_stages(pipeline), in_dtype,
                                 platform)
    if entry is None:
        return None
    return entry.get("serve_pages")


# ---------------------------------------------------------------------------
# interior-precision axis (ops/precision.py, docs/tpu_notes.md "Interior
# precision")
# ---------------------------------------------------------------------------

def record_interior_precision(stages, in_dtype, platform: str,
                              mode: str) -> None:
    """Stamp the APPLIED interior-precision mode into this chain's
    streamed-pick cache entry — the precision axis rides next to
    (k, inflight, serve_buckets) under one signature, so a later launch of
    the same chain knows which lowering the previous tune ran under (a
    cached K measured on a bf16-lowered program is not comparable to an f32
    rebuild). Unknown modes are dropped, not stored — the cache must never
    carry a value :func:`_norm_entry` would strip on the next read."""
    mode = str(mode).strip().lower()
    if mode not in ("off", "auto", "bf16", "int8"):
        return
    sig = _streamed_sig(_serve_sig_stages(stages), in_dtype, platform)
    cur = _streamed_cache.get(sig) or _disk_load().get(_sig_str(sig)) \
        or {"k": 1, "inflight": None}
    entry = {**cur, "interior_precision": mode}
    _streamed_cache[sig] = entry
    _disk_store(sig, entry)


def cached_interior_precision(stages, in_dtype,
                              platform: str) -> Optional[str]:
    """The interior-precision mode the chain's last recorded tune was
    measured under; None when never stamped (pre-round-17 entries)."""
    entry = cached_streamed_pick(_serve_sig_stages(stages), in_dtype,
                                 platform)
    if entry is None:
        return None
    return entry.get("interior_precision")


# ---------------------------------------------------------------------------
# device-count axis (futuresdr_tpu/shard, docs/parallel.md "Mesh-sharded
# device plane")
# ---------------------------------------------------------------------------

def record_shard_devices(stages, in_dtype, platform: str, n: int) -> None:
    """Stamp the measured best shard width into this chain's streamed-pick
    cache entry — the device-count axis rides next to (k, inflight,
    serve_buckets, interior_precision) under one signature, so a later
    launch of the same chain spreads over the width the previous tune
    measured instead of guessing. Non-positive widths are dropped, not
    stored (the :func:`_norm_entry` contract)."""
    try:
        n = int(n)
    except (TypeError, ValueError):
        return
    if n < 1:
        return
    sig = _streamed_sig(_serve_sig_stages(stages), in_dtype, platform)
    cur = _streamed_cache.get(sig) or _disk_load().get(_sig_str(sig)) \
        or {"k": 1, "inflight": None}
    entry = {**cur, "n_devices": n}
    _streamed_cache[sig] = entry
    _disk_store(sig, entry)


def cached_shard_devices(stages, in_dtype, platform: str) -> Optional[int]:
    """The shard width the chain's last :func:`autotune_shard` measured;
    None when never stamped."""
    entry = cached_streamed_pick(_serve_sig_stages(stages), in_dtype,
                                 platform)
    if entry is None:
        return None
    return entry.get("n_devices")


# ---------------------------------------------------------------------------
# adaptive-wire start-point axis (tpu/kernel_block.WireController,
# docs/tpu_notes.md "The host data path")
# ---------------------------------------------------------------------------

def record_wire_start(stages, in_dtype, platform: str, fmt: str) -> None:
    """Stamp the measured best wire format into this chain's streamed-pick
    cache entry — the adaptive wire controller's START POINT. The mid-stream
    policy (``tpu_adaptive_wire``) then begins at the format the last tune
    measured fastest instead of the build-time default, and only moves off
    it when the live SNR / link-occupancy windows say so. Unknown formats
    are dropped, not stored (the :func:`_norm_entry` contract)."""
    from ..ops.wire import WIRE_FORMATS
    fmt = str(fmt).strip().lower()
    if fmt not in WIRE_FORMATS:
        return
    sig = _streamed_sig(_serve_sig_stages(stages), in_dtype, platform)
    _record_wire_sig(sig, fmt)


def _record_wire_sig(sig: tuple, fmt: str) -> None:
    cur = _streamed_cache.get(sig) or _disk_load().get(_sig_str(sig)) \
        or {"k": 1, "inflight": None}
    entry = {**cur, "wire": fmt}
    _streamed_cache[sig] = entry
    _disk_store(sig, entry)


def cached_wire_start(stages, in_dtype, platform: str) -> Optional[str]:
    """The wire format the chain's last :func:`autotune_streamed` measured
    fastest (the adaptive policy's start point); None when never stamped
    (pre-round-22 entries)."""
    entry = cached_streamed_pick(_serve_sig_stages(stages), in_dtype,
                                 platform)
    if entry is None:
        return None
    return entry.get("wire")


def autotune_shard(stages, in_dtype, frame: Optional[int] = None,
                   k: int = 1, devices: Sequence[int] = (1, 2, 4, 8),
                   min_seconds: float = 0.3,
                   inst: Optional[TpuInstance] = None,
                   record: bool = True) -> Tuple[int, Dict[int, float]]:
    """Measure the DATA-sharded program per device count and pick the best
    width (the device-count axis of the streamed-pick cache).

    For each candidate D (capped at the visible device count) the real
    sharded dispatch loop runs — one ``[D, k, frame]`` group per call,
    host staging in, gathered sinks out, exactly what
    ``shard.data.ShardRunner`` dispatches — and the aggregate sample rate
    is measured. Returns ``(best_D, {D: Msps})`` and records the winner
    under the chain's streamed-pick signature. A width is only ever
    PICKED over a smaller one when it measured strictly faster, so
    degenerate hosts (a 2-core CI box timing an 8-way virtual mesh) keep
    their honest small width."""
    import jax

    from ..shard.data import ShardedProgram
    from ..shard.plan import plan_shard
    inst = inst or instance()
    pipe = stages if isinstance(stages, Pipeline) \
        else Pipeline(list(stages), in_dtype)
    m = pipe.frame_multiple
    f = frame or inst.frame_size
    f = max(m, (f // m) * m)
    avail = len(jax.devices())
    results: Dict[int, float] = {}
    best, best_rate = 1, -1.0
    for D in sorted({int(d) for d in devices if 0 < int(d) <= avail}):
        try:
            host = np.zeros((D, k, f), dtype=pipe.in_dtype)
            if D == 1:
                # the honest baseline: the REAL unsharded program at the
                # SAME megabatch form (one dispatch per k-frame group —
                # what a shard=off launch with frames_per_dispatch=k
                # dispatches). A k-looped per-frame baseline would pay k
                # dispatch round-trips per group and bias the pick wide.
                import jax
                if k == 1:
                    fn1 = jax.jit(pipe.fn(), donate_argnums=())
                else:
                    _inner = pipe.fn()
                    fn1 = jax.jit(
                        lambda c, xs: jax.lax.scan(
                            lambda cc, xk: _inner(cc, xk), c, xs),
                        donate_argnums=())
                carry = pipe.init_carry()

                def group(c, _fn=fn1):
                    x = xfer.to_device(host[0, 0] if k == 1 else host[0],
                                       inst.device)
                    c, y = _fn(c, x)
                    return c, np.asarray(y)
            else:
                prog = ShardedProgram(pipe, plan_shard(pipe, mode="data",
                                                       n_devices=D))
                fnD, carry = prog.compile(f, k)

                def group(c, _fn=fnD, _p=prog):
                    c, y = _fn(c, _p.place(host[:, 0] if k == 1 else host))
                    return c, np.asarray(y)
            with _profile.compiling("autotune", "autotune",
                                    f"shard_d={D},frame={f},k={k}"):
                carry, _ = group(carry)
            n = 0
            t0 = time.perf_counter()
            while True:
                carry, _ = group(carry)
                n += D * k
                if time.perf_counter() - t0 > min_seconds or n > 10000:
                    break
            rate = n * f / (time.perf_counter() - t0) / 1e6
        except Exception as e:                 # OOM, short mesh, …
            log.warning("autotune_shard D=%d failed: %r", D, e)
            continue
        results[D] = round(rate, 1)
        if rate > best_rate:
            best_rate, best = rate, D
    log.info("autotune_shard best: D=%d (%.1f Msps) over %s", best,
             best_rate, results)
    if record and results:
        record_shard_devices(pipe.stages, pipe.in_dtype, inst.platform, best)
    return best, results


# ---------------------------------------------------------------------------
# Pallas block-shape axis (tpu/pallas_tune.py, docs/tpu_notes.md "Pallas
# autotune plane")
# ---------------------------------------------------------------------------

def record_pallas_blocks(stages, in_dtype, platform: str, device: str,
                         blocks: Dict[str, int]) -> None:
    """Stamp measured Pallas block shapes for one chip generation into this
    chain's streamed-pick cache entry — the ``pallas_blocks`` axis rides
    next to (k, inflight, serve_buckets, interior_precision, n_devices)
    under one signature, keyed per device kind INSIDE the axis so one
    entry serves mixed chip generations (a v5e sweep must not clobber the
    v5p picks). Unknown kernel keys and non-positive shapes are dropped,
    not stored (the :func:`_norm_entry` contract: the cache must never
    carry a value the next read would strip)."""
    from ..ops.pallas_kernels import DEFAULT_BLOCKS
    good: Dict[str, int] = {}
    for kn, bv in (blocks or {}).items():
        try:
            bv = int(bv)
        except (TypeError, ValueError):
            continue
        if kn in DEFAULT_BLOCKS and bv > 0:
            good[str(kn)] = bv
    if not good or not device:
        return
    sig = _streamed_sig(_serve_sig_stages(stages), in_dtype, platform)
    cur = _streamed_cache.get(sig) or _disk_load().get(_sig_str(sig)) \
        or {"k": 1, "inflight": None}
    tbl = {d: dict(b) for d, b in (cur.get("pallas_blocks") or {}).items()}
    tbl[str(device)] = good
    entry = {**cur, "pallas_blocks": tbl}
    _streamed_cache[sig] = entry
    _disk_store(sig, entry)


def cached_pallas_blocks(stages, in_dtype, platform: str,
                         device: str) -> Optional[Dict[str, int]]:
    """The measured block table of a previous sweep for this chain on this
    chip generation; None when never swept (kernel init then compiles with
    the hand-picked :data:`~futuresdr_tpu.ops.pallas_kernels.DEFAULT_BLOCKS`)."""
    entry = cached_streamed_pick(_serve_sig_stages(stages), in_dtype,
                                 platform)
    if entry is None:
        return None
    blocks = (entry.get("pallas_blocks") or {}).get(str(device))
    return dict(blocks) if blocks else None


def autotune_pallas_blocks(stages, in_dtype,
                           inst: Optional[TpuInstance] = None,
                           kernels: Optional[Sequence[str]] = None,
                           frame: int = 1 << 16, reps: int = 3,
                           force: bool = False,
                           record: bool = True) -> Dict[str, int]:
    """Sweep the Pallas kernel block shapes for this chip generation and
    install the winners process-wide (sweep → record →
    :func:`~futuresdr_tpu.ops.pallas_kernels.set_tuned_blocks` — the
    driver of ``tpu/pallas_tune.py``).

    A cache hit (this chain was swept on this device kind before) SKIPS
    the sweep entirely and just installs the recorded winners;
    ``force=True`` re-measures. A recorded winner can never regress the
    hand-picked defaults: the defaults are always in the candidate set
    and win ties (see :func:`~futuresdr_tpu.tpu.pallas_tune.sweep_blocks`)."""
    from ..ops.pallas_kernels import set_tuned_blocks
    from . import pallas_tune
    inst = inst or instance()
    dev = pallas_tune.device_key()
    chain = _serve_sig_stages(stages)
    if not force:
        hit = cached_pallas_blocks(chain, in_dtype, inst.platform, dev)
        if hit is not None:
            log.info("pallas-blocks cache hit (%s): %s — sweep skipped",
                     dev, hit)
            set_tuned_blocks(hit)
            return hit
    winners, matrix = pallas_tune.sweep_blocks(kernels=kernels, frame=frame,
                                               reps=reps)
    log.info("pallas-blocks sweep (%s): winners=%s over %s", dev, winners,
             {k: {b: round(t * 1e3, 3) for b, t in m.items()}
              for k, m in matrix.items()})
    if record and winners:
        record_pallas_blocks(chain, in_dtype, inst.platform, dev, winners)
    set_tuned_blocks(winners)
    return winners


def autotune_serve(pipeline, frame_size: Optional[int] = None,
                   inst: Optional[TpuInstance] = None,
                   capacities: Sequence[int] = (1, 2, 4, 8, 16, 32, 64),
                   reps: int = 4, min_gain: float = 1.2,
                   record: bool = True) -> Tuple[list, Dict[int, float]]:
    """Measure the vmapped serving program per slot-bucket capacity and pick
    the bucket ladder (the serving-plane axis next to (wire, frame, K,
    depth) — docs/serving.md "Autotuned slot buckets").

    For each candidate capacity the REAL serving step
    (``serve.engine.build_slot_program`` — vmapped program + active-lane
    mask, exactly what the engine dispatches) runs fully occupied and the
    aggregate session-frame rate is measured. The ladder keeps doubling
    while aggregate throughput still grows by ``min_gain``× per doubling —
    past that point a bigger bucket only adds latency and pad-lane compute
    for the same chip output, so admission stops growing there. Returns
    ``(ladder, {capacity: session_frames_per_sec})`` and records the ladder
    under the chain's streamed-pick signature (``record=False`` for
    measurement-only sweeps)."""
    import jax
    import jax.numpy as jnp

    from ..serve.engine import build_slot_program
    inst = inst or instance()
    m = pipeline.frame_multiple
    fs = frame_size or inst.frame_size
    fs = max(m, (fs // m) * m)
    results: Dict[int, float] = {}
    ladder: list = []
    prev_rate = None
    fresh = pipeline.init_carry()
    for cap in sorted({int(c) for c in capacities if int(c) > 0}):
        prog = build_slot_program(pipeline, cap)
        pages = jax.tree_util.tree_map(
            lambda l: jnp.stack([jnp.asarray(l)] * cap), fresh)
        pmap = xfer.to_device(np.arange(cap, dtype=np.int32), inst.device)
        no_fresh = xfer.to_device(np.zeros((cap,), dtype=bool), inst.device)
        x = xfer.to_device(np.zeros((cap, fs), dtype=pipeline.in_dtype),
                           inst.device)
        act = xfer.to_device(np.ones((cap,), dtype=bool), inst.device)
        with _profile.compiling("autotune", "autotune",
                                f"serve_cap={cap},frame={fs}"):
            pages, outs = prog(pages, pmap, no_fresh, x, act)  # warm/compile
            jax.block_until_ready(outs)
        t0 = time.perf_counter()
        for _ in range(reps):
            pages, outs = prog(pages, pmap, no_fresh, x, act)
        jax.block_until_ready(outs)
        dt = max(time.perf_counter() - t0, 1e-9)
        rate = cap * reps / dt
        results[cap] = rate
        log.info("autotune_serve: capacity %d -> %.1f session-frames/s "
                 "(%.1f dispatches/s)", cap, rate, reps / dt)
        if prev_rate is not None and rate < prev_rate * min_gain:
            break
        ladder.append(cap)
        prev_rate = rate
    if record and ladder:
        record_serve_buckets(pipeline, pipeline.in_dtype, inst.platform,
                             ladder)
        # the largest kept bucket is the page-pool capacity pick: the
        # engine seeds its paged pool there on the next launch (one
        # compile) instead of growing through the ladder under churn
        record_serve_pages(pipeline, pipeline.in_dtype, inst.platform,
                           ladder[-1])
    return ladder, results


class StreamedResults(dict):
    """The ``autotune_streamed`` sweep matrix: a plain dict keyed by
    ``(wire, frame, depth, k)`` (so it iterates/sorts uniformly), with the
    winning megabatch size stamped as the ``frames_per_dispatch`` ATTRIBUTE —
    feed it to ``TpuKernel(frames_per_dispatch=…)`` — and the winning
    in-flight depth as ``frames_in_flight`` (the credit-controller seed)."""

    frames_per_dispatch: int = 1
    frames_in_flight: int = 0


def autotune_streamed(stages: Sequence[Stage], in_dtype,
                      wires: Optional[Sequence[str]] = None,
                      frames: Optional[Sequence[int]] = None,
                      depths: Sequence[int] = (2, 4, 8),
                      ks: Sequence[int] = (1, 4),
                      min_seconds: float = 0.3,
                      min_snr_db: Optional[float] = 60.0,
                      inst: Optional[TpuInstance] = None
                      ) -> Tuple[str, int, int, Dict]:
    """Returns ``(best_wire, best_frame, best_depth, results)`` for the
    STREAMED path; ``results[(wire, frame, depth, k)] = Msps`` (a
    :class:`StreamedResults`), and the winning megabatch size is stamped at
    ``results.frames_per_dispatch`` (an attribute, so the dict itself stays a
    uniformly tuple-keyed matrix).

    ``ks`` sweeps the megabatch frames-per-dispatch axis (``lax.scan`` of k
    frames per program call, ``ops/stages.py``): K>1 amortizes per-dispatch
    host overhead, which dominates small-frame throughput on the CPU backend
    and behind high-RTT links — but the scan's static shape costs padding at
    EOS and K-1 frames of trickle latency, so K=1 stays the default whenever
    the measured gain does not beat it.

    An explicit (non-"auto") ``config.tpu_wire_format`` /
    ``FUTURESDR_TPU_WIRE_FORMAT`` pins the wire and only (frame, depth, k) are
    swept. Otherwise the candidate set is the analytic pick from the measured
    link envelope (:func:`pick_wire`) plus ``f32`` as the exact baseline, so
    the sweep stays small and the chosen format's advantage is measured, not
    assumed.

    ``stages`` may be a ready-made
    :class:`~futuresdr_tpu.ops.stages.FanoutPipeline` (a fan-out region) or
    :class:`~futuresdr_tpu.ops.stages.DagPipeline` (a general DAG region —
    nested fan-out / merges / the diamond closure): the sweep then measures
    the multi-output drain loop and records the pick under the region's
    SHAPE signature, which the device-graph fusion pass looks up when it
    launches the fused ``TpuFanoutKernel``/``TpuDagKernel``."""
    from ..config import config
    from ..ops.stages import DagPipeline, FanoutPipeline
    inst = inst or instance()
    # ONE Pipeline for everything: wired_fn caches per (wire name, k) on the
    # instance, so the jit function identity stays stable and each (wire,
    # frame, k) shape compiles once — not once per depth (compile_wired hands
    # out a fresh carry per call, so reuse across measurements is safe)
    pipe = stages if isinstance(stages, (FanoutPipeline, DagPipeline)) \
        else Pipeline(list(stages), in_dtype)
    if wires is None:
        pinned = config().tpu_wire_format
        if pinned != "auto":
            wires = (pinned,)
        else:
            up, down = measure_link(inst)
            if getattr(pipe, "n_branches", 0):
                # D2H budget across MIXED branch dtypes: weight each branch's
                # path rate by its dtype width relative to branch 0 (the
                # complex:real byte ratio is 2:1 under every float wire
                # format, so the np-itemsize ratio is wire-invariant) —
                # summing raw ratios against branch 0's dtype alone would
                # mis-size the down-link by up to 2x
                base = np.dtype(pipe.out_dtypes[0]).itemsize
                out_per_in = float(sum(
                    float(r) * (np.dtype(dt).itemsize / base)
                    for r, dt in zip(pipe.path_ratios, pipe.out_dtypes)))
            else:
                out_per_in = float(pipe.ratio)
            picked = pick_wire(up, down, pipe.in_dtype, pipe.out_dtype,
                               out_per_in, min_snr_db=min_snr_db)
            wires = ("f32",) if picked == "f32" else ("f32", picked)
            log.info("link %.1f/%.1f MB/s → wire candidates %s",
                     up / 1e6, down / 1e6, wires)
    if frames is None:
        frames = default_frames(inst.platform)
    results = StreamedResults()
    best = ("f32", 0, 0, 1)
    best_rate = -1.0
    m = pipe.frame_multiple
    for wname in wires:
        for f in frames:
            f = max(m, (f // m) * m)
            for d in depths:
                for k in dict.fromkeys(ks):
                    try:
                        rate = _measure_wired(pipe, wname, f, d, inst,
                                              min_seconds, k=k)
                    except Exception as e:   # OOM at large frames, etc.
                        log.warning(
                            "autotune_streamed (%s, %d, %d, k=%d) failed: %r",
                            wname, f, d, k, e)
                        continue
                    results[(wname, f, d, k)] = round(rate, 1)
                    # ties go to K=1: scan overhead must EARN its latency
                    if rate > best_rate:
                        best_rate = rate
                        best = (wname, f, d, k)
    results.frames_per_dispatch = best[3]
    results.frames_in_flight = best[2]
    if isinstance(pipe, DagPipeline):
        # the canonicalized DAG signature already maps a devchain-composed
        # region (per-member nodes) and a hand-built pipeline of the same
        # stages to one key — one record suffices
        record_streamed_pick(pipe, pipe.in_dtype, inst.platform, best[3],
                             inflight=best[2])
        record_wire_start(pipe, pipe.in_dtype, inst.platform, best[0])
    elif isinstance(pipe, FanoutPipeline):
        # record BOTH fan-out-shaped signatures: the pipeline's (possibly
        # LTI-merged) stage names AND the caller's raw lists — the devchain
        # lookup composes from per-member stage lists, which match the raw
        # names whenever the caller's optimize=True merged across what are
        # separate members in the flowgraph (the same both-signatures rule
        # as the linear branch below)
        record_streamed_pick(pipe, pipe.in_dtype, inst.platform, best[3],
                             inflight=best[2])
        record_wire_start(pipe, pipe.in_dtype, inst.platform, best[0])
        raw_p, raw_b = pipe.raw_stage_lists
        raw_sig = _make_sig(inst.platform, pipe.in_dtype,
                            _fanout_names(raw_p, raw_b))
        _record_sig(raw_sig, best[3], inflight=best[2])
        _record_wire_sig(raw_sig, best[0])
    else:
        # record under BOTH the caller's raw stage list and the optimized
        # pipeline stages: TpuStage/TpuKernel instances carry post-optimize
        # stage lists, so the devchain lookup sees those names
        for sig_stages in (list(stages), pipe.stages):
            record_streamed_pick(sig_stages, pipe.in_dtype, inst.platform,
                                 best[3], inflight=best[2])
            record_wire_start(sig_stages, pipe.in_dtype, inst.platform,
                              best[0])
    log.info("autotune_streamed best: wire=%s frame=%d depth=%d k=%d "
             "(%.1f Msps)", *best, best_rate)
    return best[0], best[1], best[2], results
