"""Layered global configuration.

Re-design of the reference's config system (``src/runtime/config.rs:16-210``): defaults ←
``~/.config/futuresdr_tpu/config.toml`` ← project ``config.toml`` ← ``FUTURESDR_TPU_*`` env vars.
Typed knobs plus a free-form ``misc`` map with typed ``get``.
"""

from __future__ import annotations

import os

import tomllib
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

__all__ = ["Config", "config", "reload_config"]

_ENV_PREFIX = "FUTURESDR_TPU_"


@dataclass
class Config:
    # Defaults mirror the reference's (`config.rs:180-210`) except buffer_size: the
    # reference tunes 32 KiB for per-item CPU loops; this runtime's blocks are
    # numpy/XLA-vectorized, where larger work windows win (measured 2× on perf/fir).
    queue_size: int = 8192                 # inbox capacity
    buffer_size: int = 262144              # stream buffer size in bytes
    slab_reserved: int = 128               # reserved history items for slab buffers
    stack_size: int = 16 * 1024 * 1024     # (informational; Python threads use default)
    log_level: str = "info"
    default_scheduler: str = "async"       # "async" | "threaded"
    ctrlport_enable: bool = False
    ctrlport_bind: str = "127.0.0.1:1337"
    frontend_path: Optional[str] = None
    # Telemetry (telemetry/spans.py): span recording off by default — the
    # metrics registry (telemetry/prom.py) is always on, spans are opt-in.
    trace: bool = False                    # FUTURESDR_TPU_TRACE=1 records spans
    trace_ring: int = 1 << 18              # per-thread span ring capacity
    # Flowgraph doctor (telemetry/doctor.py): the watchdog thread is opt-in;
    # the latency histograms it reads are always on (metrics-plane contract).
    doctor: bool = False                   # FUTURESDR_TPU_DOCTOR=1 starts the
    #   stall watchdog when the first Runtime is constructed
    doctor_interval: float = 1.0           # watchdog sampling period, seconds
    doctor_window: int = 5                 # consecutive no-progress samples
    #   before a trip (trip latency ≈ interval × window)
    doctor_dir: str = ""                   # write flight-recorder dumps here
    #   ("" = keep in memory only; served via GET /api/fg/{fg}/doctor/)
    # Frame-lineage tracing plane (telemetry/lineage.py) and the lifecycle
    # event journal (telemetry/journal.py) — docs/observability.md "Frame
    # lineage & flow traces" / "The event journal".
    lineage_stride: int = 64               # sample 1-in-N frames for lineage
    #   records (trace id + per-lane stamps): 0 disables (one falsy check
    #   per frame), 1 samples every frame (tests/smokes). Sampled records
    #   feed Perfetto flow links, doctor tail attribution, and OpenMetrics
    #   exemplars on fsdr_e2e_latency_seconds
    lineage_ring: int = 512                # completed lineage records kept
    journal_ring: int = 1024               # lifecycle events kept in the
    #   process-global journal ring (REST cursor: GET /api/events/)
    journal_dir: str = ""                  # spool every journal event as one
    #   JSONL line under this directory (atomic append; "" = ring only)
    journal_spool_mb: int = 64             # spool rotation cap, MiB per file:
    #   past it the active events_<pid>.jsonl atomically renames to .1 (.1
    #   shifts to .2, …) and a fresh file opens — long runs stay bounded at
    #   ~(keep+1) x cap. 0 = never rotate (the pre-rotation behavior)
    journal_spool_keep: int = 4            # rotated spool files kept per pid;
    #   the oldest beyond this is deleted at rotation time
    # Fleet observability plane (telemetry/fleet.py, docs/observability.md
    # "The fleet plane"): per-host pressure exports on every control port
    # (GET /api/host/), a cross-host aggregator (GET /api/fleet/), and the
    # pressure-routed admission front door (serve/router.py). OFF by default:
    # with no peers configured every hot-path hook (fleet.tick) is one falsy
    # check — the ≤3% telemetry-overhead contract.
    fleet_peers: str = ""                  # comma-separated control-port
    #   addresses ("10.0.0.1:1337,10.0.0.2:1337"); "" = fleet plane disabled.
    #   Env: FUTURESDR_TPU_FLEET_PEERS
    fleet_poll_interval: float = 1.0       # peer poll cadence, seconds
    fleet_stale_s: float = 0.0             # a host whose last good summary is
    #   older than this reads `stale`; 0 = auto (3 x fleet_poll_interval)
    fleet_down_errors: int = 2             # consecutive poll failures that
    #   flip a host stale -> down (a SIGKILLed peer reads down within 2
    #   poll intervals); the first failure alone marks it stale
    fleet_skew: float = 0.5                # pressure-skew verdict threshold:
    #   max-min per-host credit pressure past it surfaces the hottest host's
    #   eviction candidates as the migration hint
    fleet_hysteresis: float = 0.1          # admission-router switch band: a
    #   candidate host must beat the current pick's pressure/p99 by this
    #   margin (same shed rung) before routing moves — no flapping
    fleet_host_id: str = ""                # this host's id in fleet views and
    #   merged-metrics host= labels ("" = <hostname>:<pid>)
    # Profile plane (telemetry/profile.py, docs/observability.md "The
    # profile plane"): MFU/HBM-utilization denominators. 0 = autodetect the
    # chip from jax.devices()[0].device_kind (utils/roofline.detect_peaks);
    # set BOTH to pin peaks on an unknown chip (or to force an MFU stamp on
    # the CPU backend for CI smokes — perf/profile_smoke.py does exactly
    # that). Env: FUTURESDR_TPU_PEAK_FLOPS / FUTURESDR_TPU_PEAK_HBM_GBPS.
    peak_flops: float = 0.0                # chip peak, FLOP/s (bf16 matmul)
    peak_hbm_gbps: float = 0.0             # chip HBM bandwidth, GB/s
    doctor_action: str = "record"          # watchdog-trip escalation
    #   (telemetry/doctor.py): "record" keeps today's flight-record-only
    #   behavior; "cancel" additionally cancels the wedged flowgraph after
    #   recording — the run raises FlowgraphError instead of hanging
    # Fault tolerance (docs/robustness.md): per-block failure policies
    # (runtime/block.py BlockPolicy — a kernel's own .policy attribute wins
    # over these process defaults), transfer retry (ops/xfer.py), and run
    # deadlines (runtime/runtime.py).
    block_policy: str = "fail_fast"        # default on_error policy:
    #   "fail_fast" | "restart" | "isolate"; env FUTURESDR_TPU_BLOCK_POLICY
    block_max_restarts: int = 3            # restart budget per block
    block_backoff: float = 0.05            # restart backoff base, seconds
    #   (exponential per attempt, capped at BlockPolicy.backoff_cap)
    block_isolate_groups: str = ""         # isolate-group assignment spec
    #   "block_name=group;other_block=group2": a member's failure retires the
    #   WHOLE named subgraph (group-wide port EOS in topological order) while
    #   independent branches finish — the config-side form of
    #   BlockPolicy(isolate_group=...); applies to blocks with no own policy
    xfer_retries: int = 3                  # transient H2D/D2H retries per transfer
    xfer_backoff: float = 0.005            # transfer retry backoff base, seconds
    #   (jittered exponential; jitter never changes the retry COUNT)
    xfer_deadline: float = 30.0            # per-transfer deadline, seconds (0 = none):
    #   retries stop once the next backoff would cross it
    run_timeout: float = 0.0               # Runtime.run deadline, seconds (0 = none):
    #   on expiry the run is flight-recorded and cancelled (EOS drain + join)
    #   and raises FlowgraphError instead of hanging the caller
    run_timeout_grace: float = 5.0         # post-cancel join grace before the
    #   deadline path gives up and raises with the flowgraph still wedged
    autotune_cache_dir: str = "~/.cache/futuresdr_tpu"   # persisted
    #   autotune_streamed picks (JSON, tpu/autotune.py); "off"/"" disables
    # Host data path (docs/tpu_notes.md "The host data path"): the staging
    # arena (ops/arena.py — recycled host buffers for wire-encode outputs,
    # H2D staging parts and megabatch pads) and the codec worker pool
    # (ops/codec_pool.py — host encode/decode off the drain thread).
    host_arena_mb: int = 256               # arena pool byte cap: past it a
    #   released buffer is dropped to the allocator instead of pooled
    host_codec_workers: int = 2            # codec threads per lane (encode /
    #   decode), at least 1
    tpu_inflight: int = 0                  # in-flight credit budget of the
    #   streamed drain loop: 0 = auto — an adaptive, hysteretic credit
    #   controller (tpu/kernel_block.py CreditController) seeds from the
    #   autotune_streamed pick (or tpu_frames_in_flight) and adjusts at
    #   runtime from link idle/backpressure signals; N>0 pins the budget
    #   (as does an explicit per-kernel frames_in_flight argument)
    tpu_adaptive_wire: bool = False        # mid-stream adaptive wire
    #   switching (tpu/kernel_block.py WireController): a hysteretic
    #   controller reads the measured stream SNR of the active quantized
    #   format and the h2d link occupancy windows, and retunes the wire
    #   format between dispatch groups (bit-exact replay of the switch
    #   boundary included). Off by default: the wire format is part of the
    #   numerics contract, so opting in is explicit
    tpu_wire_snr_budget_db: float = 40.0   # stream-SNR floor of the
    #   adaptive-wire policy: the active quantized format WIDENS (toward
    #   f32) when its measured SNR dips below this; a NARROWER format is
    #   only adopted when its predicted SNR clears this plus the
    #   controller's hysteresis margin
    checkpoint_dir: str = ""               # persist the committed carry-
    #   checkpoint ring across PROCESSES (docs/robustness.md): each commit
    #   also lands as an atomic, integrity-checked snapshot file under this
    #   directory, and recover() falls back to it when no in-kernel
    #   checkpoint survives (a process restart). "" = off (default)
    # TPU-specific knobs (no reference analog; this is the compute-plane config).
    tpu_frame_size: int = 1 << 18          # samples per device frame
    tpu_frames_in_flight: int = 4          # dispatch pipeline depth
    tpu_wire_format: str = "auto"          # host↔device wire codec (ops/wire.py):
    #   "auto" | "f32" | "bf16" | "sc16" | "sc8"; env FUTURESDR_TPU_WIRE_FORMAT
    tpu_frames_per_dispatch: int = 0       # megabatch K: frames lax.scan'ed through
    #   the compiled pipeline per program call (amortizes per-dispatch host
    #   overhead); env FUTURESDR_TPU_FRAMES_PER_DISPATCH.
    #   0 = auto: one dispatch per frame, EXCEPT a device-graph-fused chain
    #   that autotune_streamed already tuned in this process, which launches
    #   with its measured K (runtime/devchain.py). An explicit 1 pins
    #   dispatch-per-frame everywhere (latency-critical deployments).
    # Multi-tenant serving (futuresdr_tpu/serve, docs/serving.md): slot
    # buckets and per-tenant admission budget of the vmapped serving engine.
    serve_buckets: str = ""                # slot-bucket ladder, e.g. "1,4,16,64";
    #   "" = auto (the cached autotune_serve pick for the pipeline, else the
    #   default power-of-two ladder to 64)
    serve_queue_frames: int = 2            # shared admission budget = this many
    #   queued-but-undispatched frames per slot, divided fairly between
    #   tenants (serve/credits.py TenantCreditController)
    serve_retired_keep: int = 64           # retired-session views kept for the
    #   REST plane (a faulted client rarely comes back to DELETE); the oldest
    #   beyond this are forgotten so fault churn cannot grow the registry
    #   without bound
    # Crash-safe serving (docs/robustness.md "Serving-plane recovery"):
    # durable per-session carry snapshots, drain lifecycle and the SLO-aware
    # overload-shedding ladder of the serving engine.
    serve_persist_dir: str = ""            # durable session state: per-slot
    #   carry snapshots land here (atomic rename + CRC, keyed by session id
    #   + pipeline-signature hash — utils/snapshot.py) and a VIRGIN
    #   ServeEngine incarnation re-admits every persisted session
    #   bit-identically. "" = off (default)
    serve_persist_every: int = 0           # persistence cadence in serving
    #   steps: every Nth step() queues a background snapshot of every lane
    #   (one falsy check when 0 = off — step() stays inside the ≤3%
    #   telemetry overhead budget); evictions and drains persist regardless
    serve_slo_ms: float = 0.0              # per-frame submit→result latency
    #   SLO driving the shedding ladder (serve/overload.py); 0 = ladder
    #   driven by queue pressure only
    serve_shed_hi: float = 0.85            # queue-pressure high watermark:
    #   consecutive steps at/above it escalate the shedding ladder one rung
    serve_shed_lo: float = 0.50            # low watermark: the ladder only
    #   unwinds (one rung at a time — hysteretic recovery) after sustained
    #   pressure at/below it
    serve_shed_trip: int = 3               # consecutive over-watermark/SLO
    #   steps per one-rung escalation
    serve_shed_clear: int = 8              # consecutive healthy steps per
    #   one-rung unwind
    serve_brownout: str = "off"            # optional third shedding rung
    #   under sustained overload: "off" (default — rungs 1-2 only, both
    #   bit-exact for residents) | "k" (drop megabatch K to 1 on resident
    #   buckets — latency over throughput; K>1 vs K=1 round differently by
    #   repo contract) | "precision" (retune interior precision via
    #   ops/precision.py — SNR-bounded quality loss for the duration)
    serve_brownout_precision: str = "bf16"  # the mode the "precision"
    #   brownout rung lowers to: "bf16" (default) or "int8" (the deeper
    #   ladder rung — FIR-family stages drop to quantized int8 MXU matmuls,
    #   ~36 dB SNR; int8 stages carry float weights and quantize in-trace,
    #   so engage/release stays a leafwise dtype conversion)
    serve_drain_on_sigterm: bool = False   # register_app installs a SIGTERM
    #   hook that drains every registered serving app (refuse admissions,
    #   finish in-flight, persist all lanes) — the rolling-restart contract
    serve_inflight: int = 1                # overlapped-step depth: how many
    #   dispatch groups the engine keeps in flight before draining the
    #   oldest (CreditController-governed, docs/serving.md "The overlapped
    #   step"). 1 (default) = launch-then-drain each step, byte-for-byte
    #   the synchronous engine; >1 overlaps H2D(t+1) ∥ compute(t) ∥
    #   D2H(t-1) and adapts within [2, depth] off wire/compute balance
    # Interior precision (ops/precision.py, docs/tpu_notes.md "Interior
    # precision"): SNR-budgeted lowering of interior DAG edges and stage
    # accumulation inside the fused device programs. "off" (default) is
    # BIT-IDENTICAL to an unlowered build; "auto" lowers only where the
    # measured per-edge SNR vs the f32 reference clears the budget; "bf16"
    # force-lowers every supporting stage/edge (budget ignored, SNR still
    # measured). Env: FUTURESDR_TPU_INTERIOR_PRECISION etc.
    interior_precision: str = "off"        # "off" | "auto" | "bf16"
    interior_snr_budget_db: float = 40.0   # per-edge SNR floor for "auto"
    #   (bf16 edges measure ~55 dB on unit-power Gaussian frames, so the
    #   default accepts bf16 and refuses anything sc8-grade)
    interior_precision_overrides: str = "" # per-stage pins,
    #   "fir=off;fft2048=bf16": "off" keeps a stage f32 whatever the budget
    #   says, a precision forces it — the config-side form of the per-stage
    #   ctrl retune (TpuKernel ctrl {"stage": ..., "interior_precision": ...})
    # Mesh-sharded device plane (futuresdr_tpu/shard, docs/parallel.md
    # "Mesh-sharded device plane"): lift fused device programs onto the
    # chip mesh. "off" (default) is the single-device contract —
    # shard_pipeline returns the SAME program object, bit-identical by
    # construction. Env: FUTURESDR_TPU_SHARD etc.
    shard: str = "off"                     # "off" | "auto" | "data" | "model"
    shard_devices: int = 0                 # mesh width (0 = every visible
    #   device); requesting more than exist REFUSES loudly at plan time
    #   (parallel/mesh.make_mesh — never a silent truncation)
    serve_shard_devices: int = 0           # slot-axis sharding of the
    #   serving engine (sessions x devices, docs/serving.md): a bucket's
    #   session lanes spread one contiguous block per device; 0 = off.
    #   Buckets whose capacity does not divide evenly stay unsharded.
    tpu_checkpoint_every: int = 1          # carry-checkpoint cadence of the
    #   device-plane recovery contract (docs/robustness.md "Device-plane
    #   recovery"): snapshot the kernel carry every Nth dispatch group (host
    #   copy rides the D2H lane) so a `restart` re-inits from the checkpoint
    #   and REPLAYS the in-flight frames bit-correct instead of forfeiting
    #   them. 1 (default) = every drained group; 0 = off (restart falls back
    #   to fresh-carry forfeiture, billed on fsdr_frames_forfeited_total);
    #   env FUTURESDR_TPU_CHECKPOINT_EVERY. Larger cadences trade snapshot
    #   D2H bandwidth for a longer replay window. The cadence self-arms only
    #   when a restart consumer exists (kernel/config restart policy, a
    #   restartable fused devchain, or an explicit per-kernel cadence) —
    #   fail_fast runs pay nothing.
    misc: dict = field(default_factory=dict)

    def get(self, key: str, default: Any = None) -> Any:
        """Typed free-form lookup (`config.rs:37-48`)."""
        if hasattr(self, key) and key != "misc":
            return getattr(self, key)
        return self.misc.get(key, default)

    def _apply(self, d: dict, env: bool = False):
        for k, v in d.items():
            if env and not hasattr(self, k) and hasattr(self, "tpu_" + k):
                # FUTURESDR_TPU_WIRE_FORMAT etc.: the env prefix already spells
                # the plane, so the stripped key lacks the ``tpu_`` head. Env
                # vars only — a TOML ``wire_format`` key stays in misc (it was
                # never a typed knob, and silently promoting it would change
                # existing configs' behavior)
                k = "tpu_" + k
            if hasattr(self, k) and k != "misc":
                cur = getattr(self, k)
                if isinstance(cur, bool) and isinstance(v, str):
                    v = v.lower() in ("1", "true", "yes", "on")
                elif isinstance(cur, int) and not isinstance(cur, bool):
                    v = int(v)
                elif isinstance(cur, float):
                    v = float(v)
                setattr(self, k, v)
            else:
                self.misc[k] = v


def _load() -> Config:
    c = Config()
    for path in (
        Path.home() / ".config" / "futuresdr_tpu" / "config.toml",
        Path.cwd() / "config.toml",
    ):
        try:
            if path.is_file():
                with open(path, "rb") as f:
                    c._apply(tomllib.load(f))
        except (OSError, tomllib.TOMLDecodeError):
            pass
    env = {
        k[len(_ENV_PREFIX):].lower(): v
        for k, v in os.environ.items()
        if k.startswith(_ENV_PREFIX)
    }
    c._apply(env, env=True)
    return c


_config: Optional[Config] = None


def config() -> Config:
    """The process-global config singleton (`config.rs:16`)."""
    global _config
    if _config is None:
        _config = _load()
    return _config


def reload_config() -> Config:
    global _config
    _config = _load()
    return _config
