#!/bin/bash
# Repo health check (reference: check.sh — fmt/clippy/test across targets).
# Runs: native C++ tests, the Python suite on the virtual 8-device CPU mesh, and the
# driver entry validation (single-chip compile + multi-chip sharding dry-run).
set -e
cd "$(dirname "$0")"

echo "== native =="
make -C native test

echo "== telemetry overhead gate (docs/observability.md budget) =="
JAX_PLATFORMS=cpu python -m pytest -q \
    tests/test_telemetry.py::test_telemetry_disabled_overhead_null_rand

echo "== profile plane smoke (docs/observability.md 'The profile plane') =="
# a warmed streamed run bills exactly ONE warmup compile and ZERO
# steady-state fsdr_compiles_total increments; the live mfu stamp is
# present (config peak overrides exercise the unknown-chip path); serving
# bucket compiles bill once per resident bucket, never per step
JAX_PLATFORMS=cpu python perf/profile_smoke.py --smoke

echo "== device-graph fusion gate (docs/tpu_notes.md 'Device-graph fusion') =="
# fused A/B smoke: the linear pass engages (dispatches drop 3x -> 1x per
# frame), the fan-out pass engages (1->2 broadcast region: H2D bytes bill
# exactly ONE upload per marginal frame via fsdr_xfer_bytes_total, one
# multi-output dispatch per frame), AND the
# general-DAG pass engages (diamond broadcast->merge + nested fan-out:
# dispatches/frame == 1 with interior-edge D2H bytes == 0 — the fused side's
# marginal D2H equals exactly the sink payloads)
JAX_PLATFORMS=cpu python perf/devchain_ab.py --smoke
# fusion equality tests, then the DECLINED mode (FSDR_NO_DEVCHAIN=1) over the
# device-plane suite: the per-hop fallback must stand alone
JAX_PLATFORMS=cpu python -m pytest -q tests/test_devchain.py
FSDR_NO_DEVCHAIN=1 JAX_PLATFORMS=cpu python -m pytest -q \
    tests/test_devchain.py tests/test_tpu_stages.py tests/test_tpu_tags.py \
    tests/test_tpu_frames.py tests/test_retune.py

echo "== host data path gate (docs/tpu_notes.md 'The host data path') =="
# the staging arena's steady-state allocation count is O(1) per frame class
# (misses flat over a sustained window, the packed sc16 class included), a
# codec worker count below 1 is a configuration error, the credit
# controller's hysteresis
JAX_PLATFORMS=cpu python -m pytest -q tests/test_arena.py

echo "== single-shot uplink gate (docs/tpu_notes.md 'The single-shot uplink') =="
# coalesced H2D: a quantizing-wire streamed chain bills exactly ONE physical
# h2d start per dispatch group (payload + scale ride one packed buffer) and
# stays bit-identical to the per-part form a single-part wire takes;
# zero-copy ingest: a registered read-only capture over the aliasing (f32)
# wire skips every ring-exit copy (frac == 1.0); packed replay/fault
# bit-equality, deferred consume, adaptive wire switching, autotune wire axis
JAX_PLATFORMS=cpu python -m pytest -q tests/test_uplink.py

echo "== interior precision gate (docs/tpu_notes.md 'Interior precision') =="
# SNR-budgeted lowering correctness: interior_precision=off is BIT-identical
# (same program object, same bits), the auto plan lowers the resident
# fir64+fft2048 chain with every MEASURED per-edge SNR over the budget and
# the end-to-end output inside budget − incoherent-sum allowance, and the
# fused Pallas PFB / FIR→decimate kernels match the matmul paths they replace
JAX_PLATFORMS=cpu python perf/precision_ab.py --smoke

echo "== pallas autotune cache gate (docs/tpu_notes.md 'Pallas autotune plane') =="
# streamed-pick cache round-trip for the pallas_blocks axis: recorded block
# winners survive a streamed k/inflight re-record, a malformed axis on disk
# loses ONLY itself (per-axis guarded parse — the k pick survives), and a
# second autotune_pallas_blocks call is a cache hit that skips the sweep
JAX_PLATFORMS=cpu python - <<'EOF'
import importlib, json, os, tempfile
td = tempfile.mkdtemp()
os.environ["FUTURESDR_TPU_AUTOTUNE_CACHE_DIR"] = td
import numpy as np
from futuresdr_tpu.ops.stages import fir_stage, mag2_stage, Pipeline
from futuresdr_tpu.ops import pallas_kernels as pk
at = importlib.import_module("futuresdr_tpu.tpu.autotune")
pallas_tune = importlib.import_module("futuresdr_tpu.tpu.pallas_tune")

taps = np.random.default_rng(0).standard_normal(33).astype(np.float32)
P = Pipeline([fir_stage(taps), mag2_stage()], np.complex64)

# record (junk keys dropped at the gate) + read back, per-device-kind keyed
at.record_pallas_blocks(P.stages, P.in_dtype, "cpu", "v5e",
                        {"fir": 2048, "bogus": 7, "pfb": -1})
assert at.cached_pallas_blocks(P.stages, P.in_dtype, "cpu", "v5e") == \
    {"fir": 2048}
assert at.cached_pallas_blocks(P.stages, P.in_dtype, "cpu", "v5p") is None

# axis survives a streamed k/inflight re-record on the same signature
at.record_streamed_pick(P.stages, P.in_dtype, "cpu", 4, inflight=2)
assert at.cached_pallas_blocks(P.stages, P.in_dtype, "cpu", "v5e") == \
    {"fir": 2048}
e = at.cached_streamed_pick(P.stages, P.in_dtype, "cpu")
assert e["k"] == 4 and e["inflight"] == 2, e

# disk round-trip through a cleared memo (a fresh process would see this)
at._disk_memo.clear(); at._streamed_cache.clear()
assert at.cached_pallas_blocks(P.stages, P.in_dtype, "cpu", "v5e") == \
    {"fir": 2048}

# a malformed axis on disk loses only itself — the entry (k pick) survives
path = os.path.join(td, "streamed_picks.json")
with open(path) as f:
    d = json.load(f)
d[next(iter(d))]["pallas_blocks"] = "garbage"
with open(path, "w") as f:
    json.dump(d, f)
at._disk_memo.clear(); at._streamed_cache.clear()
e = at.cached_streamed_pick(P.stages, P.in_dtype, "cpu")
assert e is not None and e["k"] == 4 and "pallas_blocks" not in e, e

# driver: first call sweeps + records, second is a cache hit (no sweep)
at._disk_memo.clear(); at._streamed_cache.clear()
calls = {"n": 0}
orig = pallas_tune.sweep_blocks
def counting(*a, **k):
    calls["n"] += 1
    return orig(*a, **k)
pallas_tune.sweep_blocks = counting
w1 = at.autotune_pallas_blocks(P.stages, P.in_dtype, kernels=("rotator",),
                               frame=1 << 14, reps=1)
assert calls["n"] == 1 and "rotator" in w1, (calls, w1)
w2 = at.autotune_pallas_blocks(P.stages, P.in_dtype, kernels=("rotator",),
                               frame=1 << 14, reps=1)
assert calls["n"] == 1, "cache hit must skip the sweep"
assert w2 == w1 and pk.tuned_blocks()["rotator"] == w1["rotator"]
pk.set_tuned_blocks(None)
print("pallas autotune cache round-trip: OK")
EOF

echo "== multi-tenant serving gate (docs/serving.md) =="
# N sessions of one receiver chain through a single vmapped dispatch per
# frame: dispatches/frame == 1 regardless of the active session count,
# session join/leave under load causes ZERO recompiles of resident slot
# buckets, a simulated crash-restart with durable persistence resumes 100%
# of sessions bit-identically to the uncrashed engine, and an admission
# storm sheds newcomers while residents keep delivering
JAX_PLATFORMS=cpu python perf/serve_ab.py --smoke

echo "== serve churn gate (docs/serving.md 'Paged session carries') =="
# the paged-engine acceptance regime: join/leave EVERY step for 100 events
# at N=64, K in {1,4} — ZERO recompiles of the resident capacity (the page
# table absorbs all churn as host map edits), one dispatch per step
JAX_PLATFORMS=cpu python perf/serve_ab.py --churn --smoke

echo "== mesh-sharded device plane gate (docs/parallel.md) =="
# the data-sharded fused program on the virtual 8-device mesh: bit-identical
# per shard to the D=1 program at matched K, ONE dispatch per group (the
# per-shard dispatch count never multiplies with D), ZERO cross-shard
# collectives in the compiled HLO (interior edges never leave their shard)
JAX_PLATFORMS=cpu python perf/multichip_ab.py --smoke

echo "== fleet observability gate (docs/observability.md 'The fleet plane') =="
# three live control-port hosts over real sockets: the FleetView reaches 3
# ready, the merged /api/fleet/metrics exposition is host-labelled and
# scrape-stable, the first admit lands on the least-pressure host, and after
# SIGKILL of that host the view flips it stale -> down (journal-ordered) with
# 100% of subsequent admits routed to the survivors
JAX_PLATFORMS=cpu python perf/fleet_smoke.py --smoke

echo "== chaos smoke (docs/robustness.md invariants) =="
# seeded fault injection at every site × every failure policy on the CPU
# backend: restart recovers bit-correct, isolate finishes independent
# branches, fail_fast keeps today's behavior, transfer retries are
# deterministic, no run hangs past its deadline or leaks threads — plus
# the serving plane: SIGKILL mid-serve + restart resumes every persisted
# session bit-identically (serve-crash-restart) and an overload storm
# sheds only via the documented ladder (serve-overload-shed)
JAX_PLATFORMS=cpu python perf/chaos.py --smoke

echo "== lineage & journal smoke (docs/observability.md 'Frame lineage') =="
# 1-in-1 sampled streamed run: the Perfetto export renders a sampled frame
# as ONE connected s/t/f flow chain spanning >=4 lanes, tail attribution
# names a slowest pipeline lane consistent with its own per-lane split, and
# the lifecycle journal drains through the REST cursor contract (pages of 3,
# no gaps, same seq order as the unlimited read)
FUTURESDR_TPU_LINEAGE_STRIDE=1 JAX_PLATFORMS=cpu python - <<'EOF'
import json
import numpy as np
from futuresdr_tpu import Flowgraph, Runtime
from futuresdr_tpu.blocks import Head, NullSink, NullSource
from futuresdr_tpu.config import config
from futuresdr_tpu.dsp import firdes
from futuresdr_tpu.ops import fir_stage, mag2_stage
from futuresdr_tpu.telemetry import journal, lineage, spans
from futuresdr_tpu.tpu import TpuKernel

assert lineage.tracer().stride == 1, lineage.tracer().stride
frame = 1 << 14
n = 24 * frame
c = config()
c.buffer_size = max(c.buffer_size, 4 * frame * 8)
fg = Flowgraph()
taps = firdes.lowpass(0.2, 64).astype(np.float32)
tk = TpuKernel([fir_stage(taps), mag2_stage()], np.complex64,
               frame_size=frame, frames_in_flight=4)
fg.connect(NullSource(np.complex64), Head(np.complex64, n), tk,
           NullSink(np.float32))
Runtime().run(fg)

recs = lineage.tracer().records()
assert recs, "1-in-1 sampling produced no completed lineage records"

# Perfetto flow chains: at least one record renders as a connected
# s -> t... -> f chain sharing one id across >=4 lanes
trace = spans.chrome_trace()
flows = {}
for ev in trace["traceEvents"]:
    if ev.get("cat") == "lineage":
        flows.setdefault(ev["id"], []).append(ev)
assert trace["otherData"]["lineage_flows"] == len(flows) > 0, \
    trace["otherData"]
chained = 0
for tid, evs in flows.items():
    phs = [e["ph"] for e in evs]
    if len(evs) >= 4 and phs[0] == "s" and phs[-1] == "f" and \
            all(p == "t" for p in phs[1:-1]) and evs[-1].get("bp") == "e":
        lanes = [e["args"]["lane"] for e in evs]
        assert lanes[0] == "ingest" and lanes[-1] == "emit", lanes
        chained += 1
assert chained, "no connected s/t/f flow chain spanning >=4 lanes"
json.dumps(trace)  # the export must stay JSON-serializable

# tail attribution: slowest lane named, consistent with its own split
tail = lineage.tail_report()
assert tail and tail["e2e_samples"] > 0, tail
sl = tail["slowest_lane"]
assert sl in lineage.PIPELINE_LANES, tail
pipe = {ln: d["total_s"] for ln, d in tail["lanes"].items()
        if ln in lineage.PIPELINE_LANES}
assert sl == max(pipe, key=pipe.get), (sl, pipe)

# journal: the run journaled its kernel init; the cursor contract drains
# everything in order without gaps
j = journal.journal()
full = j.events()["events"]
assert any(e["cat"] == "kernel" and e["event"] == "init" for e in full)
drained, cur = [], 0
while True:
    page = j.events(since=cur, limit=3)
    assert not page["gap"], page
    drained.extend(page["events"])
    if not page["events"] or page["next"] == cur:
        break
    cur = page["next"]
seqs = [e["seq"] for e in drained]
assert seqs == [e["seq"] for e in full] == sorted(seqs), \
    "cursor drain disagrees with the unlimited read"
print(f"lineage smoke: {len(recs)} records, {chained} flow chain(s), "
      f"slowest lane {sl}, journal drained {len(seqs)} events: OK")
EOF

echo "== python suite =="
python -m pytest tests/ -q

echo "== graft entries =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 python - <<'EOF'
import jax
jax.config.update("jax_platforms", "cpu")
import sys
sys.path.insert(0, ".")
from __graft_entry__ import entry, dryrun_multichip
fn, args = entry()
jax.jit(fn)(*args)
dryrun_multichip(8)
print("entry + dryrun_multichip(8): OK")
EOF

echo "ALL CHECKS PASSED"
