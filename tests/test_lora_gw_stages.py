"""The on-device LoRaWAN gateway (``models/lora/rx_stages``) against the
benchmark's float64 receiver (``benchmark/harness/refs_lora.py``, which imports
nothing of the program): record for record, through ``Runtime()`` and the app,
at small size: 4 channels x SF7-SF9 (LDRO on at SF9), frames of 16384 samples
(5120 per channel at 250 kHz: five SF9 symbols, so every SF9 packet outlives a
frame many times).

Tolerances (float32 program against float64 reference, CPU, PR 33): CFO
0.002 Hz, rest of the timing 2e-6 chips, mean peak share 3e-7 of itself at
most over the seeds below; the limits sit 25 to 100 times above.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmark"))
from harness import refs_lora as R                                  # noqa: E402

from futuresdr_tpu.models.lora.rx_stages import (lora_gw_stages,    # noqa: E402
                                                 parse_records, record_counters)

FRAME, N_CH, SFS = 16384, 4, (7, 8, 9)
MAX_LEN = {7: 48, 8: 32, 9: 24}
SMALL = dict(n_channels=N_CH, sfs=SFS, max_payload=MAX_LEN, ldro_from_sf=9,
             done_slots=8)
L = FRAME * 5 // (4 * N_CH)            # 250 kHz samples a channel a frame
N0 = 1e-2
CFO_TOL, TIMING_TOL, SHARE_RTOL = 0.05, 5e-5, 3e-5


def air(packets, n_frames, seed):
    """``packets``: (channel, sf, first sample, payload, snr_db, cfo_hz)."""
    rng = np.random.default_rng(seed)
    n = n_frames * FRAME
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)) * np.sqrt(N0 / 2)
    for c, sf, t0, payload, snr, cfo in packets:
        R.add_packet(x, N_CH, c, sf, payload, t0, snr, N0, cfo, rng.uniform(0, 1),
                     R.ldro(sf, 9))
    return x.astype(np.complex64)


def train(seed, n_frames, duty=0.3):
    """Per branch packets one after another with exponential gaps, the
    benchmark's law at small size; different SFs overlap on a channel."""
    rng = np.random.default_rng(seed)
    out, n = [], n_frames * FRAME
    for c in range(N_CH):
        for sf in SFS:
            mean = R.packet_chips(sf, 30, sf >= 9) * N_CH * R.SLOT / R.BW
            t = 2000 + rng.uniform(0, mean)
            while True:
                length = int(rng.integers(5, MAX_LEN[sf] + 1))
                dur = R.packet_chips(sf, length, sf >= 9) * N_CH * R.SLOT / R.BW
                if t + dur > n - 4000:
                    break
                out.append((c, sf, float(t), rng.integers(0, 256, length, dtype=np.uint8)
                            .tobytes(), float(rng.uniform(3, 9)),
                            float(rng.uniform(-10e3, 10e3))))
                t += dur + rng.exponential(mean * (1 - duty) / duty)
    return out


def reference_blocks(x):
    gw = R.Gateway(N_CH, SFS, MAX_LEN, ldro_from_sf=9)
    return [gw.frame(x[i:i + FRAME]) for i in range(0, len(x), FRAME)]


@pytest.fixture(scope="module")
def small():
    import jax
    stage = lora_gw_stages(**SMALL)[0]
    return stage, jax.jit(stage.fn)


def run_frames(small, x):
    stage, fn = small
    carry, out = stage.init_carry(np.complex64), []
    for i in range(0, len(x), FRAME):
        carry, y = fn(carry, x[i:i + FRAME])
        out.append(np.asarray(y))
    return out


def same_records(mine, ref):
    """Parsed packets of the program against the reference's records."""
    keys = ("channel", "sf", "start", "end", "length", "crc_ok", "n_sym", "payload")
    assert [[p[k] for k in keys] for p in mine] == [[r[k] for k in keys] for r in ref]
    for p, r in zip(mine, ref):
        assert abs(p["cfo_hz"] - r["cfo_hz"]) <= CFO_TOL
        assert abs(p["timing"] - r["timing"]) <= TIMING_TOL
        assert abs(p["share"] - r["share"]) <= SHARE_RTOL * r["share"]
        assert abs(p["snr_db"] - r["snr_db"]) <= 1e-3


def same_as_reference(blocks, x):
    """Every block: header counts and every record equal the reference's."""
    got = []
    for block, (records, counts) in zip(blocks, reference_blocks(x)):
        head, mine = parse_records(block)
        assert head == {f"lora_{k}": v for k, v in counts.items()}
        same_records(mine, records)
        got += mine
    return got


# -- (a) record for record, through Runtime() and the app ------------------------

@pytest.mark.parametrize("seed", [11, 2147483659, 4000000007])
def test_app_through_runtime_equals_reference(seed):
    from futuresdr_tpu import Runtime
    from futuresdr_tpu.apps.lora_gw import build_flowgraph
    from futuresdr_tpu.blocks import VectorSource
    sent = train(seed, 24)
    x = air(sent, 24, seed + 1)
    fg, kernel, rx = build_flowgraph(VectorSource(x), frame_size=FRAME, **SMALL)
    Runtime().run(fg)
    ref = [r for records, _ in reference_blocks(x) for r in records]
    same_records(rx.packets, ref)
    assert len(ref) >= 8 and {r["sf"] for r in ref} == set(SFS)
    assert sorted(rx.frames) == sorted(p[3] for p in sent)      # each once
    assert rx.extra_metrics() == {"frames": 24, "packets": len(sent), "crc_bad": 0,
                                  "overflow": 0}
    assert kernel.extra_metrics()["frames_dispatched"] == 24
    # a packet that outlives a frame: negative start, three frames and more
    assert min(p["start"] for p in rx.packets) < -3 * L


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_blocks_equal_reference_header_and_records(small, seed):
    sent = train(seed, 12, duty=0.4)
    x = air(sent, 12, seed + 100)
    got = same_as_reference(run_frames(small, x), x)
    assert sorted(p["payload"] for p in got if p["crc_ok"]) == sorted(p[3] for p in sent)


# -- (b) frames and edges --------------------------------------------------------

def test_packet_spans_frames_and_one_ends_on_a_frames_last_sample(small):
    payload = bytes(range(24))
    t0 = 3000.4
    for _ in range(4):                       # move the packet until its end is the edge
        x = air([(2, 9, t0, payload, 6.0, 4321.0)], 16, 5)
        recs = [(i, r) for i, (records, _) in enumerate(reference_blocks(x))
                for r in records]
        assert len(recs) == 1
        frame_i, r = recs[0]
        if r["end"] == L:
            break
        t0 += (L - r["end"]) * N_CH * R.SLOT / 250e3
    assert r["end"] == L and r["start"] < -3 * L and frame_i >= 3
    got = same_as_reference(run_frames(small, x), x)
    assert [p["payload"] for p in got] == [payload] and got[0]["end"] == L


def test_two_sfs_overlap_on_one_channel(small):
    a, b = bytes(range(40)), bytes(range(100, 120))
    x = air([(1, 7, 9000.2, a, 4.0, -7000.0), (1, 9, 5000.7, b, 4.0, 9000.0),
             (1, 8, 30000.0, bytes(10), 5.0, 100.0)], 14, 7)
    got = same_as_reference(run_frames(small, x), x)
    assert sorted((p["sf"], p["payload"]) for p in got if p["crc_ok"]) == \
        [(7, a), (8, bytes(10)), (9, b)]
    assert all(p["channel"] == 1 for p in got)


def test_noise_alone_emits_nothing(small):
    x = air([], 6, 9)
    for block in run_frames(small, x):
        head, mine = parse_records(block)
        assert mine == [] and head["lora_emitted"] == 0 and head["lora_overflow"] == 0


# -- (c) capacities --------------------------------------------------------------

def test_every_lane_of_an_sf_in_flight_at_once(small):
    """Every branch is a lane of its SF's scan and no pool stands between a
    preamble and its lane: all channels busy on one SF overflow nothing."""
    pk = [(c, 8, 4000.0 + 900 * c, bytes([c] * 20), 6.0, 1000.0 * c) for c in range(4)]
    x = air(pk, 8, 13)
    ref = [r for records, _ in reference_blocks(x) for r in records]
    assert sorted(r["payload"] for r in ref) == sorted(p[3] for p in pk)
    heads, mine = zip(*(parse_records(b) for b in run_frames(small, x)))
    assert sum(h["lora_overflow"] for h in heads) == 0
    assert max(h["lora_in_flight"] for h in heads) == 4
    same_records([p for ps in mine for p in ps], ref)


def test_done_rows_full_counts_overflow():
    import jax
    pk = [(c, 7, 4000.0, bytes([c] * 5), 6.0, 0.0) for c in range(3)]
    x = air(pk, 4, 17)
    stage = lora_gw_stages(**dict(SMALL, done_slots=2))[0]
    heads, mine = zip(*(parse_records(b) for b in run_frames((stage, jax.jit(stage.fn)), x)))
    assert sum(h["lora_emitted"] for h in heads) == 2 == sum(len(m) for m in mine)
    assert sum(h["lora_overflow"] for h in heads) == 1


def test_header_that_announces_too_much_is_let_go(small):
    x = air([(0, 7, 3000.0, bytes(60), 8.0, 0.0)], 6, 19)       # 60 > 48
    heads, mine = zip(*(parse_records(b) for b in run_frames(small, x)))
    assert sum(h["lora_synced"] for h in heads) == 1
    assert sum(h["lora_header_ok"] for h in heads) == 0 and not any(mine)


# -- (d) the program's shape -----------------------------------------------------

def test_no_retrace_over_100_frames(small):
    stage, fn = small
    x = air(train(31, 10), 10, 32)
    carry = stage.init_carry(np.complex64)
    for i in range(100):
        k = (i % 10) * FRAME
        carry, _ = fn(carry, x[k:k + FRAME])
    assert fn._cache_size() == 1


def test_emit_args_carry_the_counters_only_while_tracing():
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.telemetry import spans
    from futuresdr_tpu.tpu import TpuKernel
    x = air(train(41, 6), 6, 42)
    called = []
    for tracing in (False, True):
        stages = lora_gw_stages(**SMALL)
        inner = stages[0].counters
        stages[0].counters = lambda frame, inner=inner: called.append(tracing) or inner(frame)
        sink, fg = VectorSink(np.int32), Flowgraph()
        fg.connect(VectorSource(x), TpuKernel(stages, np.complex64, frame_size=FRAME), sink)
        spans.drain()
        spans.enable(tracing)
        try:
            Runtime().run(fg)
        finally:
            spans.enable(False)
        got = np.asarray(sink.items()).reshape(6, -1)
        emits = [e.args for e in spans.drain() if e.name == "emit" and e.args
                 and "lora_emitted" in e.args]
        if not tracing:
            assert emits == [] and called == []
            continue
        assert set(called) == {True}
        names = {"lora_detected", "lora_synced", "lora_header_ok", "lora_emitted",
                 "lora_crc_bad", "lora_in_flight", "lora_symbols", "lora_overflow"}
        assert all(set(a) >= names for a in emits)
        assert sorted(a["lora_symbols"] for a in emits) == \
            sorted(record_counters(b)["lora_symbols"] for b in got)
        assert sum(a["lora_emitted"] for a in emits) > 0


# -- (e) the scan step's window fetch --------------------------------------------

@pytest.mark.parametrize("sf", [7, 8, 9, 10, 11, 12])
def test_window_fetch_equals_dynamic_slice_bit_for_bit(sf):
    """The fetch is a copy: every window of every lane is ``lax.dynamic_slice``'s
    bit for bit, at the published sizes (8 lanes, ``T = 4 S + 40960``): the first
    row, the clamped last one, remainders 0, 1 and 127 of a row of 128 at either
    end, every remainder somewhere, and positions outside, clipped as before."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from futuresdr_tpu.models.lora.rx_stages import _window_fetch
    S, C = 2 << sf, 8
    T = 4 * S + 40960
    rng = np.random.default_rng(sf)
    ext = rng.standard_normal((C, 2 * T), np.float32).view(np.complex64)
    ext[0, :3] = [np.complex64(complex(-0.0, np.inf)), np.nan, 1e-42]   # bits, not values
    fetch = jax.jit(lambda e, p: _window_fetch(e, S)(p))
    old = jax.jit(jax.vmap(lambda row, p: lax.dynamic_slice(row, (p,), (S,))))
    last = T - S                                         # a multiple of 128
    cases = [[0, 1, 127, 128, 129, last, last - 1, last - 127],
             [-1, -(1 << 20), last + 1, T, T + 12345, 1 << 30, 255, 1023],
             rng.integers(0, last + 1, C), rng.integers(0, last + 1, C),
             last - 131 * np.arange(C), 1024 * np.arange(C) + 1023]
    cases += [128 * rng.integers(0, last // 128, C) + 8 * k + np.arange(C) for k in range(16)]
    seen = set()
    for pos in cases:
        pos = jnp.asarray(pos, jnp.int32)
        want = np.asarray(old(ext, jnp.clip(pos, 0, last)))
        got = np.asarray(fetch(ext, pos))
        assert got.dtype == want.dtype and got.shape == (C, S)
        assert np.array_equal(got.view(np.uint64), want.view(np.uint64))
        seen |= {int(p) % 128 for p in np.clip(np.asarray(pos), 0, last)}
    assert seen == set(range(128))


@pytest.fixture(scope="module")
def v5e():
    """One chip of a v5e that is described, not attached (the TPU's compiler
    is loaded by the one worker that runs this file, inside this fixture)."""
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        for k, v in (("TPU_LOG_DIR", "disabled"), ("TPU_ACCELERATOR_TYPE", "v5litepod-4"),
                     ("TPU_WORKER_HOSTNAMES", "localhost"), ("TPU_SKIP_MDS_QUERY", "1")):
            mp.setenv(k, v)
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:                            # no TPU compiler loads here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo.devices[0]


def compiled_for(device, stage, frame):
    """The stage's program as the chip's compiler leaves it (text; nothing runs)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    chip = SingleDeviceSharding(device)
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
    carry = jax.tree_util.tree_map(spec, jax.eval_shape(
        lambda: stage.init_carry(np.complex64)))
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)   # it could not be read back
    compilation_cache.reset_cache()
    try:
        return jax.jit(stage.fn).trace(carry, spec(np.zeros(frame, np.complex64))) \
            .lower(lowering_platforms=("tpu",)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


def computations(text):
    """``{computation: its lines}`` of an optimized HLO module."""
    import re
    comps, comp = {}, None
    for line in text.splitlines():
        head = re.match(r"\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$", line)
        if head:
            comp = head.group(1)
            comps[comp] = []
        elif line.strip() == "}":
            comp = None
        elif comp:
            comps[comp].append(line)
    return comps


def while_bodies(text):
    """``{while: (computation it is in, its body)}`` and ``{computation:
    instructions}`` of an optimized HLO module; parameters, tuple plumbing,
    constants and bitcasts are not counted."""
    import re
    free = ("parameter(", "get-tuple-element(", "tuple(", "constant(", "bitcast(")
    whiles, count = {}, {}
    for comp, lines in computations(text).items():
        count[comp] = 0
        for line in lines:
            instr = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s", line)
            if not instr:
                continue
            body = re.search(r"\swhile\(.*body=%?([\w.\-]+)", line)
            if body:
                whiles[instr.group(1)] = (comp, body.group(1))
            count[comp] += not any(f" {op}" in line for op in free)
    return whiles, count


def called_from(comps, comp):
    """``comp`` and every computation its instructions call, downwards."""
    import re
    seen, todo = set(), [comp]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", line)
    return seen


#: instructions of a scan body of the small gateway as compiled for a v5e: 50, 63
#: and 66 as PR 37 leaves it (49, 49 and 65 at PR 34, whose SF7 and SF8 steps ran
#: three convolutions each and SF9's six, every one with its DFT matrix built
#: inside it, and whose twiddles were a fusion without operands in the step); the
#: parent of PR 34 held 94, 95 and 114 and each ran, besides, two loops of one trip
#: a lane and 6 instructions a trip
STEP_CEILING = 72


def test_compiled_for_v5e_one_while_per_sf_and_a_short_step(v5e, monkeypatch):
    """No loop inside a scan step: the program compiled for the chip (Mosaic
    kernel and MXU DFTs as there) holds one ``while`` per SF, the scan, and a
    step's body stays under the ceiling. A step's DFT is its few-row form
    (``mxu_fft.form``): one convolution a stage, and no table of it is built
    inside the step: no fusion without operands sits in a scan body (XLA sinks
    what is elementwise from an ``iota`` into the loop, where a DFT matrix was
    512^2 cosines and sines a step), and no cosine, sine or exponential but
    those of the step's own rotation of its lanes."""
    import re

    from futuresdr_tpu.models.lora import rx_stages
    from futuresdr_tpu.ops import mxu_fft
    monkeypatch.setattr(rx_stages, "_interpret", lambda: False)
    monkeypatch.setattr(mxu_fft, "_impl", "mxu")
    text = compiled_for(v5e, lora_gw_stages(**SMALL)[0], FRAME)
    whiles, count = while_bodies(text)
    bodies = {body for _, body in whiles.values()}
    sizes = sorted(count[b] for b in bodies)
    print(f"whiles {len(whiles)}, scan bodies of {sizes} instructions")
    assert len(whiles) == len(SFS)
    assert not [w for w, (inside, _) in whiles.items() if inside in bodies]
    assert text.count('custom_call_target="tpu_custom_call"') >= len(SFS)
    assert max(sizes) <= STEP_CEILING
    comps = computations(text)
    stages = []
    for body in bodies:
        inside = [line for c in called_from(comps, body) for line in comps[c]]
        # neither in the body nor inside one of its fusions (the parent's DFT
        # matrices were fusions without operands INSIDE their convolution's fusion)
        assert not [line for line in inside if re.search(r"\sfusion\(\), kind=", line)]
        # the step's own two exp(): its lanes' rotation and the turn by tau, each an
        # exponential, a cosine and a sine (the parent's bodies held 23 and 40)
        assert sum(len(re.findall(r"\s(?:cosine|sine|exponential)\(", line))
                   for line in inside) <= 6
        stages.append(sum(" convolution(" in line for line in inside))
    # SF7's 256 points direct, SF8's 512 and SF9's 1024 four-step: a convolution a stage
    assert sorted(stages) == [1, 2, 2]
    assert [mxu_fft.form(2 << sf, N_CH) for sf in SFS] == \
        ["planes_direct", "planes_four_step", "planes_four_step"]


def test_dfts_through_the_matmul_forms_give_the_default_runs_records(small, monkeypatch):
    """The capture of ``test_blocks_equal_reference...`` with every DFT forced
    through ``ops/mxu_fft``'s matmul forms on the CPU (the few-row forms in the
    scan steps, the many-row ones in ``detect`` and the bank): the record blocks
    hold the packets of the default run (``jnp.fft``), packet for packet, and the
    float fields agree within the limits the reference is held to."""
    import jax

    from futuresdr_tpu.ops import mxu_fft
    sent = train(21, 12, duty=0.4)
    x = air(sent, 12, 121)
    default = run_frames(small, x)
    monkeypatch.setattr(mxu_fft, "_impl", "mxu")
    stage = lora_gw_stages(**SMALL)[0]
    forced = run_frames((stage, jax.jit(stage.fn)), x)
    n = 0
    for a, b in zip(forced, default):
        (head_a, mine_a), (head_b, mine_b) = parse_records(a), parse_records(b)
        assert head_a == head_b
        same_records(mine_a, mine_b)
        n += len(mine_a)
    assert n == len(sent) >= 8
    same_as_reference(forced, x)


def test_record_layout_is_the_references():
    rec = {"channel": 3, "sf": 9, "start": -70000, "end": 123, "cfo_hz": -4321.5,
           "timing": 0.125, "snr_db": 3.5, "share": 0.4375, "length": 5,
           "crc_ok": True, "n_sym": 28, "payload": b"hello"}
    counts = dict(zip(R.COUNTERS, range(1, 9)), emitted=1)
    block = R.build_block([rec], counts, FRAME // 8)
    head, mine = parse_records(block)
    assert mine == [rec] and head == {f"lora_{k}": v for k, v in counts.items()}
    assert R.parse_block(block) == (counts, [rec])
    assert record_counters(np.zeros(2048, np.int32)) == {}


def test_host_path_builds_one_receiver_per_channel_and_sf():
    from futuresdr_tpu.apps.lora_gw import build_flowgraph
    from futuresdr_tpu.blocks import VectorSource
    fg, kernel, tags = build_flowgraph(VectorSource(np.zeros(16, np.complex64)),
                                       use_tpu=False, sfs=(7, 12))
    assert kernel is None and len(tags) == 16
    assert sorted({t.freq_hz for t in tags})[0] == 867.1e6
