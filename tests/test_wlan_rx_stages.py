"""The on-device 802.11a/g receiver (``models/wlan/rx_stages``) against the
float64 reference (``models/wlan/reference``): step by step, byte by byte,
through ``TpuKernel`` in a flowgraph, and through checkpoint/restore.

Tolerances (float32 program against float64 reference, measured on the seeded
capture of ``test_steps_against_reference``, CPU, PR 26): ``H`` 8.1e-7
relative, equalised symbols 2.8e-6 absolute, LLRs 1.1e-5 absolute at a largest
LLR of 4.5. The limits sit 11 to 13 times above those readings. A bfloat16
LLR reads 1.6e-2 there (8 mantissa bits), a hundred times the limit, which the
test asserts; a default-precision matmul on the TPU rounds its operands the
same way (4e-3 relative) and misses ``H`` and the symbols by as much.
"""

import numpy as np
import pytest

from futuresdr_tpu.models.wlan import Mac, coding, encode_frame
from futuresdr_tpu.models.wlan import reference as ref
from futuresdr_tpu.models.wlan.rx_stages import parse_records, wlan_rx_stages
from futuresdr_tpu.ops.stages import Pipeline
from futuresdr_tpu.ops.viterbi import viterbi_blocks, viterbi_core

H_RTOL, EQ_ATOL, LLR_ATOL = 1e-5, 3e-5, 1.5e-4
LLR_MEAN_RTOL = 5e-6           # a record's mean |LLR|, relative
FRAME, CARRY = 16384, 12288
SMALL = dict(carry_len=CARRY, max_psdu=400, cand_slots=32, lanes=16)


def air(packets, n, seed, noise=0.003):
    """``packets``: (position, rate, psdu, cfo, gain). One capture of ``n``
    samples with complex Gaussian noise of deviation ``noise`` per component."""
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)) * noise
    for pos, rate, psdu, cfo, gain in packets:
        s = encode_frame(psdu, rate, int(rng.integers(1, 128))).astype(np.complex128)
        x[pos:pos + len(s)] += gain * s * np.exp(
            1j * (cfo * np.arange(len(s)) + rng.uniform(0, 2 * np.pi)))
    return x.astype(np.complex64)


def psdu_of(rng, length):
    return Mac().frame(bytes(rng.integers(0, 256, length - 28, dtype=np.uint8)))


def train(rates, n, seed, lengths=(28, 300), gap=(320, 2000)):
    """Packets of ``rates`` in turn, one after another, until ``n`` is full."""
    rng = np.random.default_rng(seed)
    out, pos, i = [], 400, 0
    while True:
        rate = rates[i % len(rates)]
        psdu = psdu_of(rng, int(rng.integers(*lengths)))
        n_s = len(encode_frame(psdu, rate))
        if pos + n_s + 400 > n:
            return out
        out.append((pos, rate, psdu, float(rng.uniform(-0.03, 0.03)),
                    10 ** (rng.uniform(0, 6) / 20)))
        pos += n_s + int(rng.integers(*gap))
        i += 1


@pytest.fixture(scope="module")
def small():
    """The receiver at a small size, compiled once: (run, probe)."""
    import jax
    pipe = Pipeline(wlan_rx_stages(**SMALL), np.complex64)
    fn, _ = pipe.compile(FRAME, donate=False)
    return pipe, fn, jax.jit(pipe.stages[0].fn.probe)


def run_frames(pipe, fn, x, frame=FRAME):
    carry, out = pipe.init_carry(), []
    for j in range(len(x) // frame):
        carry, y = fn(carry, x[j * frame:(j + 1) * frame])
        out.append(np.asarray(y))
    return out


def same_as_reference(blocks, x, frame=FRAME, carry_len=CARRY, overflow=0):
    """Every record block against the reference's decode of the same frame;
    returns the PSDUs in the order they were emitted."""
    psdus = []
    for j, block in enumerate(blocks):
        head, got = parse_records(block)
        want, counts = ref.receive_frame(
            x[j * frame:(j + 1) * frame],
            x[max(0, j * frame - carry_len):j * frame], carry_len)
        assert head["wlan_overflow"] <= overflow
        assert [head[f"wlan_{k}"] for k in ("detected", "aligned", "signal_ok",
                                            "emitted")] == \
            [counts[k] for k in ("detected", "aligned", "signal_ok", "emitted")]
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert (g["lts_start"], g["rate"], g["length"], g["psdu"]) == \
                (w.lts_start, w.rate, w.length, w.psdu)
            assert g["seed_ok"] and abs(g["cfo"] - w.cfo) < 2e-6
            assert abs(g["snr_db"] - w.snr_db) < 0.05
            assert abs(g["llr_mean"] - w.llr_mean) < LLR_MEAN_RTOL * w.llr_mean
        psdus += [g["psdu"] for g in got]
    return psdus


# -- (c) the Viterbi core ------------------------------------------------------

@pytest.mark.parametrize("rate", ["1/2", "2/3", "3/4"])
def test_viterbi_core_bit_equals_numpy_ragged(rate):
    """Ragged lanes, one puncturing each. The LLRs are multiples of 1/8, so
    float32 and float64 add them exactly and even ties fall the same way."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    lens = [30, 99, 437, 1000, 24, 0, 613]
    T = max(lens)
    llr = np.zeros((T, 2, len(lens)), np.float32)
    want = []
    for lane, n in enumerate(lens):
        if not n:                                  # an unused lane
            want.append(np.zeros(0, np.uint8))
            continue
        bits = rng.integers(0, 2, n).astype(np.uint8)
        bits[-6:] = 0
        c = coding.puncture(coding.conv_encode(bits), rate)
        soft = np.round(((2.0 * c - 1) * 3 + rng.standard_normal(len(c)) * 2.5) * 8) / 8
        full = coding.depuncture(soft, rate)[:2 * n]
        want.append(ref.viterbi_numpy(full, n))
        llr[:n, :, lane] = full.reshape(n, 2)
    tables = (coding._PREV_S, coding._PREV_B, coding._BM0, coding._BM1)
    got = np.asarray(jax.jit(lambda a, b: viterbi_core(a, b, *tables))(
        jnp.asarray(llr), jnp.asarray(lens, jnp.int32)))
    for lane, n in enumerate(lens):
        np.testing.assert_array_equal(got[:n, lane], want[lane])
        assert not got[n:, lane].any()


def test_blocks_bit_equal_the_uncut_core_on_the_cells_mix():
    """The data trellis cut into pieces of 1024 steps with 128 of overlap
    (the shipped default) against the uncut core, on mother-code LLRs the
    float64 receiver produced from packets drawn as the benchmark cell draws
    them: the three length classes up to 1534 bytes, all eight rates (three
    puncturings), each at the LOW end of its SNR rule (12 dB + 3 dB per
    coded bit), CFO within 100 kHz."""
    import jax
    import jax.numpy as jnp
    from futuresdr_tpu.models.wlan.consts import MCS_TABLE
    rng = np.random.default_rng(26)
    n0, sent, pos = 1e-4, [], 400
    for i in range(24):
        lo, hi = [(28, 128), (129, 600), (1000, 1534)][i % 3]
        rate = ref.RATES[i % 8]
        psdu = psdu_of(rng, int(rng.integers(lo, hi + 1)))
        gain = np.sqrt(10 ** ((12 + 3 * MCS_TABLE[rate].n_bpsc) / 10) * n0 / (52 / 4096))
        sent.append((pos, rate, psdu, float(rng.uniform(-0.0314, 0.0314)), gain))
        pos += len(encode_frame(psdu, rate)) + 320
    x = air(sent, pos + 400, seed=27, noise=np.sqrt(n0 / 2))
    got, _ = ref.receive_window(x, 0, keep_trace=True)
    assert [p.psdu for p in got] == [p[2] for p in sent]
    T = max(p.steps for p in got)
    stream = np.zeros((2, len(got), T), np.float32)
    for i, p in enumerate(got):       # the scrambled pad after the tail too
        m = p.trace["mother"][:2 * T].reshape(-1, 2)
        stream[:, i, :len(m)] = m.T
    steps = jnp.asarray([p.steps for p in got], jnp.int32)
    tables = (coding._PREV_S, coding._PREV_B, coding._BM0, coding._BM1)
    whole = jax.jit(lambda a, b: viterbi_core(
        jnp.transpose(a, (2, 0, 1)), b, *tables))(stream, steps)
    cut = jax.jit(lambda a, b: viterbi_blocks(
        a, b, *tables, n_blocks=256))(stream, steps)
    np.testing.assert_array_equal(np.asarray(cut), np.asarray(whole).T)


# -- (a) against the reference --------------------------------------------------

@pytest.mark.parametrize("rate", ref.RATES)
def test_rate_decodes_byte_equal(small, rate):
    pipe, fn, _ = small
    sent = train([rate], 3 * FRAME, seed=ref.RATES.index(rate))
    x = air(sent, 3 * FRAME, seed=100)
    assert same_as_reference(run_frames(pipe, fn, x), x) == [p[2] for p in sent]


def test_steps_against_reference(small):
    """Candidate and LTS starts equal; H, equalised symbols and LLRs within
    the float32 tolerances of the module docstring."""
    import jax.numpy as jnp
    pipe, _, probe = small
    sent = train(ref.RATES, CARRY + FRAME, seed=11, lengths=(40, 100),
                 gap=(320, 700))
    window = air(sent, CARRY + FRAME, seed=12)
    _, block, t = probe(jnp.stack([jnp.real(window[:CARRY]), jnp.imag(window[:CARRY])]),
                        jnp.asarray(window[CARRY:]))
    t = {k: np.asarray(v) for k, v in t.items()}
    want, counts = ref.receive_window(window, 0, keep_trace=True)
    assert [(int(s), int(l)) for s, l, c in zip(t["start"], t["lts"], t["cand"]) if c] \
        == counts["candidates"]
    assert {w.rate for w in want} == set(range(8))
    from futuresdr_tpu.models.wlan.consts import DATA_CARRIERS, MCS_TABLE
    worst = {"H": 0.0, "eq": 0.0, "llr": 0.0, "llr_bf16": 0.0}
    for w in want:
        lane = next(i for i in range(len(t["slot"]))
                    if t["lane_ok"][i] and t["lts"][t["slot"][i]] == w.lts_start)
        slot = t["slot"][lane]
        H = (t["Hr"][slot] + 1j * t["Hi"][slot])[:48]
        Hw = w.trace["H"][DATA_CARRIERS % 64]
        worst["H"] = max(worst["H"], np.max(np.abs(H - Hw) / np.abs(Hw)))
        rows = slice(t["lane_to"][lane] - len(w.trace["eq"]), t["lane_to"][lane])
        eq = t["eq_r"][rows] + 1j * t["eq_i"][rows]
        worst["eq"] = max(worst["eq"], np.max(np.abs(eq - w.trace["eq"])))
        nb = MCS_TABLE[ref.RATES[w.rate]].n_bpsc
        llr = t["llr"][rows].reshape(-1, 6, 48)[:, :nb].transpose(0, 2, 1).reshape(-1)
        worst["llr"] = max(worst["llr"], np.max(np.abs(llr - w.trace["llrs"])))
        low = np.asarray(jnp.asarray(llr).astype(jnp.bfloat16).astype(jnp.float32))
        worst["llr_bf16"] = max(worst["llr_bf16"],
                                np.max(np.abs(low - w.trace["llrs"])))
    assert worst["H"] < H_RTOL and worst["eq"] < EQ_ATOL, worst
    assert worst["llr"] < LLR_ATOL < 0.1 * worst["llr_bf16"], worst
    assert [p["psdu"] for p in parse_records(np.asarray(block))[1]] == \
        [w.psdu for w in want if w.end > CARRY]


def test_length_1_and_4095_across_frames():
    """LENGTH 1 and 4095 at 6 Mbit/s, the shipped carry: the long packet lies
    over four frames and is emitted once, by the frame in which it ends."""
    frame = 32768
    pipe = Pipeline(wlan_rx_stages(cand_slots=8, lanes=4), np.complex64)
    fn, _ = pipe.compile(frame, donate=False)
    rng = np.random.default_rng(5)
    sent = [(700, "bpsk_1_2", bytes([0xA5]), 0.01, 1.0),
            (2000, "bpsk_1_2", bytes(rng.integers(0, 256, 4095, dtype=np.uint8)),
             -0.02, 1.5)]
    x = air(sent, 5 * frame, seed=6)
    blocks = run_frames(pipe, fn, x, frame)
    assert same_as_reference(blocks, x, frame, ref.CARRY_LEN) == [p[2] for p in sent]
    assert [parse_records(b)[0]["wlan_emitted"] for b in blocks] == [1, 0, 0, 1, 0]
    assert parse_records(blocks[3])[1][0]["lts_start"] < -2 * frame


def test_straddling_and_back_to_back_at_sifs(small):
    pipe, fn, _ = small
    rng = np.random.default_rng(21)
    a, b, c = (psdu_of(rng, n) for n in (200, 90, 120))
    n_a = len(encode_frame(a, "qpsk_1_2"))
    n_b = len(encode_frame(b, "qam16_3_4"))
    pos_a = FRAME - n_a // 2                      # ends in the second frame
    sent = [(pos_a, "qpsk_1_2", a, 0.02, 1.0),
            (pos_a + n_a + 320, "qam16_3_4", b, -0.01, 2.0),
            (pos_a + n_a + 320 + n_b + 320, "qam64_2_3", c, 0.005, 1.2)]
    x = air(sent, 3 * FRAME, seed=22)
    blocks = run_frames(pipe, fn, x)
    assert same_as_reference(blocks, x) == [a, b, c]
    assert [parse_records(blk)[0]["wlan_emitted"] for blk in blocks] == [0, 3, 0]


def test_no_packet_and_false_alarm(small):
    """A preamble with noise where SIGNAL should be: detected and aligned (by
    the window of its frame, and by the next, whose carry holds it), never
    emitted with a good FCS. Noise alone: nothing detected."""
    pipe, fn, _ = small
    from futuresdr_tpu.models.wlan import ofdm, payload_from_mpdu
    x = air([], 3 * FRAME, seed=31, noise=0.05)
    x[5000:5320] += ofdm.make_preamble()
    blocks = run_frames(pipe, fn, x)
    # the garbage SIGNAL field may read a LENGTH above this small program's
    # max_psdu (not above the shipped 4095): counted, never decoded
    same_as_reference(blocks, x, overflow=1)
    heads = [parse_records(b) for b in blocks]
    assert [h[0]["wlan_detected"] for h in heads] == [1, 1, 0]
    assert heads[0][0]["wlan_aligned"] == 1 and heads[2][0]["wlan_emitted"] == 0
    assert not any(payload_from_mpdu(p["psdu"]) for h in heads for p in h[1])


def test_more_packets_than_lanes_is_counted(small):
    """31 short packets in one window of 16 lanes: the first 16 come out
    right, the rest are counted as overflow, nothing is corrupted."""
    pipe, fn, _ = small
    sent = train(["qam64_3_4"], FRAME, seed=41, lengths=(28, 40), gap=(320, 330))
    assert len(sent) > SMALL["lanes"]
    x = air(sent, FRAME, seed=42)
    head, got = parse_records(run_frames(pipe, fn, x)[0])
    assert head["wlan_emitted"] == SMALL["lanes"] == len(got)
    assert head["wlan_overflow"] == len(sent) - SMALL["lanes"]
    assert [g["psdu"] for g in got] == [p[2] for p in sent[:SMALL["lanes"]]]


# -- (b) through TpuKernel, and the app -----------------------------------------

def test_kernel_in_flowgraph_equals_pipeline_and_counts_reach_emit(small):
    from futuresdr_tpu import Flowgraph, Runtime
    from futuresdr_tpu.blocks import VectorSink, VectorSource
    from futuresdr_tpu.telemetry import spans
    from futuresdr_tpu.tpu import TpuKernel
    pipe, fn, _ = small
    sent = train(ref.RATES, 4 * FRAME, seed=51)
    x = air(sent, 4 * FRAME, seed=52)
    kernel = TpuKernel(wlan_rx_stages(**SMALL), np.complex64, frame_size=FRAME)
    assert kernel.out_frame == FRAME // 8 and kernel.pipeline.out_dtype == np.int32
    sink = VectorSink(np.int32)
    fg = Flowgraph()
    fg.connect(VectorSource(x), kernel, sink)
    spans.drain()
    spans.enable(True)
    try:
        Runtime().run(fg)
    finally:
        spans.enable(False)
    got = np.asarray(sink.items()).reshape(4, -1)
    want = run_frames(pipe, fn, x)
    np.testing.assert_array_equal(got, np.stack(want))
    psdus = [p["psdu"] for b in got for p in parse_records(b)[1]]
    assert psdus == [p[2] for p in sent]                 # each once, in order
    emits = [e.args for e in spans.drain() if e.name == "emit" and e.args
             and "wlan_emitted" in e.args]
    assert sorted(a["wlan_emitted"] for a in emits) == \
        sorted(parse_records(b)[0]["wlan_emitted"] for b in got)
    assert all(a["wlan_overflow"] == 0 and a["wlan_steps"] > 0
               and a["wlan_pieces"] >= a["wlan_emitted"] for a in emits)


def test_app_posts_payloads_on_rx():
    from futuresdr_tpu import Runtime
    from futuresdr_tpu.apps.wlan_rx import build_flowgraph
    from futuresdr_tpu.blocks import VectorSource
    from futuresdr_tpu.models.wlan import payload_from_mpdu
    sent = train(["qpsk_3_4", "bpsk_1_2"], 3 * FRAME, seed=61)
    x = air(sent, 3 * FRAME, seed=62)
    fg, kernel, rx = build_flowgraph(VectorSource(x), frame_size=FRAME, **SMALL)
    Runtime().run(fg)
    assert rx.frames == [payload_from_mpdu(p[2]) for p in sent]
    assert rx.extra_metrics() == {"frames": 3, "psdus": len(sent), "fcs_bad": 0,
                                  "overflow": 0}
    assert kernel.extra_metrics()["frames_dispatched"] == 3


# -- (d) the carry through checkpoint and restore --------------------------------

def test_carry_checkpoint_restore(small):
    pipe, fn, _ = small
    sent = train(ref.RATES, 4 * FRAME, seed=71)
    x = air(sent, 4 * FRAME, seed=72)
    whole = run_frames(pipe, fn, x)
    carry = pipe.init_carry()
    for j in range(2):
        carry, _ = fn(carry, x[j * FRAME:(j + 1) * FRAME])
    fins, treedef = pipe.snapshot_carry(carry)
    leaves = [f() for f in fins]
    assert pipe.carry_matches(leaves, treedef, pipe.init_carry())
    assert leaves[0].shape == (2, CARRY) and leaves[0].dtype == np.float32
    carry = pipe.restore_carry(leaves, treedef)
    for j in (2, 3):
        carry, y = fn(carry, x[j * FRAME:(j + 1) * FRAME])
        np.testing.assert_array_equal(np.asarray(y), whole[j])
