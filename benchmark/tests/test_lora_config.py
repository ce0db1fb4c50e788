"""``lora_gw_eu868``: what the float64 receiver recovers of a capture at the
published shapes, the benchmark's own transmitter against the program's, how
``reference()`` is served from one frame, and what ``judge`` accepts and
refuses."""

import json
from pathlib import Path

import numpy as np
import pytest

from harness import cells
from harness import refs_lora as R

BENCH = Path(__file__).resolve().parents[1]
CM = cells.load_module(BENCH / "configs" / "lora_gw_eu868.py")
CFG = json.loads((BENCH / "configs" / "lora_gw_eu868.json").read_text())
FRAME = CFG["expected_on_chip"]["frame_size"]
SMALL = CFG["rehearsal"]["frame_size"]


@pytest.mark.parametrize("seed", [1, 77, 4_000_000_019])
def test_float64_reference_recovers_every_packet_the_guarantee_covers(seed):
    """8 channels x SF7 ... SF12, a whole 64-frame capture: what ``judge``
    asks of a pass: every packet that ``must_deliver`` names (``sinr_margin_db``
    above its SF's demodulation floor: 3 in 4 and more) and no smaller a
    share of all than ``delivered_share_min`` is delivered once with a good
    CRC in the frame in which it ends, nothing is invented, and what is lost
    lies under the floor + 0.5 dB on these seeds (4000000019 loses three SF7
    packets, one 0.11 dB above it)."""
    x = CM.make_input(CFG, seed, 64, FRAME)
    sched = CM.schedule(CFG, seed, 64, FRAME)
    sent = CM.sent_payloads(CFG, seed, 64, FRAME)
    assert 180 <= len(sent) <= 250 and {p[1] for p in sched} == set(range(7, 13))
    assert {p[0] for p in sched} == set(range(8))
    s = CM._sizes(CFG, FRAME)
    air = sum(R.packet_chips(p[1], p[3], R.ldro(p[1], 11)) for p in sched) * 12.8 / len(x)
    assert 8.0 <= air <= 11.5                    # branches on the air at an instant
    assert all(13 <= p[3] <= s["max_payload"][p[1]] for p in sched)
    blocks = CM.decode_capture(x, FRAME, s)
    got = []
    for block in blocks[:64]:
        head, records = R.parse_block(block)
        assert head["emitted"] == len(records) and head["overflow"] == 0
        assert all(0 < r["end"] <= FRAME * 5 // 32 + (2 << r["sf"]) for r in records)
        got += [(r["channel"], r["sf"], r["payload"]) for r in records if r["crc_ok"]]
    assert len(set(got)) == len(got) and set(got) <= set(sent)
    owed = [p for p, m in zip(sent, CM.must_deliver(CFG, sched, FRAME)) if m]
    assert set(owed) <= set(got) and len(owed) >= 0.75 * len(sent)
    assert len(got) >= CFG["guarantees"]["delivered_share_min"] * len(sent)
    floor = CFG["guarantees"]["demod_floor_db"]
    assert all(v < floor[str(q[1])] + 0.5 for p, q, v in
               zip(sent, sched, CM.worst_sinr_db(CFG, sched, FRAME)) if p not in got)
    assert any(p[1] == 12 and p[3] >= 40 for p in sched)      # one outlives 12 frames
    # the first frame behind the seam is the first frame of a fresh receiver
    assert R.parse_block(blocks[64])[1] == R.parse_block(blocks[0])[1]


def test_own_transmitter_equals_the_programs():
    from futuresdr_tpu.models.lora import LoraParams, coding, encode_payload_symbols
    rng = np.random.default_rng(4)
    for sf in range(7, 13):
        payload = rng.integers(0, 256, 13 + 7 * sf, dtype=np.uint8).tobytes()[:64]
        de = sf >= 11
        mine = R.symbols_of(payload, sf, de)
        theirs = encode_payload_symbols(payload, LoraParams(sf=sf, cr=1, ldro=de))
        assert mine == [int(v) for v in theirs]
        assert len(mine) == R.n_data_symbols(sf, len(payload), de)
        assert R.decode_packet(mine, sf, de) == (payload, True)
    assert R.crc16(b"123456789") == coding.crc16(b"123456789") == 0x31C3
    t = np.arange(128.0)
    np.testing.assert_allclose(
        R.modulate([5], 7, t + 12.25 * 128)[:128],
        np.exp(2j * np.pi * (t * t / 256 + t * (5 / 128 - 0.5))), atol=1e-9)


def test_reference_is_found_by_content_and_judge_refuses_each_departure():
    x = CM.make_input(CFG, 7, 64, SMALL).reshape(64, SMALL)
    want = np.concatenate([CM.reference(CFG, x[j], x[j - 1] if j else None)
                           for j in range(64)])
    ok, d = CM.judge(CFG, want.copy(), want, True)
    assert ok and d["packets"] > 8 and d["sent"] == d["delivered"] >= d["owed"] > 8
    words = SMALL // 8
    # a frame of a later pass is found again; the first one behind the seam too
    again = CM.reference(CFG, x[5].copy(), x[4])
    assert np.array_equal(again, want[5 * words:6 * words])
    seam = CM.reference(CFG, x[0], x[63])
    assert R.parse_block(seam)[1] == R.parse_block(want[:words])[1]
    with pytest.raises(KeyError):
        CM.reference(CFG, x[5] + 1, x[4])

    first = next(j for j in range(64) if want[j * words + 4])      # a block with an entry
    e = first * words + R.HEADER_WORDS

    def refused(edit, n_blocks=64):
        got = want[:n_blocks * words].copy()
        edit(got)
        return not CM.judge(CFG, got, want[:n_blocks * words], True)[0]

    assert refused(lambda g: g.__setitem__(e + 16, g[e + 16] ^ 1))     # a payload bit
    assert refused(lambda g: g.__setitem__(first * words + 8, 1))      # overflow
    assert refused(lambda g: g.__setitem__(e + 2, g[e + 2] + 1))       # start
    cfo = np.array([1e4], np.float32).view(np.int32)[0]
    assert refused(lambda g: g.__setitem__(e + 4, cfo))
    share = (want[e + 7:e + 8].view(np.float32) * np.float32(1.01)).view(np.int32)[0]
    assert refused(lambda g: g.__setitem__(e + 7, share))
    assert refused(lambda g: g.__setitem__(0, 0))                      # no header
    # a packet lost from a whole pass (its entry's CRC verdict cleared on both
    # sides, so the blocks still agree) is caught by what was SENT
    lost = want.copy()
    lost[e + 9] = 0
    lost[first * words + 5] += 1
    assert not CM.judge(CFG, lost.copy(), lost, True)[0]
    assert not CM.judge(CFG, want[:-1], want, True)[0]


def test_frame_cost_counts_shapes_not_the_implementation():
    c = CM.frame_cost(CFG, FRAME, "sc16")
    assert c["bytes"] == FRAME * 4 + FRAME // 2
    # detection alone: 48 branches x 4 windows a symbol x (10 + 5 SF) a chip
    detect = sum(8 * 4 * (40960 / (2 << sf)) * (1 << sf) * (10 + 5 * sf)
                 for sf in range(7, 13))
    assert detect < c["flops"] < 2 * detect
    # 1.47 us of operations beside 1.44 us of bytes: neither bound is near the program
    assert 1e-6 < c["flops"] / 197e12 < 2e-6 and 1e-6 < c["bytes"] / 819e9 < 2e-6


def test_scope_map_takes_in_while_bodies():
    """``tools/lora_scopes.opmap``: a ``while`` body whose parameter is a tuple
    is a computation like any other, and an instruction of it that carries no
    metadata has the scope of the ``while`` that runs it."""
    import sys
    sys.path.insert(0, str(BENCH / "tools"))
    import lora_scopes
    import scope_times

    text = """HloModule jit_run_packed
%body.1 (wide.param: (s32[]{:T(128)}, f32[8,256]{1,0:T(8,128)})) -> (s32[]{:T(128)}, f32[8,256]{1,0:T(8,128)}) {
  %wide.param = (s32[]{:T(128)}, f32[8,256]{1,0:T(8,128)}) parameter(0)
  %get-tuple-element.1 = f32[8,256]{1,0:T(8,128)} get-tuple-element(%wide.param), index=1
  %dynamic-slice.7 = f32[1,256]{1,0:T(1,128)} dynamic-slice(%get-tuple-element.1), dynamic_slice_sizes={1,256}
  %fusion.9 = f32[8,256]{1,0:T(8,128)} fusion(%dynamic-slice.7), kind=kLoop, calls=%fused.9, metadata={op_name="jit(run)/lora_gw/while/body/sync/add"}
}
ENTRY %main.3 (p: f32[8,256]) -> f32[8,256] {
  %p = f32[8,256]{1,0} parameter(0)
  %while.5 = (s32[]{:T(128)}, f32[8,256]{1,0:T(8,128)}) while(%p), condition=%cond.1, body=%body.1, metadata={op_name="jit(run)/lora_gw/while/body/closed_call/demod/vmap()/gather"}
}
"""
    scopes = set(lora_scopes.SCOPES)
    assert "dynamic-slice.7" not in scope_times.opmap_from_hlo(text, scopes)
    ops = lora_scopes.opmap(text, scopes)
    assert ops["fusion.9"] == "sync" and ops["while.5"] == "demod"
    assert ops["dynamic-slice.7"] == "demod" and ops["p"] == scope_times.OTHER
