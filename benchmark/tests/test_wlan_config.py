"""``wlan_rx_20msps``: the capture's margins, the benchmark's own transmitter
against the program's, and what ``judge`` accepts and refuses."""

import json
from pathlib import Path

import numpy as np
import pytest

from harness import cells
from harness import refs_wlan as W

BENCH = Path(__file__).resolve().parents[1]
CM = cells.load_module(BENCH / "configs" / "wlan_rx_20msps.py")
CFG = json.loads((BENCH / "configs" / "wlan_rx_20msps.json").read_text())
FRAME = CFG["expected_on_chip"]["frame_size"]


@pytest.mark.parametrize("seed", [1, 2_000_000_011, 3_999_999_979])
def test_float64_reference_recovers_every_packet_sent(seed):
    """The SNR rule's margins are ample: over a whole capture (16 frames of
    262144, about 480 packets) the reference delivers every PSDU sent, once,
    in order, by the frame in which it ends; a large seed works too."""
    x = CM.make_input(CFG, seed, 16, FRAME).reshape(16, FRAME)
    sent = CM.sent_psdus(CFG, seed, 16, FRAME)
    sched = CM.schedule(CFG, seed, 16, FRAME)
    assert 400 <= len(sent) <= 560
    air = sum(W.packet_samples(s[1], s[2]) for s in sched) / x.size
    assert 0.78 <= air <= 0.88
    assert {s[1] for s in sched} == set(range(8))
    got = []
    for j in range(16):
        head, packets = W.parse_block(CM.reference(CFG, x[j], x[j - 1] if j else None))
        assert head["emitted"] == len(packets) and head["overflow"] == 0
        ends = [p["lts_start"] + 128 + W.SYM * (1 + W.n_symbols(p["rate"], p["length"]))
                for p in packets]
        assert all(0 < e <= FRAME for e in ends)       # owned by this frame
        got += [p["psdu"] for p in packets]
    assert got == sent and all(W.fcs_ok(p) for p in got)


def test_own_transmitter_equals_the_programs():
    from futuresdr_tpu.models.wlan import encode_frame
    rng = np.random.default_rng(4)
    for rate, name in enumerate(W.RATE_NAMES):
        psdu = W.mpdu(rng.integers(0, 256, 50 + 31 * rate, dtype=np.uint8).tobytes(), rate)
        mine = W.transmit(psdu, rate, 17 + rate)
        np.testing.assert_allclose(mine, encode_frame(psdu, name, 17 + rate),
                                   atol=1e-6)
        assert len(mine) == W.packet_samples(rate, len(psdu))


def test_judge_accepts_the_reference_and_refuses_each_departure():
    r = CFG["rehearsal"]
    frame = r["frame_size"]
    x = CM.make_input(CFG, 7, 4, frame).reshape(4, frame)
    want = np.concatenate([CM.reference(CFG, x[j], x[j - 1] if j else None)
                           for j in range(4)])
    ok, d = CM.judge(CFG, want.copy(), want, True)
    assert ok and d["packets"] > 8 and d["mismatch"] == 0

    def refused(edit):
        got = want.copy()
        edit(got)
        return not CM.judge(CFG, got, want, True)[0]

    area = W.HEADER_WORDS + W.ENTRY_WORDS * r["lanes"]
    assert refused(lambda g: g.__setitem__(area, g[area] ^ 1))     # a PSDU bit
    assert refused(lambda g: g.__setitem__(5, 1))                  # overflow
    assert refused(lambda g: g.__setitem__(W.HEADER_WORDS, g[W.HEADER_WORDS] + 1))
    cfo = np.array([1e-3], np.float32).view(np.int32)[0]
    assert refused(lambda g: g.__setitem__(W.HEADER_WORDS + 3, cfo))
    assert refused(lambda g: g.__setitem__(0, 0))                  # no header
    assert not CM.judge(CFG, want[:-1], want, True)[0]


def test_frame_cost_counts_the_mix_not_the_padding():
    c = CM.frame_cost(CFG, FRAME, "sc16")
    assert c["bytes"] == FRAME * 4 + FRAME // 2
    # ~150 000 trellis steps a frame at 386 operations each are most of it
    assert 0.6 < 150_000 * 386 / c["flops"] < 0.9
    assert CM.needed_flops(1000, []) == 19_000


def test_no_window_of_the_mix_fills_the_lanes():
    """Lanes and candidate slots are capacities of the receiver (overflow is
    counted and fails ``judge``): over 60 seeds the fullest window (carry +
    frame) of the capture holds at most 3/4 of the shipped lanes. PR 26's
    first lane count, 64, met windows of 66 packets on three seeds of 50."""
    p = CFG["parameters"]
    fullest = 0
    for seed in list(range(50)) + [777, 31337, 2_999_999_999, 3_000_000_019] \
            + list(range(10**9, 10**9 + 6)):
        sched = CM.schedule(CFG, seed, 16, FRAME)
        ends = np.array([s[0] + W.packet_samples(s[1], s[2]) for s in sched])
        starts = np.array([s[0] for s in sched])
        n = 16 * FRAME
        for j in range(16):
            lo, hi = j * FRAME - p["carry_len"], (j + 1) * FRAME
            whole = (starts >= lo) & (ends <= hi)
            if j == 0:                       # the replay: the carry is frame 15's tail
                whole |= starts >= n + lo
            fullest = max(fullest, int(whole.sum()))
    assert 60 <= fullest <= 0.75 * p["lanes"] and p["cand_slots"] >= 2 * p["lanes"]
