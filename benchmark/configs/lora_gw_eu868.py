"""Builder of ``lora_gw_eu868``: a LoRaWAN gateway for the eight EU868 uplink
channels, SF7 to SF12 listened to at once on each: 48 receivers in one device
program per frame (``futuresdr_tpu/models/lora/rx_stages.py``).

The ``stream`` driver's interface, used in a way ``benchmark/README.md`` does
not spell out (as ``wlan_rx_20msps`` did for records; here also for time):

    make_kernel(cfg, rehearse)             -> the TpuKernel block
    make_input(cfg, seed, n_frames, frame) -> the capture, complex64; the
        module KEEPS it (and its schedule), because
    reference(cfg, x, history)             -> ONE RECORD BLOCK of the float64
        receiver for the frame ``x``: a packet lasts up to seventeen frames and
        the driver passes one frame of history, so the module decodes the
        whole capture once (``harness/refs_lora.Gateway``, frame after frame,
        then its first frame once more behind the seam) and finds ``x`` in it
        by its content; ``history is None`` is the capture's first frame on a
        fresh receiver
    judge(cfg, got, want, rehearse)        -> concatenated record blocks,
        parsed and compared packet by packet; when ``got`` is a whole pass of
        the capture (the pre-check) the packets delivered are also held to the
        packets SENT: none twice, none that was not sent, every one that
        stays well above its SF's demodulation floor (``must_deliver``), and
        of all of them no smaller a share than ``delivered_share_min``
    frame_cost(cfg, frame, wire)           -> what a gateway needs per frame

The reference, the transmitter and the record reader are the benchmark's own
(``harness/refs_lora.py``); the program is imported only through ``TpuKernel``
and the stage constructor ``lora_gw_stages``, which is handed the reference's
filter taps.
"""

from __future__ import annotations

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import costs
from harness import refs_lora as R

_STATE = {}                     # the capture this process made, and its decode


def _sizes(cfg: dict, frame: int) -> dict:
    """The deployment's sizes at this frame size: the published ones, or the
    rehearsal's where the frame is the rehearsal's."""
    out = dict(cfg["parameters"])
    if frame == cfg["rehearsal"]["frame_size"]:
        out.update(cfg["rehearsal"])
    out["max_payload"] = {int(k): v for k, v in out["max_payload"].items()}
    return out


def _air_samples(s: dict, sf: int, length: int) -> float:
    """A packet's airtime in wideband samples."""
    return R.packet_chips(sf, length, R.ldro(sf, s["ldro_from_sf"])) \
        * s["n_channels"] * R.SLOT / R.BW


def make_kernel(cfg: dict, rehearse: bool):
    from futuresdr_tpu.models.lora.rx_stages import lora_gw_stages
    from futuresdr_tpu.tpu import TpuKernel

    p = cfg["parameters"]
    frame = cfg["rehearsal"]["frame_size"] if rehearse else p["frame_size"]
    s = _sizes(cfg, frame or cfg["expected_on_chip"]["frame_size"])
    stages = lora_gw_stages(
        n_channels=s["n_channels"], sfs=s["sfs"], max_payload=s["max_payload"],
        ldro_from_sf=s["ldro_from_sf"], done_slots=s["done_slots"],
        chan_taps=R.channelizer_taps(s["n_channels"]), resamp_taps=R.resampler_taps())
    return TpuKernel(stages, np.dtype(p["in_dtype"]), frame_size=frame,
                     frames_in_flight=p["frames_in_flight"], wire=p["wire"])


def schedule(cfg: dict, seed: int, n_frames: int, frame: int) -> list:
    """What is on the air, without the samples: per packet ``(channel, sf,
    first sample (real), length, snr_db, cfo_hz, phase)``, branch by branch."""
    a, s = cfg["assumed"], _sizes(cfg, frame)
    rng = np.random.default_rng(seed)
    n, edge = n_frames * frame, a["edge_samples"]
    out = []
    for c in range(s["n_channels"]):
        for sf in s["sfs"]:
            lo, hi = a["min_payload"], s["max_payload"][sf]
            air = np.mean([_air_samples(s, sf, ln) for ln in range(lo, hi + 1)])
            gap = air * (1 - a["duty"]) / a["duty"]
            t = edge + rng.uniform(0, gap)
            while True:
                length = int(rng.integers(lo, hi + 1))
                dur = _air_samples(s, sf, length)
                if t + dur > n - edge:
                    break
                out.append((c, sf, float(t), length,
                            float(rng.uniform(*a["snr_db_range"])),
                            float(rng.uniform(-a["cfo_max_hz"], a["cfo_max_hz"])),
                            float(rng.uniform(0, 1))))
                t += dur + rng.exponential(gap)
    return out


def worst_sinr_db(cfg: dict, sched: list, frame: int) -> list:
    """Per packet of ``sched``: its power over the noise in 125 kHz plus the
    other packets on its channel (every other SF: their chirps spread over
    its band like noise), at the worst instant of its airtime, in dB."""
    s = _sizes(cfg, frame)
    span = [(p[2], p[2] + _air_samples(s, p[1], p[3])) for p in sched]
    out = []
    for i, p in enumerate(sched):
        a, b = span[i]
        over = [(max(a, span[j][0]), min(b, span[j][1]), 10 ** (q[4] / 10))
                for j, q in enumerate(sched)
                if j != i and q[0] == p[0] and span[j][0] < b and span[j][1] > a]
        edges = sorted({a, b} | {e[0] for e in over} | {e[1] for e in over})
        worst = max((sum(e[2] for e in over if e[0] <= (u + v) / 2 < e[1])
                     for u, v in zip(edges[:-1], edges[1:])), default=0.0)
        out.append(10 * math.log10(10 ** (p[4] / 10) / (1 + worst)))
    return out


def must_deliver(cfg: dict, sched: list, frame: int) -> list:
    """Which packets must each be delivered: those that stay
    ``sinr_margin_db`` above their SF's demodulation floor throughout (nearer
    the floor a loss is a matter of chance: ``delivered_share_min`` bounds
    how many)."""
    g = cfg["guarantees"]
    return [sinr >= g["demod_floor_db"][str(p[1])] + g["sinr_margin_db"]
            for p, sinr in zip(sched, worst_sinr_db(cfg, sched, frame))]


def sent_payloads(cfg: dict, seed: int, n_frames: int, frame: int) -> list:
    """``(channel, sf, PHYPayload)`` of the capture's packets, in schedule order."""
    rng = np.random.default_rng([seed, 1])
    return [(p[0], p[1], rng.integers(0, 256, p[3], dtype=np.uint8).tobytes())
            for p in schedule(cfg, seed, n_frames, frame)]


def make_input(cfg: dict, seed: int, n_frames: int, frame: int) -> np.ndarray:
    s = _sizes(cfg, frame)
    n, n0 = n_frames * frame, cfg["assumed"]["noise_power"]
    rng = np.random.default_rng([seed, 2])
    x = (rng.standard_normal(2 * n, np.float32)
         * np.float32(math.sqrt(n0 / 2))).view(np.complex64)
    sched = schedule(cfg, seed, n_frames, frame)
    sent = sent_payloads(cfg, seed, n_frames, frame)

    # packets of different branches overlap in time: each is made alone, in
    # threads, and added in schedule order
    def make(i):
        c, sf, t0, _, snr, cfo, ph = sched[i]
        i0 = int(math.ceil(t0))
        n_s = int(math.floor(t0 + _air_samples(s, sf, len(sent[i][2])))) - i0 + 1
        buf = np.zeros(n_s, np.complex64)
        R.add_packet(buf, s["n_channels"], c, sf, sent[i][2], t0 - i0, snr, n0, cfo, ph,
                     R.ldro(sf, s["ldro_from_sf"]))
        return i0, buf

    with ThreadPoolExecutor(8) as pool:
        for i0, buf in pool.map(make, range(len(sched))):
            x[i0:i0 + len(buf)] += buf
    _STATE.clear()
    _STATE.update(x=x, frame=frame, sizes=s, sent=sent,
                  must=must_deliver(cfg, sched, frame))
    return x


def decode_capture(x: np.ndarray, frame: int, s: dict) -> list:
    """The float64 receiver over the capture, channels in threads (each
    branch still one at a time, frame after frame), then the first frame
    again behind the seam: ``n_frames + 1`` record blocks."""
    n_frames = len(x) // frame
    order = list(range(n_frames)) + [0]

    def channel(c):
        fe = R.FrontEnd(c, s["n_channels"])
        branches = [R.Branch(c, sf, s["max_payload"][sf], s["ldro_from_sf"])
                    for sf in s["sfs"]]
        out = []
        for k in order:
            y = fe(x[k * frame:(k + 1) * frame].astype(np.complex128))
            out.append([b.frame(y) for b in branches])
        return out

    with ThreadPoolExecutor(s["n_channels"]) as pool:
        per_channel = list(pool.map(channel, range(s["n_channels"])))
    return [R.build_block(*R.merge_frame([rk for ch in per_channel for rk in ch[i]]),
                          frame // 8) for i in range(len(order))]


def _key(x: np.ndarray) -> bytes:
    return np.ascontiguousarray(x[:512]).tobytes()


def reference(cfg: dict, x: np.ndarray, history=None) -> np.ndarray:
    st = _STATE
    if "x" not in st or st["frame"] != len(x):
        raise RuntimeError("lora_gw_eu868.reference: make_input() has not made "
                           "a capture of this frame size in this process")
    if "blocks" not in st:
        frame = st["frame"]
        st["blocks"] = decode_capture(st["x"], frame, st["sizes"])
        st["index"] = {_key(st["x"][k * frame:(k + 1) * frame]): k
                       for k in range(len(st["x"]) // frame)}
    k = st["index"][_key(np.asarray(x))]
    if k == 0 and history is not None:
        k = len(st["blocks"]) - 1
    return st["blocks"][k]


def judge(cfg: dict, got: np.ndarray, want: np.ndarray, rehearse: bool):
    """Every frame's good-CRC packets the same, in the same order, as the
    float64 receiver's: (channel, SF, start, end, length, bytes) equal; CFO,
    timing and the mean peak share within their tolerances; header counts
    consistent; overflow 0. Over a whole pass of the capture the delivered
    packets are packets sent, none twice, all of those well above their floor
    and no smaller a share of all than the guarantee states. CFO and timing pass the bank, the
    resampler and three DFTs; the share passes every DFT of the packet, so it
    is the number that holds the program's precision."""
    c = cfg["correctness"]
    frame = (cfg["rehearsal"] if rehearse else cfg["expected_on_chip"])["frame_size"]
    words = frame // 8
    d = {"frames": 0, "packets": 0, "crc_bad": 0, "overflow": 0, "mismatch": 0,
         "cfo_err_max_hz": 0.0, "timing_err_max": 0.0, "share_err_max_rel": 0.0,
         "sent": None, "owed": None, "delivered": None, "why": None}

    def fail(why):
        d["mismatch"] += 1
        d["why"] = d["why"] or why

    if got.shape != want.shape or len(got) % words or not len(got):
        fail(f"shape {got.shape} against {want.shape}, blocks of {words}")
        return False, d
    delivered = []
    for j in range(len(got) // words):
        head, mine = R.parse_block(got[j * words:(j + 1) * words])
        _, ref = R.parse_block(want[j * words:(j + 1) * words])
        d["frames"] += 1
        if head is None:
            fail(f"block {j}: no header")
            continue
        d["overflow"] += head["overflow"]
        if not (head["emitted"] == len(mine) and min(head.values()) >= 0
                and head["crc_bad"] == sum(not p["crc_ok"] for p in mine)):
            fail(f"block {j}: header {head}")
        good = [p for p in mine if p["crc_ok"]]
        d["crc_bad"] += len(mine) - len(good)
        ref = [p for p in ref if p["crc_ok"]]
        d["packets"] += len(ref)
        delivered += [(p["channel"], p["sf"], p["payload"]) for p in good]
        key = lambda p: (p["channel"], p["sf"], p["start"], p["end"], p["length"],
                         p["payload"])
        if [key(p) for p in good] != [key(p) for p in ref]:
            fail(f"block {j}: {len(good)} good packets against {len(ref)}")
            continue
        for p, r in zip(good, ref):
            d["cfo_err_max_hz"] = max(d["cfo_err_max_hz"], abs(p["cfo_hz"] - r["cfo_hz"]))
            d["timing_err_max"] = max(d["timing_err_max"], abs(p["timing"] - r["timing"]))
            d["share_err_max_rel"] = max(d["share_err_max_rel"],
                                         abs(p["share"] - r["share"]) / r["share"])
    n_capture = len(_STATE["x"]) // frame if "x" in _STATE else -1
    if d["frames"] == n_capture and _STATE.get("frame") == frame:
        sent = _STATE["sent"]
        owed = [p for p, m in zip(sent, _STATE["must"]) if m]
        d["sent"], d["owed"], d["delivered"] = len(sent), len(owed), len(delivered)
        if len(set(delivered)) != len(delivered) or set(delivered) - set(sent) \
                or set(owed) - set(delivered) \
                or len(delivered) < cfg["guarantees"]["delivered_share_min"] * len(sent):
            fail(f"a pass of the capture delivered {len(delivered)} packets of "
                 f"{len(sent)} sent ({len(set(delivered) - set(sent))} never sent, "
                 f"{len(set(owed) - set(delivered))} of {len(owed)} owed missing)")
    ok = not d["mismatch"] and not d["overflow"] \
        and d["cfo_err_max_hz"] <= c["cfo_tolerance_hz"] \
        and d["timing_err_max"] <= c["timing_tolerance_chips"] \
        and d["share_err_max_rel"] <= c["share_tolerance_rel"]
    return bool(ok), d


def needed_flops(n_channels: int, sfs, frame: int, symbols: dict) -> float:
    """Operations a gateway needs for one frame, by the conventions of
    ``harness/costs.py``, from shapes and not from the implementation:

    * the bank: per wideband sample one half-slot rotation (6), 12 real x
      complex taps (4 a tap), and per slot step an 8-point DFT, 5 n log2 n;
    * the resampler: per 250 kHz sample 24 real x complex taps (4 a tap);
    * detection, per (channel, SF): a window of 2^SF chips at a hop of a
      quarter symbol = 4 windows a symbol, each a dechirp (6 a chip), a DFT of
      2^SF points (5 n log2 n: what gr-lora_sdr runs at one sample a chip; the
      second sample a chip this program uses for the fraction of the timing
      is its own choice and not needed work), |X|^2 and a maximum (4 a bin);
    * aligned symbols (``symbols``: SF -> how many in the frame): the same
      per symbol once, plus 6 a chip for the CFO rotation;
    * bit work is not counted.
    """
    per_channel = frame * 5 // (4 * n_channels)      # 250 kHz samples a channel
    ops = frame * (6 + 12 * 4 + 5 * math.log2(n_channels))
    ops += per_channel * n_channels * 24 * 4
    for sf in sfs:
        n = 1 << sf
        per_window = 6 * n + 5 * n * sf + 4 * n
        ops += n_channels * 4 * (per_channel / (2 * n)) * per_window
        ops += symbols.get(sf, 0) * (per_window + 6 * n)
    return float(ops)


def kernel_costs(s: dict, frame: int) -> dict:
    """What the program's two Pallas kernels need per frame, from the static
    shapes (``harness/costs.py``), under the names the per-layer metrics ask
    for (``cost`` in ``layer_metrics/pallas.*.json``):

    * ``pfb``: the bank, one call a frame: ``frame / n_channels`` rows of the
      channelizer's taps (``taps_per_branch`` a branch) and an 8-point DFT;
    * ``window_fetch``: per SF one window of ``OS * 2^SF`` samples a channel
      in every step of the SF's scan, which runs ``ceil(L / S) + 6`` steps
      over the ``L = frame * 5 / (4 * n_channels)`` samples a channel that
      the front end yields a frame."""
    n = s["n_channels"]
    k = -(-len(R.channelizer_taps(n)) // n)
    per_channel = frame * 5 // (4 * n)
    fetch = [costs.window_fetch_cost(n, R.OS << sf, -(-per_channel // (R.OS << sf)) + 6)
             for sf in s["sfs"]]
    return {"pfb": costs.pfb_cost(frame // n, n, k),
            "window_fetch": {key: sum(c[key] for c in fetch) for key in ("flops", "bytes")}}


def frame_cost(cfg: dict, frame: int, wire: str) -> dict:
    """Per frame: the operations above with the mix's mean of aligned symbols
    (the schedule of seed 0 stands for every seed: the draws are the same law)
    and the bytes that must cross HBM: the wire's samples in, the record block
    out; under ``kernels`` what each Pallas kernel needs (``kernel_costs``)."""
    s = _sizes(cfg, frame)
    n_frames = 64
    symbols = {}
    for _, sf, _, length, *_ in schedule(cfg, 0, n_frames, frame):
        de = R.ldro(sf, s["ldro_from_sf"])
        symbols[sf] = symbols.get(sf, 0) + (R.n_data_symbols(sf, length, de) + 12) / n_frames
    return {"flops": needed_flops(s["n_channels"], s["sfs"], frame, symbols),
            "bytes": float(frame * cfg["wire_bytes"][wire] + frame // 8 * 4),
            "kernels": kernel_costs(s, frame)}
