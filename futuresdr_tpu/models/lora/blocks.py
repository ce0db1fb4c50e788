"""Streaming LoRa blocks wrapping the frame-level PHY (reference `examples/lora/src`
block chain: Modulator | FrameSync → FftDemod → GrayMapping → Deinterleaver →
HammingDecoder → HeaderDecoder → Decoder — collapsed into TX/RX blocks batched per frame)."""

from __future__ import annotations

from collections import deque
from typing import Deque, Optional

import numpy as np

from ...runtime.kernel import Kernel, message_handler
from ...types import Pmt
from . import phy
from .phy import LoraParams

__all__ = ["LoraTransmitter", "LoraReceiver", "LoraGatewayRecords"]


class LoraTransmitter(Kernel):
    """Message port ``tx`` (Blob) → chirp baseband stream with inter-frame gaps."""

    def __init__(self, params: LoraParams = LoraParams(), gap_symbols: int = 4):
        super().__init__()
        self.params = params
        self.gap = gap_symbols * params.n
        self._pending: Deque[np.ndarray] = deque()
        self._current: Optional[np.ndarray] = None
        self._eos = False
        self.output = self.add_stream_output("out", np.complex64)

    @message_handler(name="tx")
    async def tx_handler(self, io, mio, meta, p: Pmt) -> Pmt:
        if p.is_finished():
            self._eos = True
            io.call_again = True
            return Pmt.ok()
        try:
            payload = p.to_blob()
        except Exception:
            return Pmt.invalid_value()
        frame = phy.modulate_frame(payload, self.params)
        self._pending.append(np.concatenate([frame, np.zeros(self.gap, np.complex64)]))
        io.call_again = True
        return Pmt.ok()

    async def work(self, io, mio, meta):
        out = self.output.slice()
        produced = 0
        while produced < len(out):
            if self._current is None:
                if not self._pending:
                    break
                self._current = self._pending.popleft()
            k = min(len(out) - produced, len(self._current))
            out[produced:produced + k] = self._current[:k]
            produced += k
            self._current = self._current[k:] if k < len(self._current) else None
        if produced:
            self.output.produce(produced)
        if self._eos and self._current is None and not self._pending:
            io.finished = True
        elif produced and (self._current is not None or self._pending):
            io.call_again = True


class LoraReceiver(Kernel):
    """Chirp stream → decoded payload messages on ``rx`` (+ ``crc_ok`` flag in a map)."""

    def __init__(self, params: LoraParams = LoraParams(), max_payload: int = 256,
                 implicit_payload_len: Optional[int] = None):
        super().__init__()
        self.params = params
        # implicit-header frames carry no length field — the receiver must be
        # told (decoder.rs:36); required iff params.implicit_header
        self.implicit_payload_len = implicit_payload_len
        if params.implicit_header and (implicit_payload_len is None
                                       or implicit_payload_len < 0):
            raise ValueError("LoraReceiver with implicit_header params needs "
                             "implicit_payload_len >= 0")
        n = params.n
        # worst-case frame length in samples, for the inter-window overlap;
        # ldro payload blocks carry only sf-2 nibbles per column
        max_payload = max(max_payload, implicit_payload_len or 0)
        sf_app = params.sf - 2 if params.ldro_on else params.sf
        n_sym = 8 + (4 + params.cr) * (2 * (max_payload + 2) // sf_app + 2)
        self.OVERLAP = (params.n_preamble + 5 + params.n_null + n_sym) * n
        self.frames = []
        self.crc_flags = []
        self._tail = np.zeros(0, np.complex64)
        self._tail_abs = 0
        self._seen = set()
        self.input = self.add_stream_input("in", np.complex64, min_items=4 * n)
        self.add_message_output("rx")

    async def work(self, io, mio, meta):
        inp = self.input.slice()
        n = len(inp)
        if n == 0:
            if self.input.finished():
                io.finished = True
            return
        buf = np.concatenate([self._tail, inp[:n]])
        base = self._tail_abs
        for start in phy.detect_frames(buf, self.params):
            abs_start = base + start
            key = abs_start // (self.params.n // 2)   # quantized dedup key
            if key in self._seen:
                continue
            r = phy.demodulate_frame(buf, start, self.params,
                                     n_payload=self.implicit_payload_len)
            if r is None:
                continue
            payload, crc_ok, hdr = r
            self._seen.add(key)
            self.frames.append(payload)
            self.crc_flags.append(crc_ok)
            mio.post("rx", Pmt.map({"payload": Pmt.blob(payload),
                                    "crc_ok": Pmt.bool_(crc_ok)}))
        keep = min(len(buf), self.OVERLAP)
        self._tail = buf[len(buf) - keep:].copy()
        self._tail_abs = base + len(buf) - keep
        self._seen = {k for k in self._seen
                      if k * (self.params.n // 2) >= self._tail_abs - self.OVERLAP}
        self.input.consume(n)
        if self.input.finished() and self.input.available() == 0:
            io.finished = True


class LoraGatewayRecords(Kernel):
    """Record blocks of ``rx_stages.lora_gw_stages`` (one per device frame,
    ``block_words`` int32 each) → ``rx`` messages, the ones a
    ``LoraReceiver`` + ``multichannel.ChannelTag`` pair posts per channel today
    (``payload``, ``crc_ok``, ``freq``) with the branch's ``sf`` beside them:
    the host end of the on-device gateway. Keeps its totals as metrics for the
    REST plane."""

    def __init__(self, block_words: int, channels_hz=None):
        super().__init__()
        from .rx_stages import parse_records    # jax: not at package import
        self._parse = parse_records
        self.block_words = int(block_words)
        self.channels_hz = None if channels_hz is None else list(channels_hz)
        self.frames = []           # payloads with a good CRC, in order of ending
        self.packets = []          # every record entry parsed, CRC good or not
        self.totals = {"frames": 0, "packets": 0, "crc_bad": 0, "overflow": 0}
        self.input = self.add_stream_input("in", np.int32,
                                           min_items=self.block_words)
        self.add_message_output("rx")

    def extra_metrics(self) -> dict:
        return dict(self.totals)

    async def work(self, io, mio, meta):
        inp = self.input.slice()
        n = len(inp) // self.block_words
        for i in range(n):
            head, packets = self._parse(
                inp[i * self.block_words:(i + 1) * self.block_words])
            self.totals["frames"] += 1
            self.totals["overflow"] += head.get("lora_overflow", 0)
            for pkt in packets:
                self.packets.append(pkt)
                if not pkt["crc_ok"]:
                    self.totals["crc_bad"] += 1
                    continue
                self.totals["packets"] += 1
                self.frames.append(pkt["payload"])
                d = {"payload": Pmt.blob(pkt["payload"]), "crc_ok": Pmt.bool_(True),
                     "sf": Pmt.f64(pkt["sf"]), "channel": Pmt.f64(pkt["channel"])}
                if self.channels_hz is not None:
                    d["freq"] = Pmt.f64(self.channels_hz[pkt["channel"]])
                mio.post("rx", Pmt.map(d))
        if n:
            self.input.consume(n * self.block_words)
        if self.input.finished() and \
                self.input.available() < self.block_words:
            # what is left is the cut block of a last partial frame
            self.input.consume(self.input.available())
            io.finished = True
