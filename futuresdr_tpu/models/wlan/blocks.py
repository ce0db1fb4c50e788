"""Streaming WLAN blocks wrapping the frame-level PHY.

Reference: the WLAN example wires ~8 blocks (`examples/wlan/src/bin/loopback.rs:30-123`);
here the TX is one message→stream block and the RX one stream→message block around the
batched PHY functions — the per-frame computation is a single fused program (TPU-first),
while the actor runtime still provides streaming, backpressure, and the message plane.
"""

from __future__ import annotations

from collections import deque
from typing import Deque, Optional

import numpy as np

from ...runtime.kernel import Kernel, message_handler
from ...types import Pmt
from . import phy
from .mac import Mac

__all__ = ["WlanEncoder", "WlanDecoder", "WlanRecords"]


class WlanEncoder(Kernel):
    """Message port ``tx`` (Blob payload) → baseband sample stream with inter-frame
    gap (the reference's Mac → Encoder → Mapper → Prefix path)."""

    def __init__(self, mcs: str = "qpsk_1_2", gap_samples: int = 500,
                 use_mac: bool = True):
        super().__init__()
        self.mcs = mcs
        self.gap = gap_samples
        self.mac = Mac() if use_mac else None
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
        psdu = self.mac.frame(payload) if self.mac else payload
        frame = phy.encode_frame(psdu, self.mcs)
        burst = np.concatenate([frame, np.zeros(self.gap, np.complex64)])
        self._pending.append(burst)
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


class WlanDecoder(Kernel):
    """Baseband stream → decoded payload messages on port ``rx`` (the reference's
    SyncShort → SyncLong → FFT → FrameEqualizer → Decoder path, batched)."""

    #: sample overlap kept between work windows so frames spanning the boundary survive
    OVERLAP = 4096

    def __init__(self, use_mac: bool = True, chunk: int = 1 << 16):
        super().__init__()
        self.mac = Mac() if use_mac else None
        self.chunk = chunk
        self.frames = []           # decoded PSDUs (or payloads with MAC)
        self._tail = np.zeros(0, np.complex64)
        self._tail_abs = 0         # absolute index of tail[0]
        self._seen_abs = set()     # absolute lts starts already decoded
        self.input = self.add_stream_input("in", np.complex64, min_items=1024)
        self.add_message_output("rx")

    async def work(self, io, mio, meta):
        inp = self.input.slice()
        n = len(inp)
        if n < self.chunk and not self.input.finished():
            return          # wait for a fuller window (upstream produce re-arms us)
        if n == 0:
            if self.input.finished():
                io.finished = True
            return
        buf = np.concatenate([self._tail, inp[:n]])
        base = self._tail_abs
        # burst-batched decode: every frame in the window shares one batched Viterbi
        # scan when a jax backend is up; falls back to per-frame numpy otherwise
        for frame in phy.decode_stream_batch(buf):
            abs_lts = base + frame.start
            if abs_lts in self._seen_abs:
                continue
            self._seen_abs.add(abs_lts)
            psdu = frame.psdu
            if self.mac:
                payload = self.mac.deframe(psdu)
                if payload is None:
                    continue
                self.frames.append(payload)
                mio.post("rx", Pmt.blob(payload))
            else:
                self.frames.append(psdu)
                mio.post("rx", Pmt.blob(psdu))
        keep = min(len(buf), self.OVERLAP)
        self._tail = buf[len(buf) - keep:].copy()
        self._tail_abs = base + len(buf) - keep
        self._seen_abs = {a for a in self._seen_abs if a >= self._tail_abs - self.OVERLAP}
        self.input.consume(n)
        if self.input.finished() and self.input.available() == 0:
            io.finished = True


class WlanRecords(Kernel):
    """Record blocks of ``rx_stages`` (one per device frame, ``block_words``
    int32 each) → payload messages on ``rx``: the host end of the on-device
    receiver. Checks each PSDU's FCS (``mac.payload_from_mpdu``) and keeps
    the totals it sees anyway as its metrics for the REST plane."""

    def __init__(self, block_words: int, use_mac: bool = True):
        super().__init__()
        from .rx_stages import parse_records    # jax: not at package import
        self._parse = parse_records
        self.block_words = int(block_words)
        self.mac = Mac() if use_mac else None
        self.frames = []           # payloads (PSDUs without MAC), in order
        self.packets = []          # every record entry parsed, FCS good or not
        self.totals = {"frames": 0, "psdus": 0, "fcs_bad": 0, "overflow": 0}
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
            self.totals["overflow"] += head.get("wlan_overflow", 0)
            for pkt in packets:
                self.packets.append(pkt)
                payload = self.mac.deframe(pkt["psdu"]) if self.mac \
                    else pkt["psdu"]
                if payload is None:
                    self.totals["fcs_bad"] += 1
                    continue
                self.totals["psdus"] += 1
                self.frames.append(payload)
                mio.post("rx", Pmt.blob(payload))
        if n:
            self.input.consume(n * self.block_words)
        if self.input.finished() and \
                self.input.available() < self.block_words:
            # what is left is the cut block of a last partial frame
            self.input.consume(self.input.available())
            io.finished = True
