"""What every H2D start of wire parts was GIVEN, per dispatch-group sequence
number: the replay tests of ``test_uplink.py`` and ``test_policies.py`` hold
a re-shipped group to the bytes AND the dtype of its first attempt (the
coalesced uplink ships ``uint32`` words; a replay that shipped the same bytes
as ``uint8`` would miss the compiled program's signature)."""

import numpy as np

from futuresdr_tpu.ops import xfer


class ShipLog:
    def __init__(self, monkeypatch):
        self.ships = {}          # seq -> [((dtype, shape, bytes), ...), ...]
        real = xfer.start_device_transfer_parts

        def spy(parts, device=None, seq=None, group=None):
            if seq is not None:          # a dispatch group, not a parameter
                self.ships.setdefault(seq, []).append(tuple(
                    (np.asarray(p).dtype, np.asarray(p).shape,
                     np.asarray(p).tobytes()) for p in parts))
            return real(parts, device, seq, group)

        monkeypatch.setattr(xfer, "start_device_transfer_parts", spy)

    def reshipped(self) -> dict:
        """``seq -> attempts`` of every group that crossed more than once."""
        return {s: a for s, a in self.ships.items() if len(a) > 1}

    def assert_reships_identical(self, dtype=np.uint32) -> int:
        """Every re-shipped group is ONE part of ``dtype``, the same shape
        and bytes on every attempt; returns how many groups re-shipped."""
        again = self.reshipped()
        for seq, attempts in again.items():
            first = attempts[0]
            assert len(first) == 1 and first[0][0] == dtype, (seq, first[0][:2])
            for a in attempts[1:]:
                assert a == first, f"group {seq} re-shipped other bytes/dtype"
        return len(again)
