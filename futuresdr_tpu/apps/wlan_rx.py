"""802.11a/g monitor: one 20 MHz channel, the whole receiver on the device.

Reference: ``examples/wlan`` (``SyncShort`` → ``SyncLong`` → FFT →
``FrameEqualizer`` → ``ViterbiDecoder`` → ``Mac``; ``loopback.rs:30-123``) and
``perf/wlan/rx.rs``. Here source → ``TpuKernel(wlan_rx_stages())`` →
:class:`~futuresdr_tpu.models.wlan.blocks.WlanRecords`: one device program per
frame turns samples into records, and a small host block checks each PSDU's
FCS and posts it on ``rx``. ``use_tpu=False`` is the host receiver
(``WlanDecoder``) on the same ports. A monitor of several channels gives each
its own kernel.
"""

from __future__ import annotations

import numpy as np

from ..blocks import SeifyBuilder
from ..runtime import Flowgraph, Runtime

def build_flowgraph(source=None, use_tpu: bool = True, frame_size=None,
                    **stage_params):
    """Assemble the receiver; returns ``(fg, kernel_or_None, decoder)``: the
    decoder block posts PSDU payloads on its message port ``rx`` and keeps
    them in ``.frames``. ``frame_size`` (default: the instance's) goes to the
    kernel, ``stage_params`` to ``wlan_rx_stages`` (tests and rehearsals
    shrink the carry and the capacities; the default is the shipped
    receiver)."""
    from ..models.wlan import WlanDecoder, WlanRecords
    fg = Flowgraph()
    if source is None:
        source = SeifyBuilder().args("driver=dummy,throttle=false").build_source()
    if not use_tpu:
        rx = WlanDecoder()
        fg.connect(source, rx)
        return fg, None, rx
    from ..models.wlan.rx_stages import wlan_rx_stages
    from ..tpu import TpuKernel
    kernel = TpuKernel(wlan_rx_stages(**stage_params), np.complex64,
                       frame_size=frame_size)
    rx = WlanRecords(kernel.out_frame)
    fg.connect(source, kernel, rx)
    return fg, kernel, rx


def main(argv=None):
    import argparse
    p = argparse.ArgumentParser(description="802.11a/g monitor on the TPU")
    p.add_argument("--args", default="driver=dummy,throttle=false")
    p.add_argument("--cpu", action="store_true", help="the host receiver instead")
    a = p.parse_args(argv)
    fg, _, rx = build_flowgraph(SeifyBuilder().args(a.args).build_source(),
                                use_tpu=not a.cpu)
    Runtime().run(fg)
    print(f"{len(rx.frames)} PSDUs with a good FCS")


if __name__ == "__main__":
    main()
