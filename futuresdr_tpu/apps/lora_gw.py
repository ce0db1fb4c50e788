"""LoRaWAN EU868 gateway: eight channels, SF7 to SF12 at once, on the device.

Reference: ``examples/lora/src/bin/rx_all_channels_eu.rs`` (one wideband
stream → ``PfbChannelizer`` → a ``PfbArbResampler`` per channel → a LoRa
receiver per channel, one SF a run). Here source →
``TpuKernel(lora_gw_stages())`` →
:class:`~futuresdr_tpu.models.lora.blocks.LoraGatewayRecords`: one device
program per frame listens to all 48 (channel, SF) pairs and turns samples into
records; the host block posts each payload with a good CRC on ``rx`` (a map of
``payload``, ``crc_ok``, ``freq``, ``sf``). The source delivers 1.6 Msps centred
on 867.8 MHz (:data:`CENTER_HZ`). ``use_tpu=False`` is the host chain of
``models.lora.multichannel.build_multichannel_rx(use_channelizer=True)``, one
receiver per (channel, SF), centred on 867.9 MHz where its bank needs the
channels on its grid.
"""

from __future__ import annotations

import numpy as np

from ..blocks import SeifyBuilder
from ..runtime import Flowgraph, Runtime

SAMPLE_RATE = 1.6e6
CENTER_HZ = 867.8e6
SFS = (7, 8, 9, 10, 11, 12)


def build_flowgraph(source=None, use_tpu: bool = True, frame_size=None,
                    **stage_params):
    """Assemble the gateway; returns ``(fg, kernel_or_None, rx)``. With
    ``use_tpu`` ``rx`` is the one record block (``.frames``, message port
    ``rx``); without, the list of ``ChannelTag`` blocks, one per (channel,
    SF), each with its own ``out`` port. ``frame_size`` (default: the
    instance's) goes to the kernel, ``stage_params`` to ``lora_gw_stages``
    (tests and rehearsals take fewer channels and SFs)."""
    from ..models.lora.multichannel import EU868_CHANNELS_HZ
    fg = Flowgraph()
    if source is None:
        source = SeifyBuilder().args("driver=dummy,throttle=false").build_source()
    if not use_tpu:
        from ..models.lora import LoraParams
        from ..models.lora.multichannel import build_multichannel_rx
        tags = []
        for sf in stage_params.get("sfs", SFS):
            params = LoraParams(sf=sf, cr=1, sync_word=0x34, ldro=None)
            _, _, t = build_multichannel_rx(
                source, SAMPLE_RATE, 867.9e6, params, fg=fg, use_channelizer=True,
                spacing_hz=200e3)
            tags += t
        return fg, None, tags
    from ..models.lora.blocks import LoraGatewayRecords
    from ..models.lora.rx_stages import lora_gw_stages
    from ..tpu import TpuKernel
    kernel = TpuKernel(lora_gw_stages(**stage_params), np.complex64,
                       frame_size=frame_size)
    n = int(stage_params.get("n_channels", 8))
    rx = LoraGatewayRecords(
        kernel.out_frame, EU868_CHANNELS_HZ if n == len(EU868_CHANNELS_HZ) else None)
    fg.connect(source, kernel, rx)
    return fg, kernel, rx


def main(argv=None):
    import argparse
    p = argparse.ArgumentParser(description="LoRaWAN EU868 gateway on the TPU")
    p.add_argument("--args", default="driver=dummy,throttle=false")
    p.add_argument("--cpu", action="store_true", help="the host receivers instead")
    a = p.parse_args(argv)
    fg, _, rx = build_flowgraph(SeifyBuilder().args(a.args).build_source(),
                                use_tpu=not a.cpu)
    Runtime().run(fg)
    if not a.cpu:
        print(f"{len(rx.frames)} payloads with a good CRC")


if __name__ == "__main__":
    main()
