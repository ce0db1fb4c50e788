"""IEEE 802.11a/g/p OFDM transceiver — the flagship application.

Re-design of the reference's largest example (``examples/wlan/``, 4.3k LoC, a port of
gr-ieee802-11): full TX (scramble/convolutional-code/interleave/map/IFFT+CP/preamble) and
RX (detect/sync/equalize/demap/Viterbi/descramble) with MAC framing, built frame-level and
batched for the TPU.
"""

from .consts import MCS_TABLE, Mcs
from .phy import (encode_frame, decode_frame, decode_stream, decode_stream_batch,
                  DecodedFrame)
from .mac import Mac, mpdu_from_payload, payload_from_mpdu
from .blocks import WlanEncoder, WlanDecoder, WlanRecords
from .channels import channel_to_freq, freq_to_channel, parse_channel
from . import coding, ofdm

__all__ = ["MCS_TABLE", "Mcs", "encode_frame", "decode_frame", "decode_stream",
           "decode_stream_batch", "DecodedFrame", "Mac", "mpdu_from_payload",
           "payload_from_mpdu", "WlanEncoder", "WlanDecoder", "WlanRecords", "coding",
           "ofdm",
           "channel_to_freq", "freq_to_channel", "parse_channel"]
