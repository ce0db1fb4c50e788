"""WLAN transceiver tests: coding round-trips, PHY loopback (clean + impaired), and the
full flowgraph loopback — mirroring the reference's `examples/wlan/src/bin/loopback.rs`.
"""

import numpy as np
import pytest

from futuresdr_tpu.models.wlan import (MCS_TABLE, encode_frame, decode_frame,
                                       decode_stream, Mac, WlanEncoder, WlanDecoder,
                                       coding, ofdm)
from futuresdr_tpu.models.wlan.phy import bytes_to_bits, bits_to_bytes


def test_scrambler_roundtrip():
    rng = np.random.default_rng(0)
    bits = rng.integers(0, 2, 500).astype(np.uint8)
    s = coding.scramble(bits, 0x5B)
    assert not np.array_equal(s, bits)
    np.testing.assert_array_equal(coding.descramble(s, 0x5B), bits)


def test_conv_code_viterbi_clean():
    rng = np.random.default_rng(1)
    bits = np.concatenate([rng.integers(0, 2, 200), np.zeros(6)]).astype(np.uint8)
    coded = coding.conv_encode(bits)
    llrs = coded.astype(np.float64) * 2 - 1
    dec = coding.viterbi_decode(llrs, len(bits))
    np.testing.assert_array_equal(dec, bits)


def test_viterbi_corrects_errors():
    rng = np.random.default_rng(2)
    bits = np.concatenate([rng.integers(0, 2, 400), np.zeros(6)]).astype(np.uint8)
    coded = coding.conv_encode(bits)
    llrs = (coded.astype(np.float64) * 2 - 1)
    flip = rng.choice(len(llrs), size=len(llrs) // 20, replace=False)  # 5% bit flips
    llrs[flip] *= -1
    dec = coding.viterbi_decode(llrs, len(bits))
    np.testing.assert_array_equal(dec, bits)


@pytest.mark.parametrize("rate", ["1/2", "2/3", "3/4"])
def test_puncture_depuncture_viterbi(rate):
    rng = np.random.default_rng(3)
    bits = np.concatenate([rng.integers(0, 2, 300), np.zeros(6)]).astype(np.uint8)
    coded = coding.conv_encode(bits)
    punct = coding.puncture(coded, rate)
    llrs = punct.astype(np.float64) * 2 - 1
    dep = coding.depuncture(llrs, rate)
    dec = coding.viterbi_decode(dep, len(bits))
    np.testing.assert_array_equal(dec, bits)


def test_interleaver_roundtrip():
    for n_bpsc in (1, 2, 4, 6):
        n_cbps = 48 * n_bpsc
        rng = np.random.default_rng(4)
        bits = rng.integers(0, 2, 3 * n_cbps).astype(np.uint8)
        inter = coding.interleave(bits, n_cbps, n_bpsc)
        deint = coding.deinterleave(inter.astype(np.float64), n_cbps, n_bpsc)
        np.testing.assert_array_equal(deint.astype(np.uint8), bits)


@pytest.mark.parametrize("mod", ["bpsk", "qpsk", "qam16", "qam64"])
def test_map_demap_roundtrip(mod):
    rng = np.random.default_rng(5)
    n_bpsc = {"bpsk": 1, "qpsk": 2, "qam16": 4, "qam64": 6}[mod]
    bits = rng.integers(0, 2, 48 * n_bpsc).astype(np.uint8)
    syms = ofdm.map_bits(bits, mod)
    llrs = ofdm.demap_llrs(syms, mod)
    np.testing.assert_array_equal((llrs > 0).astype(np.uint8), bits)


@pytest.mark.parametrize("mcs", list(MCS_TABLE))
def test_phy_loopback_clean(mcs):
    psdu = bytes(f"Hello TPU-native 802.11 with {mcs}!".encode()) * 3
    frame = encode_frame(psdu, mcs)
    decoded = decode_stream(frame)
    assert len(decoded) == 1, f"{mcs}: expected 1 frame, got {len(decoded)}"
    assert decoded[0].psdu == psdu
    assert decoded[0].mcs.name == mcs


def test_phy_loopback_noise_cfo_delay():
    """Impaired channel: delay + AWGN + carrier frequency offset (loopback.rs adds
    channel impairments the same way)."""
    rng = np.random.default_rng(6)
    psdu = b"The quick brown fox jumps over the lazy dog" * 4
    frame = encode_frame(psdu, "qpsk_1_2")
    sig = np.concatenate([np.zeros(777, np.complex64), frame,
                          np.zeros(500, np.complex64)])
    n = np.arange(len(sig))
    cfo = 2 * np.pi * 1e-4
    sig = sig * np.exp(1j * cfo * n)
    sig = sig + (0.02 * (rng.standard_normal(len(sig))
                         + 1j * rng.standard_normal(len(sig)))).astype(np.complex64)
    decoded = decode_stream(sig.astype(np.complex64))
    assert len(decoded) == 1
    assert decoded[0].psdu == psdu


def test_mac_roundtrip():
    mac = Mac()
    mpdu = mac.frame(b"payload!")
    assert mac.deframe(mpdu) == b"payload!"
    corrupted = bytearray(mpdu)
    corrupted[10] ^= 0xFF
    assert mac.deframe(bytes(corrupted)) is None


def test_flowgraph_loopback():
    """Full actor-runtime loopback: Encoder block → channel Apply → Decoder block
    (the reference's `loopback.rs:30-123`)."""
    from futuresdr_tpu import Flowgraph, Runtime, Pmt
    from futuresdr_tpu.blocks import Apply

    rng = np.random.default_rng(7)
    fg = Flowgraph()
    enc = WlanEncoder("qpsk_1_2")
    chan = Apply(lambda x: x + (0.01 * (rng.standard_normal(len(x))
                                        + 1j * rng.standard_normal(len(x)))
                                ).astype(np.complex64), np.complex64)
    dec = WlanDecoder()
    fg.connect(enc, chan, dec)

    payloads = [f"frame number {i}".encode() * 5 for i in range(5)]
    rt = Runtime()
    running = rt.start(fg)
    for p in payloads:
        rt.scheduler.run_coro_sync(running.handle.call(enc, "tx", Pmt.blob(p)))
    rt.scheduler.run_coro_sync(running.handle.call(enc, "tx", Pmt.finished()))
    running.wait_sync()
    assert dec.frames == payloads


def test_decode_stream_batch_matches_per_frame():
    """Burst-batched Viterbi decoding must find the same frames as the per-frame path."""
    rng = np.random.default_rng(11)
    from futuresdr_tpu.models.wlan import decode_stream_batch

    mac = Mac()
    parts = []
    sent = []
    for i in range(6):
        psdu = mac.frame(f"batch frame {i}".encode() * 3)
        sent.append(psdu)
        parts += [encode_frame(psdu, "qam16_1_2"), np.zeros(400, np.complex64)]
    sig = np.concatenate(parts)
    sig = (sig + 0.01 * (rng.standard_normal(len(sig))
                         + 1j * rng.standard_normal(len(sig)))).astype(np.complex64)
    per_frame = [f.psdu for f in decode_stream(sig)]
    batched = [f.psdu for f in decode_stream_batch(sig)]
    assert per_frame == sent
    assert batched == sent


def test_bit_packing():
    data = b"\x01\x80\xff"
    bits = bytes_to_bits(data)
    assert bits[0] == 1 and bits[7] == 0
    assert bits[8] == 0 and bits[15] == 1
    assert bits_to_bytes(bits) == data


def test_jitted_head_matches_host_path():
    """demod_head_jax (LTS channel est + SIGNAL demap in one jit) agrees with the
    host path (estimate_channel + equalize + BPSK demap) including under CFO."""
    from futuresdr_tpu.models.wlan import ofdm
    from futuresdr_tpu.models.wlan.jax_demod import demod_head_jax
    from futuresdr_tpu.models.wlan.phy import encode_frame

    mac = Mac()
    psdu = mac.frame(b"head path check" * 4)
    sig = encode_frame(psdu, "bpsk_1_2")
    sig = np.concatenate([np.zeros(100, np.complex64), sig])
    start = ofdm.detect_packets(sig)[0]
    _, lts_start, _cfo = ofdm.sync_long(sig, start)
    for cfo in (0.0, 0.003, -0.008):
        head = sig[lts_start:lts_start + 208]
        Hj, llrs_j = demod_head_jax(head, cfo)
        host = head * np.exp(-1j * cfo * np.arange(208)) if cfo else head
        Hh = ofdm.estimate_channel(host, 0)
        spec = ofdm.ofdm_demodulate_symbols(host[128:], 1)
        eq = ofdm.equalize(spec, Hh, symbol_offset=0)
        llrs_h = ofdm.demap_llrs(eq.reshape(-1), "bpsk")
        np.testing.assert_allclose(Hj, Hh.astype(np.complex64), atol=2e-4)
        np.testing.assert_allclose(llrs_j, llrs_h.astype(np.float32), atol=2e-3)


def test_full_decode_with_jax_paths_forced():
    """End-to-end decode on the jax head+body paths (the default route): every
    MCS loops back clean."""
    mac = Mac()
    for mcs in ("bpsk_1_2", "qam16_1_2", "qam64_3_4"):
        psdu = mac.frame(f"jax path {mcs}".encode() * 20)   # > 8 symbols
        sig = encode_frame(psdu, mcs)
        sig = np.concatenate([np.zeros(171, np.complex64), sig,
                              np.zeros(64, np.complex64)])
        sig = (sig * np.exp(1j * 0.002 * np.arange(len(sig)))).astype(np.complex64)
        frames = decode_stream(sig)
        assert len(frames) == 1 and frames[0].psdu == psdu, mcs


def test_short_frame_jax_head_host_body():
    """n_sym < 8: the jax HEAD (complex64 H) feeds the host numpy body demod —
    the mixed path must decode clean too."""
    mac = Mac()
    psdu = mac.frame(b"tiny")             # few symbols at qam16
    sig = encode_frame(psdu, "qam16_1_2")
    sig = np.concatenate([np.zeros(130, np.complex64), sig,
                          np.zeros(64, np.complex64)])
    sig = (sig * np.exp(1j * 0.0015 * np.arange(len(sig)))).astype(np.complex64)
    frames = decode_stream(sig)
    assert len(frames) == 1 and frames[0].psdu == psdu
    assert frames[0].n_symbols < 8        # really the mixed path


def test_native_viterbi_bit_matches_numpy():
    """The C++ ACS loop decodes bit-identically to the numpy trellis (same tie
    convention), across short/long frames and noisy LLRs."""
    import futuresdr_tpu.models.wlan.coding as c
    if c._native_lib() is None:
        pytest.skip("native toolchain unavailable")
    rng = np.random.default_rng(0)
    for n in (24, 97, 511, 513, 3000):
        bits = rng.integers(0, 2, n).astype(np.uint8)
        bits[-6:] = 0
        llrs = (c.conv_encode(bits).astype(np.float64) * 2 - 1
                + 0.5 * rng.standard_normal(2 * n))
        native = c.viterbi_decode(llrs, n)
        saved, c._NATIVE = c._NATIVE, 0          # force the numpy path:
        saved_thr, c._SCAN_THRESHOLD = c._SCAN_THRESHOLD, n + 1   # no native, no scan
        try:
            ref = c.viterbi_decode(llrs, n)
        finally:
            c._NATIVE, c._SCAN_THRESHOLD = saved, saved_thr
        assert np.array_equal(native, ref), n
        assert np.array_equal(native, bits), f"decode errors at n={n}"


def test_noisy_burst_train_no_mislock_no_dup():
    """Regression for two RX-chain defects found at 25 dB: (1) sync_long's
    search window ended before LTS2 when detection fired early, so the
    cyclic-prefix ghost won the 64-apart pairing — a deterministic one-symbol
    mislock whose garbage SIGNAL passed parity and LOST the real frame;
    (2) noise re-triggering the plateau detector inside a burst produced
    duplicate/garbage decodes. 60 noisy frames must come back exactly once
    each, nothing else."""
    rng = np.random.default_rng(1234)
    mac = Mac()
    parts, sent = [], []
    for i in range(60):
        psdu = mac.frame(bytes(rng.integers(0, 256, 256, dtype=np.uint8)))
        sent.append(psdu)
        parts += [encode_frame(psdu, "qpsk_1_2"), np.zeros(300, np.complex64)]
    sig = np.concatenate(parts)
    sigma = np.sqrt(np.mean(np.abs(sig) ** 2) * 10 ** (-25 / 10) / 2)
    sig = (sig + sigma * (rng.standard_normal(len(sig))
                          + 1j * rng.standard_normal(len(sig)))
           ).astype(np.complex64)
    got = [f.psdu for f in decode_stream(sig)]
    assert got == sent, (len(got), len(set(got) & set(sent)))


def test_frame_snr_estimate():
    """Per-frame SNR from the LTS repetitions (`frame_equalizer.rs:64` snr()):
    tracks the actual channel SNR within a few dB, and orders clean vs noisy."""
    from futuresdr_tpu.models.wlan.phy import decode_stream, encode_frame
    rng = np.random.default_rng(8)
    psdu = b"snr probe frame" * 3
    burst = encode_frame(psdu, "qpsk_1_2")
    sig_p = np.mean(np.abs(burst) ** 2)
    got = {}
    for snr_db in (30.0, 10.0):
        sigma = np.sqrt(sig_p / (2 * 10 ** (snr_db / 10)))
        x = np.concatenate([np.zeros(300, np.complex64), burst,
                            np.zeros(300, np.complex64)])
        x = (x + sigma * (rng.standard_normal(len(x))
                          + 1j * rng.standard_normal(len(x)))).astype(np.complex64)
        frames = decode_stream(x)
        assert len(frames) == 1 and frames[0].psdu == psdu
        got[snr_db] = frames[0].snr_db
        assert abs(frames[0].snr_db - snr_db) < 6.0, (snr_db, frames[0].snr_db)
    assert got[30.0] > got[10.0]


def test_random_config_roundtrip_fuzz():
    """Seeded sweep over random (MCS, length, CFO, delay) frames: every
    combination decodes exactly through the full stream RX."""
    from futuresdr_tpu.models.wlan.phy import decode_stream, encode_frame
    from futuresdr_tpu.models.wlan.consts import MCS_TABLE
    rng = np.random.default_rng(80211)
    names = list(MCS_TABLE)
    for trial in range(10):
        mcs = names[int(rng.integers(0, len(names)))]
        n_pay = int(rng.integers(1, 500))
        psdu = rng.integers(0, 256, n_pay).astype(np.uint8).tobytes()
        burst = encode_frame(psdu, mcs)
        x = np.concatenate([np.zeros(int(rng.integers(100, 900)), np.complex64),
                            burst, np.zeros(300, np.complex64)])
        cfo = float(rng.uniform(-0.002, 0.002))
        x = (x * np.exp(1j * cfo * np.arange(len(x)))).astype(np.complex64)
        # 28 dB channel: comfortably above 64QAM-3/4's requirement, so every
        # MCS in the sweep must decode error-free
        sigma = float(np.sqrt(np.mean(np.abs(burst) ** 2) / (2 * 10 ** 2.8)))
        x = (x + sigma * (rng.standard_normal(len(x))
                          + 1j * rng.standard_normal(len(x)))).astype(np.complex64)
        frames = decode_stream(x)
        assert len(frames) == 1 and frames[0].psdu == psdu, (trial, mcs, n_pay)


def test_viterbi_terminates_at_tail_not_pad():
    """Regression (r4 fuzz campaign): the decoder must decode exactly
    SERVICE+PSDU+tail — the pad bits after the tail stay scrambled, so tracing
    back from state 0 at the padded n_sym*n_dbps length corrupted the final
    bytes for seed/content combos with nonzero scrambled pad."""
    from futuresdr_tpu.models.wlan.phy import decode_stream, encode_frame
    # the exact (mcs, length, content) triple the campaign caught
    rng = np.random.default_rng(5)
    for _ in range(6):
        rng.integers(0, 256, 1)
    rng.integers(0, 256, 195)
    psdu = rng.integers(0, 256, 195).astype(np.uint8).tobytes()
    burst = encode_frame(psdu, "qam16_3_4")
    x = np.concatenate([np.zeros(200, np.complex64), burst,
                        np.zeros(200, np.complex64)])
    frames = decode_stream(x)
    assert len(frames) == 1 and frames[0].psdu == psdu
    # sweep a band of lengths at the highest-rate MCSes (clean channel: every
    # single one must be exact; pre-fix this band failed sporadically)
    for mcs in ("qam16_3_4", "qam64_2_3", "qam64_3_4"):
        for n_pay in (185, 189, 195):
            p2 = rng.integers(0, 256, n_pay).astype(np.uint8).tobytes()
            b2 = encode_frame(p2, mcs)
            x2 = np.concatenate([np.zeros(150, np.complex64), b2,
                                 np.zeros(150, np.complex64)])
            f2 = decode_stream(x2)
            assert len(f2) == 1 and f2[0].psdu == p2, (mcs, n_pay)


def test_channel_table_matches_reference():
    """models/wlan/channels.py: the 67-channel table equals `channels.rs:1-72`
    entry by entry (derived arithmetic vs the reference's literal list), and
    the parse API mirrors its error semantics."""
    import re
    from pathlib import Path

    import pytest

    from futuresdr_tpu.models.wlan.channels import (CHANNELS, channel_to_freq,
                                                    freq_to_channel,
                                                    parse_channel)
    assert len(CHANNELS) == 67
    assert channel_to_freq(1) == 2412e6 and channel_to_freq(14) == 2484e6
    assert channel_to_freq(36) == 5180e6 and channel_to_freq(184) == 5920e6
    assert channel_to_freq(35) is None          # gaps stay gaps
    assert freq_to_channel(5860e6) == 172
    assert parse_channel("165") == 5825e6
    for bad in ("x", "35", "0"):
        with pytest.raises(ValueError, match="WLAN channel"):
            parse_channel(bad)
    ref = Path("/root/reference/examples/wlan/src/channels.rs")
    if ref.exists():                            # full parity check when present
        pairs = re.findall(r"\((\d+),\s*([\d.]+)e6\)", ref.read_text())
        assert CHANNELS == {int(c): float(f) * 1e6 for c, f in pairs}
