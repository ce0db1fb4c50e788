"""costs.py against counts worked out by hand."""

import pytest

from harness import costs, peaks, refs


def test_spectrum_frame_at_262144():
    # FIR: 64 taps x 4 ops (complex x real MAC) = 256 ops/sample
    # FFT: 128 transforms of 2048: 5 * 2048 * 11 = 112640 each = 55 ops/sample
    # |x|^2: 3 ops/sample                       total 314 ops/sample
    c = costs.spectrum_frame_cost(262144, 64, 2048, 4, 2)
    assert c["flops"] == 262144 * 314 == 82313216
    # sc16: 4 bytes up per sample, 2 bytes down per item
    assert c["bytes"] == 262144 * 6 == 1572864
    assert costs.spectrum_frame_cost(262144, 64, 2048, 8, 4)["bytes"] == 262144 * 12


def test_four_step_executes_more_than_the_fft_needs():
    need = costs.fft_flops(2048, 1)
    assert need == 5 * 2048 * 11
    ran = costs.fft_four_step_executed_flops(32, 64, 1)
    assert ran == 8 * 2048 * 32 + 6 * 2048 + 8 * 2048 * 64
    assert ran > 10 * need


def test_fm_front_end_frame_at_65500():
    interp, decim, taps = refs.fm_resampler()
    assert (interp, decim, len(taps)) == (24, 125, 4533)
    c = costs.fm_front_end_frame_cost(65500, 128, 4, interp, decim, len(taps))
    # xlating FIR: 16375 outputs x (128 complex MACs x 8 + 6 for the rotator)
    xl = 16375 * (8 * 128 + 6)
    # discriminator: 16375 x 8; resampler: 3144 outputs x 189 taps x 2
    assert 16375 * 24 // 125 == 3144 and -(-4533 // 24) == 189
    assert c["flops"] == xl + 16375 * 8 + 3144 * 2 * 189 == 18185682
    assert c["bytes"] == 65500 * 8 + 3144 * 4 == 536576


def test_roofline_names_its_bound():
    pk = peaks.peaks_for("TPU v5 lite")
    r = costs.roofline({"flops": 82313216.0, "bytes": 1572864.0}, pk)
    assert r["bound"] == "memory"
    assert r["min_s"] == pytest.approx(1572864 / 819e9)
    assert r["t_flops_s"] == pytest.approx(82313216 / 197e12)
    r = costs.roofline({"flops": 1e15, "bytes": 1.0}, pk)
    assert r["bound"] == "compute"


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("_source")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("cpu")
