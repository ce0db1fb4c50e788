"""costs.py against counts worked out by hand."""

import pytest

from harness import costs, peaks, refs, refs_lora


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


def test_kernel_costs_by_hand():
    # a bank of 4 branches, 3 taps each, 10 rows out: per row 4 x 3 complex x
    # real MACs (4 ops) and a 4-point FFT (5 * 4 * 2)
    c = costs.pfb_cost(10, 4, 3)
    assert c["flops"] == 10 * (4 * 3 * 4 + 40) == 880
    # rows in with the 2 rows of history, rows out, two float32 planes; taps
    assert c["bytes"] == (12 * 4 + 10 * 4) * 8 + 4 * 3 * 4 == 752
    # 7 fetches of a 256-sample window from each of 2 lanes: read + write
    c = costs.window_fetch_cost(2, 256, 7)
    assert c == {"flops": 0.0, "bytes": 7 * 2 * 256 * 2 * 4 * 2}
    # 100 trellis steps at 386 ops; two float32 LLRs in, one byte out
    assert costs.trellis_cost(100, 386) == {"flops": 38600.0, "bytes": 900.0}


def _frame_cost(name, frame):
    from harness import cells

    cfg = cells.load_json(cells.BENCH_DIR / "configs" / f"{name}.json")
    mod = cells.load_module(cells.BENCH_DIR / "configs" / f"{name}.py")
    return mod, cfg, mod.frame_cost(cfg, frame, "sc16")


@pytest.mark.parametrize("name,frame,flops,nbytes", [
    # what frame_cost returned before it gained its "kernels" key: the
    # numerator of program_roofline may not move
    ("lora_gw_eu868", 262144, 289511356.0, 1179648.0),
    ("lora_gw_eu868", 16384, 9462236.0, 73728.0),
    ("wlan_rx_20msps", 262144, 78622077.75, 1179648.0),
    ("wlan_rx_20msps", 16384, 4046633.5, 73728.0),
])
def test_frame_cost_top_level_as_before(name, frame, flops, nbytes):
    _, _, c = _frame_cost(name, frame)
    assert c["flops"] == flops and c["bytes"] == nbytes


def test_gateway_kernel_costs_by_hand():
    _, _, c = _frame_cost("lora_gw_eu868", 16384)
    # the rehearsal's gateway: 4 channels, 12 taps a branch, 4096 rows a frame
    assert len(refs_lora.channelizer_taps(4)) == 48
    assert c["kernels"]["pfb"] == costs.pfb_cost(4096, 4, 12)
    # 5120 samples a channel a frame; SF7-9: windows of 256, 512, 1024 in
    # scans of 20 + 6, 10 + 6 and 5 + 6 steps, 4 lanes
    assert 16384 * 5 // 16 == 5120
    want = sum(steps * 4 * w * 16 for w, steps in ((256, 26), (512, 16), (1024, 11)))
    assert c["kernels"]["window_fetch"] == {"flops": 0.0, "bytes": float(want)}


def test_trellis_cost_follows_the_packets():
    mod, cfg, c = _frame_cost("wlan_rx_20msps", 16384)
    sched = mod.schedule(cfg, 0, 16, 16384)
    steps = sum(16 + 8 * s[2] + 6 for s in sched) / 16
    assert mod.TRELLIS_OPS == 386
    assert c["kernels"]["viterbi"] == costs.trellis_cost(steps, 386)
