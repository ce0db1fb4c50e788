"""``fm_serve_1msps_sc16`` and its cell ``fm_serve_sc16_sat``: the manifest's
entries, the stations as 16-bit words, the float64 reference of those words,
the bytes a dispatch needs, and the cell end to end on the CPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from harness import cells, costs, refs

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
CM = cells.load_module(BENCH / "configs" / "fm_serve_1msps_sc16.py")
CFG = json.loads((BENCH / "configs" / "fm_serve_1msps_sc16.json").read_text())
SIB = json.loads((BENCH / "configs" / "fm_serve_1msps.json").read_text())
SIB_CM = cells.load_module(BENCH / "configs" / "fm_serve_1msps.py")
FRAME = CFG["expected_on_chip"]["frame_size"]

TEN = {"program.device_ms_per_frame", "program_roofline", "serve.step_ms_p50",
       "serve.assemble_ms_p50", "serve.h2d_ms_p50", "serve.program_ms_p50",
       "serve.d2h_ms_p50", "link.h2d_gbps", "device.idle_share",
       "trace.spans_dropped"}


def test_manifest_resolves_the_cell_with_the_siblings_ten_metrics():
    cell = cells.resolve("fm_serve_sc16_sat")
    sib = cells.resolve("fm_serve_sat")
    assert cell.config_name == "fm_serve_1msps_sc16" and cell.chips == 1
    assert cell.driver_name == "serve" and cell.traffic_name == sib.traffic_name
    assert cell.traffic == sib.traffic              # the same file, unedited
    assert set(cell.end_to_end) == {"throughput_msps", "setup_s"}
    # the sibling's metrics, the ten it had when this cell came among them;
    # a metric added to both later keeps this true
    mine = {m.name for m in cell.layer_metrics}
    assert mine == {m.name for m in sib.layer_metrics} and TEN <= mine


def test_the_two_serving_configurations_differ_in_the_frame_format_alone():
    for key in ("process_env", "expected_on_chip", "rehearsal", "reduced"):
        assert CFG[key] == SIB[key], key
    # the comparison is the sibling's; beside it this file keeps its readings
    mine = dict(CFG["correctness"])
    measured = mine.pop("measured")
    assert mine == SIB["correctness"]
    tol = mine["abs_tolerance"]
    assert max(measured["shipped"]["precheck_max_abs_err"]) < tol / 3
    assert min(measured["control"]["sampled_max_abs_err"]) > 3 * tol
    p, q = dict(CFG["parameters"]), dict(SIB["parameters"])
    assert p.pop("wire") == "sc16" and p == q
    assert set(CFG["guarantees"]) == set(SIB["guarantees"]) | {"exact_ingest"}
    assert CFG["assumed"]["full_scale"] == 32768
    assert CFG["assumed"]["period_frames"] == SIB["assumed"]["period_frames"]


@pytest.mark.parametrize("seed", [1, 2_000_000_011, 3_999_999_979])
def test_reference_recovers_each_stations_tone(seed):
    """A station is the sibling's, at a quarter of full scale, rounded to
    int16 once: the float64 reference of the WORDS demodulates the tone the
    generator drew (2/3 of full deviation at its frequency, 48 kHz out)."""
    for lane in (0, 17, 63):
        w = CM.lane_signal(CFG, seed, lane, FRAME)
        assert w.dtype == np.uint32 and w.shape == (2, FRAME)
        x = SIB_CM.lane_signal(SIB, seed, lane, FRAME)  # the float station
        q = w.view(np.int16).reshape(2, FRAME, 2)
        assert int(np.abs(q).max()) <= 8192             # 12 dB of headroom
        np.testing.assert_array_equal(q[..., 0], np.rint(x.real.astype(np.float64) * 8192))
        np.testing.assert_array_equal(q[..., 1], np.rint(x.imag.astype(np.float64) * 8192))
        np.testing.assert_array_equal(
            CM.from_words(w, CFG), q[..., 0] / 32768.0 + 1j * (q[..., 1] / 32768.0))
        audio = CM.reference(CFG, w.reshape(-1))
        assert len(audio) == 2 * CFG["expected_on_chip"]["audio_per_frame"]
        rng = np.random.default_rng([seed, lane])
        n = 2 * FRAME
        cycles = int(rng.integers(max(2, n // 3300), max(3, n // 330)))
        # whole periods in the buffer: the tone falls on one bin of the
        # steady part's DFT (the first 600 samples hold the filters' rise)
        steady = audio[len(audio) // 2:]
        spec = np.abs(np.fft.rfft(steady * np.hanning(len(steady))))
        f_peak = np.argmax(spec) * refs.FM_AUDIO_RATE / len(steady)
        f_tone = cycles * refs.FM_INPUT_RATE / n
        assert abs(f_peak - f_tone) <= refs.FM_AUDIO_RATE / len(steady)
        assert np.max(np.abs(steady)) == pytest.approx(2 / 3, rel=0.02)
        # quantization is the radio's: the float station's audio differs from
        # the words' by the 16-bit noise, far above the cell's limit on the
        # SYSTEM's error against the words' own reference
        drift = np.max(np.abs(audio - SIB_CM.reference(SIB, x.reshape(-1))))
        assert 1e-6 < drift < 1e-2


def test_dispatch_cost_counts_four_bytes_a_sample_in():
    c = CM.dispatch_cost(CFG, FRAME, 64)
    s = SIB_CM.dispatch_cost(SIB, FRAME, 64)
    assert c["flops"] == s["flops"] == 64 * 18185682
    assert c["bytes"] == 64 * (FRAME * 4 + 3144 * 4) == s["bytes"] - 64 * FRAME * 4
    interp, decim, taps = refs.fm_resampler()
    lane = costs.fm_front_end_frame_cost(FRAME, 128, 4, interp, decim, len(taps))
    assert lane["bytes"] == FRAME * 8 + 3144 * 4        # the harness counts 8


def test_judge_holds_the_siblings_limit():
    want = np.zeros(3144)
    assert CM.judge(CFG, want + 4.9e-4, want) == (True, pytest.approx(4.9e-4))
    assert not CM.judge(CFG, want + 5.1e-4, want)[0]
    assert not CM.judge(CFG, want[:-1], want)[0]
    assert CFG["correctness"]["abs_tolerance"] <= SIB["correctness"]["abs_tolerance"]


def test_cell_rehearses_end_to_end_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_REHEARSE="1")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "fm_serve_sc16_sat", "--seed", "2147483659", "--seconds", "2",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["rehearse"] is True and line["correct"] is False
    assert set(line["metrics"]) == {"throughput_msps", "setup_s"}
    assert line["failed"] == 0 and line["attempted"] > 0
    notes = next(json.loads(ln)["notes"] for ln in
                 reversed(p.stderr.splitlines()) if ln.startswith('{"notes"'))
    tol = CFG["correctness"]["abs_tolerance"]
    assert notes["precheck"]["ok"] and notes["precheck"]["max_abs_err"] < tol
    assert notes["sampled"]["bad"] == 0 and notes["sampled"]["frames"] >= 16
    assert notes["sampled"]["max_abs_err"] < tol
    assert notes["frame_size"] == 2000 and notes["capacity"] == 4
