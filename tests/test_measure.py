"""run_marginal: the honest scan-marginal throughput harness (docs/tpu_notes.md)."""
import numpy as np
import pytest

from futuresdr_tpu.ops import fir_stage
from futuresdr_tpu.ops.stages import Pipeline
from futuresdr_tpu.utils.measure import run_marginal


def test_run_marginal_positive_rate():
    rng = np.random.default_rng(0)
    taps = rng.standard_normal(32).astype(np.float32)
    pipe = Pipeline([fir_stage(taps)], np.float32)
    x = rng.standard_normal(1 << 16).astype(np.float32)
    import jax
    rate = run_marginal(pipe.fn(), jax.device_put(pipe.init_carry()),
                        jax.device_put(x), k_pair=(4, 64), reps=2)
    assert rate > 0


def _pin_peaks(monkeypatch):
    """A known denominator on the CPU host: the config override (the v5e
    table figures), so the mfu/bound fields are exercised without a chip."""
    from futuresdr_tpu.config import config
    monkeypatch.setattr(config(), "peak_flops", 197e12)
    monkeypatch.setattr(config(), "peak_hbm_gbps", 819.0)


def test_pipeline_roofline_accounting(monkeypatch):
    """utils/roofline: XLA cost analysis per fused prefix; stage numbers are
    differences, totals match the full program, and rate_sps fills in the
    achieved-flops fields (mfu only with a known peak)."""
    import numpy as np
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fft_stage, fir_stage, mag2_stage
    from futuresdr_tpu.utils.roofline import pipeline_roofline

    stages = [fir_stage(firdes.lowpass(0.2, 64).astype(np.float32)),
              fft_stage(1024), mag2_stage()]
    r = pipeline_roofline(stages, np.complex64, 1 << 16, rate_sps=1e6,
                          backend="cpu")
    assert [s["name"] for s in r["stages"]] == ["fir", "fft1024", "mag2"]
    assert r["flops_per_sample"] > 50            # an FFT chain is not free
    assert r["bytes_per_sample"] >= 12           # >= read cx64 + write f32
    total = sum(s["flops_per_sample"] for s in r["stages"])
    assert abs(total - r["flops_per_sample"]) < 1e-6
    assert r["achieved_flops"] == 1e6 * r["flops_per_sample"]
    assert "mfu" not in r                        # no public CPU peak
    _pin_peaks(monkeypatch)
    r2 = pipeline_roofline(stages, np.complex64, 1 << 16, rate_sps=1e9,
                           backend="cpu")
    assert 0 < r2["mfu"] < 1 and "bound" in r2["stages"][0]


def test_roofline_decimating_stage(monkeypatch):
    """A decimating FIR's roofline attribution: the per-stage prefix math
    holds through a rate change (the prefix output shrinks by the decimation
    factor), and the downstream stage is charged at its own (reduced) rate —
    per-sample numbers stay per REGION-INPUT sample."""
    import numpy as np
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage, mag2_stage
    from futuresdr_tpu.utils.roofline import pipeline_roofline

    taps = firdes.lowpass(0.1, 64).astype(np.float32)
    stages = [fir_stage(taps, decim=4, name="decim4"), mag2_stage()]
    _pin_peaks(monkeypatch)
    r = pipeline_roofline(stages, np.complex64, 1 << 16, backend="cpu")
    assert [s["name"] for s in r["stages"]] == ["decim4", "mag2"]
    assert all(s["flops_per_sample"] > 0 for s in r["stages"])
    assert r["stages"][0]["bytes_per_sample"] > 0
    # mag2's MARGINAL bytes may legitimately be <= 0: fusing |x|² onto the
    # decimator replaces the prefix's materialized complex output with a
    # quarter-rate f32 one — the prefix-difference charges that saving to
    # the stage that caused it. Totals stay positive and consistent.
    assert r["bytes_per_sample"] > 0
    # the decimator dominates: mag2 runs on 1/4 of the samples
    assert r["stages"][0]["flops_per_sample"] > \
        r["stages"][1]["flops_per_sample"]
    total = sum(s["flops_per_sample"] for s in r["stages"])
    assert abs(total - r["flops_per_sample"]) < 1e-6
    assert r["stages"][0]["bound"] in ("hbm", "compute")


def test_graph_roofline_fanout_per_node(monkeypatch):
    """graph_roofline on a FanoutPipeline: one node per producer/branch,
    per-node differences sum to the full program's totals, and rate_sps
    fills the achieved/mfu fields exactly like the linear form."""
    import numpy as np
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage, mag2_stage
    from futuresdr_tpu.ops.stages import FanoutPipeline
    from futuresdr_tpu.utils.roofline import graph_roofline

    taps = firdes.lowpass(0.2, 32).astype(np.float32)
    t2 = firdes.lowpass(0.1, 16).astype(np.float32)
    fo = FanoutPipeline([fir_stage(taps, name="prod")],
                        [[mag2_stage()], [fir_stage(t2, decim=4, name="b1")]],
                        np.complex64)
    _pin_peaks(monkeypatch)
    r = graph_roofline(fo, 1 << 14, rate_sps=1e6, backend="cpu")
    assert [(n["name"], n["inputs"]) for n in r["nodes"]] == \
        [("prod", []), ("mag2", [0]), ("b1", [0])]
    total = sum(n["flops_per_sample"] for n in r["nodes"])
    assert abs(total - r["flops_per_sample"]) < 1e-6
    assert r["nodes"][0]["flops_per_sample"] > 0
    assert 0 < r["mfu"] < 1
    assert all(n["bound"] in ("hbm", "compute") for n in r["nodes"])


def test_graph_roofline_dag_diamond():
    """graph_roofline on a DagPipeline diamond (producer → {a, b} → merge):
    every node gets an attribution entry in topological order and the merge
    node is charged only its own marginal cost."""
    import numpy as np
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage, mag2_stage
    from futuresdr_tpu.ops.stages import DagPipeline, add_merge_stage
    from futuresdr_tpu.utils.roofline import graph_roofline

    taps = firdes.lowpass(0.2, 32).astype(np.float32)
    dag = DagPipeline([
        ([fir_stage(taps, name="prod")], []),
        ([fir_stage(taps, name="a")], [0]),
        ([fir_stage(taps, name="b")], [0]),
        ([add_merge_stage(2), mag2_stage()], [1, 2]),
    ], np.complex64)
    r = graph_roofline(dag, 1 << 14, backend="cpu")
    assert [n["inputs"] for n in r["nodes"]] == [[], [0], [0], [1, 2]]
    assert r["nodes"][3]["name"] == "add_merge+mag2"
    total = sum(n["flops_per_sample"] for n in r["nodes"])
    assert abs(total - r["flops_per_sample"]) < 1e-6
    # the two interior FIR branches cost the same program delta
    assert r["nodes"][1]["flops_per_sample"] == \
        pytest.approx(r["nodes"][2]["flops_per_sample"], rel=0.2)
    assert "mfu" not in r                       # cpu backend: no known peak


def test_cost_of_signature_cache_reuses_records():
    """cost_of caches by signature: the second ask never compiles (callable
    untouched), and an already-compiled executable can seed the record."""
    from futuresdr_tpu.utils.roofline import cost_of

    class _FakeCompiled:
        def cost_analysis(self):
            return {"flops": 42.0, "bytes accessed": 7.0}

    sig = ("test-cost-cache", id(object()))
    out = cost_of(None, signature=sig, compiled=_FakeCompiled())
    assert out == {"flops": 42.0, "bytes": 7.0}
    # cached: fn=None would explode if the cache missed
    assert cost_of(None, signature=sig) == out


def test_cost_of_bills_reason_cost():
    """An ACTUAL cost-analysis AOT compile bills
    fsdr_compiles_total{program="cost_analysis",reason="cost"}; cache hits
    and compiled= reuse bill nothing."""
    from futuresdr_tpu.telemetry import profile
    from futuresdr_tpu.utils.roofline import cost_of

    before = profile.COMPILES.get(program="cost_analysis", reason="cost")
    sig = ("test-cost-billing", id(object()))
    cost_of(lambda x: x + 1, np.zeros(8, np.float32), signature=sig)
    assert profile.COMPILES.get(program="cost_analysis",
                                reason="cost") == before + 1
    cost_of(None, signature=sig)          # cache hit: no new record
    assert profile.COMPILES.get(program="cost_analysis",
                                reason="cost") == before + 1


def test_program_cost_signature_disambiguates_stage_params():
    """Cost-cache signatures carry the structural stage fingerprint, not
    just names: fir stages with different tap counts / decimation (all
    named "fir") must not share one cost record."""
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.ops import fir_stage
    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.utils.roofline import _stage_marker, program_cost

    t64 = firdes.lowpass(0.2, 64).astype(np.float32)
    t256 = firdes.lowpass(0.2, 256).astype(np.float32)
    # the fingerprint separates tap count and decimation where the name
    # alone ("fir" for all three) would collide in the cache
    m64 = _stage_marker(fir_stage(t64))
    m256 = _stage_marker(fir_stage(t256))
    m256d = _stage_marker(fir_stage(t256, decim=4))
    assert len({m64, m256, m256d}) == 3
    # and a cost determinant that DOES change the program (decimation: 4x
    # fewer output samples) yields a different record, not the full-rate
    # pipeline's cached one
    frame = 1 << 12
    full = program_cost(Pipeline([fir_stage(t256)], np.complex64), frame)
    decim = program_cost(Pipeline([fir_stage(t256, decim=4)], np.complex64),
                         frame)
    assert decim["bytes"] < full["bytes"]
