"""run_marginal: the honest scan-marginal throughput harness (docs/tpu_notes.md)."""
import numpy as np

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
