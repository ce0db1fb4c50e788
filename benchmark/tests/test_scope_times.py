"""tools/scope_times.py: the instruction -> scope map on hand-made HLO text,
and the per-scope table on the two recorded traces (half a second of each
configuration on the v5e, PR 24, with the op maps written in the same chip
call)."""

import importlib.util
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
RECORDED = BENCH / "recorded"

spec = importlib.util.spec_from_file_location(
    "scope_times", BENCH / "tools" / "scope_times.py")
scope_times = importlib.util.module_from_spec(spec)
spec.loader.exec_module(scope_times)

SCOPES = {"fir", "fft2048", "wire_decode", "tuner"}


@pytest.mark.parametrize("op_name,want", [
    ("jit(run_packed)/jit(main)/wire_decode/convert_element_type",
     "wire_decode"),
    ("jit(step)/vmap(tuner)/mul", "tuner"),
    ("jit(step)/vmap(jvp(tuner))/mul", "tuner"),
    ("jit(run)/fir/jit(_conv)/fft2048/dot", "fir"),      # the outermost wins
    ("jit(step)/select_n", scope_times.OTHER),
    ("jit(step)/tuner_not/mul", scope_times.OTHER),
])
def test_scope_of(op_name, want):
    assert scope_times.scope_of(op_name, SCOPES) == want


HLO = '''HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/vmap(tuner)/mul"}
  %add.1 = f32[8]{0} add(%mul.1, %p0), metadata={op_name="jit(step)/vmap(tuner)/add"}
  ROOT %neg.1 = f32[8]{0} negate(%add.1), metadata={op_name="jit(step)/fir/neg"}
}

%body (c: f32[8]) -> f32[8] {
  %c = f32[8]{0} parameter(0)
  ROOT %sin.3 = f32[8]{0} sine(%c), metadata={op_name="jit(step)/fft2048/sin"}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/wire_decode/mul"}
  %while.1 = f32[8]{0} while(%fusion.1), condition=%body, body=%body
  %reshape.7 = f32[4,2]{1,0} reshape(%fusion.2), backend_config={"x":[]}
  %or_fusion = f32[4]{0} fusion(%reshape.7, %x), kind=kLoop, calls=%bare
  ROOT %copy.4 = f32[8]{0} copy(%x)
}

%bare (q: f32[4,2]) -> f32[4] {
  %q = f32[4,2]{1,0} parameter(0)
  ROOT %r = f32[4]{0} reduce(%q), dimensions={1}
}
'''


def test_opmap_from_hlo_votes_for_fusions_without_a_scope_of_their_own():
    m = scope_times.opmap_from_hlo(HLO, SCOPES)
    assert m["mul.1"] == "tuner" and m["neg.1"] == "fir"
    assert m["fusion.1"] == "tuner"             # 2 of its 3 scoped ops
    assert m["fusion.2"] == "wire_decode"       # its own metadata wins
    assert m["while.1"] == "fft2048"            # through body/condition
    assert m["x"] == scope_times.OTHER and m["copy.4"] == scope_times.OTHER
    # no metadata anywhere: the scope of what made the first operand
    assert m["reshape.7"] == "wire_decode" and m["or_fusion"] == "wire_decode"


#: recorded in one chip call each with its op map; a pair that is not in the
#: tree (no chip was free when the tool was written) is no case
RECORDED_PAIRS = [
    (cell, config, ms) for cell, config, ms in (
        ("spectrum_sat", "spectrum_fir64_fft2048", 1.3),
        ("fm_serve_sat", "fm_serve_1msps", 12.0))
    if (RECORDED / f"{cell}_scoped.xplane.pb.gz").is_file()
    and (RECORDED / f"{config}_scoped.opmap.json").is_file()]


@pytest.mark.parametrize("cell,config,ms_per_run", RECORDED_PAIRS)
def test_scope_table_on_the_recorded_traces(cell, config, ms_per_run):
    opmap = json.loads((RECORDED / f"{config}_scoped.opmap.json").read_text())
    table = scope_times.scope_table(
        str(RECORDED / f"{cell}_scoped.xplane.pb.gz"), opmap)
    main = table[opmap["program"]]
    assert main["runs"] >= 10
    assert main["module_ms_per_run"] == pytest.approx(ms_per_run, rel=0.1)
    # a program's ops fill its time on the device, and every op has a row
    assert main["op_self_ms_per_run"] == pytest.approx(
        main["module_ms_per_run"], rel=0.05)
    assert sum(v["share"] for v in main["scopes"].values()) == \
        pytest.approx(1.0)
    named = {s: v for s, v in main["scopes"].items()
             if s != scope_times.OTHER}
    assert set(named) <= set(opmap["scopes"])
    # every stage of the chain shows, and the named scopes hold the time
    stages = [s for s in opmap["scopes"]
              if s not in ("unpack", "serve_gather", "serve_scatter")]
    assert all(s in named for s in stages), (stages, sorted(named))
    assert sum(v["share"] for v in named.values()) > 0.9


def test_scope_table_on_pr22s_recorded_trace_with_a_hand_made_map():
    """The table's arithmetic on a real device plane: PR 22's half second of
    ``spectrum_sat`` (a program without scopes) and a map of two of its ops."""
    opmap = {"program": "jit_run_packed",
             "ops": {"reshape.123": "unpack",
                     "slice_reduce_fusion": "wire_decode"}}
    table = scope_times.scope_table(
        str(RECORDED / "spectrum_sat_half_second.xplane.pb.gz"), opmap)
    main = table["jit_run_packed"]
    assert main["runs"] == 149
    assert main["module_ms_per_run"] == pytest.approx(1.308, abs=0.01)
    assert main["op_self_ms_per_run"] == pytest.approx(
        main["module_ms_per_run"], rel=0.05)
    assert main["scopes"]["unpack"]["ms_per_run"] == pytest.approx(0.40, abs=0.01)
    assert main["scopes"]["wire_decode"]["ms_per_run"] == \
        pytest.approx(0.358, abs=0.01)
    assert sum(v["share"] for v in main["scopes"].values()) == \
        pytest.approx(1.0)
    assert main["top_ops"][0][:2] == ["unpack", "reshape.123"]
