"""Serving on the radio's wire (docs/serving.md "Frames on the radio's wire"):
a ``ServeEngine`` built with ``wire="sc16"`` takes ``uint32[frame]`` words, a
complex sample a word (I the low half, Q the high half, int16 each: the bytes
of interleaved little-endian int16 I/Q), keeps them words in the session
queue, the staging sets, on the link and in the resident zero block, and
decodes them inside the step's one program at the fixed count 2^-15.

The decode is exact, so everything behind it is the complex64 engine's
program: the references are that engine fed ``words * 2^-15``
(bit for bit), the benchmark configuration's float64 reference of the same 16
bits (``benchmark/configs/fm_serve_1msps_sc16.py``), and ``_serve_ref.SoloSlot``
(the same slot program with one lane riding) for churn.
"""

import json
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from futuresdr_tpu.apps.fm_receiver import front_end_stages
from futuresdr_tpu.ops.stages import Pipeline, apply_stage
from futuresdr_tpu.serve import engine as engine_mod
from futuresdr_tpu.serve.engine import ServeEngine, build_slot_program, serve_wire

from _serve_ref import SoloSlot, assert_bit_equal

BENCH = Path(__file__).resolve().parents[1] / "benchmark"
sys.path.insert(0, str(BENCH))
from harness import cells                                           # noqa: E402

CM = cells.load_module(BENCH / "configs" / "fm_serve_1msps_sc16.py")
CFG = json.loads((BENCH / "configs" / "fm_serve_1msps_sc16.json").read_text())

FRAME = 2000
CAP = 4
_apps = iter(range(10 ** 6))


def _pipe():
    return Pipeline(front_end_stages(), np.complex64)


def _engine(wire="sc16", buckets=(CAP,), pipe=None, frame=FRAME):
    return ServeEngine(pipe or _pipe(), frame_size=frame,
                       app=f"sc16_{next(_apps)}", buckets=buckets,
                       queue_frames=4, wire=wire)


def _as_c64(words: np.ndarray) -> np.ndarray:
    """``words * 2^-15`` as complex64: a 16-bit count times a power of two
    is exact in float32."""
    q = np.ascontiguousarray(words).view(np.int16).reshape(words.shape + (2,))
    s = np.float32(2.0 ** -15)
    return (q[..., 0].astype(np.float32) * s
            + 1j * (q[..., 1].astype(np.float32) * s)).astype(np.complex64)


def _stations(seed: int, n: int = CAP) -> list:
    """``n`` listeners' stations as the cell makes them: ``[2, FRAME]`` words."""
    return [CM.lane_signal(CFG, seed, lane, FRAME) for lane in range(n)]


def _drain(eng, sids, bufs, frames):
    out = [[] for _ in sids]
    for t in range(frames):
        for sid, buf in zip(sids, bufs):
            assert eng.submit(sid, buf[t % len(buf)])
        assert eng.step() == len(sids)
        for i, sid in enumerate(sids):
            out[i] += eng.results(sid)
    return out


# -- (a) the audio: float64 reference, and the complex64 engine bit for bit -----

@pytest.mark.parametrize("seed", [1, 2_000_000_011, 3_999_999_979])
def test_audio_within_reference_and_bit_equal_complex64_engine(seed):
    bufs = _stations(seed)
    words, plain = _engine("sc16"), _engine(None)
    try:
        sw = [words.admit("t").sid for _ in bufs]
        sp = [plain.admit("t").sid for _ in bufs]
        got = _drain(words, sw, bufs, 6)
        twin = _drain(plain, sp, [_as_c64(b) for b in bufs], 6)
        for lane, (g, t, buf) in enumerate(zip(got, twin, bufs)):
            assert_bit_equal(g, t)
            x = np.concatenate([buf[k % 2] for k in range(6)])
            want = CM.reference(CFG, x)
            ok, err = CM.judge(CFG, np.concatenate(g), want)
            assert ok and err < CFG["correctness"]["abs_tolerance"], (lane, err)
        assert words.compiles == plain.compiles == 1
    finally:
        words.shutdown()
        plain.shutdown()


# -- (b) the decode is exact at the corners --------------------------------------

@pytest.mark.parametrize("i,q", [(32767, -32768), (-32768, 32767), (-1, 0),
                                 (0, -1), (1, 1), (0, 0)])
def test_decode_is_the_count_times_two_to_the_minus_15(i, q):
    eng = _engine("sc16", buckets=(1,), frame=8,
                  pipe=Pipeline([apply_stage(lambda x: x)], np.complex64))
    try:
        pairs = np.zeros((8, 2), np.int16)
        pairs[0] = (i, q)
        pairs[1:, 0] = np.arange(7) - 3
        sid = eng.admit("t").sid
        assert eng.submit(sid, pairs)
        assert eng.step() == 1
        (got,) = eng.results(sid)
        want = pairs[:, 0] * 2.0 ** -15 + 1j * (pairs[:, 1] * 2.0 ** -15)
        assert got.dtype == np.complex64
        np.testing.assert_array_equal(got, want.astype(np.complex64))
        assert got[0] == complex(i / 32768.0, q / 32768.0)
    finally:
        eng.shutdown()


# -- (c) churn under the wire, each against the slot program run solo ------------

def _solo(lane):
    return SoloSlot(_pipe(), FRAME, lane, wire="sc16")


@pytest.mark.parametrize("event", ["leave", "join", "retune", "growth",
                                   "masked_group"])
def test_churn_under_the_wire_matches_solo_slot(event, monkeypatch):
    bufs = _stations(7, 5)
    frames = [[b[k % 2] for k in range(6)] for b in bufs]
    if event == "masked_group":
        monkeypatch.setattr(engine_mod, "LANE_GROUP", 2)
    eng = _engine("sc16", buckets=(2, CAP) if event == "growth" else (CAP,))
    try:
        n0 = 2 if event == "growth" else 3
        ss = [eng.admit("t") for _ in range(n0)]
        lanes = [s.slot for s in ss]
        out = _drain(eng, [s.sid for s in ss], bufs[:n0], 3)
        if event == "leave":
            eng.close(ss[1].sid)
            live = [0, 2]
            out2 = _drain(eng, [ss[i].sid for i in live],
                          [frames[i][3:] for i in live], 3)
            for i, o in zip(live, out2):
                out[i] += o
            want = [_solo(lanes[0]).run(CAP, frames[0]),
                    _solo(lanes[1]).run(CAP, frames[1][:3]),
                    _solo(lanes[2]).run(CAP, frames[2])]
        elif event == "join":
            j = eng.admit("t")
            sids = [s.sid for s in ss] + [j.sid]
            out2 = _drain(eng, sids, [frames[i][3:] for i in range(3)]
                          + [frames[3][:3]], 3)
            out = [o + p for o, p in zip(out + [[]], out2)]
            want = [_solo(lanes[i]).run(CAP, frames[i]) for i in range(3)] \
                + [_solo(j.slot).run(CAP, frames[3][:3])]
        elif event == "retune":
            theta = 2 * np.pi * 10e3 / 1e6
            eng.retune(ss[1].sid, "tuner", phase_inc=theta)
            out2 = _drain(eng, [s.sid for s in ss],
                          [frames[i][3:] for i in range(3)], 3)
            out = [o + p for o, p in zip(out, out2)]
            want = []
            for i in range(3):
                solo = _solo(lanes[i])
                w = solo.run(CAP, frames[i][:3])
                if i == 1:
                    solo.carry = jax.tree_util.tree_map(
                        np.asarray, solo.pipe.update_stage(
                            solo.carry, "tuner", phase_inc=theta))
                want.append(w + solo.run(CAP, frames[i][3:]))
        elif event == "growth":
            j = eng.admit("t")                  # no free page: 2 -> 4
            assert eng.capacity == CAP
            sids = [s.sid for s in ss] + [j.sid]
            out2 = _drain(eng, sids, [frames[i][3:] for i in range(2)]
                          + [frames[2][:3]], 3)
            out = [o + p for o, p in zip(out + [[]], out2)]
            want = []
            for i in range(2):
                solo = _solo(lanes[i])
                want.append(solo.run(2, frames[i][:3])
                            + solo.run(CAP, frames[i][3:]))
            want.append(_solo(j.slot).run(CAP, frames[2][:3]))
            assert eng.compiles == 2
        else:
            # lanes 0 and 1 (group 0) stop riding: their group passes the
            # resident zero block of WORDS, lane 2 (group 1) rides on
            assert eng.uplink_word_parts() == 2
            g0 = eng.groups_shipped
            out[2] += _drain(eng, [ss[2].sid], [frames[2][3:]], 3)[0]
            assert eng.groups_shipped - g0 == 3
            (zero,) = eng._zero_parts.values()
            assert zero.dtype == np.uint32 and zero.shape == (2, FRAME)
            want = [_solo(lanes[0]).run(CAP, frames[0][:3]),
                    _solo(lanes[1]).run(CAP, frames[1][:3]),
                    _solo(lanes[2]).run(CAP, frames[2])]
        for o, w in zip(out, want):
            assert_bit_equal(o, w)
    finally:
        eng.shutdown()


# -- (d) what submit takes -------------------------------------------------------

@pytest.mark.parametrize("form", ["words", "pairs", "complex_into_wire",
                                  "words_into_samples", "short_into_wire",
                                  "float_kinds_into_samples"])
def test_submit_views_the_radios_bytes_and_refuses_the_other_kind(form):
    words = _stations(3, 1)[0][0]
    pairs = words.view(np.int16).reshape(FRAME, 2)
    wire = form not in ("words_into_samples", "float_kinds_into_samples")
    eng = _engine("sc16" if wire else None)
    try:
        s = eng.admit("t")
        if form in ("words", "pairs"):
            given = words if form == "words" else pairs
            assert eng.submit(s.sid, given)
            (queued, _t), = s.pending
            assert queued.dtype == np.uint32 and queued.shape == (FRAME,)
            assert np.shares_memory(queued, given)      # viewed, not copied
            assert eng.step() == 1
            view = eng.describe()
            assert view["wire"] == "sc16" and view["frame_dtype"] == "uint32"
            assert view["full_scale"] == 32768 and view["uplink_word_parts"] == 1
        elif form == "complex_into_wire":
            with pytest.raises(ValueError, match="sc16"):
                eng.submit(s.sid, _as_c64(words))
        elif form == "short_into_wire":
            with pytest.raises(ValueError):     # int16 that is not [frame, 2]
                eng.submit(s.sid, pairs.reshape(-1)[:FRAME])
        elif form == "words_into_samples":
            with pytest.raises(ValueError, match="complex64"):
                eng.submit(s.sid, words)
            view = eng.describe()
            assert view["wire"] == "raw" and view["frame_dtype"] == "complex64"
            assert view["full_scale"] is None and view["uplink_word_parts"] == 0
        else:
            # as before this PR: samples of another float kind are cast
            x = _as_c64(words)
            assert eng.submit(s.sid, x.astype(np.complex128))
            assert eng.submit(s.sid, x.real.astype(np.float32))
            assert [f.dtype for f, _t in s.pending] == [np.complex64] * 2
        assert not wire or all(f.dtype == np.uint32 for f, _t in s.pending)
    finally:
        eng.shutdown()


def test_only_sc16_under_a_complex_dtype_is_a_serving_wire():
    assert serve_wire(None, np.complex64) is None
    assert serve_wire("sc16", np.complex64).name == "sc16"
    for wire, dtype in (("sc8", np.complex64), ("f32", np.complex64),
                        ("bf16", np.complex64), ("sc16", np.float32)):
        with pytest.raises(ValueError, match="sc16"):
            ServeEngine(Pipeline([apply_stage(lambda x: x)], dtype),
                        frame_size=8, app="refused", buckets=(1,), wire=wire)


# -- (e), (f) the compiled step --------------------------------------------------

def _step_args(dtype, k=1):
    """Shapes of ``step(pages, page_map, fresh, x, active)`` at ``CAP`` lanes."""
    spec = jax.ShapeDtypeStruct
    pages = jax.tree_util.tree_map(
        lambda l: spec((CAP,) + np.shape(l), np.asarray(l).dtype),
        _pipe().init_carry())
    shape = (CAP, FRAME) if k == 1 else (CAP, k, FRAME)
    return (pages, spec((CAP,), np.int32), spec((CAP,), np.bool_),
            spec(shape, dtype), spec(shape[:-1], np.bool_))


def _lowered(wire, k=1):
    dtype = np.uint32 if wire else np.complex64
    return build_slot_program(_pipe(), CAP, k,
                              wire=serve_wire(wire, np.complex64)) \
        .lower(*_step_args(dtype, k))


def test_compiled_step_decodes_words_and_forms_no_pairs():
    low = _lowered("sc16")
    assert re.search(r"/wire_decode/", low.as_text(debug_info=True))
    # an array of two or more dimensions whose minor one is 2, as StableHLO
    # (tensor<4x2000x2xf32>) and as HLO (f32[4,2000,2]) write it: on a TPU
    # such an array is padded to 128 lanes (docs/tpu_notes.md)
    minor2 = re.compile(r"tensor<(?:\d+x)+2x[a-z]|[a-z]\w*\[(?:\d+,)+2\]")
    for text in (low.as_text(), low.compile().as_text()):
        hit = minor2.search(text)
        assert hit is None, text[max(0, hit.start() - 60):hit.end() + 20]
        assert "shift" in text


@pytest.mark.parametrize("k", [1, 2])
def test_engine_without_the_argument_compiles_the_parents_program(k):
    """``wire=None`` adds nothing to the trace: the step's text is the text
    of the program as the parent built it (the same builder called without
    the argument), and names no ``wire_decode``."""
    eng = _engine(None)
    try:
        low = eng._program(CAP, k).lower(*_step_args(np.complex64, k))
    finally:
        eng.shutdown()
    served = low.as_text()      # without locations: they name the caller's line
    assert served == _lowered(None, k).as_text()
    assert "wire_decode" not in low.as_text(debug_info=True)
    assert "shift" not in served and "ui32" not in served
    assert "wire_decode" in _lowered("sc16", k).as_text(debug_info=True)


# -- spans and the counter --------------------------------------------------------

@pytest.mark.parametrize("wire,itemsize", [("sc16", 4), (None, 8)])
def test_spans_and_counter_name_the_wire_and_its_bytes(wire, itemsize):
    import time

    from futuresdr_tpu.telemetry import spans
    name = wire or "raw"
    eng = _engine(wire)
    rec = spans.recorder()
    was = rec.enabled
    rec.enabled = True
    rec.drain()
    try:
        sids = [eng.admit("t").sid for _ in range(3)]
        bufs = _stations(5, 3)
        if wire is None:
            bufs = [_as_c64(b) for b in bufs]
        _drain(eng, sids, bufs, 2)
        want = CAP * FRAME * itemsize           # one lane group: the bucket
        got = {}
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and \
                not {"encode", "h2d_put", "H2D"} <= set(got):
            for ev in rec.drain():
                if ev.name in ("encode", "h2d_put", "H2D"):
                    got[ev.name] = ev.args
            time.sleep(0.01)
        assert {"encode", "h2d_put", "H2D"} <= set(got), sorted(got)
        for n, args in got.items():
            assert args["wire"] == name, (n, args)
        assert got["encode"]["bytes"] == want
        small = CAP * 1 + CAP * 4 + CAP * 1     # active, page map, fresh
        assert got["H2D"]["bytes"] == got["h2d_put"]["bytes"] == want + small
        assert eng.describe()["wire"] == name
    finally:
        rec.enabled = was
        rec.drain()
        eng.shutdown()
    assert engine_mod._WIRE_BYTES.get(app=eng.app, wire=name) == 2 * want
