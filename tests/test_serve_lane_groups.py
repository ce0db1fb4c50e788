"""A serving step's input crosses in LANE GROUPS (docs/serving.md "The
overlapped step"): each group with a riding lane is filled into its own
reused staging array and put on the wire while the next is filled; a group in
which no lane rides is neither filled nor shipped, and passes a device-resident
zero block to the one join that forms the program's input.

The reference of every bit-for-bit case is the WHOLE-BATCH form the engine
had before: the same frames stacked into one zeroed numpy batch and fed to
``build_slot_program`` (the served program, untouched). Not the bare pipeline:
a vmapped program rounds differently from the unbatched one on XLA:CPU.
"""

import time

import jax
import numpy as np
import pytest

from futuresdr_tpu.ops import xfer
from futuresdr_tpu.ops.stages import Pipeline, fir_stage, rotator_stage
from futuresdr_tpu.runtime import faults
from futuresdr_tpu.serve import engine as engine_mod
from futuresdr_tpu.serve.engine import ServeEngine, build_slot_program
from futuresdr_tpu.telemetry import spans

FRAME = 1024
G = 2                       # lanes per group in these tests
_apps = iter(range(10 ** 6))


@pytest.fixture(autouse=True)
def small_groups(monkeypatch):
    monkeypatch.setattr(engine_mod, "LANE_GROUP", G)


def _pipe():
    taps = np.hanning(31).astype(np.float32)
    return Pipeline([fir_stage(taps, fft_len=256), rotator_stage(0.03)],
                    np.complex64)


def _engine(capacity=8, k=1, inflight=1, frame=FRAME, queue=4):
    return ServeEngine(_pipe(), frame_size=frame, app=f"lg{next(_apps)}",
                       buckets=(capacity,), queue_frames=queue,
                       frames_per_dispatch=k, inflight=inflight)


def _frame(rng, frame=FRAME):
    return (rng.standard_normal(frame)
            + 1j * rng.standard_normal(frame)).astype(np.complex64)


class WholeBatch:
    """The whole-batch reference beside an engine: before each ``step()`` it
    reads what the step will pop, stacks it into one zeroed batch and runs
    ``build_slot_program`` on its own copy of the page pool."""

    def __init__(self, eng):
        self.eng = eng
        self.prog = build_slot_program(eng.pipeline, eng.capacity, eng.k_batch)
        self.pages = eng._pages

    def step(self) -> dict:
        """Run the reference for the engine's next step; returns
        ``{sid: [expected output per popped frame]}``."""
        eng, K = self.eng, self.eng.k_batch
        C = eng.capacity
        x = np.zeros((C, FRAME) if K == 1 else (C, K, FRAME), np.complex64)
        active = np.zeros((C,) if K == 1 else (C, K), bool)
        riders = {}
        for s in eng.table.occupants():
            frames = [f for f, _t in list(s.pending)[:K]]
            if not frames:
                continue
            riders[s.sid] = (s.slot, len(frames))
            for j, f in enumerate(frames):
                if K == 1:
                    x[s.slot], active[s.slot] = f, True
                else:
                    x[s.slot, j], active[s.slot, j] = f, True
        fresh = np.zeros((C,), bool)
        fresh[[l for l in eng._fresh_lanes if l < C]] = True
        pmap = np.asarray(eng.table.page_of_lane, np.int32)
        self.pages, (y,) = self.prog(self.pages, pmap, fresh, x, active)
        y = np.asarray(y)
        return {sid: [y[lane] if K == 1 else y[lane, j] for j in range(n)]
                for sid, (lane, n) in riders.items()}

    def check(self, want: dict) -> None:
        """The engine delivered ``want`` and holds the reference's pages."""
        for sid, outs in want.items():
            got = self.eng.results(sid)
            assert len(got) == len(outs), sid
            for a, b in zip(got, outs):
                np.testing.assert_array_equal(a, b)
        for a, b in zip(jax.tree_util.tree_leaves(self.eng._pages),
                        jax.tree_util.tree_leaves(self.pages)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _run(eng, ref, sids, plan, rng):
    """``plan``: per step, the lane indices (into ``sids``) that ride, or
    ``{index: n_frames}`` for a ragged megabatch."""
    for riding in plan:
        counts = riding if isinstance(riding, dict) else dict.fromkeys(riding, 1)
        for i, n in counts.items():
            for _ in range(n):
                assert eng.submit(sids[i], _frame(rng))
        want = ref.step()
        assert eng.step() == sum(counts.values())
        ref.check(want)


# -- (a) bit-equal to the whole-batch form --------------------------------------

@pytest.mark.parametrize("capacity,k,plan", [
    (8, 1, [range(8)] * 3),                                 # all lanes riding
    (8, 1, [[5], [5], [5]]),                                # one lane
    (8, 1, [[2, 3], [2], [3, 2]]),                          # lanes of one group
    (8, 1, [[0, 3, 4, 7], [1, 2, 5, 6], [0, 7]]),           # over all groups
    (1, 1, [[0]] * 3),                                      # C < G: one group
    (8, 2, [{0: 2, 3: 1, 6: 2}, {3: 2, 6: 1}, {0: 1}]),     # K = 2, ragged
], ids=["all", "one_lane", "one_group", "scattered", "c_lt_g", "k2_ragged"])
def test_lane_groups_bit_equal_whole_batch(capacity, k, plan):
    eng = _engine(capacity, k)
    try:
        sids = [eng.admit("t").sid for _ in range(capacity)]
        _run(eng, WholeBatch(eng), sids, plan, np.random.default_rng(1))
        assert eng.compiles == 1
    finally:
        eng.shutdown()


# -- (b) churn between steps -----------------------------------------------------

@pytest.mark.parametrize("event", ["join", "leave", "retune"])
def test_churn_between_steps_matches_whole_batch(event):
    eng = _engine(8)
    rng = np.random.default_rng(2)
    try:
        sids = [eng.admit("t").sid for _ in range(5)]
        ref = WholeBatch(eng)
        _run(eng, ref, sids, [range(5), [0, 4]], rng)
        if event == "join":
            sids.append(eng.admit("t").sid)         # a fresh lane, page map
            plan = [[5], range(6), [1, 5]]
        elif event == "leave":
            eng.close(sids[1])                      # its group mate rides on
            sids[1] = eng.admit("t").sid            # and the lane is re-bound
            plan = [[0], [0, 1], range(5)]
        else:
            eng.retune(sids[2], "rotator", phase_inc=0.11)
            ref.pages = eng._pages                  # the surgery is not under test
            plan = [[2], [2, 3], range(5)]
        _run(eng, ref, sids, plan, rng)
        assert eng.compiles == 1
    finally:
        eng.shutdown()


# -- (c) stale staging rows are masked ------------------------------------------

def test_poisoned_staging_never_reaches_carries_or_outputs():
    eng = _engine(8)
    rng = np.random.default_rng(3)
    try:
        sids = [eng.admit("t").sid for _ in range(8)]
        ref = WholeBatch(eng)
        _run(eng, ref, sids, [range(8)], rng)       # every staging array exists
        (free,) = eng._staging[(8, 1)]
        for buf in free:
            buf[...] = np.nan
        before = [np.asarray(l).copy()
                  for l in jax.tree_util.tree_leaves(eng._pages)]
        # lanes 0 and 5 ride: their group mates 1 and 4 are shipped as NaN
        assert eng.submit(sids[0], _frame(rng))
        assert eng.submit(sids[5], _frame(rng))
        want = ref.step()
        assert eng.step() == 2
        for sid, outs in want.items():
            (got,) = eng.results(sid)
            assert np.isfinite(got).all()
            np.testing.assert_array_equal(got, outs[0])
        pm = eng.table.page_of_lane
        for b, a in zip(before, jax.tree_util.tree_leaves(eng._pages)):
            a = np.asarray(a)
            for lane in (1, 2, 3, 4, 6, 7):
                np.testing.assert_array_equal(a[pm[lane]], b[pm[lane]])
        ref.check({})
        assert all(eng.results(s) == [] for s in sids)
    finally:
        eng.shutdown()


# -- (d) only riding groups cross -------------------------------------------------

@pytest.mark.parametrize("riding,groups", [
    ([0], 1), ([0, 1], 1), ([1, 6], 2), (list(range(8)), 4)])
def test_only_riding_groups_cross_the_link(riding, groups):
    eng = _engine(8)
    rng = np.random.default_rng(4)
    h2d = lambda: xfer._XFER_BYTES.get(direction="h2d")     # noqa: E731
    try:
        sids = [eng.admit("t").sid for _ in range(8)]
        for i in range(8):                          # warm-up: zero block is up
            assert eng.submit(sids[i], _frame(rng))
        assert eng.step() == 8
        for i in riding:
            assert eng.submit(sids[i], _frame(rng))
        b0, g0 = h2d(), eng.groups_shipped
        assert eng.step() == len(riding)
        small = 8 * 1 + 8 * 4 + 8 * 1               # active, page map, fresh
        assert h2d() - b0 == groups * G * FRAME * 8 + small
        assert eng.groups_shipped - g0 == groups
    finally:
        eng.shutdown()


# -- (e) nothing of batch size is allocated in a step ----------------------------

@pytest.mark.parametrize("riding", [[3], list(range(8))])
def test_warm_step_allocates_nothing_of_batch_size(monkeypatch, riding):
    eng = _engine(8)
    rng = np.random.default_rng(5)
    try:
        sids = [eng.admit("t").sid for _ in range(8)]
        for i in range(8):
            assert eng.submit(sids[i], _frame(rng))
        assert eng.step() == 8
        shapes, zeros = [], np.zeros

        def recording_zeros(shape, *a, **kw):
            shapes.append(tuple(np.atleast_1d(shape)))
            return zeros(shape, *a, **kw)

        monkeypatch.setattr(np, "zeros", recording_zeros)
        for i in riding:
            assert eng.submit(sids[i], _frame(rng))
        assert eng.step() == len(riding)
        monkeypatch.undo()
        assert shapes, "the step's small vectors are np.zeros"
        assert max(int(np.prod(s)) for s in shapes) < G * FRAME
    finally:
        eng.shutdown()


# -- (f) which lanes ride compiles nothing ---------------------------------------

@pytest.fixture
def backend_compiles():
    """Every XLA program built from here on, as chip_smoke.CompileMeter
    counts them."""
    events = []

    def on(event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            events.append(str(kw.get("fun_name", "?")))

    jax.monitoring.register_event_duration_secs_listener(on)
    yield events
    jax.monitoring.unregister_event_duration_listener(on)


@pytest.mark.parametrize("k", [1, 2])
def test_random_riding_sets_compile_nothing(backend_compiles, k):
    eng = _engine(8, k)
    rng = np.random.default_rng(6)
    try:
        sids = [eng.admit("t").sid for _ in range(8)]
        for riding in (range(8), [0]):              # warm-up: both join inputs
            for i in riding:
                assert eng.submit(sids[i], _frame(rng))
            assert eng.step()
        mark = len(backend_compiles)
        for _ in range(50):
            riding = np.flatnonzero(rng.random(8) < rng.random()) \
                if rng.random() < 0.9 else np.arange(8)
            for i in riding:
                assert eng.submit(sids[i], _frame(rng))
            assert eng.step() == len(riding)
        assert backend_compiles[mark:] == []
        assert eng.compiles == 1
    finally:
        eng.shutdown()


# -- (g) a fault on the second group rolls the step back -------------------------

def test_h2d_fault_on_second_group_rolls_back_and_retries_bit_equal(monkeypatch):
    eng = _engine(8)
    rng = np.random.default_rng(7)
    fill = ServeEngine._fill_group
    armed = []

    def fill_then_arm(buf, riders, base):
        fill(buf, riders, base)
        if base == G and not armed:                 # the 2nd group's put fails
            armed.append(faults.arm("h2d", rate=1.0, max_faults=1,
                                    transient=False))

    try:
        sids = [eng.admit("t").sid for _ in range(8)]
        ref = WholeBatch(eng)
        _run(eng, ref, sids, [range(8)], rng)
        monkeypatch.setattr(ServeEngine, "_fill_group",
                            staticmethod(fill_then_arm))
        for i in range(8):
            assert eng.submit(sids[i], _frame(rng))
        want = ref.step()
        with pytest.raises(faults.InjectedFault):
            eng.step()
        assert armed[0].fired == 1
        assert eng.dispatches == 1 and not eng._inflight
        assert all(len(eng.table.sessions[s].pending) == 1 for s in sids)
        assert len(eng._staging[(8, 1)]) == 1       # the set came back
        assert eng.step() == 8                      # the retry: same frames
        ref.check(want)
    finally:
        faults.disarm()
        eng.shutdown()


# -- (h) the staging hazard under serve_inflight 2 -------------------------------

def test_inflight_2_never_rewrites_a_staging_array_in_flight(monkeypatch):
    eng = _engine(8, inflight=2, queue=8)
    rng = np.random.default_rng(8)
    fill = ServeEngine._fill_group
    overlapped = []

    def checked_fill(buf, riders, base):
        held = [b for g in eng._inflight for b in g.staging or ()]
        assert not any(b is buf for b in held), \
            "a staging array was rewritten before its group committed"
        overlapped.append(len(eng._inflight))
        fill(buf, riders, base)

    monkeypatch.setattr(ServeEngine, "_fill_group", staticmethod(checked_fill))
    try:
        sids = [eng.admit("t").sid for _ in range(8)]
        feeds = {s: [_frame(rng) for _ in range(6)] for s in sids}
        solo = _engine(8)                           # the same frames at depth 1
        try:
            ssids = [solo.admit("t").sid for _ in range(8)]
            for t in range(6):
                for s, ss in zip(sids, ssids):
                    assert solo.submit(ss, feeds[s][t])
                assert solo.step() == 8
            expect = {s: solo.results(ss) for s, ss in zip(sids, ssids)}
        finally:
            solo.shutdown()
        for t in range(6):
            for s in sids:
                assert eng.submit(s, feeds[s][t])
            assert eng.step() == 8
        while eng.step():
            pass
        assert max(overlapped) >= 1, "no launch overlapped an older group"
        for s in sids:
            got = eng.results(s)
            assert len(got) == 6
            for a, b in zip(got, expect[s]):
                np.testing.assert_array_equal(a, b)
        assert 1 <= len(eng._staging[(8, 1)]) <= 3  # N + 1 sets at most
    finally:
        eng.shutdown()


# -- (i) fill and wire overlap ---------------------------------------------------

def test_fill_and_wire_overlap_under_a_fake_link(monkeypatch):
    """Fill slowed to 15 ms a group, the link to 15 ms a group: four groups
    take ~75 ms when each group's bytes cross during the next one's fill,
    ~120 ms in series."""
    per_group_s, n_groups = 0.015, 4
    eng = _engine(8)
    rng = np.random.default_rng(9)
    fill = ServeEngine._fill_group

    def slow_fill(buf, riders, base):
        fill(buf, riders, base)
        time.sleep(per_group_s)

    try:
        sids = [eng.admit("t").sid for _ in range(8)]
        for i in range(8):
            assert eng.submit(sids[i], _frame(rng))
        assert eng.step() == 8                      # compiles
        monkeypatch.setattr(ServeEngine, "_fill_group",
                            staticmethod(slow_fill))
        prev = xfer.set_fake_link(h2d_bps=G * FRAME * 8 / per_group_s)
        try:
            walls = []
            for _ in range(3):
                for i in range(8):
                    assert eng.submit(sids[i], _frame(rng))
                t0 = time.perf_counter()
                assert eng.step() == 8
                walls.append(time.perf_counter() - t0)
        finally:
            xfer._fake_link = prev
        fill_s = wire_s = n_groups * per_group_s
        assert min(walls) >= max(fill_s, wire_s)
        assert min(walls) < 0.85 * (fill_s + wire_s), walls
    finally:
        eng.shutdown()


# -- (j) the counters -------------------------------------------------------------

@pytest.fixture
def tracing():
    rec = spans.recorder()
    was = rec.enabled
    rec.enabled = True
    rec.drain()
    yield rec
    rec.enabled = was
    rec.drain()


def _settled(rec, want, timeout=3.0):
    """Drain until ``want`` spans have arrived: the watcher stamps late."""
    evs, deadline = [], time.monotonic() + timeout
    while time.monotonic() < deadline:
        evs += rec.drain()
        if {e.name for e in evs} >= want:
            break
        time.sleep(0.01)
    return evs


@pytest.mark.parametrize("riding,groups", [([6], 1), ([0, 2, 7], 3)])
def test_shipped_lane_counters(tracing, riding, groups):
    eng = _engine(8)
    rng = np.random.default_rng(10)
    shipped = lambda: engine_mod._LANES_SHIPPED.get(app=eng.app)  # noqa: E731
    try:
        sids = [eng.admit("t").sid for _ in range(8)]
        for i in range(8):
            assert eng.submit(sids[i], _frame(rng))
        assert eng.step() == 8
        _settled(tracing, {"H2D", "encode", "h2d_put"})
        assert (eng.lanes_shipped, eng.groups_shipped) == (8, 4)
        assert eng.describe()["shipped_lane_share"] == 1.0
        c0 = shipped()
        for i in riding:
            assert eng.submit(sids[i], _frame(rng))
        assert eng.step() == len(riding)
        evs = _settled(tracing, {"H2D", "encode", "h2d_put"})
        d = eng.describe()
        assert d["groups_shipped"] == 4 + groups
        assert d["lanes_shipped"] == 8 + groups * G == 8 + shipped() - c0
        assert d["shipped_lane_share"] == (8 + groups * G) / (2 * 8)
        for name in ("encode", "h2d_put", "H2D"):
            (e,) = [e for e in evs if e.name == name]
            assert e.args["lanes_shipped"] == groups * G, name
            assert e.args["groups_shipped"] == groups, name
            if name != "encode":
                assert e.args["bytes"] == groups * G * FRAME * 8 + 48
    finally:
        eng.shutdown()
