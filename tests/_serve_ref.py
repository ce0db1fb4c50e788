"""References for the serving tests (``tests/test_serve.py``,
``tests/test_serve_paged.py``, ``tests/test_serve_sc16.py``).

What those tests are about is ISOLATION and page bookkeeping: a session's
stream must not depend on who else rides, joins, leaves or where the pool
grows. The reference that isolates exactly that is the SAME slot program
(``build_slot_program`` at the same capacity) with the session's lane the
only active one: the engine must match it bit for bit. The bare unbatched
pipeline is a second, looser reference: a batched (vmapped) and an unbatched
XLA:CPU program reassociate the same arithmetic differently, so they agree
to 1 ulp of float32 at the frame's peak magnitude per chained stage that
rounds (an FFT-based FIR's error follows the frame's scale, not the
sample's), not bit for bit (capacity 1 happens to be bit-equal; capacity 2
and 4 are not, on jax 0.9 XLA:CPU)."""

import jax
import numpy as np

from futuresdr_tpu.serve.engine import build_slot_program, serve_wire


class SoloSlot:
    """One session's stream through the served slot program ALONE: every
    other lane masked. ``run`` may be called at several capacities in turn
    (a page-pool growth): the lane's carry page moves with it."""

    def __init__(self, pipeline, frame_size: int, lane: int, wire=None):
        self.pipe, self.frame, self.lane = pipeline, frame_size, lane
        self.carry = None                 # the lane's page after the last run
        #: an engine built on a wire: frames are its ``uint32`` words
        self.wire = serve_wire(wire, pipeline.in_dtype)
        self.dtype = np.uint32 if self.wire is not None else np.complex64

    def run(self, capacity: int, frames) -> list:
        prog = build_slot_program(self.pipe, capacity, wire=self.wire)
        template = self.pipe.init_carry()
        pages = jax.tree_util.tree_map(
            lambda l: np.stack([np.asarray(l)] * capacity), template)
        fresh = np.zeros((capacity,), bool)
        if self.carry is None:
            fresh[self.lane] = True       # as the engine admits a session
        else:
            def put(pool, page):
                pool[self.lane] = page
                return pool
            pages = jax.tree_util.tree_map(put, pages, self.carry)
        pmap = np.arange(capacity, dtype=np.int32)
        active = np.zeros((capacity,), bool)
        active[self.lane] = True
        out = []
        for f in frames:
            x = np.zeros((capacity, self.frame), self.dtype)
            x[self.lane] = f
            pages, ys = prog(pages, pmap, fresh, x, active)
            fresh = np.zeros((capacity,), bool)
            rows = tuple(np.asarray(y)[self.lane] for y in ys)
            out.append(rows[0] if len(rows) == 1 else rows)
        self.carry = jax.tree_util.tree_map(
            lambda l: np.asarray(l)[self.lane], pages)
        return out


def assert_bit_equal(got, want) -> None:
    """Streams of frames (an array, or a tuple of arrays per sink)."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        if isinstance(w, tuple):
            assert isinstance(g, tuple) and len(g) == len(w)
            for a, b in zip(g, w):
                np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_array_equal(g, w)


def assert_within_ulps(got, want, ulps: int = 1) -> None:
    """Every component of every sample within ``ulps`` ulp of float32 at the
    frame's peak magnitude: what a batched and an unbatched XLA:CPU program
    of the same arithmetic differ by (module docstring)."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for a, b in zip(g if isinstance(w, tuple) else (g,),
                        w if isinstance(w, tuple) else (w,)):
            a, b = np.asarray(a), np.asarray(b)
            assert a.shape == b.shape and a.dtype == b.dtype
            ulp = np.spacing(np.float32(np.max(np.abs(b))))
            d = a - b
            worst = max(np.max(np.abs(d.real)), np.max(np.abs(d.imag))) / ulp
            assert worst <= ulps, float(worst)
