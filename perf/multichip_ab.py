#!/usr/bin/env python
"""perf/multichip_ab — structural gate of the mesh-sharded device plane.

Runs the DATA-sharded fused program (``futuresdr_tpu/shard``) at D = 8 on a
VIRTUAL 8-device CPU mesh (``parallel.virtual_cpu_mesh``, chosen plainly at
start-up: the gate needs more devices than one host holds) and asserts what
holds on any backend: the data-sharded program is bit-identical per row to
the D=1 program at matched K, ONE dispatch per group regardless of D (the
per-shard dispatch count never multiplies), and the compiled HLO carries
ZERO cross-shard collectives (interior edges never leave their shard). It
times nothing: no cell of ``BENCHMARK.json`` runs across chips yet
(ROADMAP D8), so a scaling figure is "not measured".

Usage:
  python perf/multichip_ab.py --smoke          # the check.sh gate
"""

import argparse
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

DMAX = 8


def _chain():
    import numpy as np

    from futuresdr_tpu.ops.stages import (Pipeline, fft_stage, fir_stage,
                                          mag2_stage)
    # the resident receiver-interior shape (fir -> fft -> |x|^2)
    return Pipeline([fir_stage(np.hanning(64).astype(np.float32)),
                     fft_stage(2048), mag2_stage()], np.complex64)


def _structural_asserts() -> None:
    """The gate's invariants (module docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from futuresdr_tpu.shard import (ShardRunner, ShardedProgram,
                                     collective_ops, plan_shard)
    pipe = _chain()
    D, K, F = min(DMAX, len(jax.devices())), 2, 4096
    prog = ShardedProgram(pipe, plan_shard(pipe, mode="data", n_devices=D),
                          name="multichip_smoke")
    # 1. zero cross-shard collectives: interior edges never leave the shard
    colls = collective_ops(prog.compiled_text(F, K))
    assert not colls, f"data-sharded program has collectives: {colls}"
    # 2. per-shard dispatch count: groups dispatch ONCE, never x D; and the
    #    gathered output is bit-identical per row to the D=1 program at
    #    matched K
    runner = ShardRunner(prog, F, k=K, name="multichip_smoke")
    rng = np.random.default_rng(1)
    groups = [(rng.standard_normal((D, K, F))
               + 1j * rng.standard_normal((D, K, F))).astype(np.complex64)
              for _ in range(3)]
    outs = [runner.run_group(g) for g in groups]
    assert runner.dispatches == len(groups), \
        (runner.dispatches, len(groups))
    inner = pipe.fn()
    ref_fn = jax.jit(lambda c, xs: jax.lax.scan(
        lambda cc, xk: inner(cc, xk), c, xs))
    for d in range(D):
        c = pipe.init_carry()
        for g, got in zip(groups, outs):
            c, y = ref_fn(c, jnp.asarray(g[d]))
            assert np.array_equal(np.asarray(y), got[d]), \
                f"shard {d} diverged from the D=1 program"
    print(f"# structural: zero collectives, {runner.dispatches} dispatches "
          f"for {len(groups)} groups at D={D}, bit-equal vs D=1 — OK")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="the structural asserts (the only mode)")
    ap.parse_args(argv)

    # this harness IS the virtual-mesh run: it needs DMAX devices, more than
    # any one host holds
    from futuresdr_tpu.parallel import virtual_cpu_mesh
    virtual_cpu_mesh(DMAX)
    os.environ.setdefault("FUTURESDR_TPU_AUTOTUNE_CACHE_DIR", "off")
    _structural_asserts()
    print("multichip_ab smoke OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
