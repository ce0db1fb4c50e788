#!/usr/bin/env python
"""perf/pp — pipeline-parallel scaling probe (GPipe bubble efficiency).

Measures `make_pp_pipeline` throughput vs microbatch count: the schedule has
``n_micro + n_stages - 1`` ticks for ``n_micro`` microbatches of work, so the
ideal efficiency is ``M / (M + S - 1)`` — the probe reports measured vs ideal
so pipeline regressions (extra collectives, broken overlaps) show up as an
efficiency gap rather than a silent slowdown.

CSV: ``stages,micro,ideal_eff,msamples_per_sec``; with ``--flowgraph``, extra
``flowgraph,stages,micro,frames,msamples_per_sec`` rows run PpKernel through
the actor runtime (stream buffers + microbatching around the same mesh
program).
"""

import argparse
import sys
import time

sys.path.insert(0, ".")
sys.path.insert(0, "..")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--virtual-mesh", action="store_true",
                   help="run on max(--stages) virtual CPU devices instead of "
                        "the attached chips (stage counts beyond the devices "
                        "are skipped)")
    p.add_argument("--stages", type=int, nargs="+", default=[4, 8])
    p.add_argument("--micro", type=int, nargs="+", default=[2, 8, 32])
    p.add_argument("--width", type=int, default=256)
    p.add_argument("--mb", type=int, default=64, help="rows per microbatch")
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--flowgraph", action="store_true",
                   help="also run PpKernel through the actor runtime")
    a = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from futuresdr_tpu.parallel import (NamedSharding, P, make_mesh,
                                        make_pp_pipeline, virtual_cpu_mesh)
    if a.virtual_mesh:
        virtual_cpu_mesh(max(a.stages))

    print("stages,micro,ideal_eff,msamples_per_sec")
    rng = np.random.default_rng(0)
    d = a.width
    for S in a.stages:
        if S > len(jax.devices()):
            print(f"# skipping stages={S}: only {len(jax.devices())} devices",
                  file=sys.stderr)
            continue
        mesh = make_mesh(("pp",), shape=(S,), devices=jax.devices()[:S])
        W = jax.device_put(
            (rng.standard_normal((S, d, d)) / np.sqrt(d)).astype(np.float32),
            NamedSharding(mesh, P("pp")))
        for M in a.micro:
            fn = jax.jit(make_pp_pipeline(
                lambda w, x: jnp.tanh(x @ w), S, M, mesh))
            xm = jnp.asarray(rng.standard_normal((M, a.mb, d)),
                             dtype=jnp.float32)
            jax.block_until_ready(fn(W, xm))          # compile
            t0 = time.perf_counter()
            for _ in range(a.reps):
                y = fn(W, xm)
            jax.block_until_ready(y)
            dt = (time.perf_counter() - t0) / a.reps
            rate = M * a.mb * d / dt / 1e6
            print(f"{S},{M},{M / (M + S - 1):.3f},{rate:.1f}", flush=True)

    if a.flowgraph:
        # the same pipeline THROUGH the actor runtime: PpKernel streams frames
        # from a flowgraph (ring buffer -> microbatch -> pp mesh -> ring)
        from futuresdr_tpu import Flowgraph, Runtime
        from futuresdr_tpu.blocks import Head, NullSink, NullSource
        from futuresdr_tpu.tpu import PpKernel

        print("# flowgraph PpKernel rows: stages,micro,frames,msamples_per_sec",
              file=sys.stderr)
        for S in a.stages:
            if S > len(jax.devices()):
                print(f"# skipping flowgraph stages={S}: only "
                      f"{len(jax.devices())} devices", file=sys.stderr)
                continue
            mesh = make_mesh(("pp",), shape=(S,), devices=jax.devices()[:S])
            Wh = (rng.standard_normal((S, d, d)) / np.sqrt(d)).astype(np.float32)
            M = a.micro[-1]
            frame_items = M * a.mb * d
            # enough frames that actor spawn/teardown amortizes below ~10%
            n_frames = max(16, 4 * a.reps)
            fg = Flowgraph()
            src = NullSource(np.float32)
            head = Head(np.float32, n_frames * frame_items)
            ppk = PpKernel(lambda w, x: jnp.tanh(x @ w), Wh, mesh,
                           np.float32, np.float32, micro_shape=(a.mb, d),
                           n_micro=M)
            snk = NullSink(np.float32)
            fg.connect(src, head, ppk, snk)
            ppk.warmup()       # compile outside the timed region, through
            #                      the real dispatch path (raw rows also time
            #                      post-compile)
            t0 = time.perf_counter()
            Runtime().run(fg)
            dt = time.perf_counter() - t0
            print(f"flowgraph,{S},{M},{n_frames},"
                  f"{n_frames * frame_items / dt / 1e6:.1f}", flush=True)


if __name__ == "__main__":
    main()
