#!/usr/bin/env python
"""perf/fir — the north-star sweep: pipes × stages of (CopyRand → 64-tap FIR).

Re-design of the reference's ``perf/fir/fir.rs:14-95``: builds a grid of ``pipes``
parallel chains, each ``stages`` deep, pushes ``samples`` float32 samples per pipe, and
emits a CSV row per run: ``run,pipes,stages,samples,max_copy,scheduler,elapsed_secs``.

Schedulers: ``async`` (default single-loop), ``threaded`` (pinned multi-worker,
FlowScheduler analog), or ``tpb`` (thread-per-block, GNU-Radio-style comparison).
Add ``--tpu`` to run each pipe's FIR fused on the TPU instead of
CPU blocks.
"""

import argparse
import sys
import time

sys.path.insert(0, ".")
sys.path.insert(0, "..")

import numpy as np

from futuresdr_tpu import Flowgraph, Runtime, AsyncScheduler, ThreadedScheduler, TpbScheduler
from futuresdr_tpu.blocks import NullSource, NullSink, Head, CopyRand, Fir
from futuresdr_tpu.dsp import firdes


def run_once(pipes: int, stages: int, samples: int, max_copy: int,
             scheduler: str, use_tpu: bool) -> float:
    taps = firdes.lowpass(0.2, 64).astype(np.float32)
    fg = Flowgraph()
    sinks = []
    for _ in range(pipes):
        src = NullSource(np.float32)
        head = Head(np.float32, samples)
        fg.connect(src, head)
        last = head
        if use_tpu:
            # TPU-first mapping: the whole pipe's FIR cascade fuses into ONE XLA
            # program (SURVEY §7.5 — fusing adjacent blocks is where TPU wins over
            # per-block dispatch)
            from futuresdr_tpu.ops import fir_stage
            from futuresdr_tpu.tpu import TpuKernel
            blk = TpuKernel([fir_stage(taps, name=f"fir{i}") for i in range(stages)],
                            np.float32, frame_size=1 << 18)
            fg.connect(last, blk)
            last = blk
        else:
            for _s in range(stages):
                cr = CopyRand(np.float32, max_copy)
                fir = Fir(taps, np.float32)
                fg.connect(last, cr, fir)
                last = fir
        snk = NullSink(np.float32)
        fg.connect(last, snk)
        sinks.append(snk)
    sched = {"threaded": ThreadedScheduler, "tpb": TpbScheduler,
             "async": AsyncScheduler}[scheduler]()
    rt = Runtime(sched)
    t0 = time.perf_counter()
    rt.run(fg)
    dt = time.perf_counter() - t0
    slack = (1 << 13) if use_tpu else 64 * stages + 1   # EOS frame-contract remainder
    for s in sinks:
        assert s.n_received >= samples - slack, s.n_received
    rt.shutdown()
    return dt


def run_device_resident(pipes: int, stages: int, frame_size: int,
                        k_pair=(256, 512)) -> float:
    """North-star grid mapped TPU-first: pipes = vmapped batch axis, the per-pipe
    FIR cascade = ONE fused XLA program (LTI merge collapses the 6 stages into a
    single combined filter), carry chained frame-to-frame (overlap-save history).

    This is the data-parallel row of SURVEY §2.7: independent pipes become a batch
    dimension of one kernel, not N scheduler tasks. CopyRand has no device-resident
    role (it stresses the host scheduler); the measurement is the compute chain, the
    ``utils/measure.run_marginal`` methodology: the frame loop rides in a
    ``lax.scan`` (one dispatch = K frames, checksum feedback defeats loop hoisting)
    and the reported rate is the marginal rate between the two K values, cancelling
    the constant dispatch latency (see docs/tpu_notes.md).
    """
    import jax
    import jax.numpy as jnp
    from futuresdr_tpu.ops import fir_stage
    from futuresdr_tpu.ops.stages import Pipeline
    from futuresdr_tpu.tpu.instance import instance
    from futuresdr_tpu.utils.measure import run_marginal

    taps = firdes.lowpass(0.2, 64).astype(np.float32)
    inst = instance()
    pipe = Pipeline([fir_stage(taps, name=f"fir{i}") for i in range(stages)],
                    np.float32)
    carry0 = jax.device_put(
        jax.tree.map(lambda c: jnp.broadcast_to(c, (pipes,) + c.shape),
                     pipe.init_carry()), inst.device)
    rng = np.random.default_rng(7)
    x = jax.device_put(rng.standard_normal((pipes, frame_size)).astype(np.float32),
                       inst.device)
    return run_marginal(jax.vmap(pipe.fn()), carry0, x, k_pair) / 1e6


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--runs", type=int, default=3)
    p.add_argument("--pipes", type=int, nargs="+", default=[5])
    p.add_argument("--stages", type=int, nargs="+", default=[6])
    p.add_argument("--samples", type=int, default=15_000_000)
    p.add_argument("--max-copy", type=int, default=4096)
    p.add_argument("--scheduler", choices=["async", "threaded", "tpb"], default="async")
    p.add_argument("--tpu", action="store_true")
    p.add_argument("--device-resident", action="store_true",
                   help="HBM-resident fused cascade, pipes as a vmapped batch axis")
    p.add_argument("--frame-size", type=int, default=1 << 19)
    a = p.parse_args()
    if a.device_resident:
        print("run,pipes,stages,frame_size,msps_total")
        for r in range(a.runs):
            for pipes in a.pipes:
                for stages in a.stages:
                    msps = run_device_resident(pipes, stages, a.frame_size)
                    print(f"{r},{pipes},{stages},{a.frame_size},{msps:.1f}",
                          flush=True)
        return
    print("run,pipes,stages,samples,max_copy,scheduler,elapsed_secs,msps_total")
    for r in range(a.runs):
        for pipes in a.pipes:
            for stages in a.stages:
                dt = run_once(pipes, stages, a.samples, a.max_copy,
                              a.scheduler, a.tpu)
                msps = pipes * a.samples / dt / 1e6
                print(f"{r},{pipes},{stages},{a.samples},{a.max_copy},"
                      f"{a.scheduler},{dt:.3f},{msps:.1f}", flush=True)


if __name__ == "__main__":
    main()
