#!/usr/bin/env python
"""perf/sp — sequence-parallel stream-op scaling probe.

Measures the halo-exchange ops (`parallel.stream_sp`) per mesh size: sp_fir,
the fused sp_fir_fft_mag2 chain, and sp_dechirp_scan. Runs on the attached
devices (mesh sizes beyond them are skipped), where it shows ICI scaling;
``--virtual-mesh`` runs it on virtual CPU devices instead, where the numbers
only characterize overhead (one ppermute per frame vs local compute). Rates are measured with a
jitted steady-state loop after a warmup compile.

CSV: ``op,devices,frame,msamples_per_sec``.
"""

import argparse
import sys
import time

sys.path.insert(0, ".")
sys.path.insert(0, "..")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--devices", type=int, nargs="+", default=[2, 4, 8])
    p.add_argument("--per-shard", type=int, default=1 << 16)
    p.add_argument("--taps", type=int, default=64)
    p.add_argument("--fft", type=int, default=2048)
    p.add_argument("--sf", type=int, default=7)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--virtual-mesh", action="store_true",
                   help="run on max(--devices) virtual CPU devices instead of "
                        "the attached chips (overhead characterization only)")
    a = p.parse_args()

    import jax
    import numpy as np
    from futuresdr_tpu.parallel import (NamedSharding, P, make_mesh, sp_fir,
                                        sp_fir_fft_mag2, sp_dechirp_scan,
                                        virtual_cpu_mesh)
    if a.virtual_mesh:
        virtual_cpu_mesh(max(a.devices))

    print("op,devices,frame,msamples_per_sec")
    rng = np.random.default_rng(0)
    taps = np.hanning(a.taps).astype(np.float32)
    for nd in a.devices:
        if nd > len(jax.devices()):
            print(f"# skipping devices={nd}", file=sys.stderr)
            continue
        mesh = make_mesh(("sp",), shape=(nd,), devices=jax.devices()[:nd])
        frame = nd * a.per_shard
        x = (rng.standard_normal(frame) + 1j * rng.standard_normal(frame)
             ).astype(np.complex64)
        xs = jax.device_put(x, NamedSharding(mesh, P("sp")))
        for name, fn in (("sp_fir", sp_fir(taps, mesh)),
                         ("sp_fir_fft_mag2",
                          sp_fir_fft_mag2(taps, a.fft, mesh)),
                         ("sp_dechirp_scan", sp_dechirp_scan(a.sf, mesh))):
            jf = jax.jit(fn)
            jax.block_until_ready(jf(xs))            # compile
            t0 = time.perf_counter()
            for _ in range(a.reps):
                jax.block_until_ready(jf(xs))
            dt = (time.perf_counter() - t0) / a.reps
            print(f"{name},{nd},{frame},{frame / dt / 1e6:.1f}", flush=True)


if __name__ == "__main__":
    main()
