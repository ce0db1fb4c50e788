#!/usr/bin/env python
"""Sequence-parallel spectrum over a device mesh — the multi-chip showcase.

One logical stream is TIME-SHARDED across every device on the mesh: each shard
filters its slice (halo samples ride ``ppermute`` from the left neighbour, so
the FIR is exact across shard edges and frame edges), FFTs locally, and the
|x|² spectra come back still sharded. Runs on the attached devices (the halo
crosses ICI); ``--virtual-mesh`` runs the identical program on virtual CPU
devices instead.

Reference role: this is the distribution story the reference delegates to
ZMQ/TCP blocks between processes (``examples/zeromq``), re-designed as ONE
sharded XLA program over the mesh (SURVEY §2.7 sequence parallelism).

Run: ``python examples/sharded_spectrum.py [--devices 4] [--frames 32]``
"""
import argparse
import sys
import time

sys.path.insert(0, ".")
sys.path.insert(0, "..")


def main(argv=None):
    """Returns the last frame's spectra as the (still sharded) device array."""
    p = argparse.ArgumentParser()
    p.add_argument("--devices", type=int, default=0,
                   help="mesh size (default: every attached device)")
    p.add_argument("--frames", type=int, default=32)
    p.add_argument("--fft", type=int, default=1024)
    p.add_argument("--frame-size", type=int, default=1 << 18)
    p.add_argument("--virtual-mesh", action="store_true",
                   help="run on --devices virtual CPU devices (default 8) "
                        "instead of the attached chips")
    a = p.parse_args(argv)

    import jax
    import numpy as np
    from futuresdr_tpu.dsp import firdes
    from futuresdr_tpu.parallel import (NamedSharding, P, make_mesh,
                                        sp_fir_fft_mag2_stream,
                                        virtual_cpu_mesh)
    if a.virtual_mesh:
        virtual_cpu_mesh(a.devices or 8)

    n_dev = a.devices or len(jax.devices())
    mesh = make_mesh(("sp",), shape=(n_dev,))       # refuses a short mesh
    taps = firdes.lowpass(0.2, 64).astype(np.float32)
    fn, init_carry = sp_fir_fft_mag2_stream(taps, a.fft, mesh)
    jfn = jax.jit(fn, donate_argnums=(0,))

    n = a.frame_size - (a.frame_size % (n_dev * a.fft))
    rng = np.random.default_rng(0)
    shard = NamedSharding(mesh, P("sp"))
    carry = init_carry(np.float32)

    # pre-generate frames OUTSIDE the timed window — the measurement is the
    # sharded mesh program, not host RNG + transfer (a small rotating pool so
    # XLA can't constant-fold a single repeated input)
    pool = [jax.device_put(rng.standard_normal(n).astype(np.float32), shard)
            for _ in range(4)]
    carry, y = jfn(carry, pool[0])        # warm/compile
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for k in range(a.frames):
        carry, y = jfn(carry, pool[k % len(pool)])
    jax.block_until_ready(y)
    dt = time.perf_counter() - t0

    spec = np.asarray(y).reshape(-1, a.fft)
    print(f"mesh: {n_dev} devices ('sp' axis), frame {n} samples, "
          f"{a.frames} frames")
    print(f"throughput: {a.frames * n / dt / 1e6:.1f} Msamples/s "
          f"({a.frames * n / dt / 1e6 / n_dev:.1f} per shard)")
    print(f"spectra: {spec.shape[0]} x {a.fft} bins, "
          f"peak bin power {spec.max():.1f}")
    return y


if __name__ == "__main__":
    main()
