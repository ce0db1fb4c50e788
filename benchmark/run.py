#!/usr/bin/env python3
"""One run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's files by name (``harness/cells.py``), runs its driver in this
one process on the attached TPU, and prints one JSON object as the last line
of stdout: the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics (from a ``jax.profiler`` trace and the program's spans) with
``--trace 1``. No TPU, or fewer chips than the cell asks for: exit code 3 and
no result. The only way to the CPU is the rehearsal
(``JAX_PLATFORMS=cpu BENCH_REHEARSE=1``): tiny sizes, ``"rehearse": true``,
never ``correct``.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter_ns()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_BENCH = Path(__file__).resolve().parent
_ROOT = _BENCH.parent
EXIT_NO_DEVICE = 3


def _environment() -> None:
    """The run's own environment, before the program is imported: no autotune
    picks from an earlier run under ``~`` (two runs of one commit execute the
    same programs), and the compile cache at a fixed place in the checkout
    unless the caller placed it."""
    os.environ["FUTURESDR_TPU_AUTOTUNE_CACHE_DIR"] = "off"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if str(_ROOT) not in sys.path:
        sys.path.insert(0, str(_ROOT))
    if str(_BENCH) not in sys.path:
        sys.path.insert(0, str(_BENCH))


def _process_env(wanted: dict) -> None:
    """A configuration's ``process_env``: what its deployment sets before it
    starts the interpreter, such as the C allocator's thresholds, which are
    read once at start-up. Where this process's environment differs, set it
    and start the interpreter again in this same process (exec: no child),
    keeping the instant the first one started for ``setup_s``."""
    wanted = {str(k): str(v) for k, v in (wanted or {}).items()}
    if all(os.environ.get(k) == v for k, v in wanted.items()):
        return
    if os.environ.get("BENCH_REEXEC_T0_NS"):
        raise RuntimeError(f"process_env did not hold across exec: {wanted}")
    os.environ.update(wanted)
    os.environ["BENCH_REEXEC_T0_NS"] = str(_T_PROCESS)
    sys.stdout.flush()
    os.execv(sys.executable, [sys.executable] + sys.argv)


def _device_line(dev, count: int) -> dict:
    stats = None
    try:
        stats = dev.memory_stats()
    except Exception:                                   # noqa: BLE001
        stats = None                # the CPU backend has none: rehearsal only
    return {"platform": dev.platform, "kind": dev.device_kind, "count": count,
            "memory_peak_bytes": int((stats or {}).get("peak_bytes_in_use", 0))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    _environment()
    if not (_ROOT / "futuresdr_tpu").is_dir():
        print("benchmark/run.py: the program (futuresdr_tpu/) is not in this "
              "checkout", file=sys.stderr)
        return 2
    from harness import cells, lastline, peaks, profiler, refs, xplane
    from harness.reading import Run

    cell = cells.resolve(args.workload)
    _process_env(cell.config.get("process_env"))
    t_first = int(os.environ.pop("BENCH_REEXEC_T0_NS", 0))
    # only this process's own earlier start, moments ago, counts
    t_process = t_first if 0 < _T_PROCESS - t_first < 60e9 else _T_PROCESS
    manifest = cells.load_json(_ROOT / "BENCHMARK.json")
    seconds = float(args.seconds if args.seconds is not None
                    else manifest["run_seconds"])
    rehearse = os.environ.get("BENCH_REHEARSE") == "1"
    # tools/find_knee.py's sweep: traffic parameters replaced for one run. Such
    # a run says so in its line and is never ``correct``
    override = json.loads(os.environ.get("BENCH_TRAFFIC_OVERRIDE") or "{}")
    cell.traffic.update(override)

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not rehearse:
        print(f"benchmark/run.py: no TPU (jax.devices()[0] is "
              f"{dev.platform!r}); the CPU runs only the rehearsal "
              f"(BENCH_REHEARSE=1)", file=sys.stderr)
        return EXIT_NO_DEVICE
    if dev.platform == "tpu" and rehearse:
        print("benchmark/run.py: BENCH_REHEARSE=1 on a TPU: a rehearsal is "
              "a CPU run", file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"benchmark/run.py: {cell.name} asks for {cell.chips} chips, "
              f"jax sees {len(devs)}", file=sys.stderr)
        return EXIT_NO_DEVICE

    known = True
    try:
        pk = peaks.peaks_for(dev.device_kind)
    except peaks.UnknownDevice as e:
        if not rehearse:
            print(f"benchmark/run.py: {e}", file=sys.stderr)
        pk, known = None, False

    from futuresdr_tpu.tpu.instance import ensure_compile_cache
    cache_dir = ensure_compile_cache()
    # every program into the persistent cache, also the ones that compile in
    # under a second (jax's default leaves those out): after a cell's first
    # run in a checkout, a run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    meter = refs.CompileMeter()
    run = Run(cell=cell, seed=args.seed, seconds=seconds,
              trace=bool(args.trace), rehearse=rehearse, meter=meter,
              device=dev, peaks=pk)
    if run.trace:
        from futuresdr_tpu.telemetry import spans
        spans.enable(True)
        run.trace_window = profiler.TraceWindow()
    try:
        out = cell.driver.run(run)
        reading = out.reading
        correct = out.correct and known and dev.platform == "tpu" \
            and reading.compiles_in_window == 0
        device = _device_line(dev, cell.chips)
        breakdown = None
        if run.trace and run.trace_window.path is None:
            raise RuntimeError(f"{cell.name}: the driver took no trace: "
                               f"{out.notes}")
        if run.trace:
            tr = xplane.load(run.trace_window.path, cpu_rehearsal=rehearse)
            out.notes["trace_layout"] = tr.layout
            t0p, t1p = run.trace_window.traced_perf_ns
            if len(tr.sync_ns) >= 2:
                s0, s1 = tr.sync_ns[0], tr.sync_ns[-1]
            else:                   # no marks found: the whole traced span
                starts = [s for ev in tr.devices.values() for _, s, _ in ev]
                ends = [s + d for ev in tr.devices.values() for _, s, d in ev]
                s0, s1 = (min(starts), max(ends)) if starts else (0.0, 1.0)
            red = xplane.reduce_events(tr.devices, s0, s1)
            reading.trace, reading.traced_ns = red, (t0p, t1p)
            named = xplane.to_profile_clock(out.host_spans_named, s0, t0p)
            breakdown = xplane.breakdown(red, named)
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            metrics = {}
            for lm in cell.layer_metrics:
                v = lm.read(reading)
                if v is not None:
                    metrics[lm.name] = {"value": float(v), "unit": lm.unit}
            if red.busy_ns <= 0.0:
                correct = False
                out.notes["trace"] = "no operation ran on the device"
            missing = cells.unread(manifest, cell.name, metrics)
            if missing:
                out.notes["unread"] = missing
                print(f"benchmark/run.py: {cell.name} lists per-layer metrics "
                      f"that read nothing in this trace: {', '.join(missing)}",
                      file=sys.stderr)
        else:
            metrics = {}
            values = dict(out.end_to_end)
            values["setup_s"] = (out.window_start_ns - t_process) * 1e-9
            for name, entry in cell.end_to_end.items():
                if name not in values:
                    raise cells.BenchmarkError(
                        f"{cell.name}: driver {cell.driver_name!r} gave no "
                        f"{name!r}")
                metrics[name] = {"value": float(values[name]),
                                 "unit": entry["unit"]}
        out.notes.update(compile_cache_dir=cache_dir,
                         compiles=len(meter.events),
                         compile_s=meter.seconds(),
                         cache_hits=meter.cache_hits,
                         compiles_in_window=reading.compiles_in_window)
        print(json.dumps({"notes": out.notes}, default=str), file=sys.stderr)
        line = lastline.build(correct and not override, out.attempted,
                              out.failed, metrics, device, breakdown,
                              rehearse=rehearse)
        if override:
            line["override"] = override
        if out.notes.get("unread"):
            line["unread"] = out.notes["unread"]
        sys.stdout.flush()
        print(lastline.dumps(line), flush=True)
        return 0
    finally:
        if run.trace_window is not None:
            run.trace_window.close()


if __name__ == "__main__":
    sys.exit(main())
