"""Run one benchmark cell on the chips of this machine; print one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it builds the cell's inputs and weights from ``--seed``, warms
every program the window uses (set-up, reported as ``setup_s``), measures
for about ``--seconds`` seconds, checks what the timed path produced
against the plain float32 reference in ``bench/models``, and prints the
result as the last line of stdout. With ``--trace 1`` the window runs under
the profiler and the line carries the cell's per-layer metrics, the
device's busy and window seconds, and a ``breakdown`` of the trace.

It runs only on the accelerator: without a TPU, or with fewer chips than
the cell asks for, it exits 1 and prints no result. The compiled programs
are kept in ``.jax_cache/`` at the checkout's root unless
``JAX_COMPILATION_CACHE_DIR`` says otherwise.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    return args


def run_cell(cell, args, devices, t_start):
    """Everything after the look for a chip: (result dict, checks dict)."""
    from bench import harness, peaks, spec

    peaks.peaks(devices[0].device_kind)   # an unknown chip fails before the run
    kind = importlib.import_module(f"bench.kinds.{cell.kind}")
    tracer = harness.Tracer(bool(args.trace))
    result, e2e, ctx, readings, info = kind.run(cell, args, t_start, devices, tracer)
    ok, checks = harness.judge(readings, cell.limits)
    result["correct"] = bool(ok)
    if args.trace:
        s = tracer.summary
        result["device"]["busy_s"] = s.busy_s
        result["device"]["window_s"] = s.window_s
        result["metrics"] = spec.read_per_layer(cell, ctx)
        result["breakdown"] = s.breakdown()
    else:
        want = {m["name"] for m in cell.end_to_end}
        result["metrics"] = {k: v for k, v in e2e.items() if k in want}
    for k, v in info.items():
        print(f"info {k}: {v}", file=sys.stderr, flush=True)
    return result, checks


def main(argv=None):
    args = parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("bench: src/repro not found next to bench/; run from a checkout "
              "of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, src]

    from bench import spec

    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax

    from repro.launch.common import enable_compile_cache

    from bench import harness

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU visible (platform {devices[0].platform}); the "
              "benchmark runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} chips, "
              f"{len(devices)} visible", file=sys.stderr)
        return 1

    result, checks = run_cell(cell, args, devices[:cell.chips], T_START)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
