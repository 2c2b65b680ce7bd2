#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line of stdout.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is the ``workloads`` entry of ``BENCHMARK.json`` named by
``--workload``; its configuration, traffic and per-layer metric readers are
files under ``bench/`` found by name (``bench/harness.py``).  The run
refuses to start, with no result line and a nonzero exit, unless JAX's
first device is a TPU and the cell's chips are there.  Set-up (weights and
inputs made on the device from ``--seed``, compilation through the
persistent cache, warm-up) is timed as ``setup_s``; then the window runs for
``--seconds``; then the plain reference checks what the window produced.
``--trace 1`` traces a short window and reports the per-layer metrics
instead of the end-to-end ones.
"""
import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.resolve(args.workload)
    try:
        devs = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}; refusing to run", file=sys.stderr)
        return 2
    import jax
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = harness.CompileCounter().install()
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", f"{args.workload}-{args.seed}")
    harness.log(f"{args.workload} seed {args.seed} on {devs[0].device_kind} x{len(devs)}")
    out = cell.driver().run(cell, args.seed, args.seconds, trace_dir, devs, counter, START)
    line = harness.result_line(cell, out, bool(args.trace))
    harness.log(f"compiles: {counter.total} in all, {counter.in_window} in the window")
    harness.print_checks(out)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
