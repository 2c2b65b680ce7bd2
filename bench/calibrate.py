#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, in one process.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--half-batch-seeds 7,8,9]

For every ``--seeds`` seed, the program's compared numbers against the
plain reference, as a run computes them (the lower readings).  For every
``--control-seeds`` seed, the same numbers of the control: the reference in
the program's place at the precision below the configuration's (model
matmuls in float8, RPCA matmuls as three bfloat16 passes).
``--half-batch-seeds`` (round cells): the reference in the program's place
with half of each client's batch left out.  One JSON line per reading; no window is timed.  The
benchmark's own runs never run this.
"""
import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seeds(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def round_readings(cell, args, emit):
    from bench import round_cell as rc

    prog = rc.Program(cell)
    tr = cell.traffic
    for seed in seeds(args.seeds):
        rec = rc.Recorder({**tr, "warmup_rounds": 10**9}, 0.0, _Counter(), None)
        rc.program_rounds(prog, seed, tr["check_rounds"], rec)
        got = {"losses": [rec.losses[r] for r in range(tr["check_rounds"])],
               "first": rec.snaps[0], "after": rec.snaps[tr["check_rounds"] - 1]}
        emit("program", seed, rc.readings(got, rc.reference(cell, seed)))
    for variant, arg in (("control", args.control_seeds), ("half_batch", args.half_batch_seeds)):
        for seed in seeds(arg):
            emit(variant, seed, rc.readings(rc.reference(cell, seed, variant),
                                            rc.reference(cell, seed)))


def program_updates(prog, seed: int, rounds: list) -> dict:
    """The program's updates of the given rounds, on the host."""
    import jax
    from bench import agg_cell as ac
    from bench import gen

    base = prog.make_base(gen.seed_key(seed))
    return {k: jax.device_get(ac.lora_from_program(
        prog.agg_step(prog.make_cohort(base, ac.round_key(seed, k)))[0])) for k in rounds}


# As many aggregations a seed as a run compares.
ROUNDS = [0, 1, 2]


def agg_readings(cell, args, emit):
    from bench import agg_cell as ac

    prog = ac.Program(cell)
    for seed in seeds(args.seeds):
        got = program_updates(prog, seed, ROUNDS)
        emit("program", seed, ac.readings(got, ac.reference_updates(cell, seed, ROUNDS)))
    for seed in seeds(args.control_seeds):
        ref = ac.reference_updates(cell, seed, ROUNDS)
        emit("control", seed, ac.readings(
            ac.reference_updates(cell, seed, ROUNDS, precision="bf16x3"), ref))


class _Counter:
    open = False
    in_window = 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--half-batch-seeds", default="")
    args = ap.parse_args(argv)
    from bench import harness

    cell = harness.resolve(args.workload)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    def emit(variant, seed, vals):
        print(json.dumps({"workload": cell.name, "variant": variant, "seed": seed, **vals,
                          "t": round(time.perf_counter() - START, 1)}), flush=True)

    if cell.traffic["kind"] == "round":
        round_readings(cell, args, emit)
    else:
        agg_readings(cell, args, emit)
    return 0


if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    sys.exit(main())
