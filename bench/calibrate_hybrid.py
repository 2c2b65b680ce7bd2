#!/usr/bin/env python3
"""Readings that the hybrid round cell's limits are set from, in one process.

    python3 bench/calibrate_hybrid.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 4,5] [--half-batch-seeds 6,7]

As ``bench/calibrate.py`` does for the round cell: for every ``--seeds``
seed, the program's compared numbers against the plain reference, as a run
computes them; for every ``--control-seeds`` seed, the control's (the
reference with model matmuls in float8 and RPCA matmuls as three bfloat16
passes); for every ``--half-batch-seeds`` seed, the reference's over the
first half of every client's tokens (a client holds one sequence, so half
of its batch is half of that sequence).  One JSON line per reading; no
window is timed.  The benchmark's own runs never run this.
"""
import time

START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--half-batch-seeds", default="")
    args = ap.parse_args(argv)
    from bench import harness
    from bench import hybrid_round_cell as hc
    from bench import round_cell as rc
    from bench.calibrate import seeds

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

    tr = cell.traffic
    prog = hc.Program(cell)
    for seed in seeds(args.seeds):
        rec = hc.Recorder(cell, 0.0, harness.CompileCounter(), None)
        rec.tr = {**tr, "warmup_rounds": 10**9}
        prog.counters.clear()
        rc.program_rounds(prog, seed, tr["check_rounds"], rec)
        got = {"losses": [rec.losses[r] for r in range(tr["check_rounds"])],
               "first": rec.snaps[0], "after": rec.snaps[tr["check_rounds"] - 1]}
        del rec
        gc.collect()
        emit("program", seed, {**rc.readings(got, rc.reference(cell, seed)), **prog.counts()})
    for seed in seeds(args.control_seeds):
        emit("control", seed, rc.readings(rc.reference(cell, seed, "control"),
                                          rc.reference(cell, seed)))
    half = copy.deepcopy(cell)
    # MarkovTokens draws a sequence step by step, so the shorter sequences
    # are the first halves of the cell's.
    half.traffic["seq_len"] = tr["seq_len"] // 2
    for seed in seeds(args.half_batch_seeds):
        emit("half_batch", seed, rc.readings(rc.reference(half, seed), rc.reference(cell, seed)))
    return 0


if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    sys.exit(main())
