#!/usr/bin/env python3
"""Readings that the mesh aggregation cell's limits are set from, in one
process.

    python3 bench/calibrate_mesh.py --workload deepseek-67b.agg-c40-x4 \
        --seeds 1,2,3 [--control-seeds 4,5,6]

As ``bench/calibrate.py`` does for the agg cell, on the cell's mesh: for
every ``--seeds`` seed, every number of ``agg_mesh_cell.readings`` of the
program against the plain reference over the aggregations of
``calibrate.ROUNDS`` (the lower readings), with where the gap lies
(``spread``); for every ``--control-seeds``
seed, the same numbers of the control, the reference with its RPCA matmuls
as three bfloat16 passes, run leaf by leaf as the cell's reference is (a
seed in both lists computes its reference once).  One
JSON line per reading; no window is timed.  The benchmark's own runs never
run this.
"""
import time

START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(prog: dict, ref: dict) -> dict:
    """Where the gap lies, for choosing what to compare: each leaf's
    relative gap and the quartiles of the modules' (a layer of one leaf),
    the largest over the rounds and over all rounds' modules."""
    import numpy as np

    leaves, modules = {}, []
    for k, r_upd in ref.items():
        for leaf, r in r_upd.items():
            r = np.asarray(r, np.float64).reshape(r.shape[0], -1)
            d = np.asarray(prog[k][leaf], np.float64).reshape(r.shape) - r
            leaves[leaf] = max(leaves.get(leaf, 0.0), float(np.linalg.norm(d) / np.linalg.norm(r)))
            modules.extend(np.linalg.norm(d, axis=1) / np.linalg.norm(r, axis=1))
    return {"leaf_gaps": leaves,
            "module_gap_quartiles": [float(q) for q in np.quantile(modules, [0, .25, .5, .75, 1])]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    from bench import agg_mesh_cell as mc
    from bench import harness
    from bench.calibrate import ROUNDS, program_updates, seeds

    cell = harness.resolve(args.workload)
    try:
        devs = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    def emit(variant, seed, vals):
        print(json.dumps({"workload": cell.name, "variant": variant, "seed": seed, **vals,
                          "t": round(time.perf_counter() - START, 1)}), flush=True)

    prog = mc.Program(cell, mc.make_mesh(devs, cell.traffic))
    program, control = seeds(args.seeds), seeds(args.control_seeds)
    for seed in dict.fromkeys(program + control):
        ref = mc.reference_updates(cell, seed, ROUNDS)
        if seed in program:
            got = program_updates(prog, seed, ROUNDS)
            emit("program", seed, {**mc.readings(got, ref), **spread(got, ref)})
        if seed in control:
            got = mc.reference_updates(cell, seed, ROUNDS, precision="bf16x3")
            emit("control", seed, {**mc.readings(got, ref), **spread(got, ref)})
    return 0


if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    sys.exit(main())
