"""Perf gate over BENCH_agg.json: fail CI on aggregation perf regressions.

Reads the schema-v7 bench artifact (no jax import — this is a pure JSON
check, cheap enough to run on every CI push) and enforces the roofline /
costmodel-derived bounds each engine PR established:

  * single-call: the packed engine must not regress vs the per-leaf
    reference at the largest benched cell, and subspace SVT must stay in
    the same ballpark as gram SVT (its win grows with cohort size; the
    gate only catches a collapse).
  * multi-round carry: warm rounds must be no slower than cold rounds and
    must finish with ZERO eigh fallbacks (the cross-round carry contract —
    a warm fallback means the carried subspace stopped being reusable).
  * pipeline: every staleness-1 cell's whole-run wall clock must stay
    within a floor of the synchronous driver's (the async overlap may not
    make rounds materially slower), and the overlap win must not collapse
    as the cohort grows server-bound (crossover direction).
  * serve: the gathered-pool path must beat per-request gathers at the
    largest adapters x batch cell, and its win must grow with batch at
    fixed adapter count (the crossover the pool layout exists for).
  * mesh: every mode="mesh" cell's measured wall time must sit inside the
    ``costmodel.mesh_agg_costs`` envelope band — fused variants against
    their matching costmodel prediction — warm mesh rounds must also be
    fallback-free (fused ones included: the sharded Pallas tail must not
    reintroduce eigh fallbacks), and wherever a cohort has a 1-shard cell
    the 4-shard warm cell and its fused variant must be present and
    in-envelope (the scale-out acceptance
    cells: sharding keeps working where one device is at its
    memory-footprint worst).
  * faults: every mode="faults" run must end with a finite state, and at
    each corruption level the quarantined run's final accuracy must be no
    worse than the unguarded one (DESIGN.md §11).

The bounds are deliberately wide tolerance bands, not point predictions:
the costmodel is an order-of-magnitude envelope and CI hosts are noisy
shared cores.  A regression that escapes an 8x band is a real one.

Usage: python benchmarks/perf_gate.py [BENCH_agg.json] [--require mesh ...]
Exit 0 = all checks pass; exit 1 = at least one FAIL line printed.
"""
from __future__ import annotations

import argparse
import json
import sys

#: Packed-vs-reference speedup floor at the largest single-call cell.
PACKED_SPEEDUP_MIN = 1.0
#: Subspace-SVT wall time may not exceed this multiple of gram-SVT's.
SUBSPACE_VS_GRAM_MAX = 1.5
#: Warm carry rounds may not be slower than this multiple of cold rounds.
WARM_VS_COLD_MAX = 1.0
#: Async (staleness=1) whole-run speedup floor vs the sync driver.  On a
#: shared single core the overlap cannot win wall clock (both phases
#: timeshare the core), so this is a no-collapse guard, not a win check.
PIPELINE_SPEEDUP_MIN = 0.75
#: The overlap win at the largest cohort may trail the smallest cohort's
#: by at most this much — the pipeline's payoff must not move the wrong
#: way as rounds grow server-bound (crossover direction).
PIPELINE_DIRECTION_SLACK = 0.15
#: Gathered-pool speedup floor vs per-request gathers at the largest
#: adapters x batch serve cell (where the pool layout must win).
SERVE_GATHERED_SPEEDUP_MIN = 1.0
#: measured/predicted band for mode="mesh" cells (order-of-magnitude
#: envelope: the costmodel's dispatch floor and the shared-core collective
#: emulation are both rough on CI hosts; see costmodel.mesh_agg_costs).
MESH_ENVELOPE = (0.1, 8.0)
#: faults cells: guarded final accuracy may trail unguarded by at most
#: this much at the same corruption level (noise slack — the quarantine
#: should win outright on corrupted runs).
FAULTS_ACC_SLACK = 0.05
#: uplink cells (DESIGN.md §12): the sketch wire must cut warm-round
#: uplink bytes by at least this factor vs the dense wire...
UPLINK_REDUCTION_MIN = 4.0
#: ...while costing at most this much final accuracy vs the dense run at
#: identical settings, and actually engaging on a majority of rounds
#: (hit_rate floor keeps a permanently-gated codec from passing on the
#: trivial "never sketched, accuracy matches" axis).
UPLINK_ACC_SLACK = 0.01
UPLINK_HIT_RATE_MIN = 0.5

FAILURES: list[str] = []


def check(ok: bool, name: str, detail: str) -> None:
    status = "PASS" if ok else "FAIL"
    print(f"{status} {name}: {detail}", flush=True)
    if not ok:
        FAILURES.append(name)


def gate_single_call(records: list[dict]) -> None:
    cells = [
        r for r in records
        if r.get("method") == "fedrpca" and "mode" not in r and not r.get("masked")
    ]
    if not cells:
        print("# no single-call fedrpca cells; skipping single-call gate")
        return
    by_size: dict[tuple, dict[str, dict]] = {}
    for r in cells:
        key = (r["n_modules"], r["n_clients"])
        slot = r["engine"] if r["engine"] == "reference" else r["svt_mode"]
        by_size.setdefault(key, {})[slot] = r
    largest = max(by_size, key=lambda k: k[0] * k[1])
    cell = by_size[largest]
    if "reference" in cell and "subspace" in cell:
        speedup = cell["reference"]["us_per_call"] / cell["subspace"]["us_per_call"]
        check(
            speedup >= PACKED_SPEEDUP_MIN,
            f"packed_speedup_m{largest[0]}_c{largest[1]}",
            f"packed subspace {speedup:.2f}x vs reference "
            f"(floor {PACKED_SPEEDUP_MIN}x)",
        )
    for key, slots in sorted(by_size.items()):
        if "gram" in slots and "subspace" in slots:
            ratio = slots["subspace"]["us_per_call"] / slots["gram"]["us_per_call"]
            check(
                ratio <= SUBSPACE_VS_GRAM_MAX,
                f"subspace_vs_gram_m{key[0]}_c{key[1]}",
                f"subspace/gram wall ratio {ratio:.2f} "
                f"(ceiling {SUBSPACE_VS_GRAM_MAX})",
            )


def gate_multi_round(records: list[dict]) -> None:
    cells = [r for r in records if r.get("mode") == "multi_round"]
    if not cells:
        print("# no multi_round cells; skipping carry gate")
        return
    by_mode: dict[str, dict[str, dict]] = {}
    for r in cells:
        by_mode.setdefault(r["carry_mode"], {})[r["round_type"]] = r
    for mode, slots in sorted(by_mode.items()):
        if mode == "none" or "cold" not in slots or "warm" not in slots:
            continue
        ratio = slots["warm"]["us_per_call"] / slots["cold"]["us_per_call"]
        check(
            ratio <= WARM_VS_COLD_MAX,
            f"carry_warm_vs_cold_{mode}",
            f"warm/cold wall ratio {ratio:.2f} (ceiling {WARM_VS_COLD_MAX})",
        )
        falls = slots["warm"]["fallbacks"]
        check(
            falls == 0,
            f"carry_warm_fallbacks_{mode}",
            f"{falls} eigh fallbacks on warm rounds (must be 0)",
        )


def gate_pipeline(records: list[dict]) -> None:
    """mode="pipeline" cells: async double-buffered rounds vs the sync
    driver (DESIGN.md §8).  Floor check per staleness-1 cell plus the
    crossover-direction check across cohort sizes."""
    cells = [r for r in records if r.get("mode") == "pipeline"]
    if not cells:
        print("# no pipeline cells; skipping pipeline gate")
        return
    piped = sorted(
        (r for r in cells if r.get("staleness") == 1),
        key=lambda r: r["n_clients"],
    )
    for r in piped:
        s = r["speedup_vs_sync"]
        check(
            s >= PIPELINE_SPEEDUP_MIN,
            f"pipeline_speedup_c{r['n_clients']}",
            f"async/sync speedup {s:.3f} (floor {PIPELINE_SPEEDUP_MIN})",
        )
    if len(piped) >= 2:
        small, large = piped[0], piped[-1]
        gap = small["speedup_vs_sync"] - large["speedup_vs_sync"]
        check(
            gap <= PIPELINE_DIRECTION_SLACK,
            "pipeline_crossover_direction",
            f"speedup c{small['n_clients']}={small['speedup_vs_sync']:.3f} -> "
            f"c{large['n_clients']}={large['speedup_vs_sync']:.3f} "
            f"(may trail by at most {PIPELINE_DIRECTION_SLACK})",
        )


def gate_serve(records: list[dict]) -> None:
    """mode="serve" cells: the gathered adapter pool must beat per-request
    gathers where the workload is largest, and its advantage must grow
    with batch at fixed adapter count — the crossover direction the
    ``serve_gather_costs`` model predicts."""
    cells = [r for r in records if r.get("mode") == "serve"]
    if not cells:
        print("# no serve cells; skipping serve gate")
        return
    gathered = [r for r in cells if r.get("path") == "gathered"]
    if not gathered:
        check(False, "serve_gathered_present", "no gathered-path serve cells")
        return
    largest = max(gathered, key=lambda r: r["n_adapters"] * r["batch"])
    s = largest["speedup_vs_per_request"]
    check(
        s >= SERVE_GATHERED_SPEEDUP_MIN,
        f"serve_gathered_wins_a{largest['n_adapters']}_b{largest['batch']}",
        f"gathered {s:.2f}x vs per_request at the largest cell "
        f"(floor {SERVE_GATHERED_SPEEDUP_MIN}x)",
    )
    by_adapters: dict[int, list[dict]] = {}
    for r in gathered:
        by_adapters.setdefault(r["n_adapters"], []).append(r)
    for n_adapters, rows in sorted(by_adapters.items()):
        rows.sort(key=lambda r: r["batch"])
        if len(rows) < 2:
            continue
        lo, hi = rows[0], rows[-1]
        check(
            hi["speedup_vs_per_request"] >= lo["speedup_vs_per_request"],
            f"serve_crossover_direction_a{n_adapters}",
            f"gathered speedup b{lo['batch']}={lo['speedup_vs_per_request']:.2f} -> "
            f"b{hi['batch']}={hi['speedup_vs_per_request']:.2f} "
            "(must not shrink with batch)",
        )


def gate_mesh(records: list[dict]) -> None:
    cells = [r for r in records if r.get("mode") == "mesh"]
    if not cells:
        print("# no mesh cells; skipping mesh gate")
        return
    lo, hi = MESH_ENVELOPE

    def variant(r: dict) -> str:
        return "_fused" if r.get("fused") else ""

    for r in cells:
        env = r["us_per_call"] / r["predicted_us"]
        tag = f"s{r['shards']}_c{r['n_clients']}_{r['round_type']}{variant(r)}"
        check(
            lo <= env <= hi,
            f"mesh_envelope_{tag}",
            f"measured/predicted {env:.2f} (band [{lo}, {hi}])",
        )
        if r["round_type"] == "warm":
            check(
                r["fallbacks"] == 0,
                f"mesh_warm_fallbacks_{tag}",
                f"{r['fallbacks']} eigh fallbacks on warm sharded rounds "
                "(must be 0)",
            )
    # Scale-out acceptance: wherever a cohort ran at 1 shard, the 4-shard
    # warm cell AND its fused variant must exist and be
    # in-envelope (checked above) — here we just require their presence so
    # a silently-skipped cell (too few devices) cannot pass the gate.
    cohorts = {r["n_clients"] for r in cells if r["shards"] == 1}
    for c in sorted(cohorts):
        for fused, label in ((False, ""), (True, "_fused")):
            has4 = any(
                r["shards"] == 4 and r["n_clients"] == c
                and r["round_type"] == "warm"
                and bool(r.get("fused")) == fused
                for r in cells
            )
            check(has4, f"mesh_4shard_present_c{c}{label}",
                  "4-shard warm cell recorded" if has4
                  else "4-shard warm cell missing (skipped? too few host "
                       "devices, or the fused variant did not run)")


def gate_faults(records: list[dict]) -> None:
    """mode="faults" cells (DESIGN.md §11): every run must end finite, the
    clean (0% corruption) reference must converge, and wherever a
    corruption level ran with the quarantine both on and off the guarded
    run's final accuracy must be no worse than the unguarded one (minus a
    noise slack) — the quarantine has to pay for itself."""
    cells = [r for r in records if r.get("mode") == "faults"]
    if not cells:
        print("# no faults cells; skipping faults gate")
        return
    by_level: dict[float, dict[bool, dict]] = {}
    for r in cells:
        check(
            bool(r["finite"]),
            f"faults_finite_c{int(r['corrupt'] * 100)}_g{int(r['guard'])}",
            f"final state finite={r['finite']} (guard={r['guard']})",
        )
        by_level.setdefault(r["corrupt"], {})[bool(r["guard"])] = r
    for level, slots in sorted(by_level.items()):
        if level == 0.0 or True not in slots or False not in slots:
            continue
        guarded = slots[True]["final_acc"]
        bare = slots[False]["final_acc"]
        check(
            guarded >= bare - FAULTS_ACC_SLACK,
            f"faults_guard_helps_c{int(level * 100)}",
            f"guarded acc {guarded:.3f} vs unguarded {bare:.3f} "
            f"(slack {FAULTS_ACC_SLACK})",
        )


def gate_uplink(records: list[dict]) -> None:
    """mode="uplink" cells (DESIGN.md §12): the sketch cell must engage on
    most rounds, cut warm-round uplink bytes >= 4x vs the dense cell, and
    land within 0.01 final accuracy of the dense run at the same
    settings.  The warm-round reduction (not the cold-round-diluted mean)
    is the gated number: cold/gated rounds falling back to the dense wire
    is the codec's designed safety behaviour, not a perf regression."""
    cells = [r for r in records if r.get("mode") == "uplink"]
    if not cells:
        print("# no uplink cells; skipping uplink gate")
        return
    dense = [r for r in cells if r["uplink"] == "dense"]
    sketch = [r for r in cells if r["uplink"] != "dense"]
    check(bool(dense) and bool(sketch), "uplink_cells_paired",
          f"{len(dense)} dense / {len(sketch)} sketch cells (need >=1 each)")
    if not dense or not sketch:
        return
    base = dense[0]
    for r in sketch:
        tag = r["uplink"].replace(":", "_").replace(".", "p")
        red = r.get("reduction_vs_dense")
        check(
            red is not None and red >= UPLINK_REDUCTION_MIN,
            f"uplink_reduction_{tag}",
            f"warm-round byte reduction {red}x vs dense "
            f"(min {UPLINK_REDUCTION_MIN}x; None = never engaged)",
        )
        check(
            r["uplink_hit_rate"] >= UPLINK_HIT_RATE_MIN,
            f"uplink_hit_rate_{tag}",
            f"sketch engaged on {r['uplink_hit_rate']:.0%} of rounds "
            f"(min {UPLINK_HIT_RATE_MIN:.0%})",
        )
        gap = abs(r["final_acc"] - base["final_acc"])
        check(
            gap <= UPLINK_ACC_SLACK,
            f"uplink_acc_match_{tag}",
            f"final acc {r['final_acc']:.3f} vs dense {base['final_acc']:.3f} "
            f"(gap {gap:.4f}, max {UPLINK_ACC_SLACK})",
        )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("path", nargs="?", default="BENCH_agg.json")
    ap.add_argument(
        "--require", nargs="*", default=(),
        choices=["single_call", "multi_round", "pipeline", "serve", "mesh",
                 "faults", "uplink"],
        help="fail (instead of skip) when these record groups are absent",
    )
    args = ap.parse_args()
    with open(args.path) as f:
        payload = json.load(f)
    version = payload.get("schema_version")
    check(version == 8, "schema_version", f"got {version}, want 8")
    records = payload.get("records", [])
    present = {
        "single_call": any("mode" not in r for r in records),
        "multi_round": any(r.get("mode") == "multi_round" for r in records),
        "pipeline": any(r.get("mode") == "pipeline" for r in records),
        "serve": any(r.get("mode") == "serve" for r in records),
        "mesh": any(r.get("mode") == "mesh" for r in records),
        "faults": any(r.get("mode") == "faults" for r in records),
        "uplink": any(r.get("mode") == "uplink" for r in records),
    }
    for group in args.require:
        check(present[group], f"require_{group}",
              "records present" if present[group] else "no records of this group")
    gate_single_call(records)
    gate_multi_round(records)
    gate_pipeline(records)
    gate_serve(records)
    gate_mesh(records)
    gate_faults(records)
    gate_uplink(records)
    if FAILURES:
        print(f"# perf gate: {len(FAILURES)} check(s) FAILED: "
              f"{', '.join(FAILURES)}", flush=True)
        return 1
    print("# perf gate: all checks passed", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
