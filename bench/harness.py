"""What every cell shares: finding a workload's files by name, the chip
check, the compile counter, device facts and the result line.

A workload ``<config>.<traffic>`` in ``BENCHMARK.json`` resolves to
``bench/configs/<config>.json`` (sizes) with its plain reference
``bench/configs/<config>.ref.py``, ``bench/traffic/<traffic>.json``
(parameters for ``bench/gen.py``; its ``kind`` names the driver module
``bench/<kind>_cell.py``) and one reader ``bench/metrics/<metric>.py`` per
per-layer metric.  New cells, configurations and metrics are new files.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass, field

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclass
class Cell:
    """Everything one workload names, resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench: str = BENCH

    def reference(self):
        return load_module(os.path.join(self.bench, "configs", f"{self.config['name']}.ref.py"),
                           f"ref_{self.config['name'].replace('-', '_').replace('.', '_')}")

    def driver(self):
        return importlib.import_module(f"bench.{self.traffic['kind']}_cell")

    def reader(self, metric: str):
        return load_module(os.path.join(self.bench, "metrics", f"{metric}.py"),
                           "metric_" + metric.replace(".", "_").replace("-", "_"))


def resolve(name: str, bench: dict | None = None, bench_dir: str = BENCH) -> Cell:
    """The cell of workload ``name``: a metric without ``workloads`` applies
    to every cell (end to end) or to every cell that reports the metric it
    moves (per layer)."""
    bench = bench or benchmark(os.path.dirname(bench_dir))
    wl = [w for w in bench["workloads"] if w["name"] == name]
    if len(wl) != 1:
        raise KeyError(f"workload {name!r} is not in BENCHMARK.json")
    wl = wl[0]
    cfg_entry = [c for c in bench["configs"] if c["name"] == wl["config"]][0]
    config = load_json(os.path.join(os.path.dirname(bench_dir), cfg_entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic", f"{wl['traffic']}.json"))
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m else m["moves"] in names)]
    return Cell(name=name, chips=wl["chips"], config=config, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, bench=bench_dir)


class NoChip(RuntimeError):
    pass


def require_chips(n: int):
    """The devices to use: the first ``n`` TPU chips, or ``NoChip``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: the first device is {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chips, found {len(devs)}")
    return devs[:n]


class CompileCounter:
    """Counts backend compilations, and those inside the measured window."""

    def __init__(self):
        self.total = 0
        self.in_window = 0
        self.open = False

    def __call__(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += 1
            self.in_window += self.open

    def install(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self


def device_facts(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


@dataclass
class Outcome:
    """What a driver hands back: counts, end-to-end values, what per-layer
    readers read (``facts``), and the compared numbers (``checks``: name ->
    (value, limit))."""

    attempted: int = 0
    failed: int = 0
    values: dict = field(default_factory=dict)
    facts: dict = field(default_factory=dict)
    checks: dict = field(default_factory=dict)
    device: dict = field(default_factory=dict)
    trace: dict | None = None

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            math.isfinite(v) and v <= lim for v, lim in self.checks.values())


def result_line(cell: Cell, out: Outcome, traced: bool) -> dict:
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    if traced:
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(cell, out)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
    else:
        for m in cell.end_to_end:
            if m["name"] in out.values:
                metrics[m["name"]] = {"value": out.values[m["name"]], "unit": units[m["name"]]}
    device = dict(out.device)
    line = {"correct": out.correct, "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    if traced and out.trace is not None:
        device["busy_s"] = out.trace["busy_s"]
        device["window_s"] = out.trace["window_s"]
        line["breakdown"] = out.trace["breakdown"]
    line["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in out.checks.items()}
    return line


def print_checks(out: Outcome) -> None:
    for k, (v, lim) in out.checks.items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr, flush=True)


def log(msg: str) -> None:
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)
