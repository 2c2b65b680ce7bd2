"""Reduce a JAX profiler trace to busy time, idle gaps, exposed collectives
and the ``breakdown`` of the result line.

A trace is read into plain planes: ``{"name": str, "lines": {line: [(event
name, start_ns, duration_ns), ...]}}``.  Device planes are the ``/device:TPU:N``
planes; their ``XLA Ops`` line holds one event per operation that ran, their
``XLA Modules`` line one per program.  The benchmark's own host spans
(``jax.profiler.TraceAnnotation`` names starting with ``bench.``) lie on the
host plane, on the same clock, and ``bench.window`` marks the traced window.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")
_SUFFIX = re.compile(r"(\(\d*\))?[.\d]*$")


def load_xplane(path: str) -> list:
    """Planes of one ``.xplane.pb`` file (a file, or a profile directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"expected one .xplane.pb under {path}, found {found}")
        path = found[0]
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def save_planes(planes: list, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(planes, f)


def load_planes(path: str) -> list:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def device_planes(planes: list) -> list:
    """TPU planes that ran at least one operation, in chip order."""
    devs = [p for p in planes if p["name"].startswith("/device:TPU:")
            and p["lines"].get(OPS_LINE)]
    return sorted(devs, key=lambda p: int(p["name"].rsplit(":", 1)[1]))


def host_spans(planes: list) -> list:
    """The benchmark's host spans: (name, start, end) in ns."""
    out = []
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for events in p["lines"].values():
            out += [(n, s, s + d) for n, s, d in events if n.startswith(SPAN_PREFIX)]
    return out


def window(planes: list) -> tuple:
    """(start, end) of the ``bench.window`` span, in ns."""
    spans = [(s, e) for n, s, e in host_spans(planes) if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(spans)}")
    return spans[0]


def merge(intervals) -> list:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


def op_intervals(plane: dict, t0: float, t1: float) -> list:
    """(name, start, end) of the plane's operations, clipped to [t0, t1]."""
    return [(n, max(s, t0), min(s + d, t1)) for n, s, d in plane["lines"].get(OPS_LINE, ())
            if s + d > t0 and s < t1]


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def busy_ns(plane: dict, t0: float, t1: float) -> float:
    return total(merge((s, e) for _, s, e in op_intervals(plane, t0, t1)))


def exposed_collective_ns(plane: dict, t0: float, t1: float) -> float:
    """Time in which a collective runs on this chip and no other operation
    does."""
    ops = op_intervals(plane, t0, t1)
    coll = merge((s, e) for n, s, e in ops if is_collective(n))
    compute = merge((s, e) for n, s, e in ops if not is_collective(n))
    return total(subtract(coll, compute))


def idle_gaps(plane: dict, t0: float, t1: float) -> list:
    busy = merge((s, e) for _, s, e in op_intervals(plane, t0, t1))
    return subtract([(t0, t1)], busy)


def leaf_ops(ops: list) -> list:
    """The (name, start, end) ops that hold no other op: a while loop or a
    conditional is counted through the ops of its body, which the trace
    nests inside it."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None]) if nxt is None or nxt[1] >= o[2]]


def _base(name: str) -> str:
    return _SUFFIX.sub("", name.split(" ")[0].lstrip("%")) or name


def _module_of(plane: dict, t0: float, t1: float):
    """Function: time -> base name of the program running then."""
    mods = sorted((s, s + d, _base(n)) for n, s, d in plane["lines"].get(MODULES_LINE, ())
                  if s + d > t0 and s < t1)

    def at(t):
        for s, e, n in mods:
            if s <= t < e:
                return n
            if s > t:
                break
        return "?"

    return at


def reduce(planes: list, top: int = 10) -> dict:
    """The whole reduction of one traced window.

    Returns ``busy_s`` and ``window_s`` (busy is the union of the chip's
    operation intervals, averaged over chips), ``exposed_collective_s``
    (averaged over chips) and the ``breakdown``: the device operations that
    took most time (leaf operations, mean per chip, keyed ``program/op``) and the longest
    idle gaps of chip 0, each named by the innermost benchmark span open on
    the host at the gap's middle.
    """
    t0, t1 = window(planes)
    devs = device_planes(planes)
    if not devs:
        raise ValueError("the trace holds no device operations")
    n = len(devs)
    busy = sum(busy_ns(p, t0, t1) for p in devs) / n
    exposed = sum(exposed_collective_ns(p, t0, t1) for p in devs) / n
    by_op: dict = {}
    for p in devs:
        mod = _module_of(p, t0, t1)
        for name, s, e in leaf_ops(op_intervals(p, t0, t1)):
            key = f"{mod(s)}/{_base(name)}"
            by_op[key] = by_op.get(key, 0.0) + (e - s) / n
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    spans = [sp for sp in host_spans(planes) if sp[0] != WINDOW_SPAN]
    gaps = []
    for s, e in idle_gaps(devs[0], t0, t1):
        mid = 0.5 * (s + e)
        open_ = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ else "no bench span"
        gaps.append((name, (e - s) * 1e-9))
    gaps = sorted(gaps, key=lambda g: -g[1])[:top]
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": busy * 1e-9,
        "exposed_collective_s": exposed * 1e-9,
        "chips": n,
        "breakdown": {
            "device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps],
        },
    }
