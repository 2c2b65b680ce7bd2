"""The program's own layers in a traced window: device time per named scope,
idle gaps named by the program's host spans, and the host-device clock
offset.  It reads the planes of ``bench/trace.py`` and adds to them; every
function there returns what it returned before.

Scopes.  Each ``XLA Ops`` event of a TPU trace has event metadata whose
``tf_op`` stat holds the HLO ``op_name`` metadata of the operation: the
jitted function and the ``jax.named_scope`` path it was traced under, e.g.
``jit(agg_step)/agg.admm/while/body/agg.svt/eigh:``.  A backward pass wraps
the path in ``jvp(...)`` and ``transpose(...)``:
``jit(local_step)/vmap(...)/local.grad/transpose(jvp(...))/dot_general:``.
``jax.profiler.ProfileData`` does not expose event metadata, so
``load_xplane`` reads it from the ``.xplane.pb`` with a schema of the few
fields it needs, and keeps it per device plane as ``op_scopes``: operation
(the event's name) -> ``tf_op``.  The program's scopes start with ``agg.``
or ``local.`` (``launch/steps.py``, ``core/engine.py``, ``core/rpca.py``);
an operation's scope is the innermost one on its path.

Spans.  The program's host spans start with ``fed.`` (``fed/pipeline.py``),
the benchmark's with ``bench.``; both lie on the host plane.

Clock offset.  A v5e trace's device clock can read behind the host's
(0.2 ms and 1.1 ms in the two recorded traces under ``tests/data``): a
program then appears to start before the host span that dispatched it.
``clock_offset`` pairs each dispatch span with the program it launched and
takes the largest such lead, a lower bound on the skew (a program that
queues behind others, or a slow dispatch, hides it); host spans are
shifted by it before they name the device's idle gaps.
"""
from __future__ import annotations

import functools
import glob
import os
import re

from bench import trace

SPAN_PREFIXES = ("bench.", "fed.")
SCOPE = re.compile(r"(?:agg|local)\.[A-Za-z0-9_.]*[A-Za-z0-9_]")
UNSCOPED = "unscoped"
NO_SPAN = "no bench span"
# Dispatch span -> the program it launches.
DISPATCHES = {"bench.local_dispatch": "jit_local_step", "bench.agg_dispatch": "jit_agg_step"}


@functools.cache
def _xspace_class():
    """A message class for the part of ``tsl.profiler.XSpace`` read here:
    planes, their event metadata and stat metadata, with the field numbers
    of ``xplane.proto`` (the parser skips every other field)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="bench_xspace_subset.proto",
                                           package="bench_xspace", syntax="proto3")

    def message(name, *fields):
        m = f.message_type.add(name=name)
        for fname, number, ftype in fields:
            kind = F.TYPE_MESSAGE if ftype[0].isupper() else getattr(F, f"TYPE_{ftype.upper()}")
            fd = m.field.add(name=fname, number=number, type=kind, label=F.LABEL_OPTIONAL)
            if kind == F.TYPE_MESSAGE:
                fd.type_name = f".bench_xspace.{ftype.rstrip('*')}"
                if ftype.endswith("*"):
                    fd.label = F.LABEL_REPEATED

    message("XStat", ("metadata_id", 1, "int64"), ("str_value", 5, "string"),
            ("ref_value", 7, "uint64"))
    message("XEventMetadata", ("id", 1, "int64"), ("name", 2, "string"), ("stats", 5, "XStat*"))
    message("XStatMetadata", ("id", 1, "int64"), ("name", 2, "string"))
    # xplane.proto's maps, as the repeated key/value entries they are on the wire.
    message("EventMetadataEntry", ("key", 1, "int64"), ("value", 2, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, "int64"), ("value", 2, "XStatMetadata"))
    message("XPlane", ("name", 2, "string"), ("event_metadata", 4, "EventMetadataEntry*"),
            ("stat_metadata", 5, "StatMetadataEntry*"))
    message("XSpace", ("planes", 1, "XPlane*"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xspace.XSpace"))


def op_scopes(raw: bytes) -> dict:
    """Plane name -> {operation name: ``tf_op``} for the device planes of a
    serialized XSpace.  An operation whose name two programs share keeps
    the first path read."""
    space = _xspace_class()()
    space.ParseFromString(raw)
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op = next((k for k, v in stat_names.items() if v == "tf_op"), None)
        found = {}
        for e in plane.event_metadata:
            for st in e.value.stats:
                if st.metadata_id == tf_op:
                    found.setdefault(e.value.name,
                                     st.str_value or stat_names.get(st.ref_value, ""))
        out[plane.name] = found
    return out


def load_xplane(path: str) -> list:
    """``trace.load_xplane``'s planes, each device plane with its
    ``op_scopes``."""
    planes = trace.load_xplane(path)
    if os.path.isdir(path):
        (path,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    with open(path, "rb") as f:
        scopes = op_scopes(f.read())
    for p in planes:
        if p["name"] in scopes:
            p["op_scopes"] = scopes[p["name"]]
    return planes


def scope_of(path: str | None) -> str:
    """Innermost program scope on an ``op_name`` path, or ``unscoped``."""
    found = SCOPE.findall(path or "")
    return found[-1] if found else UNSCOPED


def scope_times(planes: list) -> dict:
    """Seconds of device time per ``<program>/<innermost scope>`` in the
    window (leaf operations, mean per chip); operations outside every
    program scope count as ``<program>/unscoped``."""
    t0, t1 = trace.window(planes)
    devs = trace.device_planes(planes)
    out: dict = {}
    for p in devs:
        mod = trace._module_of(p, t0, t1)
        names = p.get("op_scopes", {})
        for name, s, e in trace.leaf_ops(trace.op_intervals(p, t0, t1)):
            key = f"{mod(s)}/{scope_of(names.get(name))}"
            out[key] = out.get(key, 0.0) + (e - s) * 1e-9 / len(devs)
    return out


def program_spans(planes: list) -> list:
    """The host spans of the program and of the benchmark, (name, start,
    end) in ns."""
    out = []
    for p in planes:
        if p["name"].startswith("/host:"):
            for events in p["lines"].values():
                out += [(n, s, s + d) for n, s, d in events if n.startswith(SPAN_PREFIXES)]
    return out


def clock_offset(planes: list) -> float:
    """How far the device clock runs behind the host's, in ns: the largest
    lead of a program's start over the start of the dispatch span that
    launched it, or 0.  Dispatch spans and programs are paired in order;
    both cells open their window with no program in flight."""
    (dev, *_) = trace.device_planes(planes)
    spans = program_spans(planes)
    mods = sorted((s, trace._base(n)) for n, s, _ in dev["lines"].get(trace.MODULES_LINE, ()))
    lead = 0.0
    for span, program in DISPATCHES.items():
        starts = sorted(s for n, s, _ in spans if n == span)
        runs = [s for s, n in mods if n == program]
        for host, device in zip(starts, runs):
            lead = max(lead, host - device)
    return lead


def named_gaps(planes: list, offset: float = 0.0) -> list:
    """Chip 0's idle gaps in the window, (name, start, end) in ns, each
    named by the innermost program or benchmark span open at its middle
    once host spans are shifted ``offset`` ns onto the device clock."""
    t0, t1 = trace.window(planes)
    (dev, *_) = trace.device_planes(planes)
    spans = [(n, s - offset, e - offset) for n, s, e in program_spans(planes)
             if n != trace.WINDOW_SPAN]
    out = []
    for s, e in trace.idle_gaps(dev, t0, t1):
        mid = 0.5 * (s + e)
        open_ = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ else NO_SPAN
        out.append((name, s, e))
    return out


def reduce(planes: list, top: int = 10) -> dict:
    """``trace.reduce`` of the window, and what the program's layers add:
    ``scopes`` (``scope_times``), ``clock_offset_s``, ``idle_by_span``
    (chip 0's idle seconds per naming span) and, in the breakdown, the
    ``device_scopes`` that took most time.  The breakdown's ``idle_gaps``
    are named after the offset shift, by ``fed.`` spans too."""
    out = trace.reduce(planes, top)
    offset = clock_offset(planes)
    scopes = scope_times(planes)
    gaps = [(name, (e - s) * 1e-9) for name, s, e in named_gaps(planes, offset)]
    by_span: dict = {}
    for name, secs in gaps:
        by_span[name] = by_span.get(name, 0.0) + secs
    out["scopes"] = scopes
    out["clock_offset_s"] = offset * 1e-9
    out["idle_by_span"] = by_span
    out["breakdown"]["device_scopes"] = [
        [k, v] for k, v in sorted(scopes.items(), key=lambda kv: -kv[1])[:top]]
    out["breakdown"]["idle_gaps"] = [[k, v] for k, v in sorted(gaps, key=lambda g: -g[1])[:top]]
    return out
