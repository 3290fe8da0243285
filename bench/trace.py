"""Reduce a profiler trace to device busy time, op and scope sums, and the
idle gaps named by what the host was doing.

A trace is read once into plain tuples (:class:`Trace`):

- ``ops``: one ``(start_ns, end_ns, name, scope, device)`` per device
  operation on the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane,
  where ``scope`` is the op's ``tf_op`` stat, its name-scope path (e.g.
  ``jit(f)/while/body/ascii_hop_0/...``);
- ``host``: one ``(start_ns, end_ns, name)`` per host annotation kept by
  ``keep_host`` (the benchmark's ``bench.*`` annotations and the program's
  own spans).

Everything after that is arithmetic on intervals, so the tests check it on
hand-built event lists and on a cut of a trace recorded on the chip.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# name scopes the program puts around its work (core/compiled.py)
SCOPE = re.compile(r"(ascii_hop_\d+|ascii_async_fit_\d+|serve_block_\d+)")
# host annotations worth keeping: the benchmark's own, and the program's
# Telemetry spans (session, replay, flush, flush_wave, bucket_dispatch, ...)
PROGRAM_SPANS = frozenset({"session", "replay", "serve", "flush",
                           "flush_wave", "bucket_dispatch", "round", "hop"})


def keep_host(name: str) -> bool:
    return name.startswith("bench.") or name in PROGRAM_SPANS


@dataclass
class Trace:
    ops: list = field(default_factory=list)   # (start, end, name, label, dev)
    host: list = field(default_factory=list)  # (start, end, name)


def find_xplane(directory) -> Path:
    """The one ``.xplane.pb`` that ``jax.profiler.trace(directory)`` wrote."""
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory}, "
                                f"found {len(found)}")
    return found[0]


def _xspace_class():
    """The message class of the profiler's ``XSpace`` proto, built from its
    field numbers (``tsl/profiler/protobuf/xplane.proto``) so that reading
    a trace needs nothing but ``protobuf``."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    f = descriptor_pb2.FieldDescriptorProto
    i64, u64, dbl = f.TYPE_INT64, f.TYPE_UINT64, f.TYPE_DOUBLE
    s, b, msg = f.TYPE_STRING, f.TYPE_BYTES, f.TYPE_MESSAGE
    one, many = f.LABEL_OPTIONAL, f.LABEL_REPEATED
    pkg = "bench.xplane"
    spec = {
        "XStat": [("metadata_id", 1, i64, one), ("double_value", 2, dbl, one),
                  ("uint64_value", 3, u64, one), ("int64_value", 4, i64, one),
                  ("str_value", 5, s, one), ("bytes_value", 6, b, one),
                  ("ref_value", 7, u64, one)],
        "XEvent": [("metadata_id", 1, i64, one), ("offset_ps", 2, i64, one),
                   ("duration_ps", 3, i64, one), ("stats", 4, "XStat", many),
                   ("num_occurrences", 5, i64, one)],
        "XLine": [("id", 1, i64, one), ("name", 2, s, one),
                  ("timestamp_ns", 3, i64, one), ("events", 4, "XEvent", many),
                  ("duration_ps", 9, i64, one)],
        "XEventMetadata": [("id", 1, i64, one), ("name", 2, s, one),
                           ("metadata", 3, b, one),
                           ("display_name", 4, s, one),
                           ("stats", 5, "XStat", many)],
        "XStatMetadata": [("id", 1, i64, one), ("name", 2, s, one)],
        "XPlane": [("id", 1, i64, one), ("name", 2, s, one),
                   ("lines", 3, "XLine", many),
                   ("event_metadata", 4, "XPlane.EventMetadataEntry", many),
                   ("stat_metadata", 5, "XPlane.StatMetadataEntry", many),
                   ("stats", 6, "XStat", many)],
        "XSpace": [("planes", 1, "XPlane", many)],
    }
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package=pkg, syntax="proto3")

    def add(container, name, fields):
        m = container.add(name=name)
        for fname, num, kind, label in fields:
            fd = m.field.add(name=fname, number=num, label=label)
            if isinstance(kind, str):
                fd.type, fd.type_name = msg, f".{pkg}.{kind}"
            else:
                fd.type = kind
        return m

    for name, fields in spec.items():
        m = add(fdp.message_type, name, fields)
        if name == "XPlane":
            for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                                 ("StatMetadataEntry", "XStatMetadata")):
                e = add(m.nested_type, entry, [("key", 1, i64, one),
                                               ("value", 2, value, one)])
                e.options.map_entry = True
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))


def _scope_stat(stats, stat_names) -> str:
    """The ``tf_op`` stat of an op's metadata: the name-scope path of the
    HLO op (``jit(f)/while/body/ascii_hop_0/...``)."""
    for st in stats:
        if stat_names.get(st.metadata_id) == "tf_op":
            return st.str_value or stat_names.get(st.ref_value, "")
    return ""


def short_name(name: str) -> str:
    """An HLO instruction's name from the text a TPU trace gives as the op
    name (``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``)."""
    m = re.match(r"%?([^\s=]+)", name)
    return m.group(1) if m else name


def from_xspace(space) -> Trace:
    """Extract a :class:`Trace` from a parsed ``XSpace`` message."""
    out = Trace()
    for plane in space.planes:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = plane.event_metadata
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            labels: dict = {}
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                base = line.timestamp_ns
                for ev in line.events:
                    mid = ev.metadata_id
                    if mid not in labels:
                        md = meta[mid]
                        labels[mid] = (short_name(md.display_name or md.name),
                                       _scope_stat(md.stats, stat_names))
                    name, label = labels[mid]
                    start = base + ev.offset_ps * 1e-3
                    out.ops.append((start, start + ev.duration_ps * 1e-3,
                                    name, label, dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                base = line.timestamp_ns
                for ev in line.events:
                    name = meta[ev.metadata_id].name
                    if keep_host(name):
                        start = base + ev.offset_ps * 1e-3
                        out.host.append((start,
                                         start + ev.duration_ps * 1e-3, name))
    out.ops.sort()
    out.host.sort()
    return out


def load(path) -> Trace:
    space = _xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    return from_xspace(space)


# ---------------------------------------------------------------- intervals
def clip(intervals, lo: float, hi: float) -> list:
    """(start, end) pairs cut to [lo, hi]; empty ones dropped."""
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def merge(intervals) -> list:
    """The union of (start, end) pairs as sorted disjoint intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(ops, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which some device operation ran."""
    return sum(e - s for s, e in merge(clip(((o[0], o[1]) for o in ops),
                                            lo, hi)))


def leaves(ops) -> list:
    """The ops that contain no other op of their device: on a TPU the
    event of a while loop covers the events of its body's ops."""
    out, by_dev = [], {}
    for o in ops:
        by_dev.setdefault(o[4], []).append(o)
    for dev_ops in by_dev.values():
        dev_ops.sort(key=lambda o: (o[0], -o[1]))
        for o, nxt in zip(dev_ops, dev_ops[1:] + [None]):
            if nxt is None or not (nxt[0] < o[1] and nxt[1] <= o[1]):
                out.append(o)
    out.sort()
    return out


def scope_of(op) -> str:
    """The program's name scope an op ran under, or ``other``."""
    m = SCOPE.search(op[3]) or SCOPE.search(op[2])
    return m.group(1) if m else "other"


def base_name(name: str) -> str:
    """An HLO op name without its numeric suffix (``fusion.12`` ->
    ``fusion``), so instances of one kind of op group together."""
    return re.sub(r"[.\d]+$", "", name) or name


def top_ops(ops, lo: float, hi: float, k: int = 10) -> list:
    """The ``k`` largest device-time groups, ``scope/op`` -> seconds."""
    sums: dict = {}
    for o in ops:
        s, e = max(o[0], lo), min(o[1], hi)
        if e > s:
            key = f"{scope_of(o)}/{base_name(o[2])}"
            sums[key] = sums.get(key, 0.0) + (e - s) * 1e-9
    return sorted(([n, v] for n, v in sums.items()),
                  key=lambda kv: -kv[1])[:k]


def idle_gaps(ops, host, lo: float, hi: float, k: int = 10) -> list:
    """Idle device time in [lo, hi], summed by the innermost host
    annotation open at each gap's midpoint (``none`` where there is none);
    the ``k`` largest as ``[name, seconds]``."""
    busy = merge(clip(((o[0], o[1]) for o in ops), lo, hi))
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if hi > prev:
        gaps.append((prev, hi))
    sums: dict = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        inner = None
        for hs, he, name in host:
            if hs > mid:
                break
            if he >= mid and (inner is None or hs >= inner[0]):
                inner = (hs, name)
        name = inner[1] if inner else "none"
        sums[name] = sums.get(name, 0.0) + (e - s) * 1e-9
    return sorted(([n, v] for n, v in sums.items()),
                  key=lambda kv: -kv[1])[:k]


def window_of(trace: Trace, name: str = "bench.traced") -> tuple:
    """[start, end] in ns of the host annotation ``name`` (the measured
    window); the whole trace when there is none."""
    for s, e, n in trace.host:
        if n == name:
            return s, e
    starts = [o[0] for o in trace.ops] + [h[0] for h in trace.host]
    ends = [o[1] for o in trace.ops] + [h[1] for h in trace.host]
    if not starts:
        raise ValueError("empty trace")
    return min(starts), max(ends)


def reduce(trace: Trace, lo: float | None = None,
           hi: float | None = None) -> dict:
    """The per-run summary the metric readers take: window and busy
    seconds (busy averaged over the device planes); the leaf ops (``ops``)
    and their seconds summed by name scope; and the breakdown lists."""
    if lo is None or hi is None:
        lo, hi = window_of(trace)
    devices = sorted({o[4] for o in trace.ops}) or [0]
    busy = sum(busy_ns([o for o in trace.ops if o[4] == d], lo, hi)
               for d in devices) / len(devices)
    ops = leaves(trace.ops)
    scopes: dict = {}
    for o in ops:
        s, e = max(o[0], lo), min(o[1], hi)
        if e > s:
            sc = scope_of(o)
            scopes[sc] = scopes.get(sc, 0.0) + (e - s) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9,
        "scope_s": scopes,
        "ops": ops,
        "lo": lo,
        "hi": hi,
        "device_ops": top_ops(ops, lo, hi),
        "idle_gaps": idle_gaps(trace.ops, trace.host, lo, hi),
    }
