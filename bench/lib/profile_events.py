"""What the profile holds beyond ``trace.load_xplane``: the program's own
spans as the profiler recorded them, with their attributes, and the
name-scope path of each device operation.

A live ``repro.obs`` span opens a ``jax.profiler`` annotation of the same
name, with its attributes as the event's stats, so every program span is a
host event of the trace, on the trace's clock: no offset to guess.
``load`` keeps, as plain lists on that clock,

  host   ``[name, start_ns, end_ns, stats]`` of every host event
  ops    per chip, ``[name, start_ns, end_ns, path]`` of each "XLA Ops"
         event; ``path`` is the operation's ``op_name`` metadata (the
         ``jax.named_scope`` path), "" where it has none

The path is the stat ``SCOPE_STAT`` of the operation's event metadata on
the TPU's plane (there with or without the profiler's HLO protos).
``jax.profiler.ProfileData`` shows an event's own stats only, so
``op_scopes`` reads the metadata from the ``.xplane.pb`` with a schema of
just the fields it needs, and matches it to the events by the operation's
text, which is the metadata's name.

``Detail`` reduces them inside the traced window (the ``bench.round``
annotations), and ``of(ctx)`` gives a per-layer reader the traced run's
``Detail``, read once per process.  A program without the spans or scopes
leaves the readers nothing to read: they return None.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path

from bench.lib import trace as trace_lib
from bench.run import OUT_DIR

SCOPE_STAT = "tf_op"
# the client round's stage scopes (repro.core.protocol.client_round)
STAGES = ("train_weights", "compress", "train_scales")
_WRAPPER = re.compile(r"^[\w.-]+\((.*)\)$")


def _xspace_message():
    """A message class for the part of ``XSpace`` (the profiler's
    ``xplane.proto``) that ``op_scopes`` reads; every other field is
    skipped unparsed.  Text fields are read as bytes, so no name can fail
    to parse."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_profile_events.proto", package="bench_profile_events",
        syntax="proto3")

    def message(name, *fields):
        """``fields``: (name, number, type); a type given as a name is a
        message, "[]" after it repeats the field."""
        m = fdp.message_type.add(name=name)
        for fname, number, ftype in fields:
            f = m.field.add(name=fname, number=number,
                            label=F.LABEL_OPTIONAL)
            if isinstance(ftype, str):
                if ftype.endswith("[]"):
                    f.label, ftype = F.LABEL_REPEATED, ftype[:-2]
                f.type = F.TYPE_MESSAGE
                f.type_name = f".{fdp.package}.{ftype}"
            else:
                f.type = ftype

    # field numbers as in xplane.proto; the maps are repeated entries
    message("XStat", ("metadata_id", 1, F.TYPE_INT64),
            ("str_value", 5, F.TYPE_BYTES), ("ref_value", 7, F.TYPE_UINT64))
    message("XEventMetadata", ("name", 2, F.TYPE_BYTES),
            ("stats", 5, "XStat[]"))
    message("XStatMetadata", ("name", 2, F.TYPE_BYTES))
    message("EventMd", ("key", 1, F.TYPE_INT64),
            ("value", 2, "XEventMetadata"))
    message("StatMd", ("key", 1, F.TYPE_INT64), ("value", 2, "XStatMetadata"))
    message("XPlane", ("name", 2, F.TYPE_BYTES),
            ("event_metadata", 4, "EventMd[]"),
            ("stat_metadata", 5, "StatMd[]"))
    message("XSpace", ("planes", 1, "XPlane[]"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{fdp.package}.XSpace"))


def op_scopes(xplane_file: str | Path) -> dict[str, str]:
    """{operation text: its ``SCOPE_STAT`` path} over the TPU planes'
    event metadata of one ``.xplane.pb``."""
    xs = _xspace_message()()
    xs.ParseFromString(Path(xplane_file).read_bytes())
    out: dict[str, str] = {}
    for plane in xs.planes:
        if not plane.name.decode(errors="replace").startswith(
                trace_lib.DEVICE_PLANE):
            continue
        stat_names = {e.key: e.value.name.decode(errors="replace")
                      for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            md = entry.value
            for st in md.stats:
                if stat_names.get(st.metadata_id) == SCOPE_STAT:
                    path = (st.str_value.decode(errors="replace")
                            or stat_names.get(st.ref_value, ""))
                    out.setdefault(md.name.decode(errors="replace"), path)
    return out


def load(trace_dir: str | Path) -> dict:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    scopes = op_scopes(files[-1])
    host, ops = [], {}
    for plane in pd.planes:
        if plane.name.startswith(trace_lib.DEVICE_PLANE):
            chip = plane.name[len(trace_lib.DEVICE_PLANE):]
            if not chip.isdigit():
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = [[trace_lib.op_name(e), e.start_ns, e.end_ns,
                                  scopes.get(e.name, "")]
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.end_ns, dict(e.stats)]
                         for e in line.events]
    return {"host": host, "ops": ops}


def scopes_of(path: str) -> set[str]:
    """The scope names along an ``op_name`` path, transformation wrappers
    (``vmap(...)``, ``transpose(jvp(...))``) stripped."""
    out = set()
    for part in path.split("/"):
        while (m := _WRAPPER.match(part)) and m.group(1):
            out.add(part)
            part = m.group(1)
        out.add(part)
    return out


class Detail:
    """``load``'s lists inside the window ``[lo, hi]`` of the trace's
    clock (``trace.Reduction.lo`` / ``.hi``)."""

    def __init__(self, events: dict, lo: float, hi: float):
        self.lo, self.hi = lo, hi
        self.host = [(n, s, e, st) for n, s, e, st in events["host"]
                     if e > lo and s < hi]
        chips = sorted(events["ops"], key=int)
        first = events["ops"][chips[0]] if chips else []
        self.ops = [(n, s, e, p) for n, s, e, p in first if e > lo and s < hi]

    def span_union_ns(self, names) -> float:
        """Length of the union of the named host spans, clipped to the
        window (nested or concurrent spans count once)."""
        return trace_lib.union_ns([(s, e) for n, s, e, _ in self.host
                                   if n in names], self.lo, self.hi)

    def span_attr_sum(self, names, attr: str) -> int:
        """Sum of the attribute ``attr`` over the named host spans."""
        return sum(int(st[attr]) for n, _, _, st in self.host
                   if n in names and attr in st)

    def scope_self_ns(self, scopes) -> dict[str, float]:
        """Device self time (ns, first chip, clipped to the window) of the
        operations under each of ``scopes``; an operation under none of
        them counts under ""."""
        wanted, labels = set(scopes), {}

        def label(path):
            if path not in labels:
                hit = scopes_of(path) & wanted
                labels[path] = min(hit) if hit else ""
            return labels[path]

        return trace_lib.self_ns([(label(p), max(s, self.lo),
                                   min(e, self.hi))
                                  for _, s, e, p in self.ops])


_CACHE: dict[str, Detail] = {}


def of(ctx) -> Detail | None:
    """The traced run's ``Detail`` for a reader's ``MetricContext``, or
    None where the run left no trace to read."""
    trace_dir = OUT_DIR / ctx.cell["name"] / "trace"
    key = str(trace_dir)
    if key not in _CACHE:
        try:
            events = load(trace_dir)
        except FileNotFoundError:
            return None
        _CACHE[key] = Detail(events, ctx.red.lo, ctx.red.hi)
    return _CACHE[key]


def per_round_s(ns: float, ctx) -> float | None:
    return ns / 1e9 / ctx.rounds if ns else None
