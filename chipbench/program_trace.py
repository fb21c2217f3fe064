"""The traced run's xplane read with the PROGRAM's names (PR 24).

``trace_reduce.load`` (``jax.profiler.ProfileData``) sees each event's
name and own stats.  The profiler also writes, per XLA op, event-
*metadata* stats that ``ProfileData`` does not expose: ``tf_op`` (the
``jax.named_scope`` / Flax module path the op was traced under),
``flops`` and ``bytes_accessed``; and the program's host spans
(``torchacc_tpu/obs/tracing.py``: every ``span()`` is a
``TraceAnnotation`` while a trace is open) lie on ``/host:CPU`` in the
same nanoseconds.  This module parses the file once per process into

- ``host``: ``(name, start_ns, end_ns, stats)`` for every host event
  named in the program's span registry (``tracing.SPAN_NAMES``), and
- ``devices[ordinal]``: ``(op, start_ns, end_ns, scope, tf_op, flops,
  bytes, program)`` for every ``XLA Ops`` event, ``scope`` being the
  innermost component of ``tf_op`` that is a registered device scope
  (``tracing.DEVICE_SCOPES``) or ``None``, ``program`` the jitted
  program the instruction belongs to (instruction names repeat across
  programs),

and prints, once, the scope partition of the device's busy time, the
largest ops with the scope each lands under, and the device's idle gaps
by the innermost program span over each gap's midpoint.

A program without the registries (a commit before PR 24) gives ``None``
from ``get``: every reader built on it then reports nothing.  The file
is decoded with ``google.protobuf`` from a schema written out below (the
fields of ``tsl/profiler/protobuf/xplane.proto`` that are read); a TPU
run without ``google.protobuf`` raises.
"""

from __future__ import annotations

import os
import re

from chipbench import spec, trace_reduce

UNATTRIBUTED = "(unattributed)"
_WRAPPED = re.compile(r"^(?:[\w.\-]+\()+|\)+$")
_PROGRAM = re.compile(r"^(.+)\((\d+)\)$")
_cache: dict = {}


def registries():
    """``(span names, device scope names)`` of the program under test,
    or ``None`` where it has none."""
    try:
        from torchacc_tpu.obs import tracing
    except ImportError:
        return None
    spans = getattr(tracing, "SPAN_NAMES", None)
    scopes = getattr(tracing, "DEVICE_SCOPES", None)
    if not spans or not scopes:
        return None
    return tuple(spans), tuple(scopes)


# -- the file -----------------------------------------------------------------

def _xspace_class():
    """``XSpace`` built from a descriptor written here: only the fields
    this module reads; unknown fields are skipped by the decoder."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    T = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench.xplane",
        syntax="proto3")

    def message(name, *fields, oneof=None):
        m = fd.message_type.add(name=name)
        if oneof:
            m.oneof_decl.add(name=oneof)
        for fname, number, ftype, repeated, type_name in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=T.LABEL_REPEATED if repeated
                            else T.LABEL_OPTIONAL)
            if type_name:
                f.type_name = ".chipbench.xplane." + type_name
            if oneof and fname.endswith("_value"):
                f.oneof_index = 0

    i64, u64, f64 = T.TYPE_INT64, T.TYPE_UINT64, T.TYPE_DOUBLE
    s, b, msg = T.TYPE_STRING, T.TYPE_BYTES, T.TYPE_MESSAGE
    message("XStat", ("metadata_id", 1, i64, 0, None),
            ("double_value", 2, f64, 0, None),
            ("uint64_value", 3, u64, 0, None),
            ("int64_value", 4, i64, 0, None),
            ("str_value", 5, s, 0, None),
            ("bytes_value", 6, b, 0, None),
            ("ref_value", 7, u64, 0, None), oneof="value")
    message("XEvent", ("metadata_id", 1, i64, 0, None),
            ("offset_ps", 2, i64, 0, None),
            ("duration_ps", 3, i64, 0, None),
            ("stats", 4, msg, 1, "XStat"))
    message("XLine", ("name", 2, s, 0, None),
            ("timestamp_ns", 3, i64, 0, None),
            ("events", 4, msg, 1, "XEvent"))
    message("XEventMetadata", ("id", 1, i64, 0, None),
            ("name", 2, s, 0, None),
            ("stats", 5, msg, 1, "XStat"))
    message("XStatMetadata", ("id", 1, i64, 0, None),
            ("name", 2, s, 0, None))
    # map<int64, V> is a repeated entry message {key = 1, value = 2}
    message("EventMetadataEntry", ("key", 1, i64, 0, None),
            ("value", 2, msg, 0, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, i64, 0, None),
            ("value", 2, msg, 0, "XStatMetadata"))
    message("XPlane", ("name", 2, s, 0, None),
            ("lines", 3, msg, 1, "XLine"),
            ("event_metadata", 4, msg, 1, "EventMetadataEntry"),
            ("stat_metadata", 5, msg, 1, "StatMetadataEntry"))
    message("XSpace", ("planes", 1, msg, 1, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.xplane.XSpace"))


def _stat_value(stat, stat_names):
    field = stat.WhichOneof("value")
    if field is None:
        return None
    if field == "ref_value":
        return stat_names.get(stat.ref_value, "")
    return getattr(stat, field)


def _stats(stats, stat_names):
    return {stat_names.get(st.metadata_id, ""): _stat_value(st, stat_names)
            for st in stats}


def scope_of(tf_op: str, scopes) -> str | None:
    """The innermost component of an op_name path that is a registered
    scope.  ``jit(f)/jvp(fused_ce)/while/body/dot_general:`` ->
    ``fused_ce``: transforms wrap the first name under them."""
    for part in reversed(tf_op.rstrip(":").split("/")):
        name = _WRAPPED.sub("", part)
        if name in scopes:
            return name
    return None


def _events(plane, line_name=None):
    """(metadata, start_ns, end_ns, event) of a plane's events, times as
    ``ProfileData`` gives them: the line's ``timestamp_ns`` plus the
    event's offset."""
    metadata = {e.key: e.value for e in plane.event_metadata}
    for line in plane.lines:
        if line_name is not None and line.name != line_name:
            continue
        for ev in line.events:
            md = metadata.get(ev.metadata_id)
            if md is not None:
                a = line.timestamp_ns + ev.offset_ps / 1000.0
                yield md, a, a + ev.duration_ps / 1000.0, ev


def parse(path: str, span_names, scopes) -> dict:
    """-> ``{"host": [...], "devices": {ordinal: [...]}}`` (module
    docstring)."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    span_names, scopes = set(span_names), set(scopes)
    host, devices = [], {}
    for plane in space.planes:
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        if plane.name == trace_reduce.HOST_PLANE:
            host.extend((md.name, a, b, _stats(ev.stats, stat_names))
                        for md, a, b, ev in _events(plane)
                        if md.name in span_names)
            continue
        device = trace_reduce.DEVICE_PLANE.match(plane.name)
        if not device:
            continue
        # a program's executions are named ``jit_f(<program id>)``
        programs = dict(m.group(2, 1) for m in (
            _PROGRAM.match(e.value.name) for e in plane.event_metadata)
            if m)
        per_metadata, ops = {}, []
        for md, a, b, _ in _events(plane, trace_reduce.OPS_LINE):
            if md.id not in per_metadata:
                st = _stats(md.stats, stat_names)
                tf_op = str(st.get("tf_op", ""))
                per_metadata[md.id] = (
                    scope_of(tf_op, scopes), tf_op,
                    float(st.get("flops", 0) or 0),
                    float(st.get("bytes_accessed", 0) or 0),
                    programs.get(str(st.get("program_id")), ""))
            ops.append((trace_reduce.op_name(md.name), a, b,
                        *per_metadata[md.id]))
        devices[int(device.group(1))] = ops
    return {"host": host, "devices": devices}


# -- reductions (plain tuples, checked on hand-made cases) ---------------------

def _plain(ops):
    """``(op, start, end)`` tuples, as ``trace_reduce`` takes them."""
    return [(op, a, b) for op, a, b, *_ in ops]


def scope_self_time(ops, lo, hi) -> dict:
    """Nanoseconds inside [lo, hi] per scope: each event's own time (a
    ``while``'s minus its body's ops), so that the scopes partition the
    device's busy time."""
    return trace_reduce.self_time_by_name(
        [(scope or UNATTRIBUTED, a, b) for _, a, b, scope, *_ in ops],
        lo, hi)


def op_self_time(ops, lo, hi) -> dict:
    """Nanoseconds inside [lo, hi] per ``(program:op, scope, tf_op)``."""
    return trace_reduce.self_time_by_name(
        [((f"{program}:{op}", scope or UNATTRIBUTED, tf_op), a, b)
         for op, a, b, scope, tf_op, _, _, program in ops], lo, hi)


def partition(ops, lo, hi, scopes) -> dict:
    """Every registered scope's share of busy time in percent, and the
    share under no registered scope."""
    own = scope_self_time(ops, lo, hi)
    busy = trace_reduce.busy(_plain(ops), lo, hi)
    out = {s: 100.0 * own.get(s, 0.0) / busy for s in scopes}
    out[UNATTRIBUTED] = 100.0 * own.get(UNATTRIBUTED, 0.0) / busy
    return out


def span_time(host, lo, hi, name, where=None):
    """(nanoseconds inside [lo, hi], events) of the spans ``name`` whose
    stats match ``where`` (values compared as strings)."""
    spans = [(a, b) for n, a, b, stats in host if n == name and all(
        str(stats.get(k)) == str(v) for k, v in (where or {}).items())]
    clipped = trace_reduce.clip(spans, lo, hi)
    return trace_reduce.total(clipped), len(clipped)


# -- one parse a process -------------------------------------------------------

def get(observed):
    """The parsed trace of this run with ``lo``/``hi``/``scopes``, or
    ``None``: a rehearsal (no trace) or a program without registries."""
    trace = observed.get("trace")
    if trace is None:
        return None
    names = registries()
    if names is None:
        return None
    if "parsed" not in _cache:
        path = trace_reduce.find_xplane(
            os.path.join(spec.ROOT, ".cache", "chipbench_trace"))
        parsed = parse(path, *names)
        if not parsed["devices"]:
            raise trace_reduce.NoDevicePlane(
                f"{path}: no /device:TPU:<n> plane")
        parsed.update(lo=trace["lo"], hi=trace["hi"], scopes=names[1])
        _cache["parsed"] = parsed
        report(parsed)
    return _cache["parsed"]


def busiest(parsed):
    lo, hi = parsed["lo"], parsed["hi"]
    return max(parsed["devices"].values(),
               key=lambda ops: trace_reduce.busy(_plain(ops), lo, hi))


def report(parsed, top=24) -> None:
    """The lines a traced run prints before its result line."""
    lo, hi, scopes = parsed["lo"], parsed["hi"], parsed["scopes"]
    ops = busiest(parsed)
    shares = partition(ops, lo, hi, scopes)
    shown = " ".join(f"{k}={v:.2f}" for k, v in sorted(
        shares.items(), key=lambda kv: -kv[1]) if v >= 0.005)
    print(f"[program_trace] scope partition of busy time, %: {shown} "
          f"sum={sum(shares.values()):.2f}", flush=True)
    busy = trace_reduce.busy(_plain(ops), lo, hi)
    ranked = sorted(op_self_time(ops, lo, hi).items(),
                    key=lambda kv: -kv[1])
    print("[program_trace] largest ops, % of busy time -> scope: " + "; ".join(
        f"{op} {100.0 * t / busy:.2f} -> {scope}"
        for (op, scope, _), t in ranked[:top]), flush=True)
    loose = [(op, tf_op, t) for (op, scope, tf_op), t in ranked
             if scope == UNATTRIBUTED]
    if loose:
        print("[program_trace] largest unattributed ops: " + "; ".join(
            f"{op} {100.0 * t / busy:.2f} [{tf_op or 'no op_name'}]"
            for op, tf_op, t in loose[:8]), flush=True)
    gaps = trace_reduce.gaps_by_annotation(
        trace_reduce.idle_gaps(_plain(ops), lo, hi),
        [(n, a, b) for n, a, b, _ in parsed["host"]], skip=())
    print("[program_trace] idle gaps by program span, ms: " + " ".join(
        f"{k}={v:.3f}" for k, v in trace_reduce.top(gaps, scale=1e-6)),
        flush=True)
