"""From a profiler trace (``.xplane.pb``) to numbers.

What one real trace of this program on a TPU v5e looks like (looked at
by hand, PR 23): one plane ``/device:TPU:<n>`` per chip with the lines
``XLA Modules`` (one event per program execution), ``XLA Ops`` (one
event per HLO instruction executed, named by the instruction's full text
``%name = type opcode(...)``; a ``while`` and the ops of its body are
nested events on the same line) and ``Async XLA Ops`` (copy-start /
slice-start / collective-start to their done).  Host threads are lines
of the plane ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans appear
on the calling thread's line under their own name.  All starts are
nanoseconds on one clock, so a host span can be laid over device events.

Everything here works on plain ``(name, start_ns, end_ns)`` tuples, so it
can be checked on hand-made cases; ``load`` is the only part that reads
the file (with ``jax.profiler.ProfileData`` and nothing else).
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
_OP_NAME = re.compile(r"^%?([^\s=(]+)")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
_CONTAINERS = ("while", "conditional", "call")


class NoDevicePlane(RuntimeError):
    """The trace holds no ``/device:TPU:<n>`` plane: nothing ran on a
    chip, or the profiler did not see it.  Never read host threads in a
    device's place."""


def op_name(event_name: str) -> str:
    """``%fusion.4 = bf16[...] fusion(...)`` -> ``fusion.4``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def is_container(name: str) -> bool:
    return name.split(".")[0] in _CONTAINERS


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """-> {"devices": {ordinal: {"ops": [...], "async": [...],
    "modules": [...]}}, "host": [(name, start, end)]} with op names
    already shortened.  Raises ``NoDevicePlane`` without a device."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {OPS_LINE: [], ASYNC_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
            devices[int(m.group(1))] = {"ops": lines[OPS_LINE],
                                        "async": lines[ASYNC_LINE],
                                        "modules": lines[MODULES_LINE]}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith("chipbench/"))
    if not devices or not any(d["ops"] for d in devices.values()):
        raise NoDevicePlane(
            f"{path}: no /device:TPU:<n> plane with XLA Ops events")
    return {"devices": devices, "host": host}


# -- interval arithmetic ------------------------------------------------------

def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def subtract(a, b):
    """The part of union ``a`` not covered by union ``b`` (both sorted,
    disjoint)."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def window_of(host, name="chipbench/window"):
    """(start, end) of the benchmark's annotation around the window."""
    spans = [(a, b) for n, a, b in host if n == name]
    if len(spans) != 1:
        raise ValueError(f"expected one {name!r} span in the trace, "
                         f"found {len(spans)}")
    return spans[0]


def busy(ops, lo, hi) -> float:
    """Nanoseconds inside [lo, hi] in which some op ran on the device."""
    return total(union(clip([(a, b) for _, a, b in ops], lo, hi)))


def idle_gaps(ops, lo, hi):
    """The gaps of the device inside [lo, hi], sorted by start."""
    return subtract([(lo, hi)],
                    union(clip([(a, b) for _, a, b in ops], lo, hi)))


def time_by_name(ops, lo, hi, keep=None) -> dict:
    """Summed duration per op name inside [lo, hi] (containers and the
    ops nested in them both count their whole duration)."""
    out: dict = {}
    for name, a, b in ops:
        if keep is not None and not keep(name):
            continue
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def self_time_by_name(ops, lo, hi) -> dict:
    """Per op name, its duration minus that of the events nested in it:
    what to rank the device's time by without counting a ``while`` and
    its body twice."""
    out: dict = {}
    stack = []                         # [name, start, end, child_time]

    def close(item):
        name, a, b, child = item
        out[name] = out.get(name, 0.0) + max((b - a) - child, 0.0)

    for name, a, b in sorted(clip3(ops, lo, hi),
                             key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(b, stack[-1][2]) - a
        stack.append([name, a, b, 0.0])
    while stack:
        close(stack.pop())
    return out


def clip3(ops, lo, hi):
    return [(n, max(a, lo), min(b, hi)) for n, a, b in ops
            if min(b, hi) > max(a, lo)]


def leaf_intervals(ops, lo, hi, keep):
    """Union of the intervals of non-container ops that ``keep``."""
    return union([(a, b) for n, a, b in clip3(ops, lo, hi)
                  if not is_container(n) and keep(n)])


def collective_exposed(ops, async_ops, lo, hi) -> float:
    """Nanoseconds inside [lo, hi] in which a collective was in flight
    on this device and no other op ran: the union of collective events
    (sync ones on the ops line, start..done ones on the async line)
    minus the union of every non-collective, non-container op."""
    coll = union([(a, b) for n, a, b in clip3(list(ops) + list(async_ops),
                                              lo, hi) if is_collective(n)])
    other = leaf_intervals(ops, lo, hi, lambda n: not is_collective(n))
    return total(subtract(coll, other))


def gaps_by_annotation(gaps, host, skip=("chipbench/window",)) -> dict:
    """Each gap's time under the innermost benchmark annotation that
    covers its midpoint ('(none)' where no annotation does)."""
    spans = sorted(((a, b, n) for n, a, b in host if n not in skip),
                   key=lambda s: s[0])
    out: dict = {}
    for a, b in gaps:
        mid = (a + b) / 2.0
        best = None
        for sa, sb, n in spans:
            if sa > mid:
                break
            if sb >= mid and (best is None or sb - sa < best[0]):
                best = (sb - sa, n)
        key = best[1] if best else "(none)"
        out[key] = out.get(key, 0.0) + (b - a)
    return out


def top(d: dict, n=10, scale=1e-9):
    """The ``n`` largest entries as ``[[name, seconds], ...]``."""
    return [[k, v * scale] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]
