"""Device time of the programs whose name starts with ``params.prefix``
(events of the ``XLA Modules`` line: one per program execution) over the
device's busy time inside the window, worst device."""

from chipbench import trace_reduce


def share(modules, ops, lo, hi, prefix):
    """Percent, or None where no such program ran."""
    busy = trace_reduce.busy(ops, lo, hi)
    mine = trace_reduce.total(trace_reduce.union(trace_reduce.clip(
        [(a, b) for n, a, b in modules if n.startswith(prefix)], lo, hi)))
    return 100.0 * mine / busy if busy and mine else None


def read(observed, params):
    trace = observed.get("trace")
    if not trace or observed.get("kind") != params["kind"]:
        return None
    found = [share(d["modules"], d["ops"], trace["lo"], trace["hi"],
                   params["prefix"]) for d in trace["devices"].values()]
    found = [x for x in found if x is not None]
    return max(found) if found else None
