"""Exposed collective time: the part of the collectives' union during
which no other op runs on that device, over the window; worst device."""

from chipbench import trace_reduce


def read(observed, params):
    trace = observed.get("trace")
    if not trace or len(trace["devices"]) < 2:
        return None
    lo, hi = trace["lo"], trace["hi"]
    return max(100.0 * trace_reduce.collective_exposed(
        d["ops"], d["async"], lo, hi) / (hi - lo)
        for d in trace["devices"].values())
