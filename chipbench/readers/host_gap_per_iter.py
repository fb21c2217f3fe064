"""Device idle inside the window over the engine iterations in it, in
milliseconds: what the host's per-iteration work leaves the chip
waiting for.  Worst device."""

from chipbench import trace_reduce


def read(observed, params):
    trace = observed.get("trace")
    if not trace or observed.get("kind") != "serve" \
            or not observed["iterations"]:
        return None
    lo, hi = trace["lo"], trace["hi"]
    idle = max((hi - lo) - trace_reduce.busy(d["ops"], lo, hi)
               for d in trace["devices"].values())
    return idle * 1e-6 / observed["iterations"]
