"""1 - union of the device's op intervals / the annotated window; worst
device.  ``params.kind`` says which kind of cell reports it."""

from chipbench import trace_reduce


def read(observed, params):
    trace = observed.get("trace")
    if not trace or observed.get("kind") != params["kind"]:
        return None
    lo, hi = trace["lo"], trace["hi"]
    return max(100.0 * (1.0 - trace_reduce.busy(d["ops"], lo, hi) / (hi - lo))
               for d in trace["devices"].values())
