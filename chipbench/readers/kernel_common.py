"""Shared by the kernel readers: which device events are the kernel's."""

from chipbench import trace_reduce


def matcher(observed, params):
    if "names_from" in params:
        names = set(observed.get(params["names_from"]) or ())
        return (lambda n: n in names) if names else None
    prefix = params["prefix"]
    return lambda n: n.startswith(prefix)


def kernel_and_busy(observed, params):
    """Per device: (kernel ns, busy ns) inside the window; None where
    the trace holds no event of the kernel."""
    trace = observed.get("trace")
    keep = matcher(observed, params) if trace else None
    if keep is None:
        return None
    lo, hi = trace["lo"], trace["hi"]
    out = []
    for dev in trace["devices"].values():
        kernel = sum(trace_reduce.time_by_name(dev["ops"], lo, hi,
                                               keep).values())
        out.append((kernel, trace_reduce.busy(dev["ops"], lo, hi)))
    return out if any(k for k, _ in out) else None
