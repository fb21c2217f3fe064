"""Device time under registered scopes of the program
(``torchacc_tpu/obs/tracing.py`` ``DEVICE_SCOPES``) over the device's
busy time, worst device.  ``params``: ``{"scopes": [names]}`` or
``{"complement_of_registry": true}`` (the time under no registered
scope); ``"kind"`` names the kind of cell that reports it.  Each op's
own time counts once, under the innermost registered name of its
op_name path (``program_trace.py``)."""

from chipbench import program_trace


def scope_time(observed, params):
    """Per device: (nanoseconds under the scopes, busy nanoseconds), or
    None where there is nothing to read."""
    if observed.get("kind") != params["kind"]:
        return None
    parsed = program_trace.get(observed)
    if parsed is None:
        return None
    wanted = ([program_trace.UNATTRIBUTED]
              if params.get("complement_of_registry") else params["scopes"])
    lo, hi = parsed["lo"], parsed["hi"]
    out = []
    for ops in parsed["devices"].values():
        own = program_trace.scope_self_time(ops, lo, hi)
        out.append((sum(own.get(s, 0.0) for s in wanted),
                    sum(own.values())))
    return out


def read(observed, params):
    per_device = scope_time(observed, params)
    if per_device is None:
        return None
    return max(100.0 * t / busy for t, busy in per_device if busy)
