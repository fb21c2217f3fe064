"""Positions a full layer's queries attend over the positions cached for
them, in percent, over the window: how sparse the traffic made the
model.  From the counts the program puts on its ``params.span`` spans
(``sel_attended``, ``sel_cached``: decode steps and the prefill behind
each first token).  None where the program has no such counts."""

from chipbench import program_trace


def read(observed, params):
    if observed.get("kind") != params["kind"]:
        return None
    parsed = program_trace.get(observed)
    if parsed is None:
        return None
    lo, hi = parsed["lo"], parsed["hi"]
    attended = cached = 0
    for name, start, _, stats in parsed["host"]:
        if name == params["span"] and lo <= start <= hi \
                and "sel_cached" in stats:
            attended += int(stats["sel_attended"])
            cached += int(stats["sel_cached"])
    if not cached:
        return None
    print(f"[selected_context] over the window: attended {attended} of "
          f"{cached} cached positions a full layer", flush=True)
    return 100.0 * attended / cached
