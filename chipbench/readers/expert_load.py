"""The busiest held expert's load over the mean held expert's, from the
counts the program puts on its own spans (``params.span``, attributes
``moe_max``: the largest held expert's (token, expert) pairs summed over
the expert layers of the step(s) behind the span; ``moe_pairs``: all
pairs on held experts; ``moe_slots``: held experts x expert layers x
steps; ``moe_layer_steps``: expert layers x steps).  1.0 = every held
expert drew the same number of pairs in every layer of every step."""

from chipbench import program_trace


def counts(observed, span):
    """Sums of the span's ``moe_*`` attributes over the window, or None
    where the program has no such spans."""
    parsed = program_trace.get(observed)
    if parsed is None:
        return None
    lo, hi = parsed["lo"], parsed["hi"]
    total = {}
    for name, start, _, stats in parsed["host"]:
        if name == span and lo <= start <= hi and "moe_pairs" in stats:
            for key, value in stats.items():
                if key.startswith("moe_"):
                    total[key] = total.get(key, 0) + int(value)
    return total or None


def read(observed, params):
    if observed.get("kind") != params["kind"]:
        return None
    total = counts(observed, params["span"])
    if not total or not total["moe_pairs"]:
        return None
    print(f"[expert_load] over the window: {total}", flush=True)
    busiest = total["moe_max"] / total["moe_layer_steps"]
    mean = total["moe_pairs"] / total["moe_slots"]
    return busiest / mean
