"""What the rooflines of the ``mla_sparse_window_moe_decoder`` family
share: the layer counts of the cut model and the program's own counts of
the positions its decode steps worked through."""

from chipbench import program_trace


def layer_counts(observed):
    """(full layers, sliding layers) of the model as it is run."""
    kinds = observed["published"]["layer_types"][:observed["depth"]]
    sliding = sum(k == "sliding_attention" for k in kinds)
    return len(kinds) - sliding, sliding


def decode_counts(observed, span="serve/deliver"):
    """Sums over the window's DECODE steps of what the program puts on
    its ``serve/deliver`` spans for one layer of each kind:
    ``sel_attended`` (positions a full layer's queries attend: at most
    ``index_topk`` each), ``sel_cached`` (positions cached for them),
    ``win_attended`` (positions a window layer's queries attend).  A
    prefill chunk's counts come from the driver's own record of the
    chunk (``observed['prefill_chunks']``), so a request whose prefill
    began before the window is not counted whole.  Empty where the
    program has no such counts."""
    parsed = program_trace.get(observed)
    total = {}
    if parsed is None:
        return total
    lo, hi = parsed["lo"], parsed["hi"]
    for name, start, _, stats in parsed["host"]:
        if (name == span and lo <= start <= hi and "sel_cached" in stats
                and stats.get("kind") == "decode"):
            for key in ("sel_attended", "sel_cached", "win_attended"):
                total[key] = total.get(key, 0) + int(stats.get(key, 0))
    return total


def chunks(observed):
    """The prefill chunks of the window as (offset, tokens).  The
    driver's record begins at the window's start: a request that was
    mid-prefill then shows up once as (0, everything it had prefilled so
    far) — of which the window's iteration ran the last chunk alone.  A
    chunk is never longer than the longest entry that starts past 0, so
    longer entries are cut to their last chunk."""
    seen = observed["prefill_chunks"]
    most = max((n for t0, n in seen if t0 > 0), default=None)
    if most is None:
        return list(seen)
    return [(t0 + n - most, most) if n > most else (t0, n)
            for t0, n in seen]


def chunk_pairs(t0, n, most):
    """(query, position) pairs of a chunk of ``n`` queries at offset
    ``t0`` when a query at t attends ``min(t + 1, most)`` positions."""
    first_capped = max(most - 1, t0)          # first t with t + 1 >= most
    below = max(0, min(first_capped, t0 + n) - t0)
    # queries t0 .. t0 + below - 1 attend t + 1 each
    return (below * (2 * t0 + below + 1) // 2
            + (n - below) * most)


def summed(calls, peaks):
    """``calls``: (flops, bytes) of each call -> the totals and
    ``least_s`` (each call bound by the larger of the two)."""
    flops = bytes_ = least = 0.0
    for f, b in calls:
        flops, bytes_ = flops + f, bytes_ + b
        least += max(f / peaks["bf16_flops_per_s"],
                     b / peaks["hbm_bytes_per_s"])
    return {"flops": flops, "bytes": bytes_, "least_s": least}
