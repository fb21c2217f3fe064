"""What the rooflines of the ``gqa_window_moe_decoder`` family share:
the layer counts of the cut model, a token's k and v bytes a layer, and
the program's own counts of the positions its decode steps attended."""

from chipbench import program_trace
from chipbench.rooflines.sparse_select_common import (  # noqa: F401
    chunk_pairs,
    chunks,
    layer_counts,
    summed,
)


def sizes(observed):
    """``(FLOPs a (query, position) pair over all query heads, bytes of
    one position's k and v rows in one layer)``: 2 x D for the score and
    2 x D for the value a head; KH x D bf16 values each of k and v."""
    pub = observed["published"]
    nh = pub["num_attention_heads"]
    d = pub.get("head_dim") or pub["hidden_size"] // nh
    return 4.0 * d * nh, observed["kv_heads"] * d * 2 * 2.0


def decode_counts(observed, span="serve/deliver"):
    """Sums over the window's DECODE steps of what the program puts on
    its ``serve/deliver`` spans for one layer of each kind:
    ``ctx_attended`` (positions a global layer's queries attend: all
    that are cached for them), ``win_attended`` (positions a sliding
    layer's queries attend).  A prefill chunk's counts come from the
    driver's own record of the chunk.  Empty where the program has no
    such counts."""
    parsed = program_trace.get(observed)
    total = {}
    if parsed is None:
        return total
    lo, hi = parsed["lo"], parsed["hi"]
    for name, start, _, stats in parsed["host"]:
        if (name == span and lo <= start <= hi and "ctx_attended" in stats
                and stats.get("kind") == "decode"):
            for key in ("ctx_attended", "win_attended"):
                total[key] = total.get(key, 0) + int(stats.get(key, 0))
    return total
