"""The held experts' grouped matmuls of a serving window."""

from chipbench.readers import expert_load


def required(observed):
    """Per chip over the window, from the program's own counts (the
    ``moe_*`` attributes of its ``serve/deliver`` spans): each held
    expert that drew a pair in a layer of a step has its three matrices
    (hidden x width, bf16) read once, and each (token, expert) pair on a
    held expert costs 6 x hidden x width FLOPs (gate, up and down).  The
    pairs' activations are left out of the bytes (kilobytes against
    88 MB an expert)."""
    pub = observed["published"]
    total = expert_load.counts(observed, "serve/deliver") or {}
    weights = 3 * pub["hidden_size"] * pub["moe_intermediate_size"]
    return {"flops": 2.0 * weights * total.get("moe_pairs", 0),
            "bytes": 2.0 * weights * total.get("moe_hit", 0)}
