"""The held relu2 experts' grouped matmuls of a serving window."""

from chipbench.readers import expert_load


def required(observed):
    """Per chip over the window, from the program's own counts (the
    ``moe_*`` attributes of its ``serve/deliver`` spans): each held
    expert that drew a pair in a layer of a step has its TWO matrices
    (hidden x width, bf16: ``down(relu(up(x))**2)`` has no gate) read
    once, and each (token, expert) pair on a held expert costs 4 x
    hidden x width FLOPs.  ``rooflines/expert_matmul.py`` counts the
    three of a SwiGLU expert."""
    pub = observed["published"]
    total = expert_load.counts(observed, "serve/deliver") or {}
    weights = 2 * pub["hidden_size"] * pub["moe_intermediate_size"]
    return {"flops": 2.0 * weights * total.get("moe_pairs", 0),
            "bytes": 2.0 * weights * total.get("moe_hit", 0)}
