"""The held experts' grouped matmuls of a training window, forward and
backward."""

from chipbench.readers import expert_load


def required(observed):
    """Per chip over the window, from the program's own counts (the
    ``moe_*`` attributes of its ``train/step`` spans; the chips share
    the pairs and the experts alike).  Each (token, expert) pair costs
    18 x hidden x width FLOPs: gate, up and down forward, as many again
    for dX and for dW.  Bytes, all bf16: an expert layer's held matrices
    read forward, read again for dX, and dW written, once a step each
    (the token chunks' re-reads and remat's are not required); a pair's
    row read and its result written, forward, and its cotangent read and
    dX written, backward (the width-wide intermediates could stay on
    chip)."""
    pub = observed["published"]
    total = expert_load.counts(observed, "train/step") or {}
    chips = observed["chips"]
    h, f = pub["hidden_size"], pub["moe_intermediate_size"]
    pairs = total.get("moe_pairs", 0) / chips
    weights = 3 * h * f * total.get("moe_slots", 0) / chips
    return {"flops": 18.0 * pairs * h * f,
            "bytes": 2.0 * (3 * weights + 4 * pairs * h)}
