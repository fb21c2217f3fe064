"""Flash attention, forward and backward, of a training window whose
layers differ in what their masks leave: a sliding layer its band, a
full layer the causal half."""

from chipbench.rooflines import gqa_window_softmax_moe_decoder as family


def required(observed):
    """Per chip over the window.  Seven matmuls a layer (forward QK^T
    and PV; backward QK^T again, dV, dP, dQ, dK), each 2 * D
    multiply-add-flops a head and a (query, key) pair the layer's mask
    leaves visible.  Bytes: q, k, v, o, do read and dq, dk, dv written
    once each in bf16 (``rooflines/flash_attention.py``)."""
    pub = observed["published"]
    nh, kh = pub["num_attention_heads"], pub["num_key_value_heads"]
    d = pub["head_dim"]
    s, depth = observed["seq"], observed["depth"]
    rows = observed["steps"] * observed["batch"] / observed["chips"]
    pairs = sum(family.visible_pairs(s, w)
                for w in family.layer_windows(pub, depth))
    return {"flops": rows * nh * 7 * 2 * d * pairs,
            "bytes": rows * depth * s * d * 2 * (5 * nh + 4 * kh)}
