"""Causal flash attention, forward and backward, of a training window."""


def required(observed):
    """Per chip over the window.  Forward: QK^T and PV (2 matmuls);
    backward: QK^T again, dV, dP, dQ, dK (5 matmuls); each
    2*S*S*D multiply-add-flops a head, halved by the causal mask.
    Bytes: q, k, v, o, do read and dq, dk, dv written once each in bf16
    (a lower bound; the kernels are compute-bound at these shapes)."""
    pub = observed["published"]
    nh = pub["num_attention_heads"]
    kh = pub.get("num_key_value_heads") or nh
    d = pub.get("head_dim") or pub["hidden_size"] // nh
    s, depth = observed["seq"], observed["depth"]
    rows = observed["steps"] * observed["batch"] / observed["chips"]
    flops = rows * depth * nh * 7 * 2 * s * s * d * 0.5
    bytes_ = rows * depth * s * d * 2 * (5 * nh + 4 * kh)
    return {"flops": flops, "bytes": bytes_}
