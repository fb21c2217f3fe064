"""Required operations of the dense decoder family, from shapes."""


def _sizes(published):
    h = published["hidden_size"]
    nh = published["num_attention_heads"]
    kh = published.get("num_key_value_heads") or nh
    d = published.get("head_dim") or h // nh
    return h, nh, kh, d, published["intermediate_size"], \
        published["vocab_size"]


def forward_flops_per_token(published, depth, seq):
    """Multiply-adds x 2 of one token's forward pass at sequence length
    ``seq`` (causal attention: a token attends to half the row on
    average).  The embedding lookup is not a matmul and is not counted."""
    h, nh, kh, d, f, v = _sizes(published)
    proj = 2 * h * (nh * d + 2 * kh * d) + 2 * nh * d * h
    mlp = 3 * 2 * h * f
    attn = 2 * 2 * nh * d * (seq / 2.0)
    return depth * (proj + mlp + attn) + 2 * h * v


def train_flops_per_token(published, depth, seq):
    """Forward + backward: the backward needs twice the forward's
    matmul operations.  Recomputation (remat) is not required work."""
    return 3.0 * forward_flops_per_token(published, depth, seq)
