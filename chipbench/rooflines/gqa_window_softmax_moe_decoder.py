"""Required operations of the ``gqa_window_softmax_moe_decoder`` family
(Mellum2), from shapes."""


def visible_pairs(seq: int, window) -> float:
    """(query, key) pairs a row's mask leaves: ``j <= i``, and on a
    sliding layer ``j > i - window``."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


def layer_windows(published, depth):
    """Each of the first ``depth`` layers' window (None: full)."""
    return [published["sliding_window"] if t == "sliding_attention" else None
            for t in published["layer_types"][:depth]]


def forward_flops_per_token(published, depth, seq):
    """Multiply-adds x 2 of one token's forward pass at sequence length
    ``seq``: the projections, the router, the ``num_experts_per_tok``
    experts a token visits (three matrices each), the scores and values
    of the keys its layer's mask leaves it (the band on a sliding layer,
    half the row on a full one), the head.  The embedding lookup, the
    sort and the exchange are not matmuls and are not counted."""
    h, v = published["hidden_size"], published["vocab_size"]
    nh, kh = (published["num_attention_heads"],
              published["num_key_value_heads"])
    d = published["head_dim"]
    proj = 2 * h * (nh * d + 2 * kh * d) + 2 * nh * d * h
    router = 2 * h * published["num_experts"]
    experts = (published["num_experts_per_tok"] * 3 * 2 * h
               * published["moe_intermediate_size"])
    attn = sum(2 * 2 * nh * d * visible_pairs(seq, w) / seq
               for w in layer_windows(published, depth))
    return depth * (proj + router + experts) + attn + 2 * h * v


def train_flops_per_token(published, depth, seq):
    """Forward + backward: the backward needs twice the forward's
    matmul operations.  Recomputation (remat) is not required work."""
    return 3.0 * forward_flops_per_token(published, depth, seq)
