"""The fused head + cross-entropy of a training window."""


def required(observed):
    """Per chip over the window.  The head matmul forward
    (2 * tokens * hidden * vocab) and its two backward matmuls, dW and
    d(hidden): 6 * tokens * hidden * vocab.  The chunks' recomputed
    logits (remat) are not required work.  Bytes: the hidden states read
    forward and backward and their cotangent written, the head weight
    read twice and its gradient written once, all bf16 (a lower bound;
    the head is compute-bound at these shapes)."""
    pub = observed["published"]
    h, v = pub["hidden_size"], pub["vocab_size"]
    tokens = observed["tokens"] / observed["chips"]
    flops = 6.0 * tokens * h * v
    bytes_ = 2.0 * (3 * tokens * h + 3 * observed["steps"] * h * v)
    return {"flops": flops, "bytes": bytes_}
