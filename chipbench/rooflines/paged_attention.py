"""Paged attention over the live KV pool, decode and prefill chunks."""


def required(observed):
    """Per chip over the window, summed call by call (each call is
    bound by the larger of its operations and its bytes, so the least
    time is the sum of the calls' own bounds, given as ``least_s``).

    Decode call: every active slot's live keys and values are read once
    (bf16), 4*D flops a (q head, key) pair.  Prefill chunk of n tokens
    at offset t0: the keys and values up to t0+n are read once, and
    4*D*n*(t0 + n/2) flops a q head."""
    pub, peaks = observed["published"], observed["peaks"]
    nh = pub["num_attention_heads"]
    kh, depth = observed["kv_heads"], observed["depth"]
    d = pub.get("head_dim") or pub["hidden_size"] // nh
    kv_token_bytes = kh * d * 2 * 2
    flops = bytes_ = least = 0.0

    def add(f, b):
        nonlocal flops, bytes_, least
        flops += f
        bytes_ += b
        least += max(f / peaks["bf16_flops_per_s"],
                     b / peaks["hbm_bytes_per_s"])

    for tokens in observed["kv_tokens_read"]:
        add(depth * 4.0 * d * nh * tokens, depth * tokens * kv_token_bytes)
    for t0, n in observed["prefill_chunks"]:
        add(depth * 4.0 * d * nh * n * (t0 + n / 2.0),
            depth * (t0 + n) * kv_token_bytes)
    return {"flops": flops, "bytes": bytes_, "least_s": least}
