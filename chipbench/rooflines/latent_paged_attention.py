"""Latent (MLA, absorbed form) paged attention, decode and prefill
chunks: every head reads the one latent row a token has."""


def required(observed):
    """Per chip over the window, summed call by call (``least_s``: each
    call is bound by the larger of its operations and its bytes).

    A live row is ``kv_lora_rank + qk_rope_head_dim`` bf16 values (the
    pool pads it to whole lane tiles; the padding is not required work)
    and is read once a layer a call.  A (query token, row) pair costs
    ``2 * heads * (row + kv_lora_rank)`` FLOPs: the score over the whole
    row and the value sum over its latent part.  Decode call: every
    active slot's live rows.  Prefill chunk of n tokens at offset t0:
    the rows up to t0 + n once, n * (t0 + n / 2) pairs."""
    pub, peaks = observed["published"], observed["peaks"]
    latent = pub["kv_lora_rank"]
    row = latent + pub["qk_rope_head_dim"]
    pair_flops = 2.0 * pub["num_attention_heads"] * (row + latent)
    depth = observed["depth"]
    flops = bytes_ = least = 0.0

    def add(pairs, rows):
        nonlocal flops, bytes_, least
        f, b = depth * pair_flops * pairs, depth * rows * row * 2.0
        flops += f
        bytes_ += b
        least += max(f / peaks["bf16_flops_per_s"],
                     b / peaks["hbm_bytes_per_s"])

    for tokens in observed["kv_tokens_read"]:
        add(tokens, tokens)
    for t0, n in observed["prefill_chunks"]:
        add(n * (t0 + n / 2.0), t0 + n)
    return {"flops": flops, "bytes": bytes_, "least_s": least}
