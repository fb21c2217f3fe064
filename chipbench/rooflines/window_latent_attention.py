"""Latent attention of a sliding layer over its window (absorbed
form, the layer kind's own sizes)."""

from chipbench.rooflines import sparse_select_common as common


def required(observed):
    """Per chip over the window.  A query at position t attends
    ``min(t + 1, sliding_window_size)`` positions; a pair costs ``2 *
    swa heads * (row + swa_kv_lora_rank)`` FLOPs; a call reads the rows
    its queries' windows cover once, at the pool's padded width (a
    decode query its own window, a chunk of n queries at t0 the
    ``window - 1`` rows before t0 and its own)."""
    pub, peaks = observed["published"], observed["peaks"]
    _, sliding = common.layer_counts(observed)
    latent = pub["swa_kv_lora_rank"]
    row = latent + pub["swa_qk_rope_head_dim"]
    row_bytes = 2.0 * (-(-row // 128) * 128)
    pair_flops = 2.0 * pub["swa_num_attention_heads"] * (row + latent)
    window = pub["sliding_window_size"]
    attended = common.decode_counts(observed).get("win_attended", 0)
    calls = [(attended, attended)] + [
        (common.chunk_pairs(t0, n, window), t0 + n - max(t0 - (window - 1), 0))
        for t0, n in common.chunks(observed)]
    return common.summed(
        ((sliding * pair_flops * pairs, sliding * rows * row_bytes)
         for pairs, rows in calls), peaks)
