"""Latent attention of a full layer over the positions its indexer
selected (absorbed form)."""

from chipbench.rooflines import sparse_select_common as common


def required(observed):
    """Per chip over the window.  A query at position t attends
    ``min(t + 1, index_topk)`` positions; a (query, position) pair costs
    ``2 * heads * (row + kv_lora_rank)`` FLOPs (score over the whole
    row, value sum over its latent part).  The rows a call has to read
    are the selected ones at the width the pool keeps them (padded to
    whole 128-lane tiles): a decode query's own selection, a prefill
    chunk's at most every row up to its end.  The kernel of this PR
    reads every live page and masks the rest, so its share says how far
    it is from attention that reads the selection alone."""
    pub, peaks = observed["published"], observed["peaks"]
    full, _ = common.layer_counts(observed)
    latent = pub["kv_lora_rank"]
    row = latent + pub["qk_rope_head_dim"]
    row_bytes = 2.0 * (-(-row // 128) * 128)
    pair_flops = 2.0 * pub["num_attention_heads"] * (row + latent)
    most = pub["index_topk"]
    attended = common.decode_counts(observed).get("sel_attended", 0)
    calls = [(attended, attended)]
    for t0, n in common.chunks(observed):
        pairs = common.chunk_pairs(t0, n, most)
        calls.append((pairs, min(t0 + n, pairs)))
    return common.summed(
        ((full * pair_flops * pairs, full * rows * row_bytes)
         for pairs, rows in calls), peaks)
