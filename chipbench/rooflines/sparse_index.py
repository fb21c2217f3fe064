"""The learned selection of a full layer: indexer scores over the paged
index keys, and the exact ``index_topk`` best of them."""

from chipbench.rooflines import sparse_select_common as common


def required(observed):
    """Per chip over the window, call by call.  A (query, visible
    position) pair costs ``2 * index_n_heads * index_head_dim`` FLOPs
    (the heads' products; ReLU, weights and head sum ride the VPU) and
    leaves one float32 score that the selection reads once (8 bytes);
    a visible position's index key (``index_head_dim`` bf16 values) is
    read once a call.  The search for the k-th best is counted as no
    work beyond reading the scores.  Decode: every active slot's cached
    positions (the program's ``sel_cached``).  Prefill chunk of n
    queries at offset t0: the keys up to t0 + n once, n * (t0 + n / 2)
    pairs."""
    pub, peaks = observed["published"], observed["peaks"]
    full, _ = common.layer_counts(observed)
    pair_flops = 2.0 * pub["index_n_heads"] * pub["index_head_dim"]
    key_bytes = 2.0 * pub["index_head_dim"]
    cached = common.decode_counts(observed).get("sel_cached", 0)
    calls = [(cached, cached)] + [(n * (t0 + n / 2.0), t0 + n)
                                  for t0, n in common.chunks(observed)]
    return common.summed(
        ((full * pair_flops * pairs, full * (keys * key_bytes + 8.0 * pairs))
         for pairs, keys in calls), peaks)
