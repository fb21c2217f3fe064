"""Grouped-query paged attention of the GLOBAL layers of a model that
mixes them with sliding ones (``rooflines/paged_attention.py`` counts
every layer of the depth as one that reads the whole context)."""

from chipbench.rooflines import gqa_window_common as common


def required(observed):
    """Per chip over the window.  Decode steps: every active slot's
    cached k and v rows are read once a global layer, ``4 * head_dim``
    FLOPs a query head a (query, position) pair.  A prefill chunk of n
    queries at offset t0 reads the rows up to t0 + n once and attends
    ``t + 1`` positions a query."""
    full, _ = common.layer_counts(observed)
    pair_flops, row_bytes = common.sizes(observed)
    attended = common.decode_counts(observed).get("ctx_attended", 0)
    calls = [(attended, attended)] + [
        (common.chunk_pairs(t0, n, t0 + n + 1), t0 + n)
        for t0, n in common.chunks(observed)]
    return common.summed(
        ((full * pair_flops * pairs, full * rows * row_bytes)
         for pairs, rows in calls), observed["peaks"])
