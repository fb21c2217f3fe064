"""Grouped-query paged attention of a sliding layer over its window."""

from chipbench.rooflines import gqa_window_common as common


def required(observed):
    """Per chip over the window.  A query at position t attends
    ``min(t + 1, sliding_window)`` positions; a (query, position) pair
    costs ``4 * head_dim`` FLOPs a query head; a call reads the k and v
    rows its queries' windows cover once a sliding layer (a decode query
    its own window, a chunk of n queries at t0 the ``window - 1`` rows
    before t0 and its own).  The kernel fetches whole blocks — two a
    decode step at a window of one block — so its share of this says
    how far it is from reading the window's rows alone."""
    _, sliding = common.layer_counts(observed)
    pair_flops, row_bytes = common.sizes(observed)
    window = observed["published"]["sliding_window"]
    attended = common.decode_counts(observed).get("win_attended", 0)
    calls = [(attended, attended)] + [
        (common.chunk_pairs(t0, n, window),
         t0 + n - max(t0 - (window - 1), 0))
        for t0, n in common.chunks(observed)]
    return common.summed(
        ((sliding * pair_flops * pairs, sliding * rows * row_bytes)
         for pairs, rows in calls), observed["peaks"])
