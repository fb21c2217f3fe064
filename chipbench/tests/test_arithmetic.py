"""The benchmark's own arithmetic: percentiles, spreads, required
operations and bytes, the traffic generator's fixed multiset."""

import json
import os
import statistics

import pytest

from chipbench import spec, stats, traffic_gen
from chipbench.rooflines import dense_decoder, flash_attention, \
    paged_attention

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("xs,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([10, 20], 95, 19.5),
    ([7], 95, 7.0),
    (list(range(101)), 95, 95.0),
])
def test_percentile(xs, q, want):
    assert stats.percentile(xs, q) == pytest.approx(want)


def test_quartile_spread_is_the_contracts():
    xs = [100, 101, 99, 102, 98, 100.5]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.quartile_spread(xs) == pytest.approx(
        (q3 - q1) / statistics.median(xs))


def test_mistral_flops_per_token_match_the_issue():
    pub = _config("mistral-7b-v0.3")["published"]
    fwd = dense_decoder.forward_flops_per_token(pub, 2, 4096)
    head = 2 * 4096 * 32768
    # ISSUE 23: 3.63 GFLOP a token fwd+bwd at depth 2, the head 22%
    assert 3 * fwd == pytest.approx(3.63e9, rel=0.02)
    assert head / fwd == pytest.approx(0.22, abs=0.01)
    assert dense_decoder.train_flops_per_token(pub, 2, 4096) == 3 * fwd


def test_olmo_head_share():
    pub = _config("olmo-2-0425-1b")["published"]
    fwd = dense_decoder.forward_flops_per_token(pub, 3, 4096)
    assert 2 * 2048 * 100352 / fwd == pytest.approx(0.48, abs=0.02)


def test_flash_required_counts_seven_causal_matmuls():
    pub = _config("mistral-7b-v0.3")["published"]
    need = flash_attention.required(dict(
        published=pub, seq=4096, depth=2, steps=1, batch=4, chips=1))
    assert need["flops"] == pytest.approx(
        4 * 2 * 32 * 7 * 2 * 4096 * 4096 * 128 * 0.5)
    assert need["flops"] / 197e12 > need["bytes"] / 819e9   # compute-bound


def test_paged_required_is_bandwidth_bound_at_decode():
    pub = _config("mistral-7b-v0.3")["published"]
    need = paged_attention.required(dict(
        published=pub, depth=11, kv_heads=8,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        kv_tokens_read=[16 * 700], prefill_chunks=[]))
    assert need["bytes"] == 11 * 16 * 700 * 8 * 128 * 2 * 2
    assert need["least_s"] == pytest.approx(need["bytes"] / 819e9)


def test_every_seed_replays_the_same_requests_with_other_ids():
    traffic = spec.Cell("mistral7b.serve.rollout").traffic
    a = traffic_gen.Requests(traffic, 1, 1000)
    b = traffic_gen.Requests(traffic, 2**31 + 7, 1000)
    n = traffic["shapes"]
    ra = [a.next() for _ in range(2 * n + 3)]
    rb = [b.next() for _ in range(2 * n + 3)]
    sa = [(len(p), o) for p, o in ra]
    assert sa == [(len(p), o) for p, o in rb]
    assert ra[0][0] != rb[0][0]
    # each round holds the whole multiset, in another order
    assert sorted(sa[:n]) == sorted(sa[n:2 * n]) \
        == sorted(traffic_gen.shapes(traffic))
    assert sa[:n] != sa[n:2 * n]
    lo, hi = traffic["prompt_len"]["low"], traffic["prompt_len"]["high"]
    assert all(lo <= p <= hi for p, _ in sa)
    lo, hi = traffic["output_len"]["low"], traffic["output_len"]["high"]
    assert all(lo <= o <= hi for _, o in sa)


def test_ttft_reader_reads_first_tokens_inside_the_window():
    decl = spec.layer_metric("ttft_p95_ms.serve")
    read = spec.reader(decl["reader"]).read
    waits = [0.1 * k for k in range(1, 12)]
    got = read({"kind": "serve", "ttft_in_window_s": waits}, decl["params"])
    assert got == pytest.approx(stats.percentile(waits, 95) * 1e3)
    assert read({"kind": "serve", "ttft_in_window_s": []},
                decl["params"]) is None
    assert read({"kind": "train"}, decl["params"]) is None
