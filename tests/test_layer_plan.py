"""The three owners behind the serving walk, on a toy of each served
family and a dense one: the layer plan (models/transformer.layer_plan:
how a model's layers are stacked, which `layer_tree` — and through it
`generate()` — and `PagedDecoder._forward` read), the kind table
(serve/kinds.KINDS: what a kind of layer keeps in the cache, under which
names) and `make_pools`, a loop over both.  Every expectation is written
out here by hand (the stacks are the ones chipbench/layouts builds; the
pools' shapes the ones the family's own test file pins), none is
computed from the plan or the table."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
import test_sparse_window_serving as dots3
import test_ssm_serving as nemotron
import test_window_gqa_serving as kexaone

from torchacc_tpu.config import ServeConfig
from torchacc_tpu.models import get_preset
from torchacc_tpu.models.transformer import (
    layer_plan,
    layer_tree,
    planned_layers,
)
from torchacc_tpu.serve.kinds import KINDS, _check_supported, kinds_of
from torchacc_tpu.serve.kv_cache import make_pools

TINY = dict(dtype=jnp.float32, hidden_size=64, num_heads=4,
            intermediate_size=128, vocab_size=257, max_seq_len=128)
SERVE = dict(block_size=8, num_blocks=64, max_slots=3, prefill_chunk=12)
F32 = jnp.float32


def _toy(family):
    """``(ModelConfig, serve settings)`` of the family's toy."""
    if family == "dense":
        return get_preset("llama-tiny", num_layers=3, num_kv_heads=2,
                          **TINY), SERVE
    if family == "latent":
        return get_preset(
            "llama-tiny", num_layers=3, num_kv_heads=4,
            rope_interleaved=True, kv_lora_rank=32, q_lora_rank=48,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            first_dense_layers=1, num_experts=4, num_experts_per_tok=2,
            moe_intermediate_size=32, moe_scoring="sigmoid",
            moe_shared_experts=1, moe_router_width=8, moe_first_expert=2,
            moe_dispatch="grouped", **TINY), SERVE
    toy = {"indexed_latent": dots3, "window_gqa": kexaone,
           "ssm": nemotron}[family]
    return toy.model_config(toy.TOY), toy.SERVE


# what each family's layers are, in order: (stacked tree, index in it,
# kind, index among the layers of its kind)
D, L = ("dense_layers",), ("layers",)
P = [("layers", f"p{i}") for i in range(7)]
MAMBA, MOE, ATTN = (("layers", k) for k in ("mamba", "moe", "attention"))
LAYERS = {
    "dense": [(L, 0, "", 0), (L, 1, "", 1), (L, 2, "", 2)],
    "latent": [(D, 0, "", 0), (L, 0, "", 1), (L, 1, "", 2)],
    # G | G S S S | G S S S
    "indexed_latent": [
        (D, 0, "global", 0),
        (P[0], 0, "global", 1), (P[1], 0, "sliding", 0),
        (P[2], 0, "sliding", 1), (P[3], 0, "sliding", 2),
        (P[0], 1, "global", 2), (P[1], 1, "sliding", 3),
        (P[2], 1, "sliding", 4), (P[3], 1, "sliding", 5)],
    # S | S S G S S S G: seven layers that repeat nothing shorter
    "window_gqa": [
        (D, 0, "sliding", 0), (P[0], 0, "sliding", 1),
        (P[1], 0, "sliding", 2), (P[2], 0, "global", 0),
        (P[3], 0, "sliding", 3), (P[4], 0, "sliding", 4),
        (P[5], 0, "sliding", 5), (P[6], 0, "global", 1)],
    # M E M * E M E M * E
    "ssm": [
        (MAMBA, 0, "mamba", 0), (MOE, 0, "moe", 0), (MAMBA, 1, "mamba", 1),
        (ATTN, 0, "attention", 0), (MOE, 1, "moe", 1),
        (MAMBA, 2, "mamba", 2), (MOE, 2, "moe", 2), (MAMBA, 3, "mamba", 3),
        (ATTN, 1, "attention", 1), (MOE, 3, "moe", 3)],
}
# (layers of a run's body, repeats, scanned) of every run
RUNS = {
    "dense": [(1, 3, True)],
    "latent": [(1, 1, True), (1, 2, True)],
    "indexed_latent": [(1, 1, True), (4, 2, True)],
    "window_gqa": [(1, 1, True), (7, 1, True)],
    "ssm": [(10, 1, False)],
}
# what differs from the model's config in the config a layer's block
# computes under
DENSE = dict(num_experts=0, first_dense_layers=0)
FULL, LOCAL = dict(window=(-1, -1)), dict(rope_theta=500.0)
NOPE = dict(pos_emb="none", window=(-1, -1))
BLOCK_CFG = {
    "dense": [{}] * 3,
    "latent": [DENSE, {}, {}],
    "indexed_latent": [{**FULL, **DENSE}, FULL, LOCAL, LOCAL, LOCAL,
                       FULL, LOCAL, LOCAL, LOCAL],
    "window_gqa": [DENSE, {}, {}, NOPE, {}, {}, {}, NOPE],
    "ssm": [{}] * 10,
}
# name -> (shape, dtype) of every pool, at the family's serve settings
# (the window pools: 3 slots x (ceil((11 + 12) / 8) + 1) blocks and the
# null block; the state pools: 3 slots and the null slot)
POOLS = {
    "dense": {"k": ((3, 64, 8, 32), F32), "v": ((3, 64, 8, 32), F32)},
    "latent": {"latent": ((3, 64, 8, 128), F32)},
    "indexed_latent": {"latent": ((3, 64, 8, 128), F32),
                       "index": ((3, 64, 8, 16), F32),
                       "latent_win": ((6, 13, 8, 128), F32)},
    "window_gqa": {"k": ((2, 64, 8, 32), F32), "v": ((2, 64, 8, 32), F32),
                   "k_win": ((6, 13, 8, 32), F32),
                   "v_win": ((6, 13, 8, 32), F32)},
    "ssm": {"k": ((2, 64, 8, 32), F32), "v": ((2, 64, 8, 32), F32),
            "conv": ((4, 4, 384), F32), "ssm": ((4, 4, 8, 8, 16), F32)},
}
# kind of the plan -> the record that serves it ('moe' layers of a
# mixer_pattern keep nothing)
RECORDS = {
    "dense": {"": "grouped_query"},
    "latent": {"": "latent"},
    "indexed_latent": {"global": "latent_indexed",
                       "sliding": "latent_window"},
    "window_gqa": {"sliding": "grouped_query_window",
                   "global": "grouped_query"},
    "ssm": {"mamba": "state_space", "attention": "grouped_query"},
}
FAMILIES = list(LAYERS)


def _stacks(family):
    """Parameters in the family's layout whose one leaf a stack says
    where a slice came from: ``base of the stack + index``."""
    sizes = {}
    for tree, at, _, _ in LAYERS[family]:
        sizes[tree] = max(sizes.get(tree, 0), at + 1)
    params = {}
    for n, (tree, size) in enumerate(sorted(sizes.items())):
        node = params
        for key in tree[:-1]:
            node = node.setdefault(key, {})
        node[tree[-1]] = {"block": {"w": 100 * n + jnp.arange(size)}}
    return params, {tree: 100 * n for n, tree in enumerate(sorted(sizes))}


@pytest.mark.parametrize("family", FAMILIES)
def test_the_plan_names_every_layer_once_in_order(family):
    """The plan's layers are the family's, each with the index among
    its kind that counts the layers of that kind before it; its runs
    are the scans the decoder runs; `layer_tree` finds each layer's
    tree and config where the parent found them."""
    mc, _ = _toy(family)
    got = planned_layers(mc)
    assert [(e.tree, e.at, e.kind, e.of_kind) for e in got] == LAYERS[family]
    for i, e in enumerate(got):
        assert e.of_kind == [x.kind for x in got[:i]].count(e.kind)
    assert [(len(run.body), run.repeats, run.scanned)
            for run in layer_plan(mc)] == RUNS[family]
    params, base = _stacks(family)
    for i, (tree, at, _, _) in enumerate(LAYERS[family]):
        layer, block_cfg = layer_tree(mc, params, i)
        assert int(layer["block"]["w"]) == base[tree] + at
        assert block_cfg == dataclasses.replace(mc, **BLOCK_CFG[family][i])


def test_a_pattern_on_the_canonical_stack_is_walked_at_static_indices():
    """A ``layer_pattern`` without leading dense layers (gemma-style:
    `generate()` alone runs it) keeps the canonical ``layers`` stack."""
    mc = get_preset("llama-tiny", num_layers=4, num_kv_heads=2,
                    layer_pattern=("sliding", "global"), window=(7, -1),
                    **TINY)
    assert [(e.tree, e.at, e.kind, e.of_kind) for e in planned_layers(mc)] \
        == [(L, 0, "sliding", 0), (L, 1, "global", 0),
            (L, 2, "sliding", 1), (L, 3, "global", 1)]
    (run,) = layer_plan(mc)
    assert not run.scanned
    params = {"layers": {"block": {"w": jnp.arange(4)}}}
    for i in range(4):
        layer, block_cfg = layer_tree(mc, params, i)
        assert int(layer["block"]["w"]) == i
        assert block_cfg.window == ((7, -1) if i % 2 == 0 else (-1, -1))


@pytest.mark.parametrize("family", FAMILIES)
def test_make_pools_returns_the_familys_pools_by_name(family):
    mc, serve = _toy(family)
    pools = jax.eval_shape(lambda: make_pools(mc, ServeConfig(**serve)))
    assert {name: (p.shape, p.dtype) for name, p in pools.items()} \
        == POOLS[family]


@pytest.mark.parametrize("family", FAMILIES)
def test_every_kind_of_an_admitted_plan_has_a_record(family):
    """`_check_supported` admits the toy, and every kind its plan emits
    is served by the record written out above, which owns pools the
    model has; nothing else is in the table's reach."""
    mc, serve = _toy(family)
    _check_supported(mc)
    kinds = kinds_of(mc)
    assert {kind: next(n for n, r in KINDS.items() if r is record)
            for kind, (record, _, _) in kinds.items()} == RECORDS[family]
    emitted = {e.kind for e in planned_layers(mc)}
    assert set(kinds) == emitted - ({"moe"} if mc.mixer_pattern else set())
    owned = [name for record, _, _ in kinds.values()
             for name in record.names]
    assert sorted(owned) == sorted(POOLS[family])
    for kind, (record, cfg, n) in kinds.items():
        assert n == [k for _, _, k, _ in LAYERS[family]].count(kind)
        assert record.by in ("blocks", "window", "slot")
