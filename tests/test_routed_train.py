"""Training a routed-expert model with its experts spread over 'ep'
(PR 46): the grouped matmul's backward kernels, the dropless layer's
shares, the exchange across a mesh, the auxiliary loss, the ingest of
the mellum family and YaRN on one kind of layer — each against the plain
reference of the family (chipbench/reference, which imports nothing of
the program) or against ``jax.grad`` of a dense product.  Since PR 47 the
layer's row movers (``ops/moe_rows.py``: live rows only) against the XLA
gathers they replace, to the last bit.

Toy sizes; Pallas kernels in interpret mode at 128-wide tiles.
"""

import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchacc_tpu as ta
from chipbench import program
from chipbench.drivers import train_fit
from chipbench.layouts import gqa_window_softmax_moe_decoder as layout
from chipbench.reference import gqa_window_softmax_moe_decoder as ref
from chipbench.weights import gqa_window_softmax_moe_decoder as weights
from torchacc_tpu.config import ConfigError
from torchacc_tpu.models import TransformerLM, block, moe
from torchacc_tpu.models.hf import config_from_hf
from torchacc_tpu.models.transformer import kind_cfg
from torchacc_tpu.ops import moe_rows
from torchacc_tpu.ops.grouped_matmul import grouped_matmul, tile_schedule
from torchacc_tpu.train.trainer import Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG_ROW = json.load(open(os.path.join(
    ROOT, "chipbench", "configs", "mellum2-12b-a2.5b-instruct.json")))


def toy_published(**over):
    """The catalog row's config at toy widths: 4 layers (S S S G), 8
    experts top-2, a window of 8, YaRN stretched from 16 positions so
    that a 32-token row lies past the original context."""
    pub = dict(CATALOG_ROW["published"])
    pub.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, moe_intermediate_size=32, num_experts=8,
               num_experts_per_tok=2, vocab_size=256, sliding_window=8)
    rope = dict(pub["rope_parameters"])
    rope["full_attention"] = dict(rope["full_attention"],
                                  original_max_position_embeddings=16)
    pub["rope_parameters"] = rope
    pub.update(over)
    return pub


DEPTH, SEQ, ROWS = 4, 32, 4
OPT = {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.999,
       "eps": 1e-8, "weight_decay": 1e-4}


# -- the grouped matmul's backward ------------------------------------------

def _dense_groups(x, w, sizes):
    """Rows of group g times w[g], a group at a time; rows of no group 0."""
    ends = np.cumsum(sizes)
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    for g, (a, b) in enumerate(zip(ends - sizes, ends)):
        out = out.at[a:b].set(x[a:b] @ w[g])
    return out


@pytest.mark.parametrize("sizes,rows", [
    ((100, 0, 60, 130, 0), 400),      # empty groups, 110 rows past the last
    ((64, 64, 128), 256),             # whole tiles, nothing past
    ((5, 3, 200, 1), 256),            # one tile shared by three groups
    ((0, 0, 0), 128),                 # no pair at all
], ids=["empty_and_past", "whole_tiles", "shared_tile", "no_rows"])
def test_grouped_matmul_gradients_against_a_dense_product(sizes, rows):
    """dX (``gmm_dx``: the weights read transposed) and dW (``gmm_dw``:
    an empty group writes zeros) against ``jax.grad`` of the per-group
    dense product; the cotangent's rows of no group are not read and dX
    is zero there."""
    rng = np.random.default_rng(len(sizes) + rows)
    k, n = 256, 384
    sizes = np.asarray(sizes, np.int32)
    x = jnp.asarray(rng.normal(size=(rows, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(len(sizes), k, n)) * 0.1, jnp.float32)
    ct = jnp.asarray(rng.normal(size=(rows, n)), jnp.float32)
    total = int(sizes.sum())
    # a cotangent that is not finite where no group is must not matter
    ct = ct.at[total:].set(jnp.nan)
    keep = (jnp.arange(rows) < total)[:, None]

    def ours(x, w):
        y = grouped_matmul(x, w, jnp.asarray(sizes), tk=128, tn=128)
        return jnp.sum(jnp.where(keep, y * ct, 0.0))

    def dense(x, w):
        return jnp.sum(jnp.where(keep, _dense_groups(x, w, sizes) * ct, 0.0))

    (gx, gw), (rx, rw) = (jax.grad(f, (0, 1))(x, w) for f in (ours, dense))
    np.testing.assert_allclose(gx, rx, atol=2e-4)
    np.testing.assert_allclose(gw, rw, atol=2e-4)
    assert not np.any(np.asarray(gx[total:]))
    assert not np.any(np.asarray(gw[sizes == 0]))


def test_dw_schedule_gives_an_empty_group_one_step_on_a_tile_at_hand():
    """``gmm_dw`` has a block to write for every group: an empty one
    takes one step, on the row tile the step before it worked."""
    sizes = jnp.asarray([200, 0, 0, 56], jnp.int32)
    group_of, tile_of, *_, num = tile_schedule(sizes, 256, 128,
                                               visit_empty=True)
    n = int(num[0])
    assert n == 2 + 1 + 1 + 1
    assert list(np.asarray(group_of[:n])) == [0, 0, 1, 2, 3]
    assert list(np.asarray(tile_of[:n])) == [0, 1, 1, 1, 1]
    assert group_of.shape[0] == 256 // 128 + 2 * 4 - 1


# -- the row movers against the gathers they replace -------------------------

def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


def _sorted_pairs(n, k, live, seed):
    """A sorted buffer's bookkeeping for ``n`` tokens of ``k`` slots over
    16 experts of which this shard holds ``[4, 8)``: pairs on experts
    held elsewhere on both sides of the held ones, a tenth of the tokens
    not ``valid``.  ``live``: the share of pairs on held experts (0 and
    1 exactly; ``"tile"`` a few pairs, one partial row tile)."""
    rng = np.random.default_rng(seed)
    sel = rng.integers(0, 16, size=(n, k))
    elsewhere = np.where(rng.uniform(size=(n, k)) < 0.5, sel % 4,
                         8 + sel % 8)
    here = 4 + sel % 4
    if live == "tile":
        pick = np.zeros((n, k), bool)
        pick.reshape(-1)[rng.choice(n * k, size=5, replace=False)] = True
    else:
        pick = rng.uniform(size=(n, k)) < live
    sel = np.where(pick, here, elsewhere)
    valid = rng.uniform(size=(n,)) < 0.9 if live != 1.0 else np.ones(n, bool)
    local = sel - 4
    held = (local >= 0) & (local < 4) & valid[:, None]
    key = jnp.asarray(np.where(held, local, 4).reshape(n * k), jnp.int32)
    order = jnp.argsort(key, stable=True)
    unsort = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32))
    total = jnp.sum(key < 4).astype(jnp.int32)
    return (jnp.asarray(sel, jnp.int32), jnp.asarray(valid), order, unsort,
            total)


LIVE = pytest.mark.parametrize("live", [0.0, "tile", 0.25, 1.0],
                               ids=["no_rows", "one_partial_tile",
                                    "quarter", "all"])
SLOTS = pytest.mark.parametrize("k", [2, 8])
DTYPES = pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                                 ids=["bf16", "f32"])


@LIVE
@SLOTS
@DTYPES
def test_rows_out_weighed_equal_the_gather_in_the_live_rows(live, k, dtype):
    """``take_rows_weighed`` (``_combine_bwd``: the cotangent's rows
    gathered, weighed, reduced against the results) against the XLA
    expression over the whole buffer, bitwise in the rows ``[0,
    total)``; ``d_out`` is zero up to the end of the last live tile and
    ``d_w`` everywhere past ``total``; the results' rows past ``total``
    may hold anything."""
    n, h = 200, 128
    _, _, order, unsort, total = _sorted_pairs(n, k, live, seed=k)
    t, nk = int(total), n * k
    rng = np.random.default_rng(7)
    tok = (order // k).astype(jnp.int32)
    out = jnp.asarray(rng.normal(size=(nk, h)), dtype)
    w = jnp.asarray(rng.uniform(size=(n, k)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    in_group = jnp.arange(nk) < total
    dys = dy.astype(dtype)[tok].astype(jnp.float32)
    w_sorted = w.reshape(nk)[order]
    want_out = jnp.where(in_group[:, None], dys * w_sorted[:, None],
                         0.0).astype(dtype)
    want_w = jnp.where(in_group,
                       jnp.sum(dys * out.astype(jnp.float32), axis=-1), 0.0)
    got_out, got_w = moe_rows.take_rows_weighed(
        dy.astype(dtype), tok, total, w_sorted, out.at[t:].set(jnp.nan))
    np.testing.assert_array_equal(_bits(got_out[:t]), _bits(want_out[:t]))
    np.testing.assert_array_equal(_bits(got_w[:t]), _bits(want_w[:t]))
    tile = min(moe_rows.ROW_TILE, nk)
    last = min(-(-t // tile) * tile, nk)
    assert not np.any(np.asarray(got_out[t:last], np.float32))
    assert not np.any(np.asarray(got_w[t:]))


@LIVE
@SLOTS
@DTYPES
def test_rows_back_equal_the_gathered_sum(live, k, dtype):
    """``sum_rows`` with weights (``_combine`` forward) and without
    (``_take_pairs_bwd``) against the XLA expressions, bitwise: the same
    float32 sum over a token's slots in slot order; the buffer's rows
    past ``total`` are not finite and never read."""
    n, h = 200, 128
    _, _, _, unsort, total = _sorted_pairs(n, k, live, seed=10 + k)
    t, nk = int(total), n * k
    rng = np.random.default_rng(8)
    out = jnp.asarray(rng.normal(size=(nk, h)), dtype)
    garbage = out.at[t:].set(jnp.nan)
    w = jnp.asarray(rng.uniform(size=(n, k)), jnp.float32)
    held = (unsort < total).reshape(n, k)
    picked = out[unsort].reshape(n, k, -1).astype(jnp.float32)
    want = jnp.sum(jnp.where(held[..., None], picked * w[..., None], 0.0),
                   axis=1)
    got = moe_rows.sum_rows(garbage, unsort, total, w, k=k,
                            dtype=jnp.float32)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # the transpose of the take: the cotangent is zero past the total
    zeros = out.at[t:].set(0)
    want = jnp.sum(zeros[unsort].reshape(n, k, -1).astype(jnp.float32),
                   axis=1).astype(dtype)
    got = moe_rows.sum_rows(garbage, unsort, total, k=k, dtype=dtype)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def _layer_case(live, dtype, seed):
    """A shard's expert layer at toy widths (4 held experts of 16,
    top-8, 96 tokens): ``(cfg, sel, valid, ct, args)`` with ``args`` the
    rows, the weights and the three kernels."""
    n, k, h, f = 96, 8, 128, 64
    sel, valid, *_ = _sorted_pairs(n, k, live, seed=seed)
    cfg = dataclasses.replace(
        _program_cfg(toy_published(hidden_size=h, moe_intermediate_size=f,
                                   num_experts=16, num_experts_per_tok=k)),
        dtype=dtype, num_experts=4, moe_router_width=16, moe_first_expert=4)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    wts = jax.nn.softmax(jnp.asarray(rng.normal(size=(n, k)), jnp.float32))
    wg, wu = (jnp.asarray(rng.normal(size=(4, h, f)) * 0.1, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(4, f, h)) * 0.1, jnp.float32)
    ct = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    return cfg, sel, valid, ct, (x, wts, wg, wu, wd)


def _layer_call(case, threshold, monkeypatch):
    """``held_experts_ffn``'s value, load and gradients on a
    :func:`_layer_case` with the size rule at ``threshold`` rows."""
    cfg, sel, valid, ct, args = case
    monkeypatch.setattr(moe, "LIVE_ROWS_FROM", threshold)

    def run(x, wts, wg, wu, wd):
        y, load = moe.held_experts_ffn(cfg, x, sel, wts, wg, wu, wd, valid)
        return jnp.sum(y * ct), (y, load)
    (_, (y, load)), grads = jax.value_and_grad(
        run, (0, 1, 2, 3, 4), has_aux=True)(*args)
    return y, load, grads


@LIVE
@DTYPES
def test_layer_above_the_size_rule_equals_the_layer_below_it(
        live, dtype, monkeypatch):
    """The same call with the movers engaged (the rule patched to 0 rows)
    and with XLA's gathers (a rule past the buffer): the value to the
    last bit, the gradients in the rows, the weights and the three
    kernels within the file's gradient tolerance."""
    case = _layer_case(live, dtype, seed=3)
    y0, load0, g0 = _layer_call(case, 1 << 30, monkeypatch)
    y1, load1, g1 = _layer_call(case, 0, monkeypatch)
    np.testing.assert_array_equal(_bits(y1), _bits(y0))
    np.testing.assert_array_equal(load1, load0)
    for got, want in zip(g1, g0):
        assert np.all(np.isfinite(np.asarray(got, np.float32)))
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_rows_past_the_total_change_nothing(monkeypatch):
    """A grouped matmul that leaves non-finite rows where no group is
    (its contract: UNDEFINED there) changes no output and no gradient of
    the layer with the movers engaged."""
    from torchacc_tpu.ops import grouped_matmul as gm
    case = _layer_case(0.25, jnp.float32, seed=4)
    y0, _, g0 = _layer_call(case, 0, monkeypatch)
    plain = gm._grouped_matmul_pallas

    def poisoned(x, w, group_sizes, layer, **kw):
        out = plain(x, w, group_sizes, layer, **kw)
        return jnp.where(gm._in_group(x.shape[0], group_sizes), out, jnp.nan)
    monkeypatch.setattr(gm, "_grouped_matmul_pallas", poisoned)
    y1, _, g1 = _layer_call(case, 0, monkeypatch)
    np.testing.assert_array_equal(_bits(y1), _bits(y0))
    for got, want in zip(g1, g0):
        np.testing.assert_array_equal(_bits(got), _bits(want))


# -- the held-expert layer's shares add up ----------------------------------

@pytest.fixture(scope="module")
def one_layer():
    """One expert layer of the toy at seeded weights, its input rows and
    the reference's ``(y, aux)`` for them."""
    pub = toy_published()
    w = weights.make(weights.base_key(3), pub, 1, jnp.float32)
    lw = jax.tree.map(lambda a: a[0], w["layers"])
    h = jnp.asarray(np.random.default_rng(5).normal(size=(SEQ, 64)),
                    jnp.float32)
    return pub, lw, h


def _program_cfg(pub, **over):
    return dataclasses.replace(
        program.model_config(pub, DEPTH, max_seq_len=SEQ),
        dtype=jnp.float32, **over)


def test_four_shards_partial_results_sum_to_the_whole_layer(one_layer):
    """The share test: each of four shards holds two of the eight experts
    (``held_experts_ffn`` from ``moe_first_expert`` on) and adds only
    their terms; the four partial results — forward, and the gradients
    with respect to the rows and to each shard's own experts — add up to
    the uncut reference's layer."""
    pub, lw, h = one_layer
    sizes = ref.sizes_of(pub)
    want = lambda h_, lw_: jnp.sum(  # noqa: E731
        jnp.sin(ref.moe(h_, lw_, sizes, ref._f32_dot)[0]))
    want_y = ref.moe(h, lw, sizes, ref._f32_dot)[0]
    want_dh, want_dw = jax.grad(want, (0, 1))(h, lw)

    mc = _program_cfg(pub)
    logits = h @ lw["router"]
    sel, wts, _ = moe.route(mc, logits)
    got_y, got_dh = 0.0, 0.0
    for shard in range(4):
        held = slice(2 * shard, 2 * shard + 2)
        cfg = dataclasses.replace(mc, num_experts=2, moe_router_width=8,
                                  moe_first_expert=2 * shard)
        part = lambda h_, g, u, d, c=cfg: moe.held_experts_ffn(  # noqa: E731
            c, h_, sel, wts, g, u, d)[0]
        args = (h, lw["e_gate"][held], lw["e_up"][held], lw["e_down"][held])
        y = part(*args)
        got_y = got_y + y
        # d sum(sin(total)) / d part = cos(total): the same for every share
        dh, dg, du, dd = jax.vjp(part, *args)[1](jnp.cos(want_y))
        got_dh = got_dh + dh
        for got, name in ((dg, "e_gate"), (du, "e_up"), (dd, "e_down")):
            np.testing.assert_allclose(got, want_dw[name][held], atol=2e-5)
    np.testing.assert_allclose(got_y, want_y, atol=2e-5)
    # the router's own gradient reaches h through sel / weights, which
    # the shares were handed: compare what flows through the experts
    through_experts = jax.grad(
        lambda h_: jnp.sum(jnp.sin(_fixed_route_moe(h_, lw, sel, wts))))(h)
    np.testing.assert_allclose(got_dh, through_experts, atol=2e-5)


def _fixed_route_moe(h, lw, sel, wts):
    """The reference's sum over experts under a routing held fixed."""
    combine = jnp.zeros((h.shape[0], lw["router"].shape[1])).at[
        jnp.arange(h.shape[0])[:, None], sel].set(wts)
    y = 0.0
    for e in range(combine.shape[1]):
        out = (jax.nn.silu(h @ lw["e_gate"][e]) * (h @ lw["e_up"][e])) \
            @ lw["e_down"][e]
        y = y + combine[:, e:e + 1] * out
    return y


def test_auxiliary_loss_and_its_gradient_against_the_reference(one_layer):
    """``routed_experts`` gives the mean over the rows of E x sum_e f_e
    P_e, a row (sequence) at a time, and its gradient reaches the router
    through P alone — as the reference's."""
    pub, lw, h = one_layer
    sizes = ref.sizes_of(pub)
    rows = jnp.stack([h, h[::-1] * 0.5])              # two sequences
    mc = _program_cfg(pub)
    p = {"router": {"kernel": lw["router"]}, "experts/gate": lw["e_gate"],
         "experts/up": lw["e_up"], "experts/down": lw["e_down"]}

    def ours(router):
        return moe.routed_experts(
            mc, dict(p, router={"kernel": router}), rows)[1]

    def theirs(router):
        return jnp.mean(jnp.stack([
            ref.moe(r, dict(lw, router=router), sizes, ref._f32_dot)[1]
            for r in rows]))

    np.testing.assert_allclose(ours(lw["router"]), theirs(lw["router"]),
                               rtol=1e-5)
    np.testing.assert_allclose(jax.grad(ours)(lw["router"]),
                               jax.grad(theirs)(lw["router"]), atol=1e-6)
    load = moe.routed_experts(mc, p, rows)[2]
    assert load.shape == (5,)
    assert int(load[0]) == 2 * SEQ * 2 and int(load[2]) <= 8
    # one shard holds every expert: all of its buffers' rows are live
    assert int(load[3]) == int(load[4]) == 2 * SEQ * 2


# -- ingest and rope -----------------------------------------------------------

def test_hf_ingest_of_the_catalog_row():
    pub = CATALOG_ROW["published"]
    mc = config_from_hf(types.SimpleNamespace(**pub))
    assert (mc.num_layers, mc.hidden_size, mc.num_heads, mc.kv_heads,
            mc.head_size, mc.vocab_size) == (28, 2304, 32, 4, 128, 98304)
    assert mc.layer_pattern == ("sliding", "sliding", "sliding",
                                "global") * 7
    assert mc.window == (1023, -1)          # i - 1024 < j <= i
    assert (mc.num_experts, mc.num_experts_per_tok, mc.expert_ffn_size,
            mc.moe_shared_experts) == (64, 8, 896, 0)
    assert mc.moe_scoring == "softmax" and mc.moe_renorm_topk
    assert mc.moe_dispatch == "grouped" and mc.qk_norm
    assert not mc.tie_embeddings and mc.norm_eps == 1e-6
    assert mc.rope_theta == 500000.0 and mc.rope_local_theta is None
    assert mc.rope_yarn == (16.0, 8192.0, 32.0, 1.0, 1.2772588722239782,
                            True)
    assert mc.rope_yarn_kinds == ("global",)
    # the family's load-balance term is one mean over the layers; the
    # program sums the layers' terms
    assert mc.router_aux_weight == pytest.approx(0.001 / 28)
    cut = config_from_hf(types.SimpleNamespace(**pub), num_layers=4)
    assert cut.layer_pattern == ("sliding", "sliding", "sliding", "global")
    assert cut.router_aux_weight == pytest.approx(0.001 / 4)
    with pytest.raises(NotImplementedError, match="dense MLP"):
        config_from_hf(types.SimpleNamespace(**dict(
            pub, mlp_layer_types=["dense"] + pub["mlp_layer_types"][1:])))


@pytest.mark.parametrize("kind,full", [("sliding", False), ("global", True)])
def test_yarn_on_the_global_kind_only_against_the_references_rope(kind,
                                                                  full):
    pub = toy_published()
    mc = kind_cfg(_program_cfg(pub), kind)
    assert (mc.rope_yarn is not None) == full
    assert (mc.window == (-1, -1)) == full
    sizes = ref.sizes_of(pub)
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(1, SEQ, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, SEQ, 2, 16)), jnp.float32)
    pos = jnp.arange(SEQ)
    got_q, got_k = block._rope(q, k, pos[None], mc)
    inv, scale = ref.inv_freq(sizes, full)
    np.testing.assert_allclose(got_q[0], ref.rope(q[0], pos, inv, scale),
                               atol=1e-5)
    np.testing.assert_allclose(got_k[0], ref.rope(k[0], pos, inv, scale),
                               atol=1e-5)
    if full:
        plain = ref.rope(q[0], pos, *ref.inv_freq(sizes, False))
        assert float(jnp.abs(got_q[0] - plain).max()) > 0.1


# -- the step against the reference, on one device and on a mesh ------------

def _readings(ep):
    """Two steps of ``Trainer`` on the toy in float32 on ``ep`` devices
    (one device, or the experts over a mesh of four): the quantities
    the benchmark's train driver compares."""
    pub = toy_published()
    cfg = ta.Config()
    cfg.dist.ep.size = ep
    cfg.compute.dtype = "float32"
    cfg.memory.gc, cfg.memory.gc_policy = True, "save_attn"
    rng = np.random.default_rng(0)
    batches = [{"input_ids": rng.integers(0, 256, size=(ROWS, SEQ))
                .astype(np.int32)} for _ in range(2)]
    mc = program.model_config(pub, DEPTH, max_seq_len=SEQ)
    cfg.validate()
    mesh = cfg.get_mesh(jax.devices()[:ep])
    from torchacc_tpu.train.accelerate import apply_config_to_model
    trainer = Trainer(TransformerLM(apply_config_to_model(mc, cfg)), cfg,
                      optimizer=program.optimizer(OPT), mesh=mesh)
    trainer.resolve_shardings()
    key = weights.base_key(7)
    with jax.sharding.set_mesh(trainer.mesh):
        params = jax.jit(
            lambda k: layout.to_program_params(
                weights.make(k, pub, DEPTH, jnp.float32), trainer.model.cfg),
            out_shardings=trainer.state_shardings.params)(key)
    trainer.init_from_params(params)
    names = layout.canonical_names(trainer.model.cfg)
    losses, metrics = [], []
    for i, b in enumerate(batches):
        sh = trainer._batch_shardings(b)
        m = trainer.step({k: jax.device_put(v, sh[k]) for k, v in b.items()})
        losses.append(float(m["loss"]))
        metrics.append(m)
        if i == 0:
            mu = train_fit._norms(train_fit._adam_mu(trainer.state.opt_state))
    sharded = {p: a.sharding.spec for p, a in
               program.flat_paths(trainer.state.params).items()}
    return dict(
        losses=losses,
        grad_norms={names[p]: v / (1 - OPT["b1"]) for p, v in mu.items()},
        load=np.asarray(metrics[0]["moe_load"]),
        aux=float(metrics[0]["moe_aux_loss"]), sharded=sharded,
        mesh=dict(trainer.mesh.shape), batches=batches, pub=pub, key=key)


@pytest.fixture(scope="module")
def steps():
    one, mesh = _readings(1), _readings(4)
    sizes = ref.sizes_of(one["pub"])
    with jax.default_matmul_precision("highest"):
        want = ref.train_readings(
            lambda: weights.make(one["key"], one["pub"], DEPTH, jnp.float32),
            sizes, [b["input_ids"] for b in one["batches"]], OPT)
    return one, mesh, want


@pytest.mark.parametrize("which", ["one_device", "ep4_mesh"])
def test_trainer_follows_the_reference(steps, which):
    """Float32 end to end, so the tolerances are about summation order
    alone (1e-5 relative on the losses — the objective, cross-entropy
    plus the weighted load-balance term, on both sides —, 1e-4 on a
    leaf's gradient norm); the chip's bfloat16 readings and the limits
    set from them are the configuration file's."""
    one, mesh, want = steps
    got = one if which == "one_device" else mesh
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    for name, norm in want["grad_norms"].items():
        assert got["grad_norms"][name] == pytest.approx(norm, rel=1e-4), name


@pytest.fixture(scope="module")
def mesh_with_movers():
    """The mesh's two steps again with the size rule at 0 rows: every
    sorted buffer filled and read back by ``ops/moe_rows.py``'s movers
    inside the layer's ``shard_map``, scan and rematerialised chunk."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "LIVE_ROWS_FROM", 0)
        return _readings(4)


@pytest.mark.parametrize("movers", [False, True], ids=["gathers", "movers"])
def test_the_step_on_an_ep_mesh_equals_the_one_device_step(
        steps, movers, request):
    one, mesh, _ = steps
    if movers:
        mesh = request.getfixturevalue("mesh_with_movers")
    assert mesh["mesh"]["ep"] == 4 and one["mesh"]["ep"] == 1
    np.testing.assert_allclose(mesh["losses"], one["losses"], rtol=2e-6)
    for name, norm in one["grad_norms"].items():
        assert mesh["grad_norms"][name] == pytest.approx(norm, rel=2e-5)
    # every pair lands on some shard's held expert: the counts agree
    assert mesh["load"].shape == one["load"].shape == (DEPTH, 5)
    np.testing.assert_array_equal(mesh["load"][:, :3], one["load"][:, :3])
    pairs = ROWS * SEQ * 2
    assert mesh["load"][:, 0].tolist() == [pairs] * DEPTH
    # the busiest shard's sorted buffers: sized for every pair of the
    # rows it saw, live in its own experts' pairs — all of them on one
    # device, between a quarter and all over four shards
    assert one["load"][:, 3:].tolist() == [[pairs, pairs]] * DEPTH
    assert mesh["load"][:, 4].tolist() == [pairs] * DEPTH
    assert all(pairs <= 4 * live <= 4 * pairs for live in mesh["load"][:, 3])
    assert mesh["aux"] == pytest.approx(one["aux"], rel=1e-5)


def test_expert_leaves_stay_on_their_expert_dim_and_the_rest_is_shared(
        steps):
    """Experts over 'ep' on their expert dimension (never gathered: the
    layer takes them as they lie); every other matrix's state split over
    the same four devices on its hidden dimension, as fsdp splits it."""
    _, mesh, _ = steps
    sh = mesh["sharded"]
    for leaf in ("gate", "up", "down"):
        spec = sh[f"layers/block/moe/experts/{leaf}"]
        assert spec[1] == "ep" and "ep" not in jax.tree.leaves(
            [spec[0], spec[2:]])
    assert sh["lm_head/kernel"][0] == ("fsdp", "ep")
    assert sh["layers/block/attn/q_proj/kernel"][1] == ("fsdp", "ep")


def test_the_reference_in_a_lower_precision_fails_a_limit(steps):
    """The control: the same equations with every product but the
    router's in fp8 lie outside what float32 against float32 shows."""
    one, _, want = steps
    sizes = ref.sizes_of(one["pub"])
    with jax.default_matmul_precision("highest"):
        ctrl = ref.train_readings(
            lambda: weights.make(one["key"], one["pub"], DEPTH, jnp.float32),
            sizes, [b["input_ids"] for b in one["batches"][:1]], OPT,
            ref.lower_precision_dot("fp8"))
    limits = {"loss_rel": 1e-5, "grad_norm_rel": 1e-4,
              "delta_norm_rel": 1.0}
    cut = dict(want, losses=want["losses"][:1])
    readings = train_fit.compare(dict(ctrl, delta_norms=want["delta_norms"]),
                                 cut, limits)
    assert any(value > limit for _, value, limit in readings)


# -- what is still refused -----------------------------------------------------

def test_trainer_refuses_what_the_dropless_layer_has_not_run_under():
    mc = program.model_config(toy_published(), DEPTH, max_seq_len=SEQ)
    model = TransformerLM(mc)
    cfg = ta.Config()
    cfg.dist.tp.size = 2
    with pytest.raises(ConfigError, match="under tp, pp or sp"):
        Trainer(model, cfg)
    cfg = ta.Config()
    cfg.dist.ep.size = 3
    with pytest.raises(ConfigError, match="do not spread over"):
        Trainer(model, cfg, mesh=ta.Config().get_mesh(jax.devices()[:1]))
    share = dataclasses.replace(mc, num_experts=2, moe_router_width=8,
                                moe_first_expert=2)
    with pytest.raises(ConfigError, match="SHARE"):
        Trainer(TransformerLM(share), ta.Config())
    latent = dataclasses.replace(mc, kv_lora_rank=16)
    with pytest.raises(ConfigError, match="latent-attention model is not"):
        Trainer(TransformerLM(latent), ta.Config())
