"""The latent-attention / held-expert family (models/mla.py,
models/moe.py 'grouped', the latent paged pool of serve/) at a toy
preset on the CPU, against the benchmark's plain float32 reference
(chipbench/reference/mla_moe_decoder.py: the layer equations of the
configuration's source, nothing imported from the program).

Toy preset: hidden 64, 2 heads of 16 nope + 8 rope / 16 value dims,
q_lora 48, kv_lora 32, 16 experts in 4 groups (top-2 groups, top-4
experts), a shared expert, 1 dense + 2 expert layers, yarn x32 with
mscale 1 / 1.  Everything runs in float32 at ``highest``, so the
tolerances below are float32 summation-order noise on values of order
0.1-1; a wrong pair layout, scale, group limit or share shows at 1e-2
and above.
"""

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchacc_tpu as ta
from chipbench.layouts import mla_moe_decoder as layout
from chipbench.reference import mla_moe_decoder as ref
from chipbench.weights import mla_moe_decoder as weights
from torchacc_tpu.config import ConfigError
from torchacc_tpu.models import TransformerLM, mla, moe
from torchacc_tpu.models.hf import config_from_hf
from torchacc_tpu.ops.grouped_matmul import grouped_matmul, tile_schedule
from torchacc_tpu.ops.paged_attention import (
    latent_paged_attention,
    latent_query_tile,
)
from torchacc_tpu.serve import Request, ServeEngine
from torchacc_tpu.train.trainer import Trainer

TOY = dict(
    model_type="axk1", hidden_size=64, intermediate_size=128,
    num_attention_heads=2, num_key_value_heads=2, vocab_size=256,
    kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, first_k_dense_replace=1,
    moe_intermediate_size=32, moe_layer_freq=1, n_routed_experts=16,
    n_shared_experts=1, n_group=4, topk_group=2, num_experts_per_tok=4,
    norm_topk_prob=True, routed_scaling_factor=2.5, scoring_func="sigmoid",
    topk_method="none", hidden_act="silu", rms_norm_eps=1e-6,
    rope_theta=10000, max_position_embeddings=4096, num_hidden_layers=61,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=32, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=128,
                      type="yarn"),
    tie_word_embeddings=False)
DEPTH = 3
F32 = dict(param_dtype=jnp.float32, dtype=jnp.float32)


def share(first=None, held=16):
    """TOY as the chip that holds ``held`` experts from ``first`` on."""
    if first is None:
        return dict(TOY)
    return dict(TOY, n_routed_experts=held, router_n_experts=16,
                first_held_expert=first)


def model_config(published, **kw):
    return config_from_hf(types.SimpleNamespace(**published),
                          num_layers=DEPTH, max_seq_len=256, **F32, **kw)


@pytest.fixture(scope="module", autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def whole():
    """(published, canonical weights, program params, ModelConfig) of
    the toy with every expert held."""
    pub = share()
    w = weights.make(weights.base_key(2**31 + 5), pub, DEPTH, jnp.float32)
    mc = model_config(pub)
    return pub, w, layout.to_program_params(w, mc), mc


def held_share(pub_whole, w, first, held):
    """The same model cut to the experts [first, first + held)."""
    pub = share(first, held)
    cut = dict(w, moe=dict(w["moe"], **{
        k: w["moe"][k][:, first:first + held]
        for k in ("e_gate", "e_up", "e_down")}))
    mc = model_config(pub)
    return pub, cut, layout.to_program_params(cut, mc), mc


def test_ingest_reads_the_published_keys_and_the_share():
    mc = model_config(share(4, 4))
    assert (mc.kv_lora_rank, mc.q_lora_rank, mc.qk_nope_head_dim,
            mc.qk_rope_head_dim, mc.v_head_dim) == (32, 48, 16, 8, 16)
    assert (mc.first_dense_layers, mc.num_experts, mc.router_width,
            mc.moe_first_expert, mc.moe_shared_experts) == (1, 4, 16, 4, 1)
    assert (mc.moe_scoring, mc.moe_n_group, mc.moe_topk_group,
            mc.moe_route_scale, mc.moe_dispatch, mc.moe_router_bias) == (
        "sigmoid", 4, 2, 2.5, "grouped", False)
    assert mc.rope_interleaved and mc.expert_ffn_size == 32


def test_yarn_mscale_is_a_cos_sin_factor_and_a_softmax_scale():
    """mscale / mscale_all_dim = 1 / 1: the rotary factor is their
    ratio (1), the scale carries (0.1 ln 32 + 1)^2.  With mscale 2 the
    rotary factor is the ratio of the two, as the reference has it."""
    mc = model_config(share())
    m = 0.1 * np.log(32.0) + 1.0
    assert mc.rope_yarn[4] == pytest.approx(1.0)
    assert mc.query_scale == pytest.approx(24 ** -0.5 * m * m)
    pub = dict(TOY, rope_scaling=dict(TOY["rope_scaling"], mscale=2))
    mc2 = model_config(pub)
    assert mc2.rope_yarn[4] == pytest.approx((0.2 * np.log(32.0) + 1) / m)
    # the program's rotary embedding against the reference's, position
    # by position (1e-5: float32 cos/sin of angles up to ~200)
    from torchacc_tpu.models.block import _rope
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 200, 2, 8))
    pos = jnp.arange(200)
    got = _rope(x, x, pos[None], mc2)[0][0]
    want = ref.rope(x[0], pos, ref.sizes_of(pub))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_plain_forward_matches_the_reference(whole):
    pub, w, params, mc = whole
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 256, size=48))
    got = TransformerLM(mc).apply({"params": params}, ids[None])[0]
    want = ref.logits_at(w, ref.sizes_of(pub), ids, jnp.arange(48))
    # float32 summation order only (logits of order 0.5)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_param_tree_is_two_stacks_and_the_count_is_exact(whole):
    pub, w, params, mc = whole
    init = jax.eval_shape(lambda k: TransformerLM(mc).init(
        k, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, init) == jax.tree.map(
        lambda a: a.shape, params)
    assert init["dense_layers"]["block"]["mlp"]["gate_proj"][
        "kernel"].shape == (1, 64, 128)
    assert init["layers"]["block"]["moe"]["experts/gate"].shape == (
        2, 16, 64, 32)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == mc.num_params() == weights.param_count(pub, DEPTH)


def _served_gap(pub, w, requests, results):
    """Widest gap by which a served token's logit lies below the
    reference's best over every served token (the benchmark's number)."""
    sizes, worst = ref.sizes_of(pub), 0.0
    for prompt, tokens in zip(requests, results):
        ids = jnp.asarray(prompt + tokens[:-1])
        z = ref.logits_at(w, sizes, ids,
                          jnp.arange(len(prompt) - 1, len(ids)))
        picked = z[jnp.arange(len(tokens)), jnp.asarray(tokens)]
        worst = max(worst, float(jnp.max(jnp.max(z, axis=-1) - picked)))
    return worst


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("first", [None, 4], ids=["whole", "share"])
def test_serving_through_the_latent_pool_matches_the_reference(
        whole, impl, first):
    """Chunked prefill (chunks of 12 over blocks of 8: every chunk
    crosses a block) then decode through the one latent pool, four
    requests over three slots so that slots are reused and prefill
    chunks interleave with decode steps; absorbed form, kernel in
    interpret mode.  Every served token must be the reference's best up
    to float32 noise: the reference runs the EXPANDED form over the
    whole row at once.  And the module's own apply, which runs the
    expert layers under ``nn.scan`` on each layer's [E, in, out] kernels
    where the decoder's scan leaves the [L, E, in, out] stacks whole and
    hands the grouped matmul a layer index (one dense + two expert
    layers here): every served token — the prefill's first and the
    decode steps' — is the module's best too."""
    pub, w, params, mc = whole
    if first is not None:
        pub, w, params, mc = held_share(pub, w, first, 4)
    assert params["layers"]["block"]["moe"]["experts/gate"].shape[0] == 2
    cfg = ta.Config()
    cfg.serve.block_size, cfg.serve.num_blocks = 8, 64
    cfg.serve.max_slots, cfg.serve.prefill_chunk = 3, 12
    eng = ServeEngine(TransformerLM(dataclasses.replace(
        mc, attention_impl=impl)), params, cfg)
    (pool,) = eng.scheduler.pools.values()
    assert list(eng.scheduler.pools) == ["latent"]
    assert pool.shape == (DEPTH, 64, 8, 128)     # 32 + 8 values -> 128 lanes
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (5, 30, 17, 9)]
    rids = [eng.submit(Request(prompt_ids=p, max_new_tokens=6))
            for p in prompts]
    eng.run()
    results = [eng.result(r).tokens for r in rids]
    eng.close()
    assert all(len(t) == 6 for t in results)
    assert _served_gap(pub, w, prompts, results) < 1e-5
    module = jax.jit(lambda ids: TransformerLM(mc).apply(
        {"params": params}, ids[None])[0])
    for prompt, tokens in zip(prompts, results):
        z = module(jnp.asarray(prompt + tokens[:-1]))[len(prompt) - 1:]
        picked = z[jnp.arange(len(tokens)), jnp.asarray(tokens)]
        assert float(jnp.max(jnp.max(z, axis=-1) - picked)) < 1e-5


def test_absorbed_form_equals_expanded_form(whole):
    """q~ = q_nope W^K, scores over the latent row, o = (P c_kv) W^V
    against building every head's k and v (1e-5: float32 reassociation
    of two 32-term sums)."""
    _, _, params, mc = whole
    attn = jax.tree.map(lambda a: a[0], params["layers"]["block"]["attn"])
    s = 24
    h = jax.random.normal(jax.random.PRNGKey(3), (1, s, 64))
    pos = jnp.arange(s)[None]
    want = mla.expanded_attention(mc, attn, h, pos)
    q_nope, q_pe = mla.project_q(mc, attn, h, pos)
    c_kv, k_pe = mla.project_latent(mc, attn, h, pos)
    q_lat = mla.absorb_q(mc, attn, q_nope)
    scores = (jnp.einsum("bshr,bkr->bhsk", q_lat, c_kv)
              + jnp.einsum("bshp,bkp->bhsk", q_pe, k_pe)) * mla.query_scale(mc)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    o_lat = jnp.einsum("bhsk,bkr->bshr", jax.nn.softmax(scores, -1), c_kv)
    got = mla.project_out(mc, attn, mla.expand_out(mc, attn, o_lat))
    np.testing.assert_allclose(got, want, atol=1e-5)


# (q_start, context_lens) a slot, for t query tokens; tables of 24 blocks
# of 8, so the kernel's page walk takes 8 pages a step
_WALKS = {
    # 11 pages: a whole group and a partial one
    "partial_last_group": lambda t: ([84 - t, 75 - t, 88 - t], [84, 75, 88]),
    # 3 pages, 1 page: the walk ends inside its first group
    "ends_in_first_group": lambda t: ([20 - t, 0, 3], [20, t, 3 + t]),
    # q_start = 0 and ONE token banked (a chunk's other rows are padding)
    "first_token": lambda t: ([0, 0, 0], [1, 1, 1]),
    # free slots (length 0) either side of a live one
    "empty_beside_live": lambda t: ([0, 70 - t, 0], [0, 70, 0]),
}


@pytest.mark.parametrize("t", [1, 8], ids=["decode", "chunk"])
@pytest.mark.parametrize("walk", ["scattered"] + sorted(_WALKS))
def test_latent_kernel_matches_the_gather_path(t, walk):
    """The Pallas kernel (interpret mode) against the jnp gather path on
    a scattered block table: a free slot (length 0), a chunk that ends
    inside a block, query tiles whose causal reach stops early — and
    the page walk's own edges (``_WALKS``): the pages a tile can see are
    walked several at a time, from a bound read at run time."""
    s, h, r, p, bs, nb = 3, 4, 32, 8, 8, 80
    mb = 4 if walk == "scattered" else 24
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q_lat = jax.random.normal(ks[0], (s, t, h, r))
    q_pe = jax.random.normal(ks[1], (s, t, h, p))
    pool = jax.random.normal(ks[2], (2, nb, bs, 128))
    tables = jnp.asarray(np.random.default_rng(0).permutation(nb - 1)[
        :s * mb].reshape(s, mb) + 1, jnp.int32)
    if walk == "scattered":
        q0 = jnp.asarray([0, 5, 17], jnp.int32)
        ctx = jnp.asarray([t, 0, 17 + t] if t == 1
                          else [t, 5 + t - 3, 17 + t])
    else:
        assert latent_query_tile(h, r, p, bs, t, pool.dtype, False,
                                 mb)[1] == 8
        q0, ctx = (jnp.asarray(a, jnp.int32) for a in _WALKS[walk](t))
    args = (q_lat, q_pe, pool, tables, ctx, q0)
    got = latent_paged_attention(*args, layer=1, scale=0.3, impl="pallas")
    want = latent_paged_attention(*args, layer=1, scale=0.3, impl="xla")
    np.testing.assert_allclose(got, want, atol=2e-6)
    live = np.asarray(ctx) > 0
    assert float(jnp.abs(want[live]).max()) > 0.01
    assert float(jnp.abs(got[~live]).max() if (~live).any() else 0.0) == 0.0


def _brute_force_route(scores, choice, n_group, topk_group, k, scale):
    """Group-limited top-k the slow way, one token at a time."""
    n, e = scores.shape
    sel, w = np.zeros((n, k), np.int64), np.zeros((n, k))
    for t in range(n):
        groups = choice[t].reshape(n_group, e // n_group)
        gscore = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        keep = np.argsort(-gscore, kind="stable")[:topk_group]
        allowed = [i for i in range(e) if i // (e // n_group) in keep]
        best = sorted(allowed, key=lambda i: -choice[t, i])[:k]
        sel[t] = best
        picked = scores[t, best]
        w[t] = scale * picked / (picked.sum() + 1e-20)
    return sel, w


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("n_group,topk_group", [(4, 2), (1, 1)],
                         ids=["grouped", "plain_topk"])
def test_router_against_brute_force(n_group, topk_group, bias):
    """Sigmoid scores, group limit, normalised and scaled weights; the
    bias moves the selection and never the weights; one group is plain
    top-k.  Same float32 scores on both sides: the selections are equal
    and the weights agree to rounding."""
    mc = dataclasses.replace(model_config(share()), moe_n_group=n_group,
                             moe_topk_group=topk_group)
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(16) * 0.3, jnp.float32) \
        if bias else None
    sel, w, scores = moe.route(mc, logits, b)
    scores = np.asarray(scores, np.float64)
    choice = scores + (np.asarray(b, np.float64) if bias else 0.0)
    want_sel, want_w = _brute_force_route(scores, choice, n_group,
                                          topk_group, 4, 2.5)
    assert np.array_equal(np.sort(sel, axis=1), np.sort(want_sel, axis=1))
    order = np.argsort(np.asarray(sel), axis=1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, 1),
        np.take_along_axis(want_w, np.argsort(want_sel, axis=1), 1),
        rtol=1e-5)
    if n_group == 1 and not bias:
        assert np.array_equal(np.sort(sel, axis=1), np.sort(
            jax.lax.top_k(logits, 4)[1], axis=1))


def _moe_tree(w, layer=0):
    lw = jax.tree.map(lambda a: a[layer], w["moe"])
    return lw, {"router": {"kernel": lw["router"]},
                "experts/gate": lw["e_gate"], "experts/up": lw["e_up"],
                "experts/down": lw["e_down"],
                "shared": {"gate_proj": {"kernel": lw["s_gate"]},
                           "up_proj": {"kernel": lw["s_up"]},
                           "down_proj": {"kernel": lw["s_down"]}}}


@pytest.mark.parametrize("rows,sizes", [
    (256, [0, 130, 0, 5]),          # empty groups, one that spans tiles
    (256, [0, 0, 0, 0]),            # no pair on a held expert at all
    (256, [256, 0, 0, 0]),          # every pair on one expert
    (300, [1, 127, 1, 128, 43]),    # rows padded to the tile, full
    (28, [3, 0, 20, 1]),            # fewer rows than a tile
], ids=["empty_groups", "no_pairs", "one_expert", "padded_rows", "few_rows"])
@pytest.mark.parametrize("layer", [None, 0, 1, 2],
                         ids=["w3d", "layer0", "layer1", "layer2"])
def test_grouped_matmul_against_a_loop_over_groups(rows, sizes, layer):
    """The Pallas grouped matmul (interpreted here) against one plain
    product a group: float32 at ``highest``, summation-order noise only.
    Rows behind the last group are undefined by contract (the
    interpreter leaves NaN there) and are not compared.  With ``layer``
    the weights are a stack of three layers and the index is traced:
    the same kernel reads ``w[layer]`` where it lies, so the rows of the
    groups equal the call on the layer's own [g, k, n] bit for bit."""
    g = len(sizes)
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, 256))
    stack = jax.random.normal(jax.random.PRNGKey(1), (3, g, 256, 384))
    w = stack[layer or 0]
    sizes_ = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul(x, w, sizes_, tk=128, tn=128)
    if layer is not None:
        flat, got = got, jax.jit(functools.partial(
            grouped_matmul, tk=128, tn=128))(x, stack, sizes_,
                                             layer=jnp.int32(layer))
        assert np.array_equal(got[:sum(sizes)], flat[:sum(sizes)])
    assert got.shape == (rows, 384)
    start = 0
    for i, n in enumerate(sizes):
        np.testing.assert_allclose(got[start:start + n],
                                   x[start:start + n] @ w[i], atol=2e-4)
        start += n


def test_grouped_matmul_takes_a_stack_in_the_rows_dtype_only():
    """A [g, k, n] ``w`` of another dtype is converted on the way in; a
    layer stack is not (that would convert every layer of it inside the
    caller's scan) and is a typed error, as are a stack without an index
    and an index without a stack."""
    x = jnp.ones((16, 128), jnp.bfloat16)
    stack = jnp.ones((2, 2, 128, 128), jnp.float32)
    sizes = jnp.asarray([8, 8], jnp.int32)
    assert grouped_matmul(x, stack[1], sizes).dtype == jnp.bfloat16
    with pytest.raises(TypeError, match="outside the layer scan"):
        grouped_matmul(x, stack, sizes, layer=jnp.int32(1))
    with pytest.raises(ValueError, match="takes a layer index"):
        grouped_matmul(x, stack.astype(x.dtype), sizes)
    with pytest.raises(ValueError, match="takes a layer index"):
        grouped_matmul(x, stack[1], sizes, layer=jnp.int32(1))
    got = grouped_matmul(x, stack.astype(x.dtype), sizes, layer=jnp.int32(1))
    assert np.array_equal(got, jnp.full((16, 128), 128, jnp.bfloat16))


def test_grouped_matmul_schedule_visits_only_tiles_that_hold_rows():
    """12 groups over 4096 rows of which 260 belong to a group: the
    steps that work are the (group, row tile) pairs with rows in them —
    an empty group has none, a group across a tile edge has two — and
    the idle steps repeat the last one (nothing to fetch)."""
    sizes = jnp.asarray([20, 0, 100, 30, 0, 0, 50, 0, 0, 0, 0, 60], jnp.int32)
    group_of, tile_of, starts, ends, num = tile_schedule(sizes, 4096, 128)
    n = int(num[0])
    assert group_of.shape == (4096 // 128 + 12 - 1,)
    assert list(zip(group_of[:n].tolist(), tile_of[:n].tolist())) == [
        (0, 0), (2, 0), (3, 0), (3, 1), (6, 1), (11, 1), (11, 2)]
    assert set(zip(group_of[n:].tolist(), tile_of[n:].tolist())) == {(11, 2)}
    assert ends.tolist()[-1] == 260 and starts.tolist()[2] == 20


def test_nothing_is_dropped_when_every_token_picks_the_same_experts(whole):
    """A router that sends all 40 tokens to experts 0-3 (one group):
    four experts get 40 pairs each, twelve get none; the layer equals
    the reference's, which computes every expert on every token."""
    pub, w, _, mc = whole
    lw, tree = _moe_tree(w)
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64)) * 0.5
    # a router of zeros: every score ties at 0.5, and ties go to the
    # lowest index on both sides — groups 0-1, then experts 0-3
    router = jnp.zeros((64, 16))
    lw = dict(lw, router=router)
    tree = dict(tree, router={"kernel": router})
    y, _, sel, load = moe.moe_ffn(mc, tree, x)
    assert np.array_equal(np.sort(sel, axis=1),
                          np.tile(np.arange(4), (40, 1)))
    assert load.tolist() == [160, 40, 4]
    want, _ = ref.expert_layer(x, lw, ref.sizes_of(pub), ref._f32_dot)
    np.testing.assert_allclose(y, want, atol=1e-5)


def test_padding_tokens_reach_no_expert(whole):
    _, w, _, mc = whole
    _, tree = _moe_tree(w)
    x = jax.random.normal(jax.random.PRNGKey(6), (10, 64))
    valid = jnp.arange(10) < 6
    _, _, _, load = moe.moe_ffn(mc, tree, x, valid)
    assert int(load[0]) == 6 * 4


def test_the_shares_add_up_to_the_uncut_layer(whole):
    """Four chips holding experts [0,4) [4,8) [8,12) [12,16): their
    routed parts, plus the shared expert counted once, are the uncut
    reference layer (every chip adds the shared expert itself, so three
    copies of it come off the sum).  1e-5: float32 sums of 4 terms."""
    pub, w, _, _ = whole
    lw, _ = _moe_tree(w, layer=1)
    x = jax.random.normal(jax.random.PRNGKey(7), (32, 64)) * 0.5
    want, _ = ref.expert_layer(x, lw, ref.sizes_of(pub), ref._f32_dot)
    shared = ref.swiglu(x, lw["s_gate"], lw["s_up"], lw["s_down"],
                        ref._f32_dot)
    total, pairs = jnp.zeros_like(x), 0
    for first in (0, 4, 8, 12):
        cut_pub, cut_w, _, mc = held_share(pub, w, first, 4)
        cut_lw, tree = _moe_tree(cut_w, layer=1)
        y, _, _, load = moe.moe_ffn(mc, tree, x)
        # the program's share equals the reference's share
        np.testing.assert_allclose(y, ref.expert_layer(
            x, cut_lw, ref.sizes_of(cut_pub), ref._f32_dot)[0], atol=1e-5)
        total, pairs = total + y, pairs + int(load[0])
    assert pairs == 32 * 4          # every pair landed on exactly one chip
    np.testing.assert_allclose(total - 3 * shared, want, atol=1e-5)


def test_the_older_moe_paths_refuse_what_only_the_grouped_path_does():
    from torchacc_tpu.models import get_preset
    mc = get_preset("llama-tiny", num_experts=4, num_experts_per_tok=2,
                    moe_scoring="sigmoid")
    with pytest.raises(ValueError, match="moe_dispatch='grouped'"):
        TransformerLM(mc).init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))


def test_training_and_cached_decode_are_typed_refusals(whole):
    _, _, params, mc = whole
    with pytest.raises(ConfigError, match="not supported"):
        Trainer(TransformerLM(mc), ta.Config())
    with pytest.raises(NotImplementedError, match="ServeEngine"):
        TransformerLM(mc).apply({"params": params},
                                jnp.zeros((1, 4), jnp.int32),
                                mutable=["cache"])


def test_serving_still_refuses_the_other_moe_paths():
    from torchacc_tpu.models import get_preset
    from torchacc_tpu.serve.scheduler import _check_supported
    with pytest.raises(NotImplementedError, match="MoE outside"):
        _check_supported(get_preset("llama-tiny", num_experts=4))
