"""The family of two latent layer kinds ('dots3_note': a learned sparse
selection over an MLA cache on the full layers, windowed latent attention
of other sizes on the sliding ones, held experts; models/mla.py,
ops/paged_attention.py, the three pools of serve/kv_cache.py) at a toy
preset on the CPU, against the benchmark's plain float32 reference
(chipbench/reference/mla_sparse_window_moe_decoder.py: the layer
equations of ISSUE 30, nothing imported from the program).

Toy preset: hidden 64; full layers 4 heads of 16 nope + 8 rope / 16 value
dims, q_lora 48, kv_lora 32, an indexer of 4 heads of 16 selecting 12
positions; sliding layers 2 heads of 24 + 8 / 16, kv_lora 40, a window
of 11; 16 experts top-4 with a selection bias, a shared expert; the
published pattern cut to 9 layers (1 dense + two periods of full,
sliding, sliding, sliding).  Everything runs in float32 at ``highest``,
so the tolerances below are float32 summation-order noise on values of
order 0.1-1 (2e-5 on logits, as tests/test_mla_moe.py); a wrong mask,
scale, rope base, gate or pool index shows at 1e-2 and above.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchacc_tpu as ta
from chipbench.layouts import mla_sparse_window_moe_decoder as layout
from chipbench.reference import mla_sparse_window_moe_decoder as ref
from chipbench.weights import mla_sparse_window_moe_decoder as weights
from torchacc_tpu.models import TransformerLM, mla, moe
from torchacc_tpu.models.hf import config_from_hf
from torchacc_tpu.ops.paged_attention import (
    indexer_scores,
    latent_paged_attention,
    latent_query_tile,
    select_topk,
)
from torchacc_tpu.serve import Request, ServeEngine
from torchacc_tpu.serve.kv_cache import WindowBlocks, window_blocks_bound
from torchacc_tpu.serve.scheduler import _check_supported

PATTERN = (["full_attention"] + ["full_attention"] + ["sliding_attention"] * 3
           + ["full_attention"] + ["sliding_attention"] * 3)
TOY = dict(
    model_type="dots3_note", hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=4, vocab_size=256,
    kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, swa_num_attention_heads=2,
    swa_num_key_value_heads=2, swa_kv_lora_rank=40, swa_q_lora_rank=48,
    swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
    swa_rope_theta=500, swa_attention_gate_type="headwise",
    attention_gate_type="headwise", apply_mla_qkv_lora_rescale=True,
    index_head_dim=16, index_n_heads=4, index_topk=12,
    sliding_window_size=11, first_k_dense_replace=1,
    moe_intermediate_size=32, moe_layer_freq=1, n_routed_experts=16,
    n_shared_experts=1, num_experts_per_tok=4, norm_topk_prob=True,
    routed_scaling_factor=1, scoring_func="sigmoid", topk_method="noaux_tc",
    hidden_act="silu", rms_norm_eps=1e-5, rope_theta=10000,
    rope_scaling=None, max_position_embeddings=4096, num_hidden_layers=46,
    attention_bias=False, layer_types=PATTERN, tie_word_embeddings=False)
DEPTH = 9
F32 = dict(param_dtype=jnp.float32, dtype=jnp.float32)
SERVE = dict(block_size=8, num_blocks=64, max_slots=3, prefill_chunk=12)


def model_config(published, **kw):
    return config_from_hf(types.SimpleNamespace(**published),
                          num_layers=DEPTH, max_seq_len=256, **F32, **kw)


@pytest.fixture(scope="module", autouse=True)
def highest():
    rows, ref.ROWS = ref.ROWS, 16      # several blocks of rows a request
    with jax.default_matmul_precision("highest"):
        yield
    ref.ROWS = rows


@pytest.fixture(scope="module")
def whole():
    """(published, canonical weights, program params, ModelConfig) of
    the toy with every expert held."""
    w = weights.make(weights.base_key(2**31 + 5), TOY, DEPTH, jnp.float32)
    mc = model_config(TOY)
    return TOY, w, layout.to_program_params(w, mc), mc


_REF = {}


def ref_logits(pub, w, ids, positions):
    """The reference's logits of the row ``ids`` at ``positions``: one
    compiled program a configuration (the row padded to 96 ids, the
    positions to 16 by repeating the last; causal, so the padding
    changes nothing before it)."""
    key = (pub["n_routed_experts"], pub.get("first_held_expert"))
    if key not in _REF:
        sizes = ref.sizes_of(pub)
        _REF[key] = jax.jit(lambda w, ids, pos: ref.logits_at(
            w, sizes, ids, pos))
    ids, positions = np.asarray(ids), np.asarray(positions)
    pad_ids = np.zeros((96,), np.int32)
    pad_ids[:len(ids)] = ids
    pad_pos = np.full((16,), positions[-1], np.int32)
    pad_pos[:len(positions)] = positions
    return _REF[key](w, jnp.asarray(pad_ids),
                     jnp.asarray(pad_pos))[:len(positions)]


def engine(mc, params, impl, **serve):
    cfg = ta.Config()
    for key, value in dict(SERVE, **serve).items():
        setattr(cfg.serve, key, value)
    return ServeEngine(TransformerLM(dataclasses.replace(
        mc, attention_impl=impl)), params, cfg)


def test_ingest_of_the_catalogs_config_verbatim():
    """`config_from_hf` on the catalog row's ``config`` as it stands:
    the per-kind sizes, the pattern, the window and the share."""
    import json
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")]
    (row,) = [r for r in rows if r["name"] == "dots3-note-prev"]
    mc = config_from_hf(types.SimpleNamespace(**row["config"]))
    assert (mc.num_layers, mc.hidden_size, mc.vocab_size) == (46, 5120,
                                                              152064)
    assert mc.layer_pattern.count("global") == 13
    assert mc.layer_pattern.count("sliding") == 33
    assert mc.layer_pattern[:6] == ("global", "global", "sliding",
                                    "sliding", "sliding", "global")
    assert mc.window == (512, -1)            # 513 counts the token itself
    full, win = mla.kind_config(mc, "global"), mla.kind_config(mc, "sliding")
    assert (full.num_heads, full.q_lora_rank, full.kv_lora_rank,
            full.qk_nope_head_dim, full.qk_rope_head_dim, full.v_head_dim,
            full.rope_theta) == (128, 1024, 512, 128, 64, 128, 8e7)
    assert (win.num_heads, win.q_lora_rank, win.kv_lora_rank,
            win.qk_nope_head_dim, win.qk_rope_head_dim, win.v_head_dim,
            win.rope_theta) == (64, 1024, 1024, 192, 64, 128, 5e4)
    assert (mc.index_topk, mc.index_n_heads, mc.index_head_dim) == (
        2048, 64, 128) and win.index_topk == 0 and full.window == (-1, -1)
    assert mc.mla_lora_rescale and mc.attn_gate == "headwise"
    assert mla.query_scale(full) == pytest.approx(192 ** -0.5)
    assert mla.query_scale(win) == pytest.approx(256 ** -0.5)
    assert (mc.num_experts, mc.router_width, mc.num_experts_per_tok,
            mc.moe_router_bias, mc.moe_n_group, mc.moe_shared_experts,
            mc.first_dense_layers, mc.expert_ffn_size) == (
        256, 256, 8, True, 1, 1, 1, 1536)
    _check_supported(mc)
    # this issue's arithmetic: 279.6B for the language model
    assert mc.num_params() == pytest.approx(279.6e9, rel=2e-3)


def test_param_tree_is_a_stack_a_period_position_and_the_count_is_exact(
        whole):
    pub, w, params, mc = whole
    assert set(params["layers"]) == {"p0", "p1", "p2", "p3"}
    assert params["layers"]["p0"]["block"]["attn"]["index_q"][
        "kernel"].shape == (2, 48, 4, 16)
    assert "index_q" not in params["layers"]["p1"]["block"]["attn"]
    assert params["layers"]["p2"]["block"]["attn"]["kv_a_proj"][
        "kernel"].shape == (2, 64, 48)
    assert params["dense_layers"]["block"]["mlp"]["gate_proj"][
        "kernel"].shape == (1, 64, 128)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == mc.num_params() == weights.param_count(pub, DEPTH)
    assert set(layout.canonical_names(mc)) == {
        "/".join(str(getattr(k, "key", k)) for k in path) for path, _ in
        jax.tree_util.tree_flatten_with_path(params)[0]}


def _prefill_logits(eng, prompt):
    """The final prefill chunk's logits for ``prompt`` through the
    scheduler's own chunk loop (slot 0), and what it left in the
    scheduler."""
    from torchacc_tpu.serve.scheduler import Sequence
    sched = eng.scheduler
    seq = Sequence(sid=0, prompt=np.asarray(prompt, np.int32), max_new=4)
    assert sched.admit(seq)
    seen = {}
    real = sched._seed_first_token
    sched._seed_first_token = lambda s, logits: seen.update(z=logits)
    while seq.prefilled < seq.prompt_len:
        sched._prefill_one(seq)
    sched._seed_first_token = real
    sched.preempt(seq, 0.0)
    sched.finished.clear()
    return np.asarray(seen["z"])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_in_chunks_gives_the_references_logits(whole, impl):
    """Logits, not tokens: the last prompt position's logits after a
    prefill in chunks of 12 over blocks of 8 through the three pools,
    at prompt lengths on both sides of ``index_topk`` (12) and of the
    window (11) and well past both (the first window blocks are freed
    by then), against the reference's full forward."""
    pub, w, params, mc = whole
    eng = engine(mc, params, impl)
    rng = np.random.default_rng(3)
    # (the kernels run in interpret mode off the chip: fewer lengths)
    for n in (7, 11, 12, 13, 30, 61) if impl == "xla" else (13, 45):
        prompt = rng.integers(1, 256, size=n)
        got = _prefill_logits(eng, prompt)
        want = ref_logits(pub, w, prompt, [n - 1])[0]
        np.testing.assert_allclose(got, want, atol=2e-5, err_msg=str(n))
    assert eng.scheduler.window.freed > 0
    assert eng.scheduler.blocks_by_kind() == {
        "blocks_full": 0, "blocks_window": 0,
        "window_blocks_freed": eng.scheduler.window.freed}
    eng.close()


def _served_gap(pub, w, requests, results):
    worst = 0.0
    for prompt, tokens in zip(requests, results):
        ids = prompt + tokens[:-1]
        z = ref_logits(pub, w, ids, np.arange(len(prompt) - 1, len(ids)))
        picked = z[jnp.arange(len(tokens)), jnp.asarray(tokens)]
        worst = max(worst, float(jnp.max(jnp.max(z, axis=-1) - picked)))
    return worst


@pytest.mark.parametrize("impl,first", [
    ("xla", None), ("xla", 4), ("pallas", 4)],
    ids=["xla-whole", "xla-share", "pallas-share"])
def test_serving_through_the_three_pools_matches_the_reference(
        whole, impl, first):
    """Chunked prefill then decode, four requests over three slots (slots
    reused, chunks between decode steps), contexts that start below
    ``index_topk`` and the window and decode past both; every served
    token is the reference's best up to float32 noise, for the whole
    model and for the chip that holds experts [4, 8)."""
    pub, w, params, mc = whole
    if first is not None:
        pub = dict(pub, n_routed_experts=4, router_n_experts=16,
                   first_held_expert=first)
        w = {k: ({n: (leaf[:, first:first + 4] if n in
                      ("e_gate", "e_up", "e_down") else leaf)
                  for n, leaf in v.items()} if isinstance(v, dict) else v)
             for k, v in w.items()}
        mc = model_config(pub)
        params = layout.to_program_params(w, mc)
    eng = engine(mc, params, impl)
    pools = eng.scheduler.pools
    full, keys, win = pools["latent"], pools["index"], pools["latent_win"]
    assert full.shape == (3, 64, 8, 128)    # 32 + 8 values -> 128 lanes
    assert keys.shape == (3, 64, 8, 16)
    # 3 slots x (ceil((11 + 12) / 8) + 1) blocks and the null block
    assert win.shape == (6, 13, 8, 128)     # 40 + 8 values -> 128 lanes
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (5, 40, 17, 9)]
    rids = [eng.submit(Request(prompt_ids=p, max_new_tokens=8))
            for p in prompts]
    eng.run()
    results = [eng.result(r).tokens for r in rids]
    assert all(len(t) == 8 for t in results)
    assert _served_gap(pub, w, prompts, results) < 1e-5
    assert eng.scheduler.blocks_by_kind()["blocks_window"] == 0
    assert eng.scheduler.window.reserved == 0
    eng.close()


def test_the_programs_selection_is_the_references_where_the_margin_is_clear(
        whole):
    """One full layer's selection, program against reference: the
    indexer's scores over the paged index keys and ``select_topk``'s
    rule against the reference's stable sort, query by query.  Sets are
    equal wherever the reference's selection margin is over 1e-4 of the
    row's spread (float32 noise on the scores is ~1e-6 of it); the
    rows the margin excludes are few."""
    pub, w, params, mc = whole
    sizes = ref.sizes_of(pub)
    cfg = mla.kind_config(mc, "global")
    t, bs = 40, 8
    x = jax.random.normal(jax.random.PRNGKey(5), (1, t, 64)) * 0.7
    pos = jnp.arange(t)[None]
    attn = jax.tree.map(lambda a: a[0], params["layers"]["p0"]["block"]["attn"])
    lw = {k: v[0] for k, v in w["p0"].items()}
    c_q = mla.latent_q(cfg, attn, x)
    keys = mla.index_key(cfg, attn, x, pos)
    pool = jnp.zeros((1, 8, bs, 16)).at[0, 1:6].set(keys.reshape(5, bs, 16))
    tables = jnp.arange(1, 6, dtype=jnp.int32)[None]
    for impl in ("xla", "pallas"):
        got = indexer_scores(
            mla.index_query(cfg, attn, c_q, pos),
            mla.index_weights(cfg, attn, x), pool, tables,
            jnp.asarray([t]), jnp.asarray([0]), layer=0, impl=impl)[0]
        want = ref.index_scores(
            x[0], ref.rmsnorm(ref._f32_dot(x[0], lw["wq_a"]), lw["q_norm"],
                              1e-5) * (64 / 48) ** 0.5,
            ref.latents(x[0], lw, sizes, "full", pos[0], ref._f32_dot)[2],
            lw, sizes, pos[0], ref._f32_dot)
        visible = np.tril(np.ones((t, t), bool))
        np.testing.assert_allclose(np.asarray(got)[visible],
                                   np.asarray(want)[visible], atol=2e-6)
        assert np.all(np.asarray(got)[~visible] <= -1e29)
    thr, tie_hi = select_topk(got, 12)
    mine = np.asarray((got > thr[:, None]) | (
        (got == thr[:, None]) & (np.arange(t)[None] <= tie_hi[:, None])))
    theirs, margin = ref.select(want, jnp.asarray(visible), 12)
    clear = np.asarray(margin) > 1e-4
    assert clear.sum() >= t - 3
    np.testing.assert_array_equal((mine & visible)[clear],
                                  np.asarray(theirs)[clear])
    assert np.all(mine.sum(-1) == 12)        # exactly k, masked or not


@pytest.mark.parametrize("k", [1, 7, 64, 300])
def test_select_topk_is_exact_with_ties_to_the_lower_position(k):
    """Against a stable descending sort: repeated values, signed zeros,
    masked tails and rows shorter than k."""
    rng = np.random.default_rng(k)
    x = rng.normal(size=(6, 300)).astype(np.float32)
    x[0, :50] = 0.5
    x[1, 10:40], x[1, 40:60] = -0.0, 0.0
    x[2, 100:] = -1e30
    x[3] = np.round(x[3] * 4) / 4
    x[4] = 0.0
    thr, tie_hi = (np.asarray(a) for a in select_topk(jnp.asarray(x), k))
    pos = np.arange(300)[None]
    mine = (x > thr[:, None]) | ((x == thr[:, None])
                                 & (pos <= tie_hi[:, None]))
    want = np.zeros_like(mine)
    order = np.argsort(-x, axis=-1, kind="stable")[:, :k]
    np.put_along_axis(want, order, True, axis=-1)
    np.testing.assert_array_equal(mine, want)


# context lengths a slot (the t query tokens are the last banked ones);
# tables of 24 blocks of 8: the kernel's page walk takes 8 pages a step
# (2 under the window of 40: a third of the 7 blocks it spans)
_WALKS = {
    # 11 and 10 pages: a whole group, then a partial one
    "partial_last_group": [86, 75, 0],
    # 3 pages and 1: the walk ends inside its first group
    "ends_in_first_group": [20, 0, 6],
    # q_start = 0, one token banked, beside a slot that holds nothing
    "first_token": [1, 0, 1],
    # two chunk tiles a slot: the second reaches a page group further
    "two_tiles": [72, 0, 66],
}


@pytest.mark.parametrize("t", [1, 8], ids=["decode", "chunk"])
@pytest.mark.parametrize("variant", ["window", "selection"])
@pytest.mark.parametrize("walk", ["as_served"] + sorted(_WALKS))
def test_latent_kernel_variants_match_the_gather_path(t, variant, walk):
    """The Pallas kernel (interpret mode) with a window bound / under a
    selection against the jnp gather path, slots of different lengths,
    tables that hold the null block where the window has passed — and
    the page walk's own edges (``_WALKS``): the first live block of a
    window is not block 0 and the entries before it are 0, a
    selection's threshold tie falls in the last, partial group."""
    s_, h, r, pe, bs = 3, 2, 32, 8, 8
    mb = 6 if walk == "as_served" else 24
    if walk == "two_tiles" and t > 1:
        t = 16 if variant == "selection" else 64
    ks = jax.random.split(jax.random.PRNGKey(t), 5)
    ql = jax.random.normal(ks[0], (s_, t, h, r))
    qp = jax.random.normal(ks[1], (s_, t, h, pe))
    pool = jax.random.normal(ks[2], (2, 80, bs, 128))
    if walk == "as_served":
        ctx = jnp.asarray([41, 9, 0]) + jnp.asarray([t, t, 0]) - 1
    else:
        ctx = jnp.asarray([max(c, t) if c else 0 for c in _WALKS[walk]])
        if walk == "first_token":
            ctx = jnp.minimum(ctx, 1)
    q0 = jnp.maximum(ctx - t, 0)
    tables = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, 1 + s_ * mb)).reshape(s_, mb), jnp.int32)
    window = {"as_served": 10}.get(walk, 40) if variant == "window" else -1
    tq, pages = latent_query_tile(h, r, pe, bs, t, pool.dtype,
                                  variant == "selection", mb, window)
    if walk != "as_served":
        assert pages == (2 if variant == "window" else 8)
        assert walk != "two_tiles" or t == 1 or t // tq == 2
    kw = {}
    if variant == "window":
        kw["window"] = window
        # the blocks wholly before the first query's window are freed
        dead = (np.maximum(np.asarray(q0) - window, 0) // bs)
        if walk == "partial_last_group":
            assert dead[0] >= 3
        tables = jnp.asarray(np.where(
            np.arange(mb)[None] < dead[:, None], 0, np.asarray(tables)),
            jnp.int32)
    elif walk == "partial_last_group":
        # every score ties but five: the k best are the five and the
        # first k - 5 of the rest, so the tie's upper end lies in the
        # walk's second, partial group of pages
        scores = jnp.full((s_, t, mb * bs), 0.5).at[:, :, 3:40:8].set(1.0)
        k = pages * bs + 9
        thr, tie_hi = select_topk(scores, k)
        assert float(thr.min()) == float(thr.max()) == 0.5
        assert pages * bs <= int(tie_hi.min()) and int(tie_hi.max()) < 75
        kw["selection"] = (scores, thr, tie_hi)
    else:
        scores = jax.random.normal(ks[3], (s_, t, mb * bs))
        if walk != "as_served":
            # as the indexer leaves them: NEG_INF where a query cannot see
            pos = jnp.arange(mb * bs)
            q_pos = q0[:, None] + jnp.arange(t)
            scores = jnp.where((pos < ctx[:, None, None])
                               & (pos <= q_pos[..., None]), scores, -1e30)
        kw["selection"] = (scores,) + select_topk(scores, 12)
    out = {impl: latent_paged_attention(
        ql, qp, pool, tables, ctx, q0, layer=1, scale=0.3, impl=impl,
        name="variant_under_test", **kw) for impl in ("xla", "pallas")}
    np.testing.assert_allclose(out["pallas"], out["xla"], atol=2e-5)
    live = np.asarray(ctx) > 0
    assert float(jnp.abs(out["xla"][live]).max()) > 0.01
    assert float(jnp.abs(out["pallas"][~live]).max()) == 0.0


def test_the_sixteen_shares_add_up_to_the_uncut_layer(whole):
    """Sixteen chips holding one expert each: their routed parts, plus
    the shared expert counted once, are the uncut reference layer with
    its selection bias.  1e-5: float32 sums of 4 terms."""
    pub, w, params, mc = whole
    sizes = ref.sizes_of(pub)
    x = jax.random.normal(jax.random.PRNGKey(7), (32, 64)) * 0.5
    want, _ = ref.expert_layer(x, w["p1"], 1, sizes, ref._f32_dot)
    shared = ref.swiglu(x, w["p1"]["s_gate"][1], w["p1"]["s_up"][1],
                        w["p1"]["s_down"][1], ref._f32_dot)
    tree = jax.tree.map(lambda a: a[1],
                        params["layers"]["p1"]["block"]["moe"])
    total, pairs = jnp.zeros_like(x), 0
    for first in range(16):
        cut = dict(pub, n_routed_experts=1, router_n_experts=16,
                   first_held_expert=first)
        cmc = mla.kind_config(model_config(cut), "sliding")
        cut_tree = dict(tree, **{k: tree[k][first:first + 1] for k in
                                 ("experts/gate", "experts/up",
                                  "experts/down")})
        y, _, _, load = moe.moe_ffn(cmc, cut_tree, x)
        lw = dict(w["p1"], **{k: w["p1"][k][:, first:first + 1]
                              for k in ("e_gate", "e_up", "e_down")})
        np.testing.assert_allclose(y, ref.expert_layer(
            x, lw, 1, ref.sizes_of(cut), ref._f32_dot)[0], atol=1e-5)
        total, pairs = total + y, pairs + int(load[0])
    assert pairs == 32 * 4
    np.testing.assert_allclose(total - 15 * shared, want, atol=1e-5)


def test_window_blocks_are_held_only_while_the_window_reaches_them():
    """`WindowBlocks` by itself: a sequence grown far past the window in
    chunks and then a token at a time never holds more than the bound,
    what it frees is handed out again, and a release returns blocks and
    reservation."""
    bs, window, chunk = 8, 10, 12
    bound = window_blocks_bound(window, chunk, bs)
    assert bound == 4                        # ceil((11 + 12) / 8) + 1
    assert window_blocks_bound(512, 512, 128) == 10
    wb = WindowBlocks(2 * bound + 1, bs, window, bound)
    held, row = {}, np.zeros((64,), np.int32)
    other, other_row = {}, np.zeros((64,), np.int32)
    wb.reserve()
    wb.reserve()
    assert not wb.can_reserve()
    seen, most, t = set(), 0, 0
    for n in [chunk] * 12 + [1] * 150:
        wb.advance(held, row, t, t + n)
        wb.advance(other, other_row, t // 3, t // 3 + 1)
        t += n
        most = max(most, len(held))
        seen.update(held.values())
        live = {j for j in range(64) if row[j]}
        assert live == set(held) and 0 not in held.values()
        assert min(live) * bs <= max(t - n - window, 0) < (min(live) + 1) * bs \
            or min(live) == 0
        assert not set(held.values()) & set(other.values())
    assert most <= bound and t > 20 * window
    assert wb.freed > 30 and len(seen) < wb.freed   # freed blocks are reused
    assert wb.pool.in_use == len(held) + len(other)
    wb.release(list(held.values()))
    wb.release(list(other.values()))
    assert wb.pool.in_use == 0 and wb.reserved == 0 and wb.can_reserve()


def test_preemption_and_completion_return_every_block_of_every_kind(whole):
    pub, w, params, mc = whole
    eng = engine(mc, params, "xla")
    sched = eng.scheduler
    rng = np.random.default_rng(2)
    rids = [eng.submit(Request(prompt_ids=rng.integers(1, 256, size=n)
                               .tolist(), max_new_tokens=40))
            for n in (50, 20, 33)]
    most = 0
    for _ in range(30):
        eng.step()
        most = max(most, max((len(s.win_blocks) for s in sched.slot_seq
                              if s is not None), default=0))
    assert sched.window.pool.in_use > 0 and sched.pool.in_use > 0
    assert most <= sched.window.bound
    victim = next(s for s in sched.slot_seq if s is not None)
    sched.preempt(victim, 0.0)
    eng.run()
    assert {eng.result(r).finish_reason for r in rids} == {"length",
                                                           "preempted"}
    assert sched.blocks_by_kind()["blocks_full"] == 0
    assert sched.blocks_by_kind()["blocks_window"] == 0
    assert sched.window.reserved == 0
    assert sched.pool.available == SERVE["num_blocks"] - 1
    assert sched.window.pool.available == sched.window.pool.num_blocks - 1
    stats = eng.stats()
    assert stats["window_blocks_freed"] == sched.window.freed > 0
    eng.close()


@pytest.mark.parametrize("change,names", [
    (dict(prefix_cache=True), "prefix sharing across window layers"),
])
def test_serving_refuses_prefix_sharing_with_window_layers(whole, change,
                                                           names):
    _, _, params, mc = whole
    with pytest.raises(NotImplementedError, match=names):
        engine(mc, params, "xla", **change)


@pytest.mark.parametrize("override,names", [
    (dict(layer_pattern=("sliding",) + ("global",) * 8),
     "two kinds of latent layer"),
    (dict(window=(10, 3)), "two kinds of latent layer"),
    (dict(attn_gate="elementwise"), "attn_gate"),
    (dict(swa_kv_lora_rank=0), "layer_pattern"),
    (dict(swa_kv_lora_rank=0, layer_pattern=None, window=(-1, -1)),
     "headwise gate"),
])
def test_serving_still_refuses_what_stays_unsupported(whole, override,
                                                      names):
    """Each refusal names what it refuses: a sliding dense layer, a
    two-sided window, another gate, windows outside this family (a
    pattern on grouped-query or one-kind latent pools), the family's
    extras without its second kind."""
    _, _, _, mc = whole
    with pytest.raises(NotImplementedError, match=names):
        _check_supported(dataclasses.replace(mc, **override))
    from torchacc_tpu.models import get_preset
    with pytest.raises(NotImplementedError, match="sliding window"):
        _check_supported(get_preset("llama-tiny", window=(16, -1)))
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        _check_supported(get_preset("llama-tiny",
                                    layer_pattern=("sliding", "global")))


def test_the_module_forward_and_training_are_typed_refusals(whole):
    """The family runs through ServeEngine alone: the module's layer
    loop refuses a pattern beside leading dense layers, the trainer any
    latent family."""
    from torchacc_tpu.config import ConfigError
    from torchacc_tpu.train.trainer import Trainer
    _, _, params, mc = whole
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        TransformerLM(mc).apply({"params": params},
                                jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ConfigError, match="not supported"):
        Trainer(TransformerLM(mc), ta.Config())
