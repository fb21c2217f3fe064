"""The grouped-query family of two layer kinds ('exaone_moe': sliding
layers with rope and a left window, global layers with no rotary
embedding at all, per-head qk-norm, norms on the sublayers' outputs, a
dense sliding layer in front of held experts; models/transformer.kind_cfg,
ops/paged_attention.py's window walk, the four pools of
serve/kv_cache.py) at a toy preset on the CPU, against the benchmark's
plain float32 reference (chipbench/reference/gqa_window_moe_decoder.py:
the layer equations of ISSUE 33, nothing imported from the program).

Toy preset: hidden 64, 4 query / 2 key-value heads of 16, a window of 11
positions (the query's own among them), 16 experts top-4 with a selection
bias and a shared expert; the published pattern cut to 8 layers (the
dense sliding layer, then s s g s s s g).  Everything runs in float32 at
``highest``, so the tolerances below are float32 summation-order noise on
values of order 0.1-1 (2e-5 on logits, as tests/test_mla_moe.py); a
wrong mask, window edge, rope on the wrong kind, norm placement or pool
index shows at 1e-2 and above.
"""

import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchacc_tpu as ta
from chipbench.layouts import gqa_window_moe_decoder as layout
from chipbench.reference import gqa_window_moe_decoder as ref
from chipbench.weights import gqa_window_moe_decoder as weights
from torchacc_tpu.config import ConfigError
from torchacc_tpu.models import TransformerLM, generate, moe
from torchacc_tpu.models.hf import config_from_hf
from torchacc_tpu.models.transformer import kind_cfg, pattern_period
from torchacc_tpu.ops.paged_attention import (
    paged_attention,
    window_walk_blocks,
)
from torchacc_tpu.serve import Request, ServeEngine
from torchacc_tpu.serve.kv_cache import make_pools, window_blocks_bound
from torchacc_tpu.serve.scheduler import PagedDecoder, _check_supported

PATTERN = (["sliding_attention"] * 3 + ["full_attention"]) * 12
TOY = dict(
    model_type="exaone_moe", hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    vocab_size=256, first_k_dense_replace=1, hidden_act="silu",
    layer_types=PATTERN, mlp_layer_types=["dense"] + ["sparse"] * 47,
    max_position_embeddings=4096, moe_intermediate_size=32, n_group=1,
    topk_group=1, norm_topk_prob=True, num_experts=16,
    num_experts_per_tok=4, num_hidden_layers=48, num_shared_experts=1,
    num_nextn_predict_layers=1, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 10000, "rope_type": "default"},
    routed_scaling_factor=2.5, scoring_func="sigmoid", sliding_window=11,
    tie_word_embeddings=False)
DEPTH = 8
F32 = dict(param_dtype=jnp.float32, dtype=jnp.float32)
SERVE = dict(block_size=8, num_blocks=64, max_slots=3, prefill_chunk=12)


def model_config(published, depth=DEPTH, **kw):
    return config_from_hf(types.SimpleNamespace(**published),
                          num_layers=depth, max_seq_len=256, **F32, **kw)


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
    w = weights.make(weights.base_key(2**31 + 33), TOY, DEPTH, jnp.float32)
    mc = model_config(TOY)
    return TOY, w, layout.to_program_params(w, mc), mc


_REF = {}


def ref_logits(pub, w, ids, positions, control="float32"):
    """The reference's logits of the row ``ids`` at ``positions``: one
    compiled program a configuration (the row padded to 96 ids, the
    positions to 16 by repeating the last; causal, so the padding
    changes nothing before it)."""
    key = (pub["num_experts"], pub.get("first_held_expert"), control)
    if key not in _REF:
        sizes, dot = ref.sizes_of(pub), ref.lower_precision_dot(control)
        _REF[key] = jax.jit(lambda w, ids, pos: ref.logits_at(
            w, sizes, ids, pos, dot))
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
    the pattern, which kind ropes, the window, the block, the share."""
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")]
    (row,) = [r for r in rows if r["name"] == "K-EXAONE-236B-A23B"]
    mc = config_from_hf(types.SimpleNamespace(**row["config"]))
    assert (mc.num_layers, mc.hidden_size, mc.vocab_size, mc.num_heads,
            mc.kv_heads, mc.head_size) == (48, 6144, 153600, 64, 8, 128)
    assert mc.layer_pattern.count("global") == 12
    assert mc.layer_pattern[:5] == ("sliding", "sliding", "sliding",
                                    "global", "sliding")
    assert mc.window == (127, -1)            # 128 counts the token itself
    assert mc.rope_kinds == ("sliding",) and mc.rope_theta == 1e6
    full, win = kind_cfg(mc, "global"), kind_cfg(mc, "sliding")
    assert (full.pos_emb, full.window) == ("none", (-1, -1))
    assert (win.pos_emb, win.window) == ("rope", (127, -1))
    assert (mc.norm_placement, mc.qk_norm, mc.qk_norm_proj) == (
        "post", True, False)
    assert (mc.num_experts, mc.router_width, mc.num_experts_per_tok,
            mc.moe_router_bias, mc.moe_n_group, mc.moe_shared_experts,
            mc.first_dense_layers, mc.expert_ffn_size, mc.ffn_size,
            mc.moe_route_scale, mc.moe_scoring, mc.moe_dispatch) == (
        128, 128, 8, True, 1, 1, 1, 2048, 18432, 2.5, "sigmoid", "grouped")
    assert pattern_period(mc)[0] == ["sliding"]
    _check_supported(mc)
    # this issue's arithmetic: 236B with every expert held
    assert mc.num_params() == pytest.approx(236.6e9, rel=2e-3)
    # the chip's share at the cell's depth: 5,517M
    cut = config_from_hf(types.SimpleNamespace(**dict(
        row["config"], num_experts=8, router_n_experts=128,
        first_held_expert=64)), num_layers=8)
    assert (cut.num_experts, cut.router_width, cut.moe_first_expert) == (
        8, 128, 64)
    assert cut.num_params() == pytest.approx(5517e6, rel=1e-3)
    assert cut.layer_pattern.count("global") == 2


def test_param_tree_is_a_stack_a_period_position_and_the_count_is_exact(
        whole):
    pub, w, params, mc = whole
    dense, period = pattern_period(mc)
    assert dense == ["sliding"] and period == [
        "sliding", "sliding", "global", "sliding", "sliding", "sliding",
        "global"]
    assert set(params["layers"]) == {f"p{i}" for i in range(7)}
    assert params["layers"]["p2"]["block"]["attn"]["q_proj"][
        "kernel"].shape == (1, 64, 4, 16)
    assert params["layers"]["p0"]["block"]["attn"]["k_norm"][
        "scale"].shape == (1, 16)
    assert params["dense_layers"]["block"]["mlp"]["gate_proj"][
        "kernel"].shape == (1, 64, 128)
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == mc.num_params() == weights.param_count(pub, DEPTH)
    assert set(layout.canonical_names(mc)) == {
        "/".join(str(getattr(k, "key", k)) for k in path) for path, _ in
        jax.tree_util.tree_flatten_with_path(params)[0]}
    # two whole periods of the published pattern: pools of two geometries
    cfg = ta.Config()
    for key, value in SERVE.items():
        setattr(cfg.serve, key, value)
    pools = make_pools(mc, cfg.serve)
    kg, vg, kw_, vw = (pools[name] for name in ("k", "v", "k_win", "v_win"))
    assert kg.shape == vg.shape == (2, 64, 8, 32)
    # 3 slots x (ceil((11 + 12) / 8) + 1) blocks and the null block
    assert kw_.shape == vw.shape == (6, 13, 8, 32)


def _prefill_logits(eng, prompt):
    """The final prefill chunk's logits for ``prompt`` through the
    scheduler's own chunk loop (slot 0)."""
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
    prefill in chunks of 12 over blocks of 8 through the four pools, at
    prompt lengths on both sides of the window (11), on a chunk's edge
    (12, 24: the next chunk's first query reaches back over it) and
    several windows long (the first window blocks are freed by then),
    against the reference's full forward."""
    pub, w, params, mc = whole
    eng = engine(mc, params, impl)
    rng = np.random.default_rng(3)
    # (the kernels run in interpret mode off the chip: fewer lengths)
    for n in (7, 11, 12, 13, 24, 30, 61) if impl == "xla" else (13, 45):
        prompt = rng.integers(1, 256, size=n)
        got = _prefill_logits(eng, prompt)
        want = ref_logits(pub, w, prompt, [n - 1])[0]
        np.testing.assert_allclose(got, want, atol=2e-5, err_msg=str(n))
    assert eng.scheduler.window.freed > 0
    assert eng.scheduler.blocks_by_kind() == {
        "blocks_full": 0, "blocks_window": 0,
        "window_blocks_freed": eng.scheduler.window.freed}
    eng.close()


def _served_gap(pub, w, requests, results, control="float32"):
    worst = 0.0
    for prompt, tokens in zip(requests, results):
        ids = prompt + tokens[:-1]
        z = ref_logits(pub, w, ids, np.arange(len(prompt) - 1, len(ids)),
                       control)
        picked = z[jnp.arange(len(tokens)), jnp.asarray(tokens)]
        worst = max(worst, float(jnp.max(jnp.max(z, axis=-1) - picked)))
    return worst


@pytest.fixture(scope="module")
def served(whole):
    """Four requests over three slots through the engine (xla): prompts
    and the tokens served."""
    pub, w, params, mc = whole
    eng = engine(mc, params, "xla")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (5, 40, 17, 9)]
    rids = [eng.submit(Request(prompt_ids=p, max_new_tokens=8))
            for p in prompts]
    eng.run()
    results = [eng.result(r).tokens for r in rids]
    left = (eng.scheduler.blocks_by_kind(), eng.scheduler.window.reserved)
    eng.close()
    return prompts, results, left


@pytest.mark.parametrize("impl,first", [
    ("xla", None), ("xla", 4), ("pallas", 4)],
    ids=["xla-whole", "xla-share", "pallas-share"])
def test_serving_through_both_pools_matches_the_reference(
        whole, served, impl, first):
    """Chunked prefill then decode, four requests over three slots (slots
    reused, chunks between decode steps), contexts that start inside the
    window and decode past it; every served token is the reference's best
    up to float32 noise, for the whole model and for the chip that holds
    experts [4, 8)."""
    pub, w, params, mc = whole
    if first is None:
        prompts, results, (blocks, reserved) = served
    else:
        pub = dict(pub, num_experts=4, router_n_experts=16,
                   first_held_expert=first)
        w = {k: ({n: (leaf[:, first:first + 4] if n in
                      ("e_gate", "e_up", "e_down") else leaf)
                  for n, leaf in v.items()} if isinstance(v, dict) else v)
             for k, v in w.items()}
        mc = model_config(pub)
        params = layout.to_program_params(w, mc)
        eng = engine(mc, params, impl)
        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, 256, size=n).tolist()
                   for n in (5, 40, 17, 9)]
        rids = [eng.submit(Request(prompt_ids=p, max_new_tokens=8))
                for p in prompts]
        eng.run()
        results = [eng.result(r).tokens for r in rids]
        blocks, reserved = (eng.scheduler.blocks_by_kind(),
                            eng.scheduler.window.reserved)
        eng.close()
    assert all(len(t) == 8 for t in results)
    assert _served_gap(pub, w, prompts, results) < 1e-5
    assert blocks["blocks_window"] == 0 and reserved == 0


@pytest.mark.parametrize("control", ref.WRONG)
def test_the_two_errors_the_family_invites_show_in_the_logits(
        whole, served, control):
    """A forward that drops the window, and one that ropes the global
    layers, each in the reference's place: the served tokens lie far
    below ITS best (the program computes neither)."""
    pub, w, _, _ = whole
    prompts, results, _ = served
    assert _served_gap(pub, w, prompts[1:3], results[1:3], control) > 1e-2


def test_generate_runs_the_family_through_the_same_block(whole, served):
    """`models.generate` (batch-synchronous, a dense cache a layer) on
    the serving layout of the parameters: the tokens the engine served."""
    pub, w, params, mc = whole
    prompts, results, _ = served
    for prompt, tokens in list(zip(prompts, results))[1:3]:
        out = generate(TransformerLM(mc), params,
                       jnp.asarray([prompt], jnp.int32), max_new_tokens=8)
        assert np.asarray(out)[0, len(prompt):].tolist() == tokens


# -- the windowed kernel ------------------------------------------------------

def _dense_window_attention(q, k, v, ctx, q0, left, scale):
    """Masked dense attention of q [S, T, H, D] over each slot's own
    rows k, v [S, K, KH, D]: the kernel's anchor."""
    s_, t_, h, d = q.shape
    group = h // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    scores = jnp.einsum("sthd,skhd->shtk", q, k) * scale
    pos = jnp.arange(k.shape[1])
    q_pos = q0[:, None] + jnp.arange(t_)
    mask = ((pos[None, None] < ctx[:, None, None])
            & (pos[None, None] <= q_pos[..., None])
            & (pos[None, None] >= q_pos[..., None] - left))[:, None]
    p = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    return jnp.einsum("shtk,skhd->sthd", jnp.where(mask, p, 0.0), v)


@pytest.mark.parametrize("t", [1, 8, 24], ids=["decode", "chunk", "chunk3"])
@pytest.mark.parametrize("left", [4, 7, 8, 20],
                         ids=["lt_block", "block_less_1", "eq_block",
                              "gt_block"])
def test_windowed_kernel_matches_masked_dense_and_never_reads_freed_blocks(
        t, left):
    """The Pallas kernel (interpret mode) under a left window against a
    masked dense attention, decode and chunks, the window smaller than,
    equal to and larger than a block of 8; slots of different lengths,
    one of them empty.  The table's entries before the first query's
    window are 0 — freed, as `WindowBlocks` leaves them — and every pool
    block no live entry names is POISONED with NaN: an output without
    NaN read none of them."""
    s_, h, kh, d, bs, mb = 3, 4, 2, 16, 8, 12
    ks = jax.random.split(jax.random.PRNGKey(t * 31 + left), 4)
    ctx = jnp.asarray([83, max(t, 9), 0])
    q0 = jnp.maximum(ctx - t, 0)
    q = jax.random.normal(ks[0], (s_, t, h, d))
    k = jax.random.normal(ks[1], (s_, mb * bs, kh, d))
    v = jax.random.normal(ks[2], (s_, mb * bs, kh, d))
    tables = np.random.default_rng(0).permutation(
        np.arange(1, 1 + s_ * mb)).reshape(s_, mb).astype(np.int32)
    dead = np.maximum(np.asarray(q0) - left, 0) // bs
    assert dead[0] >= 4
    kp = jnp.full((2, 1 + s_ * mb, bs, kh * d), jnp.nan)
    vp = jnp.full((2, 1 + s_ * mb, bs, kh * d), jnp.nan)
    live = np.arange(mb)[None] >= dead[:, None]
    live &= np.arange(mb)[None] * bs < np.asarray(ctx)[:, None]
    for s, j in zip(*np.nonzero(live)):
        kp = kp.at[1, tables[s, j]].set(
            k[s, j * bs:(j + 1) * bs].reshape(bs, -1))
        vp = vp.at[1, tables[s, j]].set(
            v[s, j * bs:(j + 1) * bs].reshape(bs, -1))
    tables = jnp.asarray(np.where(live, tables, 0))
    assert window_walk_blocks(left, t, bs) < mb
    got = paged_attention(q, kp, vp, tables, ctx, q0, layer=1, scale=0.3,
                          window=(left, -1), impl="pallas",
                          name="window_paged_attention")
    want = _dense_window_attention(q, k, v, ctx, q0, left, 0.3)
    assert not bool(jnp.isnan(got).any())
    np.testing.assert_allclose(got[:2], want[:2], atol=2e-5)
    assert float(jnp.abs(got[2]).max()) == 0.0


@pytest.mark.parametrize("left", [-1, 9], ids=["global", "sliding"])
def test_a_chunk_taller_than_a_step_runs_as_tiles_of_its_queries(
        monkeypatch, left):
    """`query_tile`: where a chunk's rows do not fit one step's VMEM the
    call runs as tiles of the chunk's queries, each a slot of its own
    with its own first position (at K-EXAONE's 8 query heads a kv head a
    chunk of 512 is two tiles of 256; here the budget is shrunk until 24
    tokens are two tiles of 12) — and gives what the gather path gives."""
    import torchacc_tpu.ops.paged_attention as paged_mod
    s_, t, h, kh, d, bs, mb = 2, 24, 4, 2, 16, 8, 12
    monkeypatch.setattr(paged_mod, "_VMEM_BUDGET", 300_000)
    assert paged_mod.query_tile(h, kh, d, bs, t, jnp.float32) == 12
    assert paged_mod.query_tile(h, kh, d, bs, 1, jnp.float32) == 1
    ks = jax.random.split(jax.random.PRNGKey(left + 2), 3)
    q = jax.random.normal(ks[0], (s_, t, h, d))
    kp = jax.random.normal(ks[1], (2, 1 + s_ * mb, bs, kh * d))
    vp = jax.random.normal(ks[2], (2, 1 + s_ * mb, bs, kh * d))
    tables = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, 1 + s_ * mb)).reshape(s_, mb), jnp.int32)
    ctx = jnp.asarray([90, 31])            # the second slot's chunk is padded
    q0 = jnp.asarray([66, 10])
    out = {impl: paged_attention(q, kp, vp, tables, ctx, q0, layer=1,
                                 scale=0.3, window=(left, -1), impl=impl)
           for impl in ("xla", "pallas")}
    np.testing.assert_allclose(out["pallas"], out["xla"], atol=2e-5)
    with pytest.raises(ValueError, match="block_size 4"):
        paged_mod.query_tile(h, kh, d, 4, t, jnp.float32)


def test_blocks_before_the_window_are_freed_and_what_is_freed_is_not_read(
        whole):
    """Through the scheduler: a long prompt's window blocks go back as
    the window passes; poisoning every block of the sliding layers'
    pools that no live table entry names (freed ones among them) leaves
    the decode steps after it finite and the reference's."""
    pub, w, params, mc = whole
    eng = engine(mc, params, "pallas")
    sched = eng.scheduler
    prompt = np.random.default_rng(9).integers(1, 256, size=50).tolist()
    rid = eng.submit(Request(prompt_ids=prompt, max_new_tokens=6))
    while not sched.active.any():
        eng.step()
    assert sched.window.freed >= 3 and sched.window.bound == 4
    (seq,) = [s for s in sched.slot_seq if s is not None]
    assert len(seq.win_blocks) <= sched.window.bound
    held = sorted(seq.win_blocks.values())
    dead = [b for b in range(sched.window.pool.num_blocks) if b not in held]
    sched.pools = {**sched.pools, **{
        name: sched.pools[name].at[:, jnp.asarray(dead)].set(jnp.nan)
        for name in ("k_win", "v_win")}}
    eng.run()
    tokens = eng.result(rid).tokens
    assert len(tokens) == 6
    assert _served_gap(pub, w, [prompt], [tokens]) < 1e-5
    eng.close()


def test_the_sixteen_shares_add_up_to_the_uncut_layer(whole):
    """Sixteen chips holding one expert each: their routed parts, plus
    the shared expert counted once, are the uncut reference layer with
    its selection bias.  1e-5: float32 sums of 4 terms."""
    pub, w, params, mc = whole
    sizes = ref.sizes_of(pub)
    x = jax.random.normal(jax.random.PRNGKey(7), (32, 64)) * 0.5
    real = jnp.ones((32,), bool)
    want, _ = ref.expert_layer(x, w["p1"], 0, sizes, ref._f32_dot, real)
    shared = ref.swiglu(x, w["p1"]["s_gate"][0], w["p1"]["s_up"][0],
                        w["p1"]["s_down"][0], ref._f32_dot)
    tree = jax.tree.map(lambda a: a[0],
                        params["layers"]["p1"]["block"]["moe"])
    total, pairs = jnp.zeros_like(x), 0
    for first in range(16):
        cut = dict(pub, num_experts=1, router_n_experts=16,
                   first_held_expert=first)
        cut_tree = dict(tree, **{k: tree[k][first:first + 1] for k in
                                 ("experts/gate", "experts/up",
                                  "experts/down")})
        y, _, _, load = moe.moe_ffn(model_config(cut), cut_tree, x)
        lw = dict(w["p1"], **{k: w["p1"][k][:, first:first + 1]
                              for k in ("e_gate", "e_up", "e_down")})
        np.testing.assert_allclose(y, ref.expert_layer(
            x, lw, 0, ref.sizes_of(cut), ref._f32_dot, real)[0], atol=1e-5)
        total, pairs = total + y, pairs + int(load[0])
    assert pairs == 32 * 4
    np.testing.assert_allclose(total - 15 * shared, want, atol=1e-5)


def test_the_cells_window_blocks_bound_and_walk():
    """The cell's own geometry: a window of 128 over blocks of 128 is 6
    blocks a sequence a sliding layer (chunks of 512) where a full table
    holds 133, and the kernel walks 2 blocks a decode step, 6 a chunk."""
    assert window_blocks_bound(127, 512, 128) == 6
    assert window_walk_blocks(127, 1, 128) == 2
    assert window_walk_blocks(127, 512, 128) == 6


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
    eng.close()


# -- what stays unsupported, each by a typed error ----------------------------

@pytest.mark.parametrize("change,match", [
    (dict(window=(10, 4)), "two-sided window"),
    (dict(first_dense_layers=0), "layer_pattern on grouped-query pools"),
    (dict(num_experts=0, first_dense_layers=0),
     "layer_pattern on grouped-query pools"),
    (dict(layer_pattern=None), "without a layer_pattern"),
    (dict(layer_pattern=None, window=(-1, -1)),
     "rope_kinds without a layer_pattern"),
    (dict(layer_pattern=("global",) + ("sliding",) * 7,
          first_dense_layers=2),
     "layer_pattern on grouped-query pools"),
    (dict(pos_emb="alibi"), "alibi"),
], ids=["two_sided", "no_dense", "no_experts", "window_alone",
        "rope_kinds_alone", "dense_of_two_kinds", "alibi"])
def test_check_supported_refuses_what_stays_unsupported(whole, change, match):
    mc = dataclasses.replace(whole[3], **change)
    with pytest.raises(NotImplementedError, match=match):
        _check_supported(mc)


def test_prefix_cache_with_window_layers_is_refused(whole):
    pub, w, params, mc = whole
    with pytest.raises(NotImplementedError, match="prefix"):
        engine(mc, params, "xla", prefix_cache=True)


def test_trainer_and_the_modules_forward_refuse_the_family(whole):
    """Training is out of reach (66 GB at the floors): the module's
    plain forward refuses a pattern beside dense layers."""
    pub, w, params, mc = whole
    from torchacc_tpu.train.trainer import Trainer
    with pytest.raises(NotImplementedError, match="first_dense_layers"):
        TransformerLM(mc).apply({"params": params},
                                jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ConfigError, match="not supported"):
        Trainer(TransformerLM(mc), ta.Config())


def test_a_block_size_the_kernel_cannot_tile_is_a_config_error(whole):
    pub, w, params, mc = whole
    cfg = ta.Config()
    for key, value in dict(SERVE, block_size=4).items():
        setattr(cfg.serve, key, value)
    with pytest.raises(ConfigError, match="block_size"):
        PagedDecoder(mc, cfg.serve, "pallas")


# -- nothing new for a model without a window ---------------------------------

def _programs(eng):
    d = eng.scheduler.decoder
    return {k: getattr(d, k)._cache_size() for k in (
        "_decode", "_prefill", "_prefill_batch", "_sample_first",
        "_set_slot", "_cow")}


@pytest.mark.parametrize("family", ["dense", "latent", "window_gqa"])
def test_each_family_compiles_the_programs_it_compiled_before(whole, family):
    """`setup_s` is judged in every cell: serving three requests compiles
    one decode program, two prefill programs (a prompt's last chunk and
    the others), the first token's sampling and the slot splice — as
    before this family came (the parent's counts, PR 33), and for this
    family too."""
    from torchacc_tpu.models import get_preset
    tiny = dict(dtype=jnp.float32, hidden_size=64, num_heads=4,
                intermediate_size=128, vocab_size=257, max_seq_len=128)
    if family == "window_gqa":
        _, _, params, mc = whole
    else:
        mc = (get_preset("llama-tiny", num_layers=2, num_kv_heads=2, **tiny)
              if family == "dense" else get_preset(
            "llama-tiny", num_layers=3, num_kv_heads=4,
            rope_interleaved=True, kv_lora_rank=32, q_lora_rank=48,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            first_dense_layers=1, num_experts=4, num_experts_per_tok=2,
            moe_intermediate_size=32, moe_scoring="sigmoid",
            moe_shared_experts=1, moe_router_width=8, moe_first_expert=2,
            moe_dispatch="grouped", **tiny))
        params = TransformerLM(mc).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = engine(mc, params, "xla", max_slots=2, prefill_chunk=8)
    rng = np.random.default_rng(0)
    eng.generate([Request(prompt_ids=rng.integers(1, 200, size=n).tolist(),
                          max_new_tokens=4) for n in (5, 19, 9)])
    assert _programs(eng) == {
        "_decode": 1, "_prefill": 2, "_prefill_batch": 0,
        "_sample_first": 1, "_set_slot": 1, "_cow": 0}
    eng.close()


def test_importing_the_package_loads_what_it_loaded_before():
    """No module of this family's is loaded by `import torchacc_tpu` or
    `torchacc_tpu.serve` that was not loaded before it came: its code
    lives in modules the other families load."""
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, torchacc_tpu, torchacc_tpu.serve; "
         "print(sorted(m for m in sys.modules if m.startswith("
         "('torchacc_tpu', 'chipbench'))))"],
        capture_output=True, text=True, check=True).stdout
    loaded = eval(out.strip().splitlines()[-1])
    assert not [m for m in loaded if m.startswith("chipbench")]
    assert "torchacc_tpu.models.mla" not in loaded
    assert "torchacc_tpu.models.moe" not in loaded
