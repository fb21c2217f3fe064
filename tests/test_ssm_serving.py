"""The family of single-mixer layers ('nemotron_h': Mamba-2 state-space
layers on a per-slot recurrent state, grouped-query layers without
rotary embedding on the paged pool, routed relu2 experts with a shared
expert, one pre-norm a layer, in a published order that has no period;
models/mamba2.py, ops/ssm_scan.py, models/block.mixer_block, the state
pools of serve/kinds.py's 'state_space' record, the layer plan's static
walk) at a toy preset on the CPU, against the benchmark's plain float32
reference
(chipbench/reference/ssm_attn_moe_decoder.py: the layer equations of
ISSUE 42 with the recurrence ONE TOKEN AT A TIME, nothing imported from
the program).

Toy preset: hidden 64, 8 state-space heads of 8 channels in 2 groups, a
state of 16, a convolution of 4, sub-chunks of 8; 4 query / 2 key-value
heads of 16; 16 experts top-4 of width 32 with a selection bias and a
shared expert of width 48; the pattern ``MEM*EMEM*E`` (10 layers).
Everything runs in float32 at ``highest``, so the tolerances below are
float32 summation-order noise on values of order 0.1-1 (2e-5 on logits,
as tests/test_window_gqa_serving.py): the chunked scan sums what the
recurrence sums in another order.  A state carried wrongly across a
chunk, a padded row that moves it, a dropped convolution tap or a wrong
group shows at 1e-2 and above.
"""

import dataclasses
import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchacc_tpu as ta
from chipbench.layouts import ssm_attn_moe_decoder as layout
from chipbench.reference import ssm_attn_moe_decoder as ref
from chipbench.weights import ssm_attn_moe_decoder as weights
from torchacc_tpu.config import ConfigError
from torchacc_tpu.models import TransformerLM, generate, mamba2, moe
from torchacc_tpu.models.hf import config_from_hf
from torchacc_tpu.models.transformer import layer_kinds, layer_tree
from torchacc_tpu.ops.ssm_scan import ssm_chunk_scan, ssm_step
from torchacc_tpu.serve import Request, ServeEngine
from torchacc_tpu.serve.kv_cache import make_pools
from torchacc_tpu.serve.scheduler import (
    PagedDecoder,
    Sequence,
    _check_supported,
)

TOY = dict(
    model_type="nemotron_h", hidden_size=64, intermediate_size=32,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    vocab_size=256, hybrid_override_pattern="MEM*EMEM*E",
    num_hidden_layers=10, mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
    ssm_state_size=16, conv_kernel=4, chunk_size=8, expand=2,
    layer_norm_epsilon=1e-5, norm_eps=1e-5, mamba_hidden_act="silu",
    mlp_hidden_act="relu2", mamba_proj_bias=False, use_conv_bias=True,
    use_bias=False, attention_bias=False, mlp_bias=False,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
    n_group=1, topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.5,
    rope_theta=10000, max_position_embeddings=4096,
    tie_word_embeddings=False, time_step_min=0.001, time_step_max=0.1,
    time_step_floor=1e-4)
DEPTH = 10
F32 = dict(param_dtype=jnp.float32, dtype=jnp.float32)
SERVE = dict(block_size=8, num_blocks=64, max_slots=3, prefill_chunk=16)


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
    w = weights.make(weights.base_key(2**31 + 5), TOY, DEPTH, jnp.float32)
    mc = model_config(TOY)
    return TOY, w, layout.to_program_params(w, mc), mc


_REF = {}


def ref_logits(pub, w, ids, positions, control="float32"):
    """The reference's logits of the row ``ids`` at ``positions``: one
    compiled program a configuration and control (the row padded to 96
    ids, the positions to 16 by repeating the last; causal, so the
    padding changes nothing before it)."""
    key = (pub["n_routed_experts"], pub.get("first_held_expert"), control)
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


# -- ingest -------------------------------------------------------------------

def test_ingest_of_the_catalogs_config_verbatim():
    """The catalog row's `config` goes through `config_from_hf` as it
    stands; the parameter count is the card's, and the benchmark's cut
    (16 of 128 experts held, whole depth) is ISSUE 42's 5.875 B."""
    (row,) = [r for r in map(json.loads, open(
        "/opt/skills/guides/model-configs/architectures.jsonl"))
        if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]
    mc = config_from_hf(types.SimpleNamespace(**row["config"]))
    kinds = layer_kinds(mc)
    assert (kinds.count("mamba"), kinds.count("moe"),
            kinds.count("attention")) == (23, 23, 6)
    assert kinds[:6] == ["mamba", "moe", "mamba", "moe", "mamba", "attention"]
    assert mc.pos_emb == "none" and mc.activation == "relu2"
    assert (mc.ssm_heads, mc.ssm_head_dim, mc.ssm_state, mc.ssm_groups,
            mc.ssm_conv, mc.ssm_chunk) == (64, 64, 128, 8, 4, 128)
    assert mamba2.d_inner(mc) == 4096 and mamba2.conv_width(mc) == 6144
    assert (mc.num_heads, mc.kv_heads, mc.head_size) == (32, 2, 128)
    assert (mc.num_experts, mc.router_width, mc.num_experts_per_tok,
            mc.expert_ffn_size, mc.shared_ffn_size) == (128, 128, 6, 1856,
                                                        3712)
    assert mc.moe_route_scale == 2.5 and mc.moe_router_bias
    assert mc.moe_dispatch == "grouped" and mc.moe_scoring == "sigmoid"
    assert abs(mc.num_params() - 31.58e9) < 0.01e9
    _check_supported(mc)
    held = config_from_hf(types.SimpleNamespace(**dict(
        row["config"], n_routed_experts=16, router_n_experts=128,
        first_held_expert=64)))
    assert (held.num_experts, held.router_width, held.moe_first_expert) \
        == (16, 128, 64)
    assert held.num_params() == 5_874_983_232
    bench = json.load(open("chipbench/configs/nemotron-3-nano-30b-a3b.json"))
    assert {k: bench["published"][k] for k in row["config"]} == dict(
        row["config"], n_routed_experts=16)
    assert weights.param_count(bench["published"], 52) == held.num_params()
    with pytest.raises(NotImplementedError, match="dense MLP"):
        config_from_hf(types.SimpleNamespace(**dict(
            row["config"], hybrid_override_pattern="M-M*")))


def test_param_tree_is_a_stack_a_kind_and_the_count_is_exact(whole):
    pub, w, params, mc = whole
    assert set(params["layers"]) == {"mamba", "moe", "attention"}
    assert params["layers"]["mamba"]["block"]["mixer"]["in_proj"][
        "kernel"].shape == (4, 64, 2 * 64 + 2 * 2 * 16 + 8)
    # the experts' width is stored at whole 128-lane tiles (zeros beyond)
    up = params["layers"]["moe"]["block"]["moe"]["experts/up"]
    assert up.shape == (4, 16, 64, 128) and not np.asarray(up[..., 32:]).any()
    assert mc.num_params() == weights.param_count(pub, DEPTH)
    tree, cfg = layer_tree(mc, params, 7)           # the last 'M' of four
    np.testing.assert_array_equal(
        tree["block"]["mixer"]["A_log"], w["mamba"]["A_log"][3])
    assert cfg is mc


# -- the mixer alone ----------------------------------------------------------

def _mixer_inputs(mc, w, b, t, seed=0):
    lw = jax.tree.map(lambda a: a[1], w["mamba"])
    p = layout.to_program_params(
        {"embed": 0, "final_norm": 0, "head": 0, "mamba": w["mamba"]}, mc)[
            "layers"]["mamba"]["block"]["mixer"]
    u = jax.random.normal(jax.random.PRNGKey(seed), (b, t, 64))
    return lw, jax.tree.map(lambda a: a[1], p), u


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("t", [5, 8, 19])
def test_whole_sequence_mixer_is_the_recurrence(whole, impl, t):
    """The chunked scan (sub-chunks of 8; lengths on and off their
    boundary, the last sub-chunk padded) against the reference's
    token-by-token recurrence, and the state it leaves."""
    pub, w, _, mc = whole
    lw, p, u = _mixer_inputs(mc, w, 2, t)
    got, (conv, ssm) = mamba2.mixer_sequence(mc, p, u, impl=impl)
    want = jnp.stack([ref.mamba_mixer(
        jnp.pad(u[i], ((0, -t % 16), (0, 0))), w["mamba"], 1,
        ref.sizes_of(pub), t, ref._f32_dot)[:t] for i in range(2)])
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert conv.shape == (2, 3 * 128) and ssm.shape == (2, 8, 8, 16)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_chunks_and_single_tokens_carry_the_state(whole, impl):
    """A row of 37 positions as chunks of 16 (the last padded from 5),
    then 6 more one token at a time: every output is the whole
    sequence's, whatever the padded rows held."""
    pub, w, _, mc = whole
    lw, p, u = _mixer_inputs(mc, w, 2, 43, seed=1)
    want, _ = mamba2.mixer_sequence(mc, p, u)
    state, got = None, []
    for t0 in range(0, 37, 16):
        n = min(16, 37 - t0)
        chunk = jnp.pad(u[:, t0:t0 + n], ((0, 0), (0, 16 - n), (0, 0)),
                        constant_values=7.0)       # padding is not zeros
        out, state = mamba2.mixer_sequence(
            mc, p, chunk, jnp.full((2,), n), state, impl)
        got.append(out[:, :n])
    conv, ssm = (s[None] for s in state)
    for t in range(37, 43):
        out, conv, ssm = mamba2.mixer_step(
            mc, p, u[:, t:t + 1], conv, ssm, 0, jnp.array([True, True]),
            impl)
        got.append(out)
    np.testing.assert_allclose(jnp.concatenate(got, axis=1), want,
                               atol=2e-5)
    # a slot that is not decoding keeps its state
    _, conv2, ssm2 = mamba2.mixer_step(
        mc, p, u[:, :1], conv, ssm, 0, jnp.array([True, False]))
    np.testing.assert_array_equal(ssm2[0, 1], ssm[0, 1])
    np.testing.assert_array_equal(conv2[0, 1], conv[0, 1])
    assert np.abs(np.asarray(ssm2[0, 0] - ssm[0, 0])).max() > 1e-4


def test_scan_kernel_writes_one_slot_of_one_layer_and_starts_fresh_on_demand():
    """ops/ssm_scan on a poisoned pool: a fresh row ignores what its
    slot held (NaN), another continues from its slot, no other slot or
    layer is touched; both implementations agree."""
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    r, t, hm, p, g, n = 2, 32, 4, 8, 2, 16
    x = jax.random.normal(k[0], (r, t, hm, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (r, t, hm)))
    dt = jnp.where(jnp.arange(t)[None, :, None] < jnp.array(
        [27, 32])[:, None, None], dt, 0.0)
    a = -jnp.exp(jax.random.normal(k[2], (hm,))) * dt
    b, c = (jax.random.normal(kk, (r, t, g, n)) for kk in k[3:5])
    pool = jax.random.normal(k[5], (3, 5, hm, p, n)).at[1, 1].set(jnp.nan)
    slots, fresh = jnp.array([3, 1]), jnp.array([0, 1])
    out = {impl: ssm_chunk_scan(x * dt[..., None], a, b, c, pool, 1, slots,
                                fresh, chunk=8, impl=impl)
           for impl in ("xla", "pallas")}
    for y, new in out.values():
        assert np.isfinite(np.asarray(y)).all()
        assert np.isfinite(np.asarray(new[1, 1])).all()
        untouched = np.ones((3, 5), bool)
        untouched[1, [1, 3]] = False
        np.testing.assert_array_equal(np.asarray(new)[untouched],
                                      np.asarray(pool)[untouched])
    np.testing.assert_allclose(out["xla"][0], out["pallas"][0], atol=1e-5)
    np.testing.assert_allclose(out["xla"][1][1], out["pallas"][1][1],
                               atol=1e-5)
    # one token of two slots is the chunk's first position
    y1, _ = ssm_step(x[:, 0], dt[:, 0], a[:, 0], b[:, 0], c[:, 0],
                     jnp.zeros_like(pool), 2)
    y0, _ = ssm_chunk_scan(x * dt[..., None], a, b, c, jnp.zeros_like(pool),
                           2, jnp.array([0, 1]), jnp.array([1, 1]), chunk=8)
    np.testing.assert_allclose(y1, y0[:, 0], atol=1e-6)
    # the step's kernel and its XLA twin: the same output, the same
    # state, only the stepped slots of the one layer touched
    step = {impl: ssm_step(x[:, 0], dt[:, 0], a[:, 0], b[:, 0], c[:, 0],
                           pool.at[1, 1].set(0.5), 2, impl=impl)
            for impl in ("xla", "pallas")}
    np.testing.assert_allclose(step["xla"][0], step["pallas"][0], atol=1e-5)
    np.testing.assert_allclose(step["xla"][1], step["pallas"][1], atol=1e-6)
    np.testing.assert_array_equal(step["pallas"][1][2, 2:], pool[2, 2:])
    np.testing.assert_array_equal(step["pallas"][1][0], pool[0])
    with pytest.raises(ValueError, match="whole sub-chunks"):
        ssm_chunk_scan(x * dt[..., None], a, b, c, pool, 1, slots, fresh,
                       chunk=12)


# -- through the engine -------------------------------------------------------

def _prefill_logits(eng, prompt, slot_state=None):
    """The final prefill chunk's logits for ``prompt`` through the
    scheduler's own chunk loop (slot 0)."""
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
    prefill in chunks of 16 (two sub-chunks of the scan) over blocks of
    8, the state carried from chunk to chunk in slot 0, at prompt
    lengths on a chunk's edge (16, 32), one past it and several chunks
    long — against the reference's full forward.  Slot 0's state is
    POISONED before every prompt: a request's first chunk starts from
    zeros, whatever the slot's last tenant left."""
    pub, w, params, mc = whole
    eng = engine(mc, params, impl)
    sched = eng.scheduler
    rng = np.random.default_rng(3)
    for n in (7, 16, 17, 45) if impl == "xla" else (17, 45):
        sched.pools = {**sched.pools, **{
            name: sched.pools[name].at[:, 0].set(jnp.nan)
            for name in ("conv", "ssm")}}
        prompt = rng.integers(1, 256, size=n)
        got = _prefill_logits(eng, prompt)
        want = ref_logits(pub, w, prompt, [n - 1])[0]
        np.testing.assert_allclose(got, want, atol=2e-5, err_msg=str(n))
    assert sched.pool.in_use == 0
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
    """Five requests over three slots through the engine (xla): prompts
    and the tokens served — slots are reused, prefill chunks interleave
    with decode steps."""
    pub, w, params, mc = whole
    eng = engine(mc, params, "xla")
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 256, size=n).tolist()
               for n in (5, 40, 17, 9, 33)]
    rids = [eng.submit(Request(prompt_ids=p, max_new_tokens=8))
            for p in prompts]
    eng.run()
    results = [eng.result(r).tokens for r in rids]
    left = eng.scheduler.pool.in_use
    eng.close()
    return prompts, results, left


@pytest.mark.parametrize("impl,first", [
    ("xla", None), ("xla", 4), ("pallas", 4)],
    ids=["xla-whole", "xla-share", "pallas-share"])
def test_serving_through_the_slot_state_and_the_pool_matches_the_reference(
        whole, served, impl, first):
    """Prefill in chunks, then decode through the slot state and the
    paged pool, token by token: every served token is the reference's
    best (gap 0 at float32) — with every expert held, and as a share of
    8 of the 16 (experts [4, 12): the absent experts' terms left out on
    both sides)."""
    pub, w, params, mc = whole
    if first is None:
        prompts, results, left = served
        assert left == 0
    else:
        pub = dict(pub, n_routed_experts=8, router_n_experts=16,
                   first_held_expert=first)
        w = dict(w, moe={k: (v[:, first:first + 8] if k in ("e_up", "e_down")
                             else v) for k, v in w["moe"].items()})
        mc = model_config(pub)
        eng = engine(mc, layout.to_program_params(w, mc), impl)
        rng = np.random.default_rng(1)
        prompts = [rng.integers(1, 256, size=n).tolist() for n in (21, 9)]
        rids = [eng.submit(Request(prompt_ids=p, max_new_tokens=6))
                for p in prompts]
        eng.run()
        results = [eng.result(r).tokens for r in rids]
        eng.close()
    assert _served_gap(pub, w, prompts, results) < 2e-5


@pytest.mark.parametrize("control", ref.WRONG)
def test_the_errors_the_family_invites_show_in_the_logits(whole, served,
                                                         control):
    """A convolution that sees the current input alone, a gate without
    its grouped norm: in the reference's place each puts other tokens
    first (the served tokens are the float32 reference's)."""
    pub, w, _, _ = whole
    prompts, results, _ = served
    assert _served_gap(pub, w, prompts, results, control) > 0.05


def test_generate_serves_the_same_tokens(whole, served):
    pub, w, params, mc = whole
    prompts, results, _ = served
    for i in (1, 4):
        out = generate(TransformerLM(mc), params, jnp.asarray([prompts[i]]),
                       max_new_tokens=8)
        assert out[0, len(prompts[i]):].tolist() == results[i]
    with pytest.raises(NotImplementedError, match="mixer_pattern"):
        generate(TransformerLM(mc), params, jnp.asarray([prompts[0]]),
                 max_new_tokens=2, use_cache=False)


def test_preemption_returns_every_block_and_the_state_restarts(whole):
    """A preempted request's blocks all return; the slot it held serves
    the next request from a fresh state (its tokens are the ones a
    clean engine serves), and the pool's null slot stays zero."""
    pub, w, params, mc = whole
    eng = engine(mc, params, "xla")
    sched = eng.scheduler
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (50, 20, 33)]
    rids = [eng.submit(Request(prompt_ids=p, max_new_tokens=30))
            for p in prompts]
    for _ in range(12):
        eng.step()
    assert sched.pool.in_use > 0
    assert float(jnp.abs(sched.pools["ssm"][:, :3]).max()) > 0
    victim = next(s for s in sched.slot_seq if s is not None)
    sched.preempt(victim, 0.0)
    late = eng.submit(Request(prompt_ids=prompts[1], max_new_tokens=6))
    eng.run()
    assert {eng.result(r).finish_reason for r in rids} == {"length",
                                                           "preempted"}
    assert sched.pool.in_use == 0
    assert sched.pool.available == SERVE["num_blocks"] - 1
    assert not np.asarray(sched.pools["ssm"][:, 3]).any()   # the null slot
    assert _served_gap(pub, w, [prompts[1]],
                       [eng.result(late).tokens]) < 2e-5
    eng.close()


def test_batched_prefill_pads_rows_onto_the_null_slot(whole):
    """prefill_batch 2 with three prompts in flight: the padded row of
    an odd batch runs on the null slot and no request's tokens move."""
    pub, w, params, mc = whole
    eng = engine(mc, params, "xla", prefill_batch=2)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (37, 18, 21)]
    rids = [eng.submit(Request(prompt_ids=p, max_new_tokens=5))
            for p in prompts]
    eng.run()
    assert eng.scheduler.decoder._prefill_batch._cache_size() == 1
    assert _served_gap(pub, w, prompts,
                       [eng.result(r).tokens for r in rids]) < 2e-5
    eng.close()


# -- the expert layer ---------------------------------------------------------

def _moe_layer(whole, at=2):
    pub, w, params, mc = whole
    lw = jax.tree.map(lambda a: a[at], w["moe"])
    tree = jax.tree.map(lambda a: a[at],
                        params["layers"]["moe"]["block"]["moe"])
    return lw, tree


def test_relu2_held_experts_are_the_dense_sum(whole):
    """The grouped path (two grouped matmuls an expert, the kernels at
    their stored width) against the dense sum over every expert."""
    pub, w, params, mc = whole
    lw, tree = _moe_layer(whole)
    x = jax.random.normal(jax.random.PRNGKey(7), (40, 64)) * 0.5
    y, scores, sel, load = jax.jit(
        lambda tree, x: moe.moe_ffn(mc, tree, x))(tree, x)

    @jax.jit
    def dense(lw, x):
        s = jax.nn.sigmoid(x @ lw["router"])
        _, top = jax.lax.top_k(s + lw["router_bias"], 4)
        wts = jnp.take_along_axis(s, top, axis=-1)
        wts = 2.5 * wts / jnp.sum(wts, axis=-1, keepdims=True)
        ffs = jnp.einsum("nef,efh->neh", jnp.square(jax.nn.relu(
            jnp.einsum("nh,efh->nef", x, lw["e_up"]))), lw["e_down"])
        combine = jnp.sum(jnp.where(
            top[:, :, None] == jnp.arange(16)[None, None, :],
            wts[:, :, None], 0.0), axis=1)                    # [n, e]
        return (jnp.square(jax.nn.relu(x @ lw["s_up"])) @ lw["s_down"]
                + jnp.einsum("ne,neh->nh", combine, ffs))

    np.testing.assert_allclose(y, dense(lw, x), atol=1e-5)
    assert int(load[0]) == 40 * 4
    assert moe.stored_expert_width(1856) == 1920
    with pytest.raises(ValueError, match="swiglu.*relu2"):
        moe.moe_ffn(dataclasses.replace(mc, activation="gelu"), tree, x)


def test_the_eight_shares_add_up_to_the_uncut_layer(whole):
    """Eight chips holding two experts each: their routed parts, plus
    the shared expert counted once, are the uncut reference layer with
    its selection bias.  1e-5: float32 sums of 4 terms."""
    pub, w, params, mc = whole
    lw, tree = _moe_layer(whole)
    sizes = ref.sizes_of(pub)
    x = jax.random.normal(jax.random.PRNGKey(7), (32, 64)) * 0.5
    want, _ = ref.expert_mixer(x, w["moe"], 2, sizes, 32, ref._f32_dot)
    shared = ref.relu2(x, lw["s_up"], lw["s_down"], ref._f32_dot)
    total, pairs = jnp.zeros_like(x), 0
    for first in range(0, 16, 2):
        cut = dict(pub, n_routed_experts=2, router_n_experts=16,
                   first_held_expert=first)
        cut_tree = dict(tree, **{k: tree[k][first:first + 2]
                                 for k in ("experts/up", "experts/down")})
        y, _, _, load = jax.jit(functools.partial(
            moe.moe_ffn, model_config(cut)))(cut_tree, x)
        cut_w = dict(w["moe"], **{k: w["moe"][k][:, first:first + 2]
                                  for k in ("e_up", "e_down")})
        np.testing.assert_allclose(y, ref.expert_mixer(
            x, cut_w, 2, ref.sizes_of(cut), 32, ref._f32_dot)[0], atol=1e-5)
        total, pairs = total + y, pairs + int(load[0])
    assert pairs == 32 * 4
    np.testing.assert_allclose(total - 7 * shared, want, atol=1e-5)


# -- what stays unsupported, each by a typed error ----------------------------

@pytest.mark.parametrize("change,match", [
    (dict(window=(10, -1)), "a window beside state-space layers"),
    (dict(layer_pattern=("global", "sliding")),
     "a window beside state-space layers"),
    (dict(kv_lora_rank=32), "latent keys beside state-space layers"),
    (dict(first_dense_layers=1), "first_dense_layers with a mixer_pattern"),
    (dict(norm_placement="post"), "one pre-norm a layer"),
    (dict(mixer_pattern=("mamba", "mlp") * 5), "mixer_pattern entries"),
    (dict(mixer_pattern=("mamba",) * 4), "4 entries for 10 layers"),
    (dict(ssm_heads=7), "'mamba' layers without their sizes"),
    (dict(num_experts=0), "'moe' layers in a mixer_pattern without experts"),
    (dict(mixer_pattern=None), "relu2 experts outside a mixer_pattern"),
    (dict(moe_dispatch="sort"), "moe_dispatch='grouped'"),
    (dict(pos_emb="alibi"), "alibi"),
], ids=["window", "layer_pattern", "latent", "dense_layers", "post_norm",
        "unknown_kind", "short_pattern", "ssm_sizes", "no_experts",
        "relu2_alone", "dispatch", "alibi"])
def test_check_supported_refuses_what_the_state_cannot_hold(whole, change,
                                                            match):
    mc = dataclasses.replace(whole[3], **change)
    with pytest.raises(NotImplementedError, match=match):
        _check_supported(mc)


def test_prefix_cache_with_state_layers_is_refused(whole):
    pub, w, params, mc = whole
    with pytest.raises(NotImplementedError, match="prefix"):
        engine(mc, params, "xla", prefix_cache=True)


def test_a_chunk_that_is_not_whole_sub_chunks_is_a_config_error(whole):
    pub, w, params, mc = whole
    cfg = ta.Config()
    for key, value in dict(SERVE, prefill_chunk=20).items():
        setattr(cfg.serve, key, value)
    with pytest.raises(ConfigError, match="sub-chunks"):
        PagedDecoder(mc, cfg.serve, "xla")


def test_trainer_and_the_modules_forward_refuse_the_family(whole):
    pub, w, params, mc = whole
    from torchacc_tpu.train.trainer import Trainer
    with pytest.raises(NotImplementedError, match="mixer_pattern"):
        TransformerLM(mc).apply({"params": params},
                                jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ConfigError, match="not supported"):
        Trainer(TransformerLM(mc), ta.Config())


# -- the pools and the programs -----------------------------------------------

def test_the_cells_pools_are_the_issues(whole):
    """At the cell's settings: k and v of the SIX attention layers, the
    convolution rows and the float32 state of the 23 state-space layers
    by slot (48 and the null slot): 0.95 + 2.24 GiB."""
    from torchacc_tpu.config import ServeConfig
    bench = json.load(open("chipbench/configs/nemotron-3-nano-30b-a3b.json"))
    mc = config_from_hf(types.SimpleNamespace(**bench["published"]),
                        param_dtype=jnp.bfloat16)
    sc = ServeConfig(block_size=128, num_blocks=1296, max_slots=48,
                     prefill_chunk=512)
    pools = jax.eval_shape(lambda: make_pools(mc, sc))
    assert {name: (p.shape, p.dtype) for name, p in pools.items()} == {
        "k": ((6, 1296, 128, 256), jnp.bfloat16),
        "v": ((6, 1296, 128, 256), jnp.bfloat16),
        "conv": ((23, 49, 3 * 6144), jnp.bfloat16),
        "ssm": ((23, 49, 64, 64, 128), jnp.float32)}
    assert mamba2.state_bytes(mc) == 2 * 2**20 + 36 * 2**10
    gib = {name: p.size * p.dtype.itemsize / 2**30
           for name, p in pools.items()}
    assert abs(gib["k"] + gib["v"] - 0.949) < 0.001
    assert abs(gib["conv"] + gib["ssm"] - 2.24) < 0.01


def _programs(eng):
    d = eng.scheduler.decoder
    return {k: getattr(d, k)._cache_size() for k in (
        "_decode", "_prefill", "_prefill_batch", "_sample_first",
        "_set_slot", "_cow")}


def test_the_family_compiles_the_programs_the_others_compile(whole):
    """`setup_s` is judged: serving three requests compiles one decode
    program, two prefill programs (a prompt's last chunk and the
    others), the first token's sampling and the slot splice — no
    program resets a slot's state (a request's first chunk does)."""
    pub, w, params, mc = whole
    eng = engine(mc, params, "xla", max_slots=2)
    rng = np.random.default_rng(0)
    eng.generate([Request(prompt_ids=rng.integers(1, 200, size=n).tolist(),
                          max_new_tokens=4) for n in (5, 39, 9)])
    assert _programs(eng) == {
        "_decode": 1, "_prefill": 2, "_prefill_batch": 0,
        "_sample_first": 1, "_set_slot": 1, "_cow": 0}
    eng.close()


def test_importing_the_package_loads_no_module_of_the_family():
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, torchacc_tpu, torchacc_tpu.serve; "
         "print(sorted(m for m in sys.modules if m.startswith("
         "('torchacc_tpu', 'chipbench'))))"],
        capture_output=True, text=True, check=True).stdout
    loaded = eval(out.strip().splitlines()[-1])
    assert "torchacc_tpu.models.mamba2" not in loaded
    assert "torchacc_tpu.ops.ssm_scan" not in loaded
    assert "torchacc_tpu.models.moe" not in loaded
