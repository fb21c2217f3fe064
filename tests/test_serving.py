"""Serving-engine tests: paged attention vs dense reference, block-pool
invariants, and continuous batching vs batch-synchronous ``generate()``.

The load-bearing guarantees (ISSUE 6 acceptance criteria):

- ``ops.paged_attention`` over random block layouts is allclose to the
  dense reference for decode (T=1) and chunked-prefill (T>1) geometry,
  MHA and GQA, with the Pallas kernel (interpret mode on CPU) matching
  the jnp fallback bit-for-bit in f32.
- the block allocator never leaks, never aliases a live block, never
  hands out the null block, and detects double-frees.
- GREEDY continuous batching — mixed prompt lengths spanning >= 8x,
  staggered arrivals, block reuse under a tiny pool — is token-IDENTICAL
  to ``models.generate`` on the same prompts.
- sampling controls: ``top_k >= vocab`` is an exact no-op, and
  top_k/top_p composition at temperature > 0 is deterministic under a
  fixed rng across jit boundaries (serving replays depend on it).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchacc_tpu.config import Config, ServeConfig
from torchacc_tpu.models import TransformerLM, get_preset
from torchacc_tpu.models.generate import _sample, generate
from torchacc_tpu.ops.attention import attention_reference
from torchacc_tpu.ops.paged_attention import paged_attention
from torchacc_tpu.serve import (
    BlockPool,
    Request,
    ServeEngine,
    blocks_needed,
)

pytestmark = pytest.mark.serving

VOCAB = 257


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset(
        "llama-tiny", dtype=jnp.float32, num_layers=2, hidden_size=64,
        num_heads=4, num_kv_heads=2, intermediate_size=128,
        vocab_size=VOCAB, max_seq_len=128)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _serve_cfg(**kw):
    base = dict(block_size=8, num_blocks=64, max_slots=4, prefill_chunk=8,
                decode_depth=2)
    base.update(kw)
    return Config(serve=ServeConfig(**base))


def _prompts(rng, lens):
    return [rng.integers(1, VOCAB, size=n).tolist() for n in lens]


def _ref_generate(model, params, prompts, max_new, eos_id=None):
    """Batch-synchronous reference: ONE ragged left-padded generate()
    call (one compile for any prompt mix)."""
    p_max = max(len(p) for p in prompts)
    ids = np.zeros((len(prompts), p_max), np.int32)
    mask = np.zeros((len(prompts), p_max), np.int32)
    for i, p in enumerate(prompts):
        ids[i, p_max - len(p):] = p
        mask[i, p_max - len(p):] = 1
    out = np.asarray(generate(
        model, params, jnp.asarray(ids), max_new_tokens=max_new,
        prompt_mask=jnp.asarray(mask), eos_id=eos_id))
    return [out[i, p_max:].tolist() for i in range(len(prompts))]


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------

def test_blocks_needed_edges():
    assert blocks_needed(0, 8) == 0
    assert blocks_needed(1, 8) == 1
    assert blocks_needed(8, 8) == 1
    assert blocks_needed(9, 8) == 2
    assert blocks_needed(-3, 8) == 0


def test_block_pool_invariants():
    pool = BlockPool(8)                      # usable blocks: 1..7
    assert pool.available == 7
    a = pool.alloc(3)
    b = pool.alloc(2)
    assert 0 not in a + b                    # null block never handed out
    assert len(set(a) | set(b)) == 5         # no aliasing between grants
    assert pool.available + pool.in_use == 7
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free(a)                         # double free detected
    with pytest.raises(ValueError):
        pool.free([0])                       # foreign/null block detected
    c = pool.alloc(4)
    assert set(c).isdisjoint(b)              # reuse never aliases live
    assert pool.available + pool.in_use == 7
    pool.free(b)
    pool.free(c)
    assert pool.available == 7               # nothing leaked


def test_block_pool_exhaustion_returns_none_never_partial():
    pool = BlockPool(4)
    assert pool.alloc(4) is None             # > capacity: no partial grant
    assert pool.available == 3
    got = pool.alloc(3)
    assert pool.alloc(1) is None
    pool.free(got)
    with pytest.raises(ValueError):
        BlockPool(1)                         # needs the null block + 1


# ---------------------------------------------------------------------------
# paged attention vs dense reference
# ---------------------------------------------------------------------------

def _random_paged_case(rng, *, slots, heads, kv_heads, d, bs, mb, t=1,
                       ctx_lens=None, dtype=jnp.float32, layers=3):
    """Scatter random per-slot contexts into a shuffled block pool of
    ``layers`` layers (every layer its own random content); return
    (paged operands, per layer the dense per-slot (q, k, v, q_start))."""
    nb = slots * mb + 1
    ctx = ctx_lens if ctx_lens is not None else [
        int(rng.integers(1, mb * bs + 1)) for _ in range(slots)]
    perm = rng.permutation(np.arange(1, nb)).tolist()
    tables = np.zeros((slots, mb), np.int32)
    k_pool = rng.standard_normal(
        (layers, nb, bs, kv_heads, d)).astype(np.float32)
    v_pool = rng.standard_normal(
        (layers, nb, bs, kv_heads, d)).astype(np.float32)
    blocks = []
    for s in range(slots):
        n_blk = blocks_needed(ctx[s], bs)
        blocks.append([perm.pop() for _ in range(n_blk)])
        tables[s, :n_blk] = blocks[s]
    q = rng.standard_normal((slots, t, heads, d)).astype(np.float32)
    q_start = np.asarray([max(c - t, 0) for c in ctx], np.int32)

    def dense(layer):
        pages = lambda pool, s: np.concatenate(  # noqa: E731
            [pool[layer, b] for b in blocks[s]], axis=0)[:ctx[s]]
        return (q, [pages(k_pool, s) for s in range(slots)],
                [pages(v_pool, s) for s in range(slots)], q_start)

    # the pool the kernel reads is the stack [L, NB, BS, KH*D]
    rows = lambda pool: jnp.asarray(  # noqa: E731
        pool.reshape(layers, nb, bs, kv_heads * d), dtype)
    paged = (jnp.asarray(q, dtype), rows(k_pool), rows(v_pool),
             jnp.asarray(tables), jnp.asarray(ctx, np.int32),
             jnp.asarray(q_start))
    return paged, dense


def _dense_reference(q, dense_k, dense_v, q_start, **kw):
    outs = []
    for s in range(q.shape[0]):
        sq, sk = q.shape[1], dense_k[s].shape[0]
        # attention_reference is bottom-right aligned (query i sits at
        # q_offset + sk - sq + i); paged semantics put it at q_start + i
        o = attention_reference(
            jnp.asarray(q[s:s + 1]), jnp.asarray(dense_k[s][None]),
            jnp.asarray(dense_v[s][None]), causal=True,
            q_offset=int(q_start[s]) + sq - sk, **kw)
        outs.append(np.asarray(o)[0])
    return np.stack(outs)


@pytest.mark.parametrize("layer", [1, 2])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_paged_attention_matches_reference_random_layouts(seed, layer):
    rng = np.random.default_rng(seed)
    paged, dense = _random_paged_case(
        rng, slots=4, heads=4, kv_heads=4, d=16, bs=8, mb=4)
    out = np.asarray(paged_attention(*paged, layer=layer, impl="xla"))
    ref = _dense_reference(*dense(layer))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("layer", [1, 3])
def test_paged_attention_gqa_chunk_matches_reference(layer):
    # T=4 chunk geometry (chunked prefill) + GQA head grouping
    rng = np.random.default_rng(3)
    paged, dense = _random_paged_case(
        rng, slots=3, heads=8, kv_heads=2, d=16, bs=8, mb=3, t=4,
        ctx_lens=[5, 17, 24], layers=4)
    out = np.asarray(paged_attention(*paged, layer=layer, impl="xla"))
    ref = _dense_reference(*dense(layer))
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("layer", [2])
def test_paged_attention_softcap_matches_reference(layer):
    rng = np.random.default_rng(4)
    paged, dense = _random_paged_case(
        rng, slots=2, heads=4, kv_heads=4, d=16, bs=8, mb=2)
    out = np.asarray(paged_attention(*paged, layer=layer, impl="xla",
                                     logit_softcap=30.0))
    ref = _dense_reference(*dense(layer), logit_softcap=30.0)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_paged_attention_inactive_slot_zeros(impl):
    rng = np.random.default_rng(5)
    paged, _ = _random_paged_case(
        rng, slots=3, heads=4, kv_heads=4, d=16, bs=8, mb=2,
        ctx_lens=[9, 1, 12])
    q, kp, vp, tables, ctx, q_start = paged
    ctx = ctx.at[1].set(0)                   # free slot parked on null block
    tables = tables.at[1, :].set(0)
    out = np.asarray(paged_attention(q, kp, vp, tables, ctx, q_start,
                                     layer=1, impl=impl))
    assert np.all(out[1] == 0.0)
    assert np.all(np.isfinite(out))


@pytest.mark.parametrize("kv_heads,t", [(2, 1), (1, 4), (4, 8)],
                         ids=["gqa_decode", "mqa_chunk", "mha_chunk"])
def test_paged_attention_pallas_interpret_matches_xla(kv_heads, t):
    # tiny grid: the Pallas kernel in interpret mode vs the jnp anchor,
    # the layer index traced (as the scheduler's scan hands it over)
    rng = np.random.default_rng(6)
    paged, _ = _random_paged_case(
        rng, slots=2, heads=4, kv_heads=kv_heads, d=16, bs=8, mb=2, t=t,
        ctx_lens=[9, 14])
    for layer in (1, 2):
        out_x = np.asarray(paged_attention(*paged, layer=layer, impl="xla"))
        out_p = np.asarray(jax.jit(
            lambda *a: paged_attention(*a[:-1], layer=a[-1], impl="pallas"))(
                *paged, jnp.int32(layer)))
        np.testing.assert_allclose(out_p, out_x, atol=1e-5, rtol=1e-5)


def test_paged_attention_validation_errors():
    q = jnp.zeros((2, 1, 4, 8))
    kp = jnp.zeros((3, 4, 8, 16))            # [L, NB, BS, 2 heads * 8]
    tables = jnp.zeros((2, 2), jnp.int32)
    lens = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError):          # 3 q heads not multiple of 2
        paged_attention(jnp.zeros((2, 1, 3, 8)), kp, kp, tables, lens, lens,
                        layer=0)
    with pytest.raises(ValueError):          # k/v pool mismatch
        paged_attention(q, kp, jnp.zeros((3, 4, 8, 32)), tables, lens, lens,
                        layer=0)
    with pytest.raises(ValueError):          # one layer's pool, no stack
        paged_attention(q, kp[0], kp[0], tables, lens, lens, layer=0)
    with pytest.raises(ValueError):          # rows are not whole heads
        paged_attention(q, kp[..., :12], kp[..., :12], tables, lens, lens,
                        layer=0)
    with pytest.raises(ValueError):          # slot-count mismatch
        paged_attention(q, kp, kp, tables[:1], lens, lens, layer=0)
    with pytest.raises(ValueError):
        paged_attention(q, kp, kp, tables, lens, lens, layer=0, impl="nope")


# ---------------------------------------------------------------------------
# the pool in the layer loop: written in place, one layer at a time
# ---------------------------------------------------------------------------

def _tiny_decoder(tiny, **serve_kw):
    from torchacc_tpu.serve.scheduler import PagedDecoder
    model, params = tiny
    sc = _serve_cfg(**serve_kw).serve
    return PagedDecoder(model.cfg, sc), model.cfg, sc, params


@pytest.mark.parametrize("layer", [0, 1])
def test_layer_write_leaves_other_layers_bit_identical(tiny, layer):
    decoder, cfg, sc, params = _tiny_decoder(tiny)
    rng = np.random.default_rng(7)
    shape = (3, sc.num_blocks, sc.block_size, cfg.kv_heads * cfg.head_size)
    kp = jnp.asarray(rng.standard_normal(shape), cfg.dtype)
    vp = jnp.asarray(rng.standard_normal(shape), cfg.dtype)
    s, t = 2, 4
    x = jnp.asarray(rng.standard_normal((s, t, cfg.hidden_size)), cfg.dtype)
    positions = jnp.asarray([[8, 9, 10, 11], [3, 4, 5, 6]], jnp.int32)
    tables = jnp.asarray([[5, 9, 0], [7, 0, 0]], jnp.int32)
    blk = jnp.asarray([[9, 9, 9, 9], [7, 7, 7, 7]], jnp.int32)
    off = positions % sc.block_size
    p_l = jax.tree.map(lambda a: a[layer], params["layers"])["block"]
    from torchacc_tpu.models.transformer import LayerAt

    def one_layer(p_l, row, x, pools):
        return decoder._layer(
            p_l, LayerAt(("layers",), row, "", row), x, pools, positions,
            {"blocks": (tables, blk, off)}, positions[:, -1] + 1, None, None)

    _, pools, _ = jax.jit(one_layer)(p_l, jnp.int32(layer), x,
                                     {"k": kp, "v": vp})
    kp2, vp2 = pools["k"], pools["v"]
    for old, new in ((kp, kp2), (vp, vp2)):
        old, new = np.asarray(old), np.asarray(new)
        written = np.zeros(shape[:3], bool)
        written[layer, np.asarray(blk), np.asarray(off)] = True
        assert np.array_equal(new[~written], old[~written])
        assert not np.any(new[written] == old[written])
        others = [l for l in range(3) if l != layer]
        assert np.array_equal(new[others], old[others])


def test_cow_clones_one_block_across_every_layer(tiny):
    decoder, cfg, sc, _ = _tiny_decoder(tiny)
    rng = np.random.default_rng(8)
    shape = (cfg.num_layers, sc.num_blocks, sc.block_size,
             cfg.kv_heads * cfg.head_size)
    kp = rng.standard_normal(shape).astype(np.float32)
    vp = rng.standard_normal(shape).astype(np.float32)
    src, dst = 11, 40
    pools = decoder._cow({"k": jnp.asarray(kp), "v": jnp.asarray(vp)},
                         jnp.int32(src), jnp.int32(dst))
    kp2, vp2 = pools["k"], pools["v"]
    for old, new in ((kp, np.asarray(kp2)), (vp, np.asarray(vp2))):
        assert np.array_equal(new[:, dst], old[:, src])
        keep = np.arange(sc.num_blocks) != dst
        assert np.array_equal(new[:, keep], old[:, keep])


@pytest.mark.parametrize("kv_heads", [2, 1], ids=["gqa", "mqa"])
def test_tp_mesh_pool_rows_split_at_head_boundaries(devices, kv_heads):
    # ServeEngine(mesh=): the pool's [KH*D] rows are sharded over 'tp'
    # in whole-head groups — and not at all where tp does not divide the
    # kv heads (the 16 lanes of one MQA head would divide by 2) — and
    # the kernel under its shard_map serves the same tokens
    import dataclasses
    from jax.sharding import Mesh, PartitionSpec as P
    cfg = get_preset(
        "llama-tiny", dtype=jnp.float32, num_layers=2, hidden_size=64,
        num_heads=4, num_kv_heads=kv_heads, intermediate_size=128,
        vocab_size=VOCAB, max_seq_len=128)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    prompts = _prompts(np.random.default_rng(9), [5, 19, 11])
    ref = _ref_generate(model, params, prompts, max_new=6)
    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("fsdp", "tp"))
    kernel = TransformerLM(dataclasses.replace(cfg, attention_impl="pallas"))
    eng = ServeEngine(kernel, params, _serve_cfg(), mesh=mesh)
    pool = eng.scheduler.pools["k"]
    assert pool.shape == (2, 64, 8, kv_heads * 16)
    assert pool.sharding.spec == P(None, None, None,
                                   "tp" if kv_heads == 2 else None)
    ids = [eng.submit(Request(prompt_ids=p, max_new_tokens=6))
           for p in prompts]
    eng.run()
    assert [eng.result(i).tokens for i in ids] == ref
    eng.close()


def test_unsliceable_head_size_is_a_typed_error_at_construction():
    # 9 kv heads of 72: no group of them is a multiple of 128 lanes, so a
    # step takes the whole row.  Pages of 4096 tokens of it are over the
    # kernel's VMEM budget whatever the chunk — refused when the decoder
    # is built, not at the first request's lowering.  A 1024-token chunk
    # over blocks of 128 is too tall for one step too, and since PR 33
    # runs as tiles of its queries (ops/paged_attention.query_tile); a
    # chunk whose whole row fits is served as it was
    import torchacc_tpu as ta
    from torchacc_tpu.ops.paged_attention import query_tile
    from torchacc_tpu.serve.scheduler import PagedDecoder
    cfg = get_preset(
        "llama-tiny", dtype=jnp.bfloat16, num_layers=1, hidden_size=648,
        num_heads=9, num_kv_heads=9, intermediate_size=128,
        vocab_size=VOCAB, max_seq_len=2048)
    assert cfg.head_size == 72
    huge = ServeConfig(block_size=4096, num_blocks=64, max_slots=4,
                       prefill_chunk=1024)
    with pytest.raises(ta.ConfigError, match="head size 72"):
        PagedDecoder(cfg, huge, "pallas")
    assert PagedDecoder(cfg, huge, "xla").impl == "xla"
    wide = ServeConfig(block_size=128, num_blocks=64, max_slots=4,
                       prefill_chunk=1024)
    assert PagedDecoder(cfg, wide, "pallas").impl == "pallas"
    assert query_tile(9, 9, 72, 128, 1024, jnp.bfloat16) == 256
    assert query_tile(9, 9, 72, 128, 128, jnp.bfloat16) == 128
    narrow = ServeConfig(block_size=128, num_blocks=64, max_slots=4,
                         prefill_chunk=128)
    assert PagedDecoder(cfg, narrow, "pallas").impl == "pallas"


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------

def test_serve_config_validation():
    Config(serve=ServeConfig()).validate()
    for bad in (dict(block_size=0), dict(num_blocks=1), dict(max_slots=0),
                dict(prefill_chunk=0), dict(policy="lifo"),
                dict(decode_depth=0), dict(max_new_tokens=0),
                dict(max_queue=0)):
        with pytest.raises(Exception):
            Config(serve=ServeConfig(**bad)).validate()


# ---------------------------------------------------------------------------
# continuous batching vs generate()
# ---------------------------------------------------------------------------

# decoder-block shapes at toy width, float32: the serving decoder runs
# the model's own block (models/block.py), so every shape TransformerLM
# trains serves token-identically to generate().  Each is the named
# family's BLOCK only (norm placement, qk-norm, biases, activation),
# never its window, pattern or softcaps.
_BIASES = dict(qkv_bias=True, o_bias=True, mlp_bias=True)
BLOCK_SHAPES = {
    "llama_pre_norm": {},
    "olmo2_post_norm_flat_qk_norm": dict(
        norm_placement="post", qk_norm=True, qk_norm_proj=True),
    "gemma2_sandwich_norms": dict(
        sandwich_norms=True, norm="rmsnorm1p", activation="geglu",
        embed_scale=True),
    "phi_parallel_shared_norm": dict(
        parallel_block=True, norm="layernorm", activation="gelu",
        partial_rotary=0.5, **_BIASES),
    "gpt_neox_parallel_own_ln2": dict(
        parallel_block=True, parallel_block_shared_norm=False,
        norm="layernorm", activation="gelu_exact", **_BIASES),
    "qwen3_per_head_qk_norm": dict(qk_norm=True),
    "nemotron_relu2": dict(activation="relu2", norm="layernorm1p"),
}


def _block_model(**shape):
    """The ``tiny`` geometry with another block shape.  Every leaf is
    moved off its initial value: norm scales start at one and biases at
    zero, where a norm applied in the wrong place or a dropped bias
    would not show."""
    cfg = get_preset(
        "llama-tiny", dtype=jnp.float32, num_layers=2, hidden_size=64,
        num_heads=4, num_kv_heads=2, intermediate_size=128,
        vocab_size=VOCAB, max_seq_len=128, **shape)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    # stacked [L, ...]: vectors (scales, biases) by 0.3, matrices by 0.05
    leaves = [x + (0.3 if x.ndim == 2 else 0.05)
              * jax.random.normal(k, x.shape, x.dtype)
              for x, k in zip(leaves, keys)]
    return model, jax.tree.unflatten(treedef, leaves)


@pytest.mark.parametrize("shape", list(BLOCK_SHAPES))
def test_greedy_continuous_batching_token_identical_mixed_lengths(
        tiny, shape):
    # prompt lengths span 25/3 > 8x; 6 requests > max_slots=4 so the
    # queue + admission path runs; prefill_chunk=8 < 25 so long prompts
    # take multiple interleaved chunks
    model, params = (_block_model(**BLOCK_SHAPES[shape])
                     if BLOCK_SHAPES[shape] else tiny)
    rng = np.random.default_rng(0)
    prompts = _prompts(rng, [3, 25, 7, 16, 4, 11])
    eng = ServeEngine(model, params, _serve_cfg())
    results = eng.generate(
        [Request(prompt_ids=p, max_new_tokens=6) for p in prompts])
    refs = _ref_generate(model, params, prompts, 6)
    for r, ref in zip(results, refs):
        assert r.tokens == ref
        assert r.finish_reason == "length"
        assert 0.0 <= r.queue_wait_s <= r.ttft_s <= r.total_s
        assert len(r.token_latencies_s) == len(r.tokens) - 1
        assert r.tokens_per_sec > 0
    stats = eng.stats()
    assert stats["requests"] == 6 and stats["tokens"] == 36
    for key in ("tokens_per_sec", "ttft_s_p50", "ttft_s_p95",
                "per_token_s_p50", "per_token_s_p95"):
        assert stats[key] >= 0.0
    eng.close()


def test_staggered_arrivals_token_identical(tiny):
    # second wave submitted MID-DECODE of the first — continuous
    # batching must admit into freed/free slots without disturbing
    # in-flight sequences
    model, params = tiny
    rng = np.random.default_rng(1)
    first, second = _prompts(rng, [3, 25, 7]), _prompts(rng, [24, 4, 12])
    eng = ServeEngine(model, params, _serve_cfg())
    ids = [eng.submit(Request(prompt_ids=p, max_new_tokens=5))
           for p in first]
    for _ in range(6):                       # mid-flight: prefill + decode
        eng.step()
    ids += [eng.submit(Request(prompt_ids=p, max_new_tokens=5))
            for p in second]
    eng.run()
    refs = _ref_generate(model, params, first + second, 5)
    for rid, ref in zip(ids, refs):
        assert eng.result(rid).tokens == ref


def test_block_free_reuse_never_leaks_or_aliases(tiny):
    # pool sized so 8 requests MUST reuse blocks (11 usable, 3 per
    # request): correctness under reuse is the aliasing proof, and the
    # per-step invariants catch leaks/aliases directly
    model, params = tiny
    rng = np.random.default_rng(2)
    prompts = _prompts(rng, [6, 3, 5, 6, 4, 6, 3, 5])
    conf = _serve_cfg(block_size=4, num_blocks=12, max_slots=3,
                      prefill_chunk=4)
    eng = ServeEngine(model, params, conf)
    ids = [eng.submit(Request(prompt_ids=p, max_new_tokens=4))
           for p in prompts]
    sched = eng.scheduler
    while eng.step():
        live = [b for s in sched.slot_seq if s is not None
                for b in s.blocks]
        deferred = [b for _, blks, _ in sched._deferred for b in blks]
        assert len(live) == len(set(live)), "live block aliased"
        assert 0 not in live + deferred, "null block allocated"
        assert set(live).isdisjoint(deferred), \
            "deferred-free block still owned by a live sequence"
        # deferred blocks stay allocator-owned until the lag matures
        assert all(sched.pool.refcount(b) >= 1 for b in deferred)
        assert sched.pool.available + sched.pool.in_use == 11
    refs = _ref_generate(model, params, prompts, 4)
    for rid, ref in zip(ids, refs):
        assert eng.result(rid).tokens == ref
    assert sched.pool.available == 11        # every block returned
    assert not sched._deferred


def test_deeper_decode_depth_token_identical(tiny):
    # the lagged-readback ring must never change tokens, only timing
    model, params = tiny
    rng = np.random.default_rng(7)
    prompts = _prompts(rng, [3, 18, 9])
    outs = []
    for depth in (1, 3):
        eng = ServeEngine(model, params, _serve_cfg(decode_depth=depth))
        rs = eng.generate(
            [Request(prompt_ids=p, max_new_tokens=6) for p in prompts])
        outs.append([r.tokens for r in rs])
    assert outs[0] == outs[1]
    assert outs[0] == _ref_generate(model, params, prompts, 6)


def test_eos_truncates_like_generate(tiny):
    model, params = tiny
    rng = np.random.default_rng(3)
    prompts = _prompts(rng, [5, 13])
    free = _ref_generate(model, params, prompts, 8)
    # eos = a token the greedy path actually emits mid-stream for row 0
    eos = free[0][2]
    eng = ServeEngine(model, params, _serve_cfg())
    results = eng.generate(
        [Request(prompt_ids=p, max_new_tokens=8, eos_id=eos)
         for p in prompts])
    for r, ref in zip(results, free):
        if eos in ref:
            cut = ref.index(eos) + 1
            assert r.tokens == ref[:cut]
            assert r.finish_reason == "eos"
        else:
            assert r.tokens == ref
            assert r.finish_reason == "length"


def test_learned_pos_serving_matches_generate_and_bounds():
    cfg = get_preset("gpt2-tiny", dtype=jnp.float32, num_layers=2,
                     hidden_size=64, num_heads=4, vocab_size=VOCAB,
                     max_seq_len=32)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(4)
    prompts = _prompts(rng, [3, 9])
    eng = ServeEngine(model, params, _serve_cfg())
    results = eng.generate(
        [Request(prompt_ids=p, max_new_tokens=4) for p in prompts])
    refs = _ref_generate(model, params, prompts, 4)
    for r, ref in zip(results, refs):
        assert r.tokens == ref
    # prompt + max_new past the learned position table fails at submit
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit(Request(prompt_ids=list(range(1, 30)),
                           max_new_tokens=8))
    eng.close()


# ---------------------------------------------------------------------------
# admission control / policy
# ---------------------------------------------------------------------------

def test_admission_rejects_unservable_and_full_queue(tiny):
    model, params = tiny
    conf = _serve_cfg(num_blocks=8, max_queue=2)   # 7 usable blocks
    eng = ServeEngine(model, params, conf)
    with pytest.raises(ValueError, match="KV blocks"):
        eng.submit(Request(prompt_ids=[1] * 40, max_new_tokens=32))
    with pytest.raises(ValueError):
        eng.submit(Request(prompt_ids=[]))
    eng.submit(Request(prompt_ids=[1, 2], max_new_tokens=2))
    eng.submit(Request(prompt_ids=[3, 4], max_new_tokens=2))
    with pytest.raises(RuntimeError, match="queue full"):
        eng.submit(Request(prompt_ids=[5, 6], max_new_tokens=2))
    eng.run()
    assert eng.stats()["requests"] == 2


def test_submit_rejects_nonpositive_max_new(tiny):
    """A decode slot always generates >= 1 token, so max_new_tokens=0
    must fail at the front door instead of silently returning one token
    (generate() returns the prompt unchanged for max_new<=0 — the
    engine cannot match that, so it refuses)."""
    model, params = tiny
    eng = ServeEngine(model, params, _serve_cfg())
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(Request(prompt_ids=[1, 2, 3], max_new_tokens=0))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(Request(prompt_ids=[1, 2, 3], max_new_tokens=-4))
    eng.close()


def test_table_width_bounded_by_position_reach_not_pool(tiny):
    """Per-token attention cost scales with block-table width, so the
    width must track the longest ADMISSIBLE sequence (max_seq_len +
    overhang), not pool capacity — growing num_blocks for concurrency
    must not inflate every slot's per-token cost."""
    model, params = tiny
    conf = _serve_cfg(num_blocks=4096)           # huge pool
    eng = ServeEngine(model, params, conf)
    width = eng.scheduler.max_blocks_per_seq
    expect = blocks_needed(
        model.cfg.max_seq_len + conf.serve.decode_depth,
        conf.serve.block_size)
    assert width == expect                       # 17, not 4095
    assert eng.scheduler.tables.shape[1] == width
    # requests beyond the position reach are rejected naming the bound
    with pytest.raises(ValueError, match="KV blocks"):
        eng.submit(Request(prompt_ids=[1] * 120, max_new_tokens=64))
    eng.close()


def test_result_pop_releases_request_state(tiny):
    """Long-running servers pop results (or discard) so completed
    request state does not accumulate for the process lifetime; the
    completion accounting itself drains the scheduler's finished list
    (O(newly finished)), so nothing depends on _all retention."""
    model, params = tiny
    rng = np.random.default_rng(11)
    eng = ServeEngine(model, params, _serve_cfg())
    rids = [eng.submit(Request(prompt_ids=p, max_new_tokens=3))
            for p in _prompts(rng, [4, 9])]
    eng.run()
    assert eng.stats()["requests"] == 2
    r0 = eng.result(rids[0], pop=True)
    assert len(r0.tokens) == 3
    eng.discard(rids[1])
    assert eng._all == {}
    with pytest.raises(KeyError):
        eng.result(rids[0])
    # aggregates accumulate at completion: popping results (the
    # documented long-running hygiene) must not shrink stats()
    assert eng.stats()["requests"] == 2
    assert eng.stats()["tokens"] == 6
    eng.close()


def test_sjf_policy_admits_short_first(tiny):
    model, params = tiny
    rng = np.random.default_rng(5)
    long_p, short_p = _prompts(rng, [20, 3])
    eng = ServeEngine(model, params, _serve_cfg(max_slots=1, policy="sjf"))
    rid_long = eng.submit(Request(prompt_ids=long_p, max_new_tokens=3))
    rid_short = eng.submit(Request(prompt_ids=short_p, max_new_tokens=3))
    eng.run()
    # one slot: sjf runs the short prompt to completion first, so the
    # long one's queue wait covers the short one's whole service time
    t_long = eng._all[rid_long].t_admit
    t_short = eng._all[rid_short].t_admit
    assert t_short < t_long
    refs = _ref_generate(model, params, [long_p, short_p], 3)
    assert eng.result(rid_long).tokens == refs[0]
    assert eng.result(rid_short).tokens == refs[1]


def test_unsupported_model_rejected_at_construction(tiny):
    _, params = tiny
    moe = get_preset("llama-tiny", dtype=jnp.float32, num_layers=2,
                     hidden_size=64, num_heads=4, num_kv_heads=2,
                     vocab_size=VOCAB, num_experts=4)
    with pytest.raises(NotImplementedError, match="MoE"):
        ServeEngine(TransformerLM(moe), params, _serve_cfg())


def test_per_request_metrics_written(tiny, tmp_path):
    model, params = tiny
    rng = np.random.default_rng(6)
    prompts = _prompts(rng, [4, 9])
    eng = ServeEngine(model, params, _serve_cfg(),
                      metrics_dir=str(tmp_path))
    eng.generate([Request(prompt_ids=p, max_new_tokens=3)
                  for p in prompts])
    eng.close()
    import glob
    import json
    files = glob.glob(str(tmp_path / "*.jsonl"))
    assert files
    recs = [json.loads(l) for f in files for l in open(f) if l.strip()]
    serve_recs = [r for r in recs if any("serve/" in k for k in r)]
    assert len(serve_recs) == 2
    for r in serve_recs:
        assert r["serve/tokens"] == 3
        assert r["serve/ttft_s"] >= 0


# ---------------------------------------------------------------------------
# sampling (satellite: top-k edge + replay determinism)
# ---------------------------------------------------------------------------

def test_sample_top_k_geq_vocab_is_exact_noop():
    logits = jnp.asarray(
        np.random.default_rng(0).standard_normal((3, VOCAB)), jnp.float32)
    rng = jax.random.PRNGKey(42)
    base = _sample(logits, rng, 0.9, top_k=0)
    for k in (VOCAB, VOCAB + 1, 10 * VOCAB):
        got = _sample(logits, rng, 0.9, top_k=k)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    # top_k=1 equals greedy regardless of rng
    assert _sample(logits, rng, 0.9, top_k=1).tolist() == \
        jnp.argmax(logits, -1).tolist()


def test_greedy_tokens_unchanged_when_batched_with_sampled(tiny):
    """The decode step's static all_greedy flag selects between an
    argmax-only trace and the full sampling trace; a greedy request
    must emit the same tokens under either — alone (all-greedy trace)
    or sharing the batch with a sampled request (mixed trace), across
    the trace flip when the sampled request finishes first."""
    model, params = tiny
    rng = np.random.default_rng(13)
    g_prompt, s_prompt = _prompts(rng, [10, 4])
    alone = ServeEngine(model, params, _serve_cfg())
    ref = alone.generate(
        [Request(prompt_ids=g_prompt, max_new_tokens=8)])[0].tokens
    alone.close()
    eng = ServeEngine(model, params, _serve_cfg())
    rid_g = eng.submit(Request(prompt_ids=g_prompt, max_new_tokens=8))
    rid_s = eng.submit(Request(prompt_ids=s_prompt, max_new_tokens=2,
                               temperature=0.8, top_k=5, seed=3))
    eng.run()     # sampled finishes first -> flips back to all-greedy
    assert eng.result(rid_g).tokens == ref
    assert len(eng.result(rid_s).tokens) == 2
    eng.close()


def test_sample_top_k_vocab_minus_one_truncates():
    """The guard's exact boundary: top_k = V - 1 (the largest value
    that must still truncate) masks exactly the minimum logit, while
    top_k = V is a no-op — an off-by-one in the `0 < top_k < V`
    condition would flip one of these."""
    v = 8
    logits = jnp.zeros((1, v)).at[0, v - 1].set(-0.1)   # near-uniform
    seen_min_at_v, seen_min_at_v1 = False, False
    for seed in range(100):
        rng = jax.random.PRNGKey(seed)
        if int(_sample(logits, rng, 1.0, top_k=v)[0]) == v - 1:
            seen_min_at_v = True
        if int(_sample(logits, rng, 1.0, top_k=v - 1)[0]) == v - 1:
            seen_min_at_v1 = True
    assert seen_min_at_v          # ~11% per draw at top_k = V
    assert not seen_min_at_v1     # masked: probability exactly 0


def test_sample_topk_topp_deterministic_across_jit(tiny):
    logits = jnp.asarray(
        np.random.default_rng(1).standard_normal((2, VOCAB)), jnp.float32)
    rng = jax.random.PRNGKey(7)
    eager = _sample(logits, rng, 0.8, top_k=5, top_p=0.9)
    jitted = jax.jit(lambda l, r: _sample(l, r, 0.8, top_k=5, top_p=0.9))
    np.testing.assert_array_equal(np.asarray(eager),
                                  np.asarray(jitted(logits, rng)))
    np.testing.assert_array_equal(np.asarray(jitted(logits, rng)),
                                  np.asarray(jitted(logits, rng)))
    # full generate(): same rng -> bitwise-identical sampled stream
    model, params = tiny
    prompt = jnp.asarray([[5, 9, 13]], jnp.int32)
    kw = dict(max_new_tokens=6, temperature=0.8, top_k=5, top_p=0.9,
              rng=jax.random.PRNGKey(11))
    a = np.asarray(generate(model, params, prompt, **kw))
    b = np.asarray(generate(model, params, prompt, **kw))
    np.testing.assert_array_equal(a, b)


def test_sampled_serving_deterministic_across_engines(tiny):
    # fixed per-request seeds: two fresh engines produce identical
    # sampled streams (replay / debugging depends on this)
    model, params = tiny
    rng = np.random.default_rng(8)
    prompts = _prompts(rng, [4, 11])
    reqs = [Request(prompt_ids=p, max_new_tokens=5, temperature=0.8,
                    top_k=7, top_p=0.9, seed=i)
            for i, p in enumerate(prompts)]
    outs = []
    for _ in range(2):
        eng = ServeEngine(model, params, _serve_cfg())
        outs.append([r.tokens for r in eng.generate(reqs)])
    assert outs[0] == outs[1]
    for toks in outs[0]:
        assert len(toks) == 5
        assert all(0 <= t < VOCAB for t in toks)
