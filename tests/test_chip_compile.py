"""Compile the main path's Pallas kernels for a described TPU v5e.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is described, not attached (``on-chip-measurement`` guide, §2):
what Mosaic refuses on the chip it refuses here — block shapes that do
not tile, too much VMEM, a kernel GSPMD would have to partition.
Nothing runs, so these say nothing about results or times.

The topology is described inside a module-scoped fixture (never at
import: only one process may load the TPU library, and every xdist
worker imports this file) and the compiles happen in the test's own
process.  All at Mistral-7B widths: 32 q / 8 kv heads, head_dim 128,
hidden 4096, ffn 14336.

The serving programs (``PagedDecoder._decode`` / ``_prefill``) are
compiled whole as well, at the benchmark's serve settings: what they
must NOT hold is a second KV pool.  The pool rides the layer scan's
carry and is written in place; a compile whose temporaries reach a
layer's pool, whose pools are not aliased in -> out, or whose text
copies or slices a pool-shaped array means the mechanism did not
engage.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

import torchacc_tpu.ops.flash_attention as flash_mod
import torchacc_tpu.ops.fused as fused_mod
import torchacc_tpu.ops.grouped_matmul as grouped_mod
import torchacc_tpu.ops.moe_rows as rows_mod
import torchacc_tpu.ops.paged_attention as paged_mod
import torchacc_tpu.ops.quantized_matmul as quant_mod
import torchacc_tpu.ops.ssm_scan as ssm_mod
from torchacc_tpu.config import ServeConfig
from torchacc_tpu.ops.attn import attention

H, KH, D, SEQ = 32, 8, 128, 4096
BF16 = jnp.bfloat16
LAYERS = 3                   # the paged kernel reads one layer of a stack


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip: keep it off around these
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_the_chip(monkeypatch):
    """The kernels ask ``interpret_mode()``, which sees the CPU backend
    here; steer them to the Mosaic lowering for the described chip."""
    for mod in (flash_mod, fused_mod, grouped_mod, paged_mod, quant_mod,
                rows_mod, ssm_mod):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


@pytest.fixture
def train_step_for_the_chip(for_the_chip, monkeypatch):
    """A whole train step also asks ``ops/attn`` which attention
    ``auto`` means, and ``ops/fused`` what runs the head's chunk: the
    flash and the head kernels, as on the chip."""
    import torchacc_tpu.ops.attn as attn_mod
    monkeypatch.setattr(attn_mod, "_on_tpu", lambda: True)
    monkeypatch.setattr(fused_mod, "on_tpu", lambda: True)


def _kernels(text, prefix):
    """The compiled text's Pallas kernels whose instruction name starts
    with ``prefix``, read as the benchmark's train driver reads them."""
    from chipbench.drivers.train_fit import _kernel_names
    return [n for n in _kernel_names(text) if n.startswith(prefix)]


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("case", ["plain", "segment_ids", "left_window",
                                  "sq_lt_sk", "alibi"])
def test_flash_fwd_bwd_compiles(one_chip, for_the_chip, case):
    """``plain`` is the train cells' call: dead steps clamped, diagonal
    tiles in two pieces (512-row and 512-key slices of the 1024-blocks),
    dk/dv on [k, q] tiles with lse / delta as ``[b, h, 1, sq]`` lane
    vectors.  The others are the paths that stand aside from the split
    (segment ids, a left window) or shift the band (``sq < sk``), and
    the ALiBi bias in both orientations (its positions are int32 iotas:
    Mosaic takes no float one)."""
    sq = SEQ // 2 if case == "sq_lt_sk" else SEQ
    kw = dict(window=(1500, -1)) if case == "left_window" else {}

    def loss(q, k, v, seg_q, seg_kv, slopes):
        if case == "segment_ids":
            kw.update(q_segment_ids=seg_q, kv_segment_ids=seg_kv)
        if case == "alibi":
            kw.update(alibi_slopes=slopes)
        return attention(q, k, v, impl="pallas", **kw).astype(
            jnp.float32).sum()

    plan = flash_mod.tile_plan(sq, SEQ, 1024, 1024, True,
                               kw.get("window", (-1, -1)), SEQ - sq,
                               has_seg=case == "segment_ids")
    assert plan["dead_fetching"] == 0 and plan["live"] < plan["steps"]
    assert plan["diagonal_split"] == {"plain": 4, "alibi": 4,
                                      "sq_lt_sk": 2}.get(case, 0)
    q = _sds((1, sq, H, D), BF16, one_chip)
    kv = _sds((1, SEQ, KH, D), BF16, one_chip)
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv,
                          _sds((1, sq), jnp.int32, one_chip),
                          _sds((1, SEQ), jnp.int32, one_chip),
                          _sds((H,), jnp.float32, one_chip))
    assert text.count("tpu_custom_call") >= 3      # fwd, dq, dkv


@pytest.mark.parametrize("blocks", [(64, 128), (192, 1024), (1024, 64)])
def test_flash_refuses_blocks_the_lanes_cannot_tile(for_the_chip, blocks):
    """On the chip lse leaves ``flash_fwd`` along the lanes in q blocks
    and the kv segment ids arrive in kv blocks, so an explicit block
    tiles by 128 — or, a q block alone, covers the whole q length.  The
    forward and the standalone backward say so before Mosaic would."""
    block_q, block_k = blocks
    q = jnp.zeros((1, SEQ, H, D), BF16)
    kv = jnp.zeros((1, SEQ, KH, D), BF16)
    lse = jnp.zeros((1, H, SEQ), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_mod.flash_attention(q, kv, kv, block_q=block_q,
                                  block_k=block_k)
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_mod.flash_attention_bwd(q, kv, kv, q, lse, q, block_q=block_q,
                                      block_k=block_k)
    # a short q in one block of its own length is taken
    flash_mod._check_blocks(64, 128, 64)


def _paged_at_a_traced_layer(q, k_pool, v_pool, tables, lens, q_start,
                             layer):
    return paged_mod.paged_attention(q, k_pool, v_pool, tables, lens,
                                     q_start, layer=layer, impl="pallas")


@pytest.mark.parametrize("t", [1, ServeConfig().prefill_chunk],
                         ids=["decode", "prefill_chunk"])
def test_paged_attention_compiles_at_default_block_size(one_chip,
                                                        for_the_chip, t):
    sc = ServeConfig()
    slots = sc.max_slots if t == 1 else 1
    mb = SEQ // sc.block_size
    pool = _sds((LAYERS, sc.num_blocks, sc.block_size, KH * D), BF16,
                one_chip)
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=one_chip)
    text = _compiled_text(
        _paged_at_a_traced_layer,
        _sds((slots, t, H, D), BF16, one_chip), pool, pool,
        i32((slots, mb)), i32((slots,)), i32((slots,)), i32(()))
    assert "tpu_custom_call" in text


def test_quantized_dot_int8_compiles(one_chip, for_the_chip):
    text = _compiled_text(
        functools.partial(quant_mod.quantized_dot, fmt="int8",
                          impl="pallas"),
        _sds((2 * SEQ, 4096), BF16, one_chip),
        _sds((4096, 14336), BF16, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fsdp,tp", [(4, 1), (2, 2)],
                         ids=["fsdp4", "fsdp2_tp2"])
def test_flash_under_a_mesh_compiles(topo, for_the_chip, fsdp, tp):
    # plain jit with sharded operands is refused ("Mosaic kernels cannot
    # be automatically partitioned"); attention() wraps the kernel in a
    # shard_map over the ambient mesh
    mesh = Mesh(np.asarray(topo.devices).reshape(fsdp, tp), ("fsdp", "tp"))
    sh = NamedSharding(mesh, P("fsdp", None, "tp", None))

    def loss(q, k, v):
        return attention(q, k, v, impl="pallas").astype(jnp.float32).sum()

    q = _sds((4, SEQ, H, D), BF16, sh)
    kv = _sds((4, SEQ, KH, D), BF16, sh)
    with jax.sharding.set_mesh(mesh):
        text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count("tpu_custom_call") >= 3


def test_paged_attention_under_a_tp_mesh_compiles(topo, for_the_chip):
    # ServeEngine(mesh=): the pool's rows are sharded over 'tp' in
    # whole-head groups
    sc = ServeConfig()
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("fsdp", "tp"))
    rep = NamedSharding(mesh, P())
    pool = _sds((LAYERS, sc.num_blocks, sc.block_size, KH * D), BF16,
                NamedSharding(mesh, P(None, None, None, "tp")))
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=rep)
    s, mb = sc.max_slots, SEQ // sc.block_size
    with jax.sharding.set_mesh(mesh):
        text = _compiled_text(
            _paged_at_a_traced_layer,
            _sds((s, 1, H, D), BF16,
                 NamedSharding(mesh, P(None, None, "tp", None))),
            pool, pool, i32((s, mb)), i32((s,)), i32((s,)), i32(()))
    assert "tpu_custom_call" in text


# the benchmark's serve cell (chipbench/traffic/rollout16.json), depth 2
SERVE = dict(block_size=128, num_blocks=256, max_slots=16, prefill_chunk=256)
SERVE_DEPTH = 2


def _serve_program(name, one_chip):
    """``PagedDecoder``'s jitted program ``name`` lowered on abstract
    arguments, as ``Scheduler._decode_once`` / ``_prefill_one`` call it,
    and the abstract pool."""
    from torchacc_tpu.models import TransformerLM, get_preset
    from torchacc_tpu.serve.kv_cache import blocks_needed, make_pools
    from torchacc_tpu.serve.scheduler import PagedDecoder

    mc = get_preset(
        "llama-tiny", num_layers=SERVE_DEPTH, hidden_size=4096,
        num_heads=H, num_kv_heads=KH, intermediate_size=14336,
        vocab_size=32768, max_seq_len=SEQ, dtype=BF16, param_dtype=BF16)
    sc = ServeConfig(**SERVE)
    decoder = PagedDecoder(mc, sc, "pallas")
    sds = functools.partial(_sds, sharding=one_chip)
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(
            lambda k: TransformerLM(mc).init(
                k, jnp.zeros((1, 8), jnp.int32))["params"],
            jax.random.PRNGKey(0)))
    pool = jax.eval_shape(lambda: make_pools(mc, sc)["k"])
    pool = sds(pool.shape, pool.dtype)
    s = sc.max_slots
    mb = min(sc.num_blocks - 1,
             blocks_needed(SEQ + sc.decode_depth, sc.block_size))
    i32, f32 = jnp.int32, jnp.float32
    if name == "decode":
        carry = {"tok": sds((s,), i32), "key": sds((s, 2), jnp.uint32)}
        lowered = decoder._decode.lower(
            params, {"k": pool, "v": pool}, carry,
            {"blocks": sds((s, mb), i32)}, sds((s,), i32),
            sds((s,), jnp.bool_), sds((s,), f32), sds((s,), i32),
            sds((s,), f32), True)
    else:
        lowered = decoder._prefill.lower(
            params, {"k": pool, "v": pool}, {"blocks": sds((mb,), i32)},
            sds((), i32),
            sds((sc.prefill_chunk,), i32), sds((), i32),
            name == "prefill_final_chunk")
    return lowered, pool


@pytest.mark.parametrize("name",
                         ["decode", "prefill_chunk", "prefill_final_chunk"])
def test_serve_program_holds_one_kv_pool(one_chip, for_the_chip, name):
    lowered, pool = _serve_program(name, one_chip)
    compiled = lowered.compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    layer_pool = pool.size // SERVE_DEPTH * pool.dtype.itemsize   # 64 MiB
    assert layer_pool == 64 * 2**20
    # a relayouted copy of one layer's pool, or a layer sliced out of
    # the stack, would be 64 MiB of temporaries or more
    assert mem.temp_size_in_bytes < layer_pool // 2
    # both pools come back in the buffers they came in
    assert mem.alias_size_in_bytes >= 2 * SERVE_DEPTH * layer_pool
    assert text.count("tpu_custom_call") == 1
    # nothing copies, slices or re-stacks a pool or one layer of it: the
    # only pool-shaped results are the in-place scatters of kv_write
    dims = [str(d) for d in pool.shape]
    shapes = "|".join(re.escape(f"bf16[{','.join(dims[i:])}]")
                      for i in (0, 1))
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(rf"= ({shapes})\S* "
                          r"(copy|dynamic-slice|dynamic-update-slice)\(",
                          line)]
    assert not moved, moved
    written = [line for line in text.splitlines()
               if re.search(rf"= ({shapes})\S* fusion\(", line)]
    assert len(written) == 2 and all("kv_write" in w for w in written), \
        written


# -- the latent-attention / held-expert family at A.X-K1's published
# geometry (chipbench/configs/a.x-k1.json, traffic/rollout32.json) --------

AXK1 = dict(
    hidden_size=7168, num_heads=64, num_kv_heads=64,
    intermediate_size=18432, vocab_size=163840, max_seq_len=SEQ,
    rope_interleaved=True, kv_lora_rank=512, q_lora_rank=1536,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    first_dense_layers=1, num_experts=12, num_experts_per_tok=8,
    moe_intermediate_size=2048, moe_scoring="sigmoid", moe_n_group=8,
    moe_topk_group=4, moe_route_scale=2.5, moe_shared_experts=1,
    moe_router_width=192, moe_first_expert=96, moe_dispatch="grouped",
    norm_eps=1e-6, rope_theta=10000.0,
    rope_yarn=(32.0, 4096.0, 32.0, 1.0, 1.0, True), query_scale=0.1309)
AXK1_SERVE = dict(block_size=128, num_blocks=640, max_slots=32,
                  prefill_chunk=512)
# one dense + TWO expert layers: at one, the scan's slice of a stack of
# one is a bitcast and no compile could show a copied expert stack
AXK1_DEPTH = 3


def _fits_the_vmem_the_call_asks_for(rows, pages, latent, select):
    """What one step of the latent kernel holds — its query tile, two
    groups of ``pages`` pool pages, the float32 score tiles of a group —
    against the ``vmem_limit_bytes`` its call hands the compiler."""
    need = paged_mod.latent_vmem_bytes(rows, pages, latent, 64, 128, 2,
                                       select)
    assert need <= paged_mod._LATENT_VMEM_BUDGET \
        < paged_mod._LATENT_VMEM_LIMIT, (rows, pages, need)


@pytest.mark.parametrize("slots,t", [(32, 1), (1, 512)],
                         ids=["decode", "prefill_chunk"])
def test_latent_paged_kernel_compiles_at_published_geometry(
        one_chip, for_the_chip, slots, t):
    """64 heads on one shared row of 512 + 64 values in a 640-lane pool
    row, block 128: t = 1 and the chunk path (query rows tiled)."""
    sds = functools.partial(_sds, sharding=one_chip)
    i32 = functools.partial(sds, dtype=jnp.int32)
    tq, pages = paged_mod.latent_query_tile(64, 512, 64, 128, t, BF16,
                                            False, 33)
    assert t % tq == 0 and tq >= min(t, 8) and pages >= 2
    _fits_the_vmem_the_call_asks_for(tq * 64, pages, 512, False)

    def call(q_lat, q_pe, pool, tables, ctx, q0, layer):
        return paged_mod.latent_paged_attention(
            q_lat, q_pe, pool, tables, ctx, q0, layer=layer, scale=0.13,
            impl="pallas")

    compiled = jax.jit(call).lower(
        sds((slots, t, 64, 512), BF16), sds((slots, t, 64, 64), BF16),
        sds((LAYERS, 640, 128, 640), BF16), i32((slots, 33)), i32((slots,)),
        i32((slots,)), i32(())).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the pool is read where it lies: no relayouted copy of it
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("rows,k,n,stack", [
    (256, 7168, 2048, ()), (4096, 7168, 2048, ()), (256, 2048, 7168, ()),
    (256, 7168, 2048, (5,))],
    ids=["decode_up", "chunk_up", "decode_down", "stack"])
def test_grouped_expert_matmul_compiles_for_the_chip(one_chip, for_the_chip,
                                                     rows, k, n, stack):
    """12 held experts of 7168 x 2048 (and the way back): the Pallas
    grouped matmul over the sorted (token, expert) pairs of a decode
    step (32 x 8) and of a prefill chunk (512 x 8), weights read in
    place (no temporary of an expert's size) — also out of the cell's
    stack of five expert layers [5, 12, 7168, 2048] through a traced
    layer index."""
    sds = functools.partial(_sds, sharding=one_chip)
    layer = {"layer": sds((), jnp.int32)} if stack else {}
    compiled = jax.jit(grouped_mod.grouped_matmul).lower(
        sds((rows, k), BF16), sds(stack + (12, k, n), BF16),
        sds((12,), jnp.int32), **layer).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


@pytest.mark.parametrize("mover", ["rows_out_weighed", "rows_back_weighed",
                                   "rows_back"])
def test_moe_row_movers_compile_for_the_chip(one_chip, for_the_chip, mover):
    """The dropless layer's movers (PR 47) at the train cell's chunk:
    8,192 gathered token rows of 2,304 bf16, 65,536 sorted rows, top-8.
    Rows out, weighed: XLA's gather and arithmetic a live tile at a time
    into a buffer nobody zeroed (``moe_rows_alloc``), no temporary of
    the buffer's size.
    Rows back: the live prefix re-tiled to rows a DMA can take
    (``moe_rows_pack``: ONE buffer-sized temporary, written in its live
    tiles only), then the sum."""
    from torchacc_tpu.models import moe
    sds = functools.partial(_sds, sharding=one_chip)
    n, k, h = 8192, 8, 2304
    nk = n * k
    assert nk == moe.MAX_SORTED_PAIRS > moe.LIVE_ROWS_FROM
    i32, f32 = jnp.int32, jnp.float32
    rows, buf = sds((n, h), BF16), sds((nk, h), BF16)
    idx, total = sds((nk,), i32), sds((), i32)
    if mover == "rows_out_weighed":
        lowered = jax.jit(rows_mod.take_rows_weighed).lower(
            rows, idx, total, sds((nk,), f32), buf)
        names = ["moe_rows_alloc"]
    else:
        weights = (sds((n, k), f32),) if mover == "rows_back_weighed" else ()
        lowered = jax.jit(lambda src, unsort, total, *w: rows_mod.sum_rows(
            src, unsort, total, *w, k=k,
            dtype=f32 if w else BF16)).lower(buf, idx, total, *weights)
        names = ["moe_rows_pack", "moe_rows_back"]
    compiled = lowered.compile()
    assert [re.sub(r"\.\d+$", "", name)
            for name in _kernels(compiled.as_text(), "moe_rows")] == names
    temp = compiled.memory_analysis().temp_size_in_bytes
    if mover.startswith("rows_out"):
        assert temp < 2**20        # the loop fills its result in place
        assert "while" in compiled.as_text()
    else:
        assert nk * h * 2 <= temp < nk * h * 2 + 2**20


def test_a_serve_chunks_expert_layer_keeps_xlas_gathers(one_chip,
                                                        for_the_chip):
    """A prefill chunk of 512 tokens x 8 (4,096 sorted pairs, 16 held
    experts out of a layer stack) lies under the size rule: the expert
    layer's only custom calls are its three grouped matmuls."""
    import dataclasses

    from torchacc_tpu.models import get_preset, moe
    sds = functools.partial(_sds, sharding=one_chip)
    n, k, h, f, held = 512, 8, 2304, 896, 16
    assert n * k <= moe.LIVE_ROWS_FROM
    cfg = dataclasses.replace(
        get_preset("llama-tiny", dtype=BF16, param_dtype=BF16),
        hidden_size=h, num_experts=held, num_experts_per_tok=k,
        moe_router_width=256, moe_first_expert=32, activation="swiglu")

    def layer(x, sel, weights, w_gate, w_up, w_down, valid, index):
        return moe.held_experts_ffn(cfg, x, sel, weights, w_gate, w_up,
                                    w_down, valid, index)
    stack = lambda a, b: sds((5, held, a, b), BF16)  # noqa: E731
    text = jax.jit(layer).lower(
        sds((n, h), BF16), sds((n, k), jnp.int32), sds((n, k), jnp.float32),
        stack(h, f), stack(h, f), stack(f, h), sds((n,), jnp.bool_),
        sds((), jnp.int32)).compile().as_text()
    assert len(_kernels(text, "grouped_matmul")) == 3
    assert len(_kernels(text, "")) == 3


def _axk1_program(name, one_chip):
    from torchacc_tpu.models import TransformerLM, get_preset
    from torchacc_tpu.serve.kv_cache import blocks_needed, make_pools
    from torchacc_tpu.serve.scheduler import PagedDecoder

    mc = get_preset("llama-tiny", num_layers=AXK1_DEPTH, dtype=BF16,
                    param_dtype=BF16, **AXK1)
    sc = ServeConfig(**AXK1_SERVE)
    decoder = PagedDecoder(mc, sc, "pallas")
    sds = functools.partial(_sds, sharding=one_chip)
    abstract = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: sds(x.shape, x.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda k: TransformerLM(mc).init(
            k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0)))
    pools = abstract(jax.eval_shape(lambda: make_pools(mc, sc)))
    s = sc.max_slots
    mb = min(sc.num_blocks - 1,
             blocks_needed(SEQ + sc.decode_depth, sc.block_size))
    i32, f32 = jnp.int32, jnp.float32
    if name == "decode":
        carry = {"tok": sds((s,), i32), "key": sds((s, 2), jnp.uint32)}
        return decoder._decode.lower(
            params, pools, carry, {"blocks": sds((s, mb), i32)},
            sds((s,), i32), sds((s,), jnp.bool_), sds((s,), f32),
            sds((s,), i32), sds((s,), f32), True), pools
    return decoder._prefill.lower(
        params, pools, {"blocks": sds((mb,), i32)}, sds((), i32),
        sds((sc.prefill_chunk,), i32), sds((), i32),
        name == "prefill_final_chunk"), pools


@pytest.mark.parametrize("name",
                         ["decode", "prefill_chunk", "prefill_final_chunk"])
def test_latent_serve_program_holds_one_latent_pool(one_chip, for_the_chip,
                                                    name):
    """The three serve programs of the A.X-K1 cell, one dense and two
    expert layers deep: ONE latent pool [L, 640, 128, 640], aliased
    in -> out, no pool-shaped copy; the latent kernel once a layer stack
    and the three grouped matmuls.  The expert kernels stay where they
    lie: the layer scan does not slice them (``PagedDecoder._forward``
    closes over the stacks) and the grouped matmul reads its layer
    through an index, so no instruction but a parameter yields an array
    of one layer's expert stack ([12, 7168, 2048] or its transpose,
    336 MiB), decode holds under 32 MiB of temporaries and a prefill
    program under one stack (what it keeps is the chunk's own sorted
    activations: 4,096 pairs x 7168 bf16 = 56 MiB and the like).  With
    the stacks on the scan's ``xs`` the same compile read 338 / 401 /
    401 MiB (sandbox compile, PR 27)."""
    lowered, pools = _axk1_program(name, one_chip)
    (pool,) = pools.values()
    compiled = lowered.compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    pool_bytes = pool.size * pool.dtype.itemsize
    assert pool.shape == (AXK1_DEPTH, 640, 128, 640)
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < (32 if name == "decode" else 256) * 2**20
    # the latent kernel in each of the two layer scans, the three
    # grouped matmuls in the second
    assert text.count("tpu_custom_call") == 5
    stack = r"bf16\[12,(7168,2048|2048,7168)\]"
    copied = [line.strip()[:160] for line in text.splitlines()
              if re.search(rf"= {stack}\S* (?!parameter\()", line)]
    assert not copied, copied
    dims = [str(d) for d in pool.shape]
    shapes = "|".join(re.escape(f"bf16[{','.join(dims[i:])}]")
                      for i in (0, 1))
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(rf"= ({shapes})\S* "
                          r"(copy|dynamic-slice|dynamic-update-slice)\(",
                          line)]
    assert not moved, moved


@pytest.mark.parametrize("rows,hidden,vocab", [
    (2048, 2048, 100352),       # olmo2-1b.train.dense4k
    (2048, 4096, 32768),        # mistral7b.train.dense4k / fsdp4
], ids=["olmo2_100k", "mistral_32k"])
def test_head_kernels_compile(one_chip, for_the_chip, rows, hidden, vocab):
    """``head_fwd`` / ``head_dx`` / ``head_dw`` at the train cells'
    published geometries, a 2048-row chunk of a bf16 head: the tiles
    ``_head_tiles`` picks from (rows, hidden, vocab) divide them, the
    blocks fit the VMEM limit the kernels state, and the dW sum goes
    in and out through one buffer."""
    tiles = fused_mod._head_tiles(rows, hidden, vocab, 2, 2)
    assert tiles is not None
    sds = functools.partial(_sds, sharding=one_chip)
    assert fused_mod._HEAD_VMEM_LIMIT <= 110 * 2**20    # of a v5e's 128

    def chunk(x, y, w, dw_sum):
        return fused_mod._head_chunk_kernels(x, y, w, dw_sum, tiles)

    compiled = jax.jit(chunk, donate_argnums=(3,)).lower(
        sds((rows, hidden), BF16), sds((rows,), jnp.int32),
        sds((hidden, vocab), BF16), sds((hidden, vocab), BF16)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert sorted(n.split(".")[0] for n in _kernels(text, "head_")) == [
        "head_dw", "head_dx", "head_fwd"]
    # the dW sum is updated in place: what the chunk holds besides its
    # arguments is the float32 logits and small change
    assert mem.alias_size_in_bytes >= hidden * vocab * 2
    assert mem.temp_size_in_bytes < rows * vocab * 4 + 64 * 2**20


def _traced_train_step(topo, chips, mc, cfg, batch, seq, optimizer=None):
    """``Trainer._train_step`` of ``accelerate()``'s model for ``mc``
    under ``cfg``, traced for ``chips`` of the described chips
    (``chipbench/tools/sandbox_compile.py``'s construction)."""
    from torchacc_tpu.models.transformer import TransformerLM
    from torchacc_tpu.train.accelerate import apply_config_to_model
    from torchacc_tpu.train.trainer import Trainer

    cfg.validate()
    names = tuple(cfg.dist.topology)
    sizes = cfg.dist.axis_sizes(chips)
    mesh = Mesh(np.asarray(topo.devices[:chips]).reshape(
        [sizes[a] for a in names]), names)
    trainer = Trainer(TransformerLM(apply_config_to_model(mc, cfg)), cfg,
                      optimizer=optimizer, mesh=mesh)
    state = trainer.abstract_state()
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    b = {"input_ids": _sds(ids.shape, ids.dtype,
                           trainer._batch_shardings({"input_ids": ids})
                           ["input_ids"])}
    with jax.sharding.set_mesh(mesh):
        return trainer, trainer._build_train_step(b).trace(state, b)


def _compiled_train_step(*args, **kw):
    """The same step, compiled."""
    trainer, traced = _traced_train_step(*args, **kw)
    return trainer, traced.lower().compile()


def _layer_whiles(text):
    """The compiled step's ``while`` instructions that belong to the
    model's layer loop (op_name under ``layers/``)."""
    return [ln.strip()[:200] for ln in text.splitlines()
            if re.search(r" while\(", ln)
            and re.search(r'op_name="[^"]*/layers/while"', ln)]


@functools.cache
def _fsdp4_toy_step(topo):
    """A small fsdp=4 train step at the framework's own choice of layer
    loop, compiled whole."""
    import torchacc_tpu as ta
    from torchacc_tpu.models import get_preset

    vocab, hidden, batch, seq = 4096, 512, 8, 1024
    mc = get_preset("llama-tiny", vocab_size=vocab, hidden_size=hidden,
                    num_layers=2, num_heads=4, num_kv_heads=4,
                    intermediate_size=1024, max_seq_len=seq, dtype=BF16)
    cfg = ta.Config()
    cfg.dist.fsdp.size = 4
    cfg.compute.bf16_compute_params = True
    return _compiled_train_step(topo, 4, mc, cfg, batch, seq)


def _under(text, scope, op):
    """The compiled text's ``op`` instructions (sync or ``-start``)
    whose op_name lies under ``scope``."""
    return [ln.strip()[:240] for ln in text.splitlines()
            if re.search(rf'op_name="[^"]*{scope}', ln)
            and re.search(rf" {op}(-start)?\(", ln)]


def test_fused_head_under_fsdp_reduces_no_logits(
        topo, train_step_for_the_chip):
    """A small fsdp=4 train step, compiled whole.  Left to the
    partitioner the head's chunk loop shards the matmul's contraction
    (hidden) dimension, and every chunk's ``[chunk_rows, vocab]``
    float32 logits cost an all-reduce (one a chunk until PR 41, two
    before PR 29).  The head keeps a chip's rows on that chip instead
    (``ops/fused._head_rows``: one ``shard_map`` over the data axes
    around the loop), so under ``fused_ce`` there is NO such
    all-reduce: ONE all-gather brings the head weight whole before the
    loop, ONE reduce-scatter takes the ``[hidden, vocab]`` dW to the
    parameter's shards after it, and nothing is rematerialised."""
    vocab, hidden, chunk_rows, fsdp = 4096, 512, 2048, 4
    trainer, compiled = _fsdp4_toy_step(topo)
    assert trainer.head_rows == "sharded"
    # the chunk runs as kernels on each chip's own rows: the shard_map
    # is manual over the whole mesh (every other axis has extent 1)
    assert trainer.head_impl == "pallas"
    text = compiled.as_text()
    assert sorted(n.split(".")[0] for n in _kernels(text, "head_")) == [
        "head_dw", "head_dx", "head_fwd"]
    head = [ln for ln in text.splitlines()
            if re.search(r'op_name="[^"]*fused_ce', ln)]
    assert head and not any("rematted_computation" in ln for ln in head)
    reduces = _under(text, "fused_ce", "all-reduce")
    assert not [ln for ln in reduces
                if re.search(rf"= f32\[{chunk_rows},{vocab}\]", ln)], reduces
    # what is summed across the chips is loss_sum and count: scalars
    assert all(re.search(r"= \(?f32\[\]", ln) for ln in reduces), reduces
    gathers = _under(text, "fused_ce", "all-gather")
    assert len(gathers) == 1 and re.search(
        rf"= bf16\[{hidden},{vocab}\]", gathers[0]), gathers
    scatters = _under(text, "fused_ce", "reduce-scatter")
    assert len(scatters) == 1 and re.search(
        rf"= f32\[{hidden // fsdp},{vocab}\]", scatters[0]), scatters
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes
            < 15.75 * 2**30)


def test_layers_under_fsdp_stay_in_the_scan(topo, train_step_for_the_chip):
    """Where the mesh shards the parameters nobody unrolls the layers:
    the scan's loop is what holds back the gathers of later layers'
    weights (unrolled, the cell's depth-6 step under fsdp=4 needs 15.80
    of 15.75 GiB: PERF.md section 7, PR 38)."""
    trainer, compiled = _fsdp4_toy_step(topo)
    assert trainer.layer_loop == "scan"
    assert trainer.model.cfg.scan_layers is True
    text = compiled.as_text()
    assert _layer_whiles(text)
    # the scanned step holds each flash kernel once — exactly the
    # ``min_kernels`` of chipbench/traffic/dense4k.fsdp4.json, whose
    # driver refuses a step with fewer (ROADMAP "Also open in the
    # yardstick": a one-kernel flash backward waits for that to change)
    assert len(_kernels(text, "flash_")) == 3
    assert text.count("tpu_custom_call") == 3 + len(_kernels(text, "head_"))
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes
            < 15.75 * 2**30)


def _dense4k_cell():
    """``mistral7b.train.dense4k``'s own configuration, traffic and
    depth out of ``chipbench/``: the arguments of ``_traced_train_step``
    after ``topo``."""
    from chipbench import program, spec

    cell = spec.Cell("mistral7b.train.dense4k")
    traffic = cell.traffic
    mc = program.model_config(cell.published, cell.depth,
                              max_seq_len=traffic["seq"],
                              **traffic.get("model_overrides", {}))
    return (cell.chips, mc, program.framework_config(traffic["settings"], 0),
            traffic["batch"], traffic["seq"],
            program.optimizer(traffic["optimizer"]))


def test_one_chip_train_step_applies_its_layers_unrolled(
        topo, train_step_for_the_chip):
    """``mistral7b.train.dense4k``'s step (the cell's own configuration,
    traffic and depth 2 out of ``chipbench/``) at the framework's own
    choice of layer loop: one chip holds the parameters whole, so the
    layers are applied unrolled — no loop over the layers, no
    ``[L, ...]`` stack of the MLP's saved activations, each layer's
    three flash kernels its own instructions, and 2.7 GiB fewer
    temporaries than the scan's 7.32 (PERF.md section 4)."""
    chips, mc, cfg, batch, seq, optimizer = _dense4k_cell()
    depth = mc.num_layers
    assert (chips, batch, seq, depth) == (1, 4, SEQ, 2)
    assert mc.scan_layers is None                # nobody chose
    assert (mc.hidden_size, mc.intermediate_size) == (4096, 14336)
    trainer, compiled = _compiled_train_step(
        topo, chips, mc, cfg, batch, seq, optimizer=optimizer)
    assert trainer.layer_loop == "unrolled"
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert not _layer_whiles(text), _layer_whiles(text)
    stacked = [ln.strip()[:200] for ln in text.splitlines()
               if re.search(rf"bf16\[{depth},{batch},{seq},14336\]", ln)]
    assert not stacked, stacked[:3]
    # by name: the head's three kernels sit in its chunk loop, once
    assert len(_kernels(text, "flash_")) == 3 * depth
    assert sorted(n.split(".")[0] for n in _kernels(text, "head_")) == [
        "head_dw", "head_dx", "head_fwd"]
    assert text.count("tpu_custom_call") == 3 * depth + 3
    assert trainer.head_impl == "pallas"
    head = [ln for ln in text.splitlines()
            if "tpu_custom_call" in ln and re.search(r"%head_", ln)]
    assert all(re.search(r'op_name="[^"]*fused_ce', ln) for ln in head)
    assert mem.temp_size_in_bytes <= 5.2 * 2**30, mem.temp_size_in_bytes


def test_one_chip_train_step_takes_the_head_whole(
        topo, train_step_for_the_chip):
    """The same cell's step, traced: one device shards no batch, so the
    head reads the mesh and takes its rows whole — no ``shard_map`` in
    the step (the flash kernels need none on one device either), the
    parent's program (PERF.md section 6, PR 41: the lowered text is
    byte-equal)."""
    trainer, traced = _traced_train_step(topo, *_dense4k_cell())
    jaxpr = str(traced.jaxpr)
    assert trainer.head_rows == "whole"
    # the layers are unrolled: the head's chunk loop is the one scan
    assert jaxpr.count(" scan[") == 1 and "shard_map" not in jaxpr


# -- two kinds of latent layer: dots3-note-prev's published geometry ---------

DOTS3_SERVE = dict(block_size=128, num_blocks=2081, max_slots=8,
                   prefill_chunk=512)
DOTS3_SEQ = 33280
# the dense layer + TWO periods (full, sliding x 3): at one period each
# position's expert stack is a stack of one (see AXK1_DEPTH)
DOTS3_DEPTH = 9


def _dots3_program(name, one_chip):
    import json
    import types

    from chipbench.layouts import mla_sparse_window_moe_decoder as layout
    from chipbench.weights import mla_sparse_window_moe_decoder as weights
    from torchacc_tpu.models.hf import config_from_hf
    from torchacc_tpu.serve.kv_cache import blocks_needed, make_pools
    from torchacc_tpu.serve.scheduler import PagedDecoder

    (row,) = [r for r in map(json.loads, open(
        "/opt/skills/guides/model-configs/architectures.jsonl"))
        if r["name"] == "dots3-note-prev"]
    pub = dict(row["config"], n_routed_experts=16, router_n_experts=256,
               first_held_expert=128)
    mc = config_from_hf(types.SimpleNamespace(**pub), num_layers=DOTS3_DEPTH,
                        max_seq_len=DOTS3_SEQ, param_dtype=BF16)
    sc = ServeConfig(**DOTS3_SERVE)
    decoder = PagedDecoder(mc, sc, "pallas")
    sds = functools.partial(_sds, sharding=one_chip)
    abstract = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: sds(x.shape, x.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda k: layout.to_program_params(
            weights.make(k, pub, DOTS3_DEPTH, BF16), mc),
        jax.random.PRNGKey(0)))
    pools = abstract(jax.eval_shape(lambda: make_pools(mc, sc)))
    s = sc.max_slots
    mb = blocks_needed(DOTS3_SEQ + sc.decode_depth, sc.block_size)
    i32, f32 = jnp.int32, jnp.float32
    if name == "decode":
        carry = {"tok": sds((s,), i32), "key": sds((s, 2), jnp.uint32)}
        return decoder._decode.lower(
            params, pools, carry,
            {"blocks": sds((s, mb), i32), "window": sds((s, mb), i32)},
            sds((s,), i32), sds((s,), jnp.bool_), sds((s,), f32),
            sds((s,), i32), sds((s,), f32), True), pools, mb
    return decoder._prefill.lower(
        params, pools,
        {"blocks": sds((mb,), i32), "window": sds((mb,), i32)}, sds((), i32),
        sds((sc.prefill_chunk,), i32), sds((), i32),
        name == "prefill_final_chunk"), pools, mb


@pytest.mark.parametrize("name",
                         ["decode", "prefill_chunk", "prefill_final_chunk"])
def test_sparse_window_serve_program_holds_three_pools_in_place(
        one_chip, for_the_chip, name):
    """The three serve programs of the dots3-note-prev cell at its own
    settings and depth (a dense layer and two periods of full, sliding,
    sliding, sliding; 16 held experts of 5120 x 1536): THREE pools —
    full layers' latent rows [3, 2081, 128, 640], their index keys
    [3, 2081, 128, 128], window layers' rows [6, 81, 128, 1152] (1,088
    values padded to whole lane tiles) — all aliased in -> out, none
    copied, sliced or relayouted; 19 Mosaic kernels (a full layer:
    indexer scores + the latent kernel under the selection, a sliding
    layer: the windowed latent kernel, an expert layer: three grouped
    matmuls; the dense scan 2, the period scan 2 + 3 + 4 x 3); no
    instruction but a parameter yields an expert-stack-shaped array
    ([16, 5120, 1536], 236 MiB); and the indexer's products of every
    head with every cached position ([chunk, 64 heads, 33,408]: 4.4 GB
    in float32) exist nowhere — what reaches HBM is one float32 a
    (query, position), 65 MiB a chunk, and the exact top-k's passes over
    it (sandbox compile, PR 30: 7.7 MiB of temporaries in decode, 453 /
    434 MiB in the prefill programs)."""
    lowered, pools, mb = _dots3_program(name, one_chip)
    compiled = lowered.compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert {name: p.shape for name, p in pools.items()} == {
        "latent": (3, 2081, 128, 640), "index": (3, 2081, 128, 128),
        "latent_win": (6, 81, 128, 1152)}
    pools = list(pools.values())
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools)
    assert mem.temp_size_in_bytes < (32 if name == "decode" else 640) * 2**20
    assert text.count("tpu_custom_call") == 19
    stack = r"bf16\[16,(5120,1536|1536,5120)\]"
    copied = [line.strip()[:160] for line in text.splitlines()
              if re.search(rf"= {stack}\S* (?!parameter\()", line)]
    assert not copied, copied
    shapes = "|".join(
        re.escape(f"bf16[{','.join(str(d) for d in p.shape[i:])}]")
        for p in pools for i in (0, 1))
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(rf"= ({shapes})\S* "
                          r"(copy|dynamic-slice|dynamic-update-slice)\(",
                          line)]
    assert not moved, moved
    # per-head scores against every cached position: [.., 64, .., T]
    # (the latent kernel's view of the chunk's scores as 64 query tiles
    # of 8 tokens, [1, 64, 8, T], is a bitcast of the same 65 MiB)
    t = mb * 128
    per_head = [line.strip()[:160] for line in text.splitlines()
                if re.search(rf"= \w+\[[\d,]*\b64,[\d,]*{t}\]", line)
                and " bitcast(" not in line]
    assert not per_head, per_head


@pytest.mark.parametrize("kernel,slots,t", [
    ("indexer", 8, 1), ("indexer", 1, 512), ("sparse", 8, 1),
    ("sparse", 1, 512), ("window", 8, 1), ("window", 1, 512)])
def test_sparse_window_kernels_compile_at_published_geometry(
        one_chip, for_the_chip, kernel, slots, t):
    """Each new kernel alone for the chip, decode step and 512-token
    chunk: the indexer (64 heads x 128 over a [L, NB, 128, 128] key
    pool), the latent kernel under a selection (128 heads on a 640-lane
    row) and with a window of 512 back (64 heads on a 1,152-lane row);
    both walk the pages a tile can see inside the kernel, several a
    step, out of a pool left in HBM, and the tile and group sizes
    ``latent_query_tile`` derives fit the VMEM the call asks for; pools
    read where they lie (under 1 MiB of temporaries beside the
    selection's own reshape)."""
    sds = functools.partial(_sds, sharding=one_chip)
    i32 = functools.partial(sds, dtype=jnp.int32)
    mb, n = 261, 261 * 128
    common = (i32((slots, mb)), i32((slots,)), i32((slots,)), i32(()))
    if kernel == "indexer":
        def call(q, w, pool, tables, ctx, q0, layer):
            return paged_mod.indexer_scores(q, w, pool, tables, ctx, q0,
                                            layer=layer, impl="pallas")
        args = (sds((slots, t, 64, 128), BF16),
                sds((slots, t, 64), jnp.float32),
                sds((LAYERS, 2081, 128, 128), BF16))
    elif kernel == "sparse":
        def call(ql, qp, pool, sc, thr, tie, tables, ctx, q0, layer):
            return paged_mod.latent_paged_attention(
                ql, qp, pool, tables, ctx, q0, layer=layer, scale=0.07,
                impl="pallas", selection=(sc, thr, tie),
                name="sparse_latent_attention")
        args = (sds((slots, t, 128, 512), BF16),
                sds((slots, t, 128, 64), BF16),
                sds((LAYERS, 2081, 128, 640), BF16),
                sds((slots, t, n), jnp.float32),
                sds((slots, t), jnp.float32), i32((slots, t)))
    else:
        def call(ql, qp, pool, tables, ctx, q0, layer):
            return paged_mod.latent_paged_attention(
                ql, qp, pool, tables, ctx, q0, layer=layer, scale=0.06,
                impl="pallas", window=512, name="window_latent_attention")
        args = (sds((slots, t, 64, 1024), BF16),
                sds((slots, t, 64, 64), BF16),
                sds((LAYERS, 81, 128, 1152), BF16))
    if kernel != "indexer":
        heads, latent = args[0].shape[2:]
        tq, pages = paged_mod.latent_query_tile(
            heads, latent, 64, 128, t, BF16, kernel == "sparse", mb,
            512 if kernel == "window" else -1)
        assert t % tq == 0 and pages >= 2
        _fits_the_vmem_the_call_asks_for(tq * heads, pages, latent,
                                         kernel == "sparse")
    compiled = jax.jit(call).lower(*args, *common).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20 + (
        slots * t * n * 4 if kernel == "sparse" else 0)


# -- grouped-query layers of two kinds: K-EXAONE-236B-A23B's published
# geometry (chipbench/configs/k-exaone-236b-a23b.json, traffic/mixed16.json)

KEXAONE_SERVE = dict(block_size=128, num_blocks=2129, max_slots=16,
                     prefill_chunk=512)
KEXAONE_SEQ = 17024
# the cell's own depth: the dense sliding layer + s s g s s s g
KEXAONE_DEPTH = 8


def _kernel_grids(lowered_text):
    """``iteration_bounds`` of every Mosaic kernel of a lowered program,
    in program order, read out of the kernels' serialized IR."""
    import base64
    import json

    from jax._src.interpreters import mlir as jmlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    grids = []
    for m in re.finditer(r'backend_config = "((?:[^"\\]|\\.)*)"',
                         lowered_text):
        cfg = m.group(1).replace("\\22", '"').replace("\\5C", "\\")
        try:
            body = json.loads(cfg)["custom_call_config"]["body"]
        except (ValueError, KeyError):
            continue
        ctx = jmlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
                enable_debug_info=False)
        (bounds,) = re.findall(r"iteration_bounds = array<i64: ([\d, ]+)>",
                               asm)
        grids.append(tuple(int(x) for x in bounds.split(",")))
    return grids


@pytest.mark.parametrize("name,grid", [
    ("decode", (16, 1, 33)), ("prefill_chunk", (1, 4, 33)),
    ("prefill_final_chunk", (1, 4, 33))])
def test_a_model_without_a_window_keeps_its_kernels_grid(one_chip,
                                                         for_the_chip, name,
                                                         grid):
    """The grouped-query kernel gained a window walk (PR 33); with no
    window it is the kernel it was: one call a program, the grid (slots,
    kv-head groups, EVERY block of the table) of the Mistral serve cell.
    (The builder's check of PR 33 went further: the three programs'
    lowered and compiled text and the kernel's IR, parent against change,
    equal but for source locations — PERF.md section 6.)"""
    lowered, _ = _serve_program(name, one_chip)
    assert _kernel_grids(lowered.as_text()) == [grid]


def _kexaone_program(name, one_chip):
    import json
    import types

    from chipbench.layouts import gqa_window_moe_decoder as layout
    from chipbench.weights import gqa_window_moe_decoder as weights
    from torchacc_tpu.models.hf import config_from_hf
    from torchacc_tpu.serve.kv_cache import blocks_needed, make_pools
    from torchacc_tpu.serve.scheduler import PagedDecoder

    (row,) = [r for r in map(json.loads, open(
        "/opt/skills/guides/model-configs/architectures.jsonl"))
        if r["name"] == "K-EXAONE-236B-A23B"]
    pub = dict(row["config"], num_experts=8, router_n_experts=128,
               first_held_expert=64)
    mc = config_from_hf(types.SimpleNamespace(**pub),
                        num_layers=KEXAONE_DEPTH, max_seq_len=KEXAONE_SEQ,
                        param_dtype=BF16)
    sc = ServeConfig(**KEXAONE_SERVE)
    decoder = PagedDecoder(mc, sc, "pallas")
    sds = functools.partial(_sds, sharding=one_chip)
    abstract = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: sds(x.shape, x.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda k: layout.to_program_params(
            weights.make(k, pub, KEXAONE_DEPTH, BF16), mc),
        jax.random.PRNGKey(0)))
    pools = abstract(jax.eval_shape(lambda: make_pools(mc, sc)))
    s = sc.max_slots
    mb = blocks_needed(KEXAONE_SEQ + sc.decode_depth, sc.block_size)
    i32, f32 = jnp.int32, jnp.float32
    if name == "decode":
        carry = {"tok": sds((s,), i32), "key": sds((s, 2), jnp.uint32)}
        return decoder._decode.lower(
            params, pools, carry,
            {"blocks": sds((s, mb), i32), "window": sds((s, mb), i32)},
            sds((s,), i32), sds((s,), jnp.bool_), sds((s,), f32),
            sds((s,), i32), sds((s,), f32), True), pools, mb
    return decoder._prefill.lower(
        params, pools,
        {"blocks": sds((mb,), i32), "window": sds((mb,), i32)}, sds((), i32),
        sds((sc.prefill_chunk,), i32), sds((), i32),
        name == "prefill_final_chunk"), pools, mb


@pytest.mark.parametrize("name",
                         ["decode", "prefill_chunk", "prefill_final_chunk"])
def test_window_gqa_serve_program_holds_four_pools_in_place(
        one_chip, for_the_chip, name):
    """The three serve programs of the K-EXAONE cell at its own settings
    and depth (the dense sliding layer, then s s g s s s g; 8 held
    experts of 6144 x 2048): FOUR pools — the global layers' k and v
    [2, 2129, 128, 1024], the sliding layers' [6, 97, 128, 1024] — all
    aliased in -> out, none copied, sliced or relayouted; 29 Mosaic
    kernels (the dense layer's windowed attention, then seven expert
    layers of one attention kernel and three grouped matmuls); a sliding
    layer's kernel walks the 2 blocks (decode) or 4 (a tile of 256 of a
    chunk's queries) its windows reach, a global layer's every block of
    the table; no
    instruction but a parameter yields an expert-stack-shaped array
    ([8, 6144, 2048], 192 MiB) (sandbox compile, PR 33: PERF.md section
    4 has the bytes)."""
    lowered, pools, mb = _kexaone_program(name, one_chip)
    # a chunk of 512 tokens x 8 query heads a kv head is more rows than a
    # step holds: it runs as two tiles of 256, each a slot of the grid
    t = 1 if name == "decode" else 512
    tq = paged_mod.query_tile(64, 8, 128, 128, t, BF16)
    assert tq == (1 if name == "decode" else 256)
    slots = (16 if name == "decode" else 1) * (t // tq)
    groups = 8 // paged_mod.heads_per_step(64, 8, 128, 128, tq, BF16)
    walked = paged_mod.window_walk_blocks(127, tq, 128)
    assert walked == (2 if name == "decode" else 4)
    attention = [g for g in _kernel_grids(lowered.as_text()) if len(g) == 3
                 and g[:2] == (slots, groups)]
    assert sorted(g[2] for g in attention) == [walked] * 6 + [mb] * 2
    compiled = lowered.compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert {name: p.shape for name, p in pools.items()} == {
        "k": (2, 2129, 128, 1024), "v": (2, 2129, 128, 1024),
        "k_win": (6, 97, 128, 1024), "v_win": (6, 97, 128, 1024)}
    pools = [pools[name] for name in ("k", "v", "k_win", "v_win")]
    assert mem.alias_size_in_bytes >= sum(
        p.size * p.dtype.itemsize for p in pools)
    assert mem.temp_size_in_bytes < (32 if name == "decode" else 256) * 2**20
    # (a chunk that is not the prompt's last feeds no head: its last
    # layer's expert matmuls are dead code, its router's counts are not)
    assert text.count("tpu_custom_call") == (
        26 if name == "prefill_chunk" else 29)
    stack = r"bf16\[8,(6144,2048|2048,6144)\]"
    copied = [line.strip()[:160] for line in text.splitlines()
              if re.search(rf"= {stack}\S* (?!parameter\()", line)]
    assert not copied, copied
    shapes = "|".join(
        re.escape(f"bf16[{','.join(str(d) for d in p.shape[i:])}]")
        for p in pools[::2] for i in (0, 1))
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(rf"= ({shapes})\S* "
                          r"(copy|dynamic-slice|dynamic-update-slice)\(",
                          line)]
    assert not moved, moved


@pytest.mark.parametrize("slots,t", [(16, 1), (1, 512)],
                         ids=["decode", "prefill_chunk"])
def test_windowed_paged_kernel_compiles_at_published_geometry(
        one_chip, for_the_chip, slots, t):
    """The grouped-query kernel under K-EXAONE's window alone: 64 query
    / 8 key-value heads of 128, a window of 127 back over blocks of 128,
    a table of 134 blocks of which it walks 2 (decode) or 4 (each of a
    chunk's two tiles of 256 queries); the pools read where they lie."""
    sds = functools.partial(_sds, sharding=one_chip)
    i32 = functools.partial(sds, dtype=jnp.int32)

    def call(q, kp, vp, tables, ctx, q0, layer):
        return paged_mod.paged_attention(
            q, kp, vp, tables, ctx, q0, layer=layer, window=(127, -1),
            impl="pallas", name="window_paged_attention")

    pool = sds((LAYERS, 97, 128, 8 * 128), BF16)
    lowered = jax.jit(call).lower(
        sds((slots, t, 64, 128), BF16), pool, pool, i32((slots, 134)),
        i32((slots,)), i32((slots,)), i32(()))
    (grid,) = _kernel_grids(lowered.as_text())
    tq = paged_mod.query_tile(64, 8, 128, 128, t, BF16)
    assert grid[0] == slots * (t // tq)
    assert grid[2] == paged_mod.window_walk_blocks(127, tq, 128) < 134
    compiled = lowered.compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert "window_paged_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


# -- layers of one mixer each: a state pool by slot beside the k/v pool ------

NEMOTRON_SERVE = dict(block_size=128, num_blocks=1296, max_slots=48,
                      prefill_chunk=512)
NEMOTRON_SEQ = 3456
NEMOTRON_DEPTH = 52


def _nemotron_program(name, one_chip):
    import json
    import types

    from chipbench.layouts import ssm_attn_moe_decoder as layout
    from chipbench.weights import ssm_attn_moe_decoder as weights
    from torchacc_tpu.models.hf import config_from_hf
    from torchacc_tpu.serve.kv_cache import blocks_needed, make_pools
    from torchacc_tpu.serve.scheduler import PagedDecoder

    pub = json.load(open(
        "chipbench/configs/nemotron-3-nano-30b-a3b.json"))["published"]
    mc = config_from_hf(types.SimpleNamespace(**pub),
                        num_layers=NEMOTRON_DEPTH, max_seq_len=NEMOTRON_SEQ,
                        param_dtype=BF16)
    sc = ServeConfig(**NEMOTRON_SERVE)
    decoder = PagedDecoder(mc, sc, "pallas")
    sds = functools.partial(_sds, sharding=one_chip)
    abstract = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: sds(x.shape, x.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda k: layout.to_program_params(
            weights.make(k, pub, NEMOTRON_DEPTH, BF16), mc),
        jax.random.PRNGKey(0)))
    pools = abstract(jax.eval_shape(lambda: make_pools(mc, sc)))
    s = sc.max_slots
    mb = blocks_needed(NEMOTRON_SEQ + sc.decode_depth, sc.block_size)
    i32, f32 = jnp.int32, jnp.float32
    if name == "decode":
        carry = {"tok": sds((s,), i32), "key": sds((s, 2), jnp.uint32)}
        return decoder._decode.lower(
            params, pools, carry, {"blocks": sds((s, mb), i32)},
            sds((s,), i32), sds((s,), jnp.bool_), sds((s,), f32),
            sds((s,), i32), sds((s,), f32), True), params, pools
    return decoder._prefill.lower(
        params, pools, {"blocks": sds((mb,), i32), "slot": sds((), i32)},
        sds((), i32), sds((sc.prefill_chunk,), i32), sds((), i32),
        name == "prefill_final_chunk"), params, pools


@pytest.mark.parametrize("name",
                         ["decode", "prefill_chunk", "prefill_final_chunk"])
def test_ssm_serve_program_fits_the_chip_with_both_pools_in_place(
        one_chip, for_the_chip, name):
    """The three serve programs of the Nemotron-3-Nano cell at its own
    settings and WHOLE depth (52 layers unrolled: 23 state-space, 23
    expert, 6 attention; 16 held experts whose kernels are stored at
    1920 for 1856): the k/v pool [6, 1296, 128, 256] x 2, the
    convolution rows [23, 49, 18432] and the float32 state
    [23, 49, 64, 64, 128] — 3.19 GiB, all aliased in -> out; beside
    11.18 GiB of weights the temporaries stay under 64 MiB (256 for the
    chunk that feeds the head), so the program fits a v5e's 15.75 GiB
    (a compile that does not fit fails here as it would on the chip);
    no instruction but a parameter yields an expert-stack- or
    state-pool-shaped array; a chunk runs 23 scan kernels (a decode
    step 23 state-update kernels), 6 attention kernels and two grouped
    matmuls an expert layer (sandbox compile, PR 42)."""
    lowered, params, pools = _nemotron_program(name, one_chip)
    compiled = lowered.compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert {name: p.shape for name, p in pools.items()} == {
        "k": (6, 1296, 128, 256), "v": (6, 1296, 128, 256),
        "conv": (23, 49, 18432), "ssm": (23, 49, 64, 64, 128)}
    pool_bytes = sum(p.size * p.dtype.itemsize for p in pools.values())
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(params))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < (
        256 if name == "prefill_final_chunk" else 64) * 2**20
    assert (pool_bytes + weight_bytes + mem.temp_size_in_bytes
            < 14.6 * 2**30)
    # (a chunk that is not the prompt's last feeds no head: its last
    # layer's expert matmuls are dead code)
    assert text.count("tpu_custom_call") == {
        "decode": 6 + 23 + 46, "prefill_chunk": 6 + 23 + 44,
        "prefill_final_chunk": 6 + 23 + 46}[name]
    shapes = r"bf16\[23,16,(2688,1920|1920,2688)\]|f32\[23,49,64,64,128\]"
    made = [line.strip()[:160] for line in text.splitlines()
            if re.search(rf"= ({shapes})\S* (copy|transpose)\(", line)]
    assert not made, made
