"""What a kind of layer keeps in the serving cache: one table.

The serving decoder (serve/scheduler.PagedDecoder) walks the model's
layer plan (models/transformer.layer_plan) and runs the model's own block
(models/block.py) on every layer; what is serving's own in a layer is its
attention over what earlier tokens left on the device.  :data:`KINDS` has
one record (:class:`Kind`) a kind of layer held today — docs/serving.md
has them side by side — and :func:`_check_supported` refuses what none of
them holds.  Serving a new kind of layer is a record here and a line in
:func:`record_of`, its kernel in ``ops/``, its weights' mapping in
models/hf.py and its scopes in obs/tracing.py: the walk, the steps and the
host loop do not learn of it.  The expert / MLP half of a layer is the
block's and is not in the table.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from torchacc_tpu.models import block, mamba2, mla
from torchacc_tpu.models.transformer import (
    MIXER_KINDS,
    kind_cfg,
    pattern_period,
    planned_layers,
)
from torchacc_tpu.ops.paged_attention import (
    index_query_tile,
    indexer_scores,
    latent_paged_attention,
    latent_query_tile,
    paged_attention,
    query_tile,
    select_topk,
)
from torchacc_tpu.serve.kv_cache import latent_row_width


# every ModelConfig field serving has been audited against — the
# rejection below is effectively an ALLOWLIST.  The block's arithmetic is
# the model's own (models/block.py): a field it reads serves as it
# trains.  What stays serving's own, and what this list guards, is the
# attention core over the paged cache (the records' ``attend``: scale,
# window, softcap, alibi), the cache row (``shapes``) and the
# layer loop (PagedDecoder._forward: stacks, patterns, pipeline): a field
# added to ModelConfig after this audit raises at engine construction
# instead of being silently ignored there (decoding tokens that diverge
# from generate() with no error).  If those three would have to read a new
# field, handle it there or in the denylist checks; then add it here.
_AUDITED_MODEL_FIELDS = frozenset({
    "activation", "attention_impl", "attn_dropout", "attn_logit_softcap",
    "cache_len", "context_parallel", "decode", "dtype", "embed_scale",
    "head_bias", "head_dim", "hidden_size", "intermediate_size",
    "layer_pattern", "logical_axis_rules", "logit_scale", "logit_softcap",
    "max_seq_len", "mlp_bias", "moe_capacity_factor", "moe_dispatch",
    "moe_renorm_topk", "norm", "norm_bias", "norm_eps", "norm_placement",
    "num_experts", "num_experts_per_tok", "num_heads", "num_kv_heads",
    "num_layers", "o_bias", "parallel_block",
    "parallel_block_shared_norm", "param_dtype", "partial_rotary",
    "pos_emb", "pp_num_micro", "pp_size", "pp_virtual", "qk_norm",
    "qk_norm_proj", "qkv_bias", "query_scale", "remat", "remat_cls",
    "remat_cnt", "remat_policy", "rope_interleaved", "rope_llama3",
    "rope_local_theta", "rope_longrope", "rope_scale", "rope_theta",
    "rope_yarn", "router_aux_weight", "sandwich_norms", "scan_layers",
    "tie_embeddings", "tp_vocab_head", "vocab_size", "window",
    # PR-7 audit: quant* select TRAIN-forward matmul execution only —
    # the param layout is unchanged and inference runs in the compute
    # dtype (generate() strips quant; block.tree_proj never
    # quantizes), so a quant-trained model serves exactly like its
    # unquantized twin.  overlap_fsdp only reshapes the train
    # layer loop (scan vs unrolled prefetch); PagedDecoder owns its
    # own loop and never consults it.
    "quant", "quant_sites", "quant_amax_history_len", "quant_impl",
    "overlap_fsdp",
    # PR-26 audit: latent attention (the 'latent' record), the two layer
    # stacks (layer_plan's dense run) and the sigmoid/grouped router,
    # shared experts and held-expert share of moe_dispatch='grouped'
    # (PagedDecoder._layer -> models/moe.moe_ffn, the module's own
    # definition)
    "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "first_dense_layers", "moe_intermediate_size",
    "moe_scoring", "moe_n_group", "moe_topk_group", "moe_route_scale",
    "moe_router_bias", "moe_shared_experts", "moe_router_width",
    "moe_first_expert",
    # PR-30 audit: two kinds of latent layer under one layer_pattern
    # (the 'latent_indexed' / 'latent_window' records and their three
    # pools, layer_plan's scan over periods), the latents' rescale and
    # the headwise gate (models/mla.py)
    "index_topk", "index_n_heads", "index_head_dim", "swa_num_heads",
    "swa_kv_lora_rank", "swa_q_lora_rank", "swa_qk_nope_head_dim",
    "swa_qk_rope_head_dim", "swa_v_head_dim", "mla_lora_rescale",
    "attn_gate",
    # PR-33 audit: windowed and full grouped-query layers under one
    # layer_pattern (the 'grouped_query_window' record over the sliding
    # layers' own k/v pools, layer_plan's scan over periods); rope_kinds
    # reaches the block through models/transformer.kind_cfg (a kind
    # without rope computes under pos_emb='none', which block.qkv reads)
    "rope_kinds",
    # PR-42 audit: layers of ONE mixer each (layer_plan walks
    # mixer_pattern: 'attention' layers on the k/v pools through the
    # 'grouped_query' record, 'moe' layers through models/moe.moe_ffn —
    # whose experts follow `activation`, swiglu or relu2, and whose
    # shared expert may have its own width —, 'mamba' layers through
    # models/mamba2 over the 'state_space' record's pools, by slot); the
    # ssm_* sizes reach only models/mamba2 and the state pools' shapes
    "mixer_pattern", "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups",
    "ssm_conv", "ssm_chunk", "moe_shared_intermediate_size",
    # PR-46 audit: YaRN on named kinds only reaches the block as
    # rope_kinds does, through models/transformer.kind_cfg (a kind
    # outside rope_yarn_kinds computes under rope_yarn=None, which
    # block._rope reads)
    "rope_yarn_kinds",
})


def _check_supported(cfg) -> None:
    """The serving surface: any dense decoder block TransformerLM
    trains (models/block.py is the one definition of both), and
    latent-attention decoders whose expert layers are the dropless
    held-expert layer (moe_dispatch='grouped') — minus what the paged
    cache, its kernel or the layer loop cannot hold, which raises a
    typed error here instead of decoding garbage."""
    unknown = ({f.name for f in dataclasses.fields(cfg)}
               - _AUDITED_MODEL_FIELDS)
    if unknown:
        raise NotImplementedError(
            f"ModelConfig grew fields the serving forward has not been "
            f"audited against: {sorted(unknown)}.  Audit their effect "
            f"on PagedDecoder's attention core, cache and layer loop "
            f"(scheduler.py) and add them to _AUDITED_MODEL_FIELDS.")
    bad = []
    if cfg.num_experts > 0 and cfg.moe_dispatch != "grouped":
        bad.append("MoE outside moe_dispatch='grouped' (the dense and "
                   "capacity dispatch paths)")
    if cfg.first_dense_layers and not cfg.num_experts:
        bad.append("first_dense_layers without expert layers")
    if cfg.kv_lora_rank and (
            cfg.pos_emb != "rope" or cfg.qk_norm or cfg.qkv_bias
            or cfg.o_bias or cfg.attn_logit_softcap or cfg.rope_scale != 1.0
            or cfg.partial_rotary != 1.0 or cfg.mlp_bias
            or cfg.activation != "swiglu"):
        bad.append("latent attention with anything but plain rope, "
                   "bias-free projections and SwiGLU")
    if cfg.pp_size > 1:
        bad.append("pipeline parallelism (pp_size > 1)")
    if cfg.context_parallel:
        bad.append("context parallelism")
    if cfg.kv_lora_rank and cfg.swa_kv_lora_rank:
        # windowed and full latent layers under one pattern: admitted
        # where the scan over periods and the three pools can hold it
        dense, period = pattern_period(cfg)
        if (not cfg.layer_pattern or set(cfg.layer_pattern)
                - {"global", "sliding"} or cfg.window[0] < 0
                or cfg.window[1] >= 0 or not cfg.index_topk
                or not cfg.num_experts or not cfg.first_dense_layers
                or "sliding" in dense or not cfg.q_lora_rank):
            bad.append("two kinds of latent layer in any arrangement but: "
                       "a layer_pattern of 'global' (indexed) and "
                       "'sliding' (left window) layers, the leading dense "
                       "layers all 'global', expert layers after them")
        if cfg.attn_gate not in ("none", "headwise"):
            bad.append(f"attn_gate {cfg.attn_gate!r}")
    else:
        if cfg.layer_pattern:
            # windowed and full grouped-query layers under one pattern:
            # admitted where the scan over periods and the pools of two
            # geometries can hold it
            dense, _ = pattern_period(cfg)
            if (cfg.kv_lora_rank
                    or set(cfg.layer_pattern) - {"global", "sliding"}
                    or cfg.window[0] < 0 or not cfg.num_experts
                    or not cfg.first_dense_layers or len(set(dense)) != 1):
                bad.append("layer_pattern on grouped-query pools in any "
                           "arrangement but: 'global' and 'sliding' (left "
                           "window) layers, leading dense layers of one "
                           "kind, expert layers after them (and on the "
                           "pool of a one-kind latent model)")
            if cfg.window[1] >= 0:
                bad.append(f"a two-sided window {cfg.window} (the paged "
                           f"cache holds no position after a query)")
        elif tuple(cfg.window) != (-1, -1):
            bad.append(f"sliding window {cfg.window} without a "
                       f"layer_pattern")
        elif cfg.rope_kinds is not None:
            bad.append("rope_kinds without a layer_pattern")
        if (cfg.index_topk or cfg.swa_kv_lora_rank
                or cfg.attn_gate != "none" or cfg.mla_lora_rescale):
            bad.append("indexed selection, a headwise gate or rescaled "
                       "latents outside the latent family of two kinds")
    if cfg.mixer_pattern:
        # layers of one mixer each: admitted with what the slot state
        # and the attention layers' one k/v pool can hold
        if set(cfg.mixer_pattern) - set(MIXER_KINDS):
            bad.append(f"mixer_pattern entries other than {MIXER_KINDS}")
        if len(cfg.mixer_pattern) < cfg.num_layers:
            bad.append(f"a mixer_pattern of {len(cfg.mixer_pattern)} entries "
                       f"for {cfg.num_layers} layers")
        if tuple(cfg.window) != (-1, -1) or cfg.layer_pattern:
            bad.append("a window beside state-space layers (a mixer_pattern "
                       "with a sliding window or a layer_pattern)")
        if cfg.kv_lora_rank:
            bad.append("latent keys beside state-space layers (a "
                       "mixer_pattern with kv_lora_rank)")
        if cfg.first_dense_layers:
            bad.append("first_dense_layers with a mixer_pattern (a layer "
                       "holds one mixer)")
        if (cfg.norm_placement != "pre" or cfg.parallel_block
                or cfg.sandwich_norms):
            bad.append("a mixer_pattern with anything but one pre-norm a "
                       "layer")
        if "moe" in cfg.mixer_pattern and not cfg.num_experts:
            bad.append("'moe' layers in a mixer_pattern without experts")
        if "mamba" in cfg.mixer_pattern and (
                min(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                    cfg.ssm_groups) < 1 or cfg.ssm_conv < 2
                or cfg.ssm_heads % cfg.ssm_groups):
            bad.append("'mamba' layers without their sizes (ssm_heads a "
                       "multiple of ssm_groups, ssm_head_dim, ssm_state, "
                       "ssm_conv >= 2)")
    elif cfg.moe_shared_intermediate_size is not None:
        bad.append("moe_shared_intermediate_size outside a mixer_pattern")
    if cfg.activation == "relu2" and cfg.num_experts \
            and not cfg.mixer_pattern:
        bad.append("relu2 experts outside a mixer_pattern")
    if cfg.pos_emb == "alibi":
        bad.append("pos_emb='alibi'")
    if bad:
        raise NotImplementedError(
            "the serving engine (torchacc_tpu/serve) does not yet "
            "support: " + ", ".join(bad) + ".  Use models.generate for "
            "these models (batch-synchronous decode covers the full "
            "model zoo).")


# -- a record's ``attend``: write-before-read over its own pools --------------

def _attend_grouped_query(cfg, impl, attn, layer, h, own, positions, addr,
                          ctx_lens, scope="paged_attn",
                          name="paged_attention"):
    """Grouped-query attention over a k and a v pool [L, NB, BS, KH*D].
    ``cfg`` carries the kind's window and rope; the window layers' kernel
    call has its own ``name`` and ``scope``: a profile reads the two
    kinds apart."""
    kp, vp = own
    tables, blk, off = addr
    s_, t_ = h.shape[:2]
    proj = block.tree_proj(cfg, attn)
    with jax.named_scope("qkv"):
        q, k, v = block.qkv(cfg, h, positions, proj,
                            block.tree_norm(cfg, attn))
    # bank this chunk's (rotated) k / raw v into the pool, THEN
    # attend over the updated pool — same write-before-read order
    # as the module's dense-cache decode branch.  One scatter per
    # pool: token n's [KH*D] row lands at (layer, block, offset),
    # a contiguous window of the carried buffer, updated in place
    flat_b, flat_o = blk.reshape(-1), off.reshape(-1)
    with jax.named_scope("kv_write"):
        kp = kp.at[layer, flat_b, flat_o].set(
            k.reshape(s_ * t_, -1).astype(kp.dtype))
        vp = vp.at[layer, flat_b, flat_o].set(
            v.reshape(s_ * t_, -1).astype(vp.dtype))
    with jax.named_scope(scope):
        out = paged_attention(
            q, kp, vp, tables, ctx_lens, positions[:, 0], layer=layer,
            scale=cfg.query_scale, window=cfg.window,
            logit_softcap=cfg.attn_logit_softcap, impl=impl, name=name)
    with jax.named_scope("o_proj"):
        return proj("o_proj", out), (kp, vp)


def _bank_latent(cfg, attn, pool, layer, h, positions, blk, off):
    """Project this chunk's latent rows and write them in place:
    ``pool`` with ``[c_kv | rope(k_pe) | padding]`` at (layer, blk,
    off)."""
    s_, t_ = h.shape[:2]
    with jax.named_scope("mla_kv"):
        c_kv, k_pe = mla.project_latent(cfg, attn, h, positions)
        row = jnp.concatenate([c_kv, k_pe], axis=-1)
        row = jnp.pad(row, ((0, 0), (0, 0),
                            (0, pool.shape[-1] - row.shape[-1])))
    with jax.named_scope("kv_write"):
        return pool.at[layer, blk.reshape(-1), off.reshape(-1)].set(
            row.reshape(s_ * t_, -1).astype(pool.dtype))


def _select(cfg, impl, attn, layer, h, c_q, keys, positions, addr, ctx_lens):
    """The indexed selection of a 'global' layer of a model of two
    latent kinds: the token banks ONE index key in the index-key pool,
    same block and offset as its latent row; the indexer kernel scores
    every visible position of the slot and :func:`select_topk` finds the
    exact ``index_topk`` best.  While no slot holds more than k
    positions the selection is every position and the search for the
    k-th best is skipped (its cache writes are not).  Returns ``(keys,
    the queries' first positions, (scores, thr, tie_hi))``."""
    tables, blk, off = addr
    s_, t_ = h.shape[:2]
    with jax.named_scope("index_write"):
        k_idx = mla.index_key(cfg, attn, h, positions)
    with jax.named_scope("kv_write"):
        keys = keys.at[layer, blk.reshape(-1), off.reshape(-1)].set(
            k_idx.reshape(s_ * t_, -1).astype(keys.dtype))
    q_start = positions[:, 0]
    with jax.named_scope("indexer"):
        scores = indexer_scores(
            mla.index_query(cfg, attn, c_q, positions).astype(keys.dtype),
            mla.index_weights(cfg, attn, h), keys, tables, ctx_lens,
            q_start, layer=layer, impl=impl)
    with jax.named_scope("index_topk"):
        thr, tie_hi = jax.lax.cond(
            jnp.max(ctx_lens) <= cfg.index_topk,
            lambda sc: (jnp.full(sc.shape[:2], -jnp.inf, jnp.float32),
                        jnp.zeros(sc.shape[:2], jnp.int32)),
            lambda sc: select_topk(sc, cfg.index_topk), scores)
    return keys, q_start, (scores, thr, tie_hi)


def _attend_latent(cfg, impl, attn, layer, h, own, positions, addr, ctx_lens,
                   scope="latent_attn", name="latent_paged_attention",
                   gated=False):
    """Latent attention in the absorbed form (models/mla.py) over
    the one pool [L, NB, BS, W]: a token banks the row
    ``[c_kv | rope(k_pe)]`` (padded to W lanes), written in place
    like a k row; the kernel reads it as key and value of every
    head; ``W_kvb`` is folded into the query and the output.  The two
    kinds of a model of two (``gated``: the headwise gate between
    ``W_kvb^V`` and ``W_o``) run it under their own ``scope`` and kernel
    ``name``: a 'sliding' layer of its own sizes over ``cfg.window``
    positions back, in the window layers' pool through their own table
    (entries before the window are 0: freed, never read); a 'global'
    layer, which also owns the index keys, over the positions
    :func:`_select` chose."""
    pool, *keys = own
    tables, blk, off = addr
    with jax.named_scope("mla_q"):
        c_q = mla.latent_q(cfg, attn, h) if keys else None
        q_nope, q_pe = mla.project_q(cfg, attn, h, positions, c_q)
        q_lat = mla.absorb_q(cfg, attn, q_nope)
    own = (_bank_latent(cfg, attn, pool, layer, h, positions, blk, off),)
    q_start = selection = None
    if keys:
        index, q_start, selection = _select(
            cfg, impl, attn, layer, h, c_q, keys[0], positions, addr,
            ctx_lens)
        own += (index,)
    with jax.named_scope(scope):
        o_lat = latent_paged_attention(
            q_lat, q_pe.astype(q_lat.dtype), own[0], tables, ctx_lens,
            positions[:, 0] if q_start is None else q_start, layer=layer,
            scale=mla.query_scale(cfg), impl=impl, window=cfg.window[0],
            selection=selection, name=name)
    if not gated:
        with jax.named_scope("o_proj"):
            return mla.project_out(
                cfg, attn, mla.expand_out(cfg, attn, o_lat)), own
    with jax.named_scope("attn_gate"):
        out = mla.head_gate(cfg, attn, h, mla.expand_out(cfg, attn, o_lat))
    with jax.named_scope("o_proj"):
        return mla.project_out(cfg, attn, out), own


def _attend_state(cfg, impl, mixer, layer, h, own, positions, addr,
                  ctx_lens):
    """A state-space layer (models/mamba2) over its state by slot: the
    convolution's last inputs and the recurrent state.  ``addr`` says
    how: ``{'active': [S]}`` in a decode step (slot i's state at index
    i), ``{'slots', 'fresh', 'n_valid'}`` [R] of a prefill's rows.
    Nothing copies a pool: the scan kernel takes it whole and the
    layer's index."""
    step = mamba2.mixer_step if "active" in addr else mamba2.mixer_chunk
    with jax.named_scope("ssm_mixer"):
        out, conv, ssm = step(cfg, mixer, h, *own, layer, **addr, impl=impl)
    return out, (conv, ssm)


# -- a record's ``shapes``: ``(shape, dtype, logical axes or None)`` a pool ---

def _kv_shapes(cfg, n, rows, block_size, dtype):
    # a row splits at head boundaries only: tp must divide the HEADS
    # (the constraint alone would split 128 lanes of one MQA head)
    tp = dict(jax.sharding.get_abstract_mesh().shape).get("tp", 1)
    axes = (None, None, None, "heads" if cfg.kv_heads % tp == 0 else None)
    shape = (n, rows, block_size, cfg.kv_heads * cfg.head_size)
    return [(shape, dtype, axes)] * 2


def _latent_shapes(cfg, n, rows, block_size, dtype):
    # no head dimension (every head reads the same row): replicated
    return [((n, rows, block_size, latent_row_width(cfg)), dtype, None)]


def _indexed_shapes(cfg, n, rows, block_size, dtype):
    # a token's latent row and its index key lie at the same block and
    # offset: one table addresses both
    return _latent_shapes(cfg, n, rows, block_size, dtype) + [
        ((n, rows, block_size, cfg.index_head_dim), dtype, None)]


def _state_shapes(cfg, n, rows, block_size, dtype):
    # the convolution's last inputs [L, slots + 1, (K - 1) * channels] and
    # the recurrent state [L, slots + 1, Hm, P, N] in float32, the same
    # size at any context
    return [((n, rows, (cfg.ssm_conv - 1) * mamba2.conv_width(cfg)), dtype,
             None),
            ((n, rows, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
             jnp.float32, None)]


# -- a record's ``tile``: ValueError where its kernel cannot tile the step ---

def _tile_grouped_query(cfg, block_size, t, dtype):
    query_tile(cfg.num_heads, cfg.kv_heads, cfg.head_size, block_size, t,
               dtype)


def _tile_latent(cfg, block_size, t, dtype, select=False):
    latent_query_tile(cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim,
                      block_size, t, dtype, select)


def _tile_indexed(cfg, block_size, t, dtype):
    _tile_latent(cfg, block_size, t, dtype, True)
    index_query_tile(cfg.index_n_heads, cfg.index_head_dim, block_size, t,
                     dtype)


@dataclasses.dataclass(frozen=True)
class Kind:
    """One kind of layer's share of the serving cache.

    ``by`` is what addresses its pools in a step: the sequence's block
    table ('blocks': reserved whole at admission), the window layers'
    table ('window': kv_cache.WindowBlocks, a block held only while the
    window reaches it) or the decode slot ('slot': state of one size at
    any context).  ``names`` are the pools it owns in
    serve/kv_cache.make_pools' dict, ``shapes(cfg, n, rows, block_size,
    dtype)`` theirs for ``n`` layers and ``rows`` of what addresses them
    (blocks, or slots and the null slot).  ``cfg(model_cfg, kind)`` is the
    config its layers compute under.  ``attend(cfg, impl, tree, layer, h,
    own, positions, addr, ctx_lens) -> (out, own')`` is the attention
    half of the layer at row ``layer`` of the pools on the normed ``h``
    [S, T, H]: ``tree`` the layer's ``params`` subtree, ``own`` its pools
    in the order of ``names`` (whole stacks), ``addr`` what ``by`` says —
    for a table ``(tables [S, MB], blk [S, T], off [S, T])``, the pool
    slot every token writes its row to (the null block for masked
    tokens), with ``ctx_lens`` the post-write context length a slot.
    ``tile(cfg, block_size, t, dtype)`` raises ``ValueError`` where its
    kernel cannot tile ``t`` query tokens a slot over such blocks."""

    by: str
    names: Tuple[str, ...]
    cfg: Callable[[Any, str], Any]
    shapes: Callable[..., Any]
    tile: Callable[..., None]
    attend: Callable[..., Any]
    params: str = "attn"


KINDS: Dict[str, Kind] = {
    "grouped_query": Kind(
        "blocks", ("k", "v"),
        lambda cfg, kind: kind_cfg(cfg, kind) if kind else cfg,
        _kv_shapes, _tile_grouped_query, _attend_grouped_query),
    "grouped_query_window": Kind(
        "window", ("k_win", "v_win"), kind_cfg, _kv_shapes,
        _tile_grouped_query,
        functools.partial(_attend_grouped_query, scope="window_paged_attn",
                          name="window_paged_attention")),
    "latent": Kind(
        "blocks", ("latent",), mla.kind_config, _latent_shapes,
        _tile_latent, _attend_latent),
    "latent_indexed": Kind(
        "blocks", ("latent", "index"), mla.kind_config, _indexed_shapes,
        _tile_indexed,
        functools.partial(_attend_latent, scope="sparse_latent_attn",
                          name="sparse_latent_attention", gated=True)),
    "latent_window": Kind(
        "window", ("latent_win",), mla.kind_config, _latent_shapes,
        _tile_latent,
        functools.partial(_attend_latent, scope="window_latent_attn",
                          name="window_latent_attention", gated=True)),
    "state_space": Kind(
        "slot", ("conv", "ssm"), lambda cfg, kind: cfg, _state_shapes,
        lambda cfg, block_size, t, dtype: None, _attend_state,
        params="mixer"),
}


def record_of(cfg, kind: str) -> str:
    """The record that serves a layer of the plan's ``kind``
    (models/transformer.layer_kinds; '' in a model of one kind) in a
    model :func:`_check_supported` admits."""
    if kind == "mamba":
        return "state_space"
    if kind == "sliding":
        return "latent_window" if cfg.kv_lora_rank else "grouped_query_window"
    if not cfg.kv_lora_rank:
        return "grouped_query"
    return "latent_indexed" if kind == "global" else "latent"


def kinds_of(cfg) -> Dict[str, Tuple[Kind, Any, int]]:
    """``{kind: (its record, the config its layers compute under, how
    many layers of it)}`` for every kind of the model's plan that keeps
    something in the cache, in the plan's order.  (A ``mixer_pattern``'s
    'moe' layers keep nothing: they are not in it.)"""
    count: Dict[str, int] = {}
    for at in planned_layers(cfg):
        count[at.kind] = count.get(at.kind, 0) + 1
    kinds = {}
    for kind, n in count.items():
        if not (kind == "moe" and cfg.mixer_pattern):
            record = KINDS[record_of(cfg, kind)]
            kinds[kind] = (record, record.cfg(cfg, kind), n)
    return kinds
