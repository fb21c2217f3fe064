"""Continuous-batching scheduler over the paged KV cache.

The design mirrors the PR-5 trainer split (train/trainer.py): a
STATELESS JITTED device step over (params, pools, slot state) and a
HOST-SIDE loop that owns every decision — admission into free slots,
which sequence prefills this iteration, eviction of finished sequences,
block free/reuse.  Three compiled programs cover any request mix:

- ``decode_step``: one token for every slot in one batched program.
  Sampling runs ON DEVICE with per-slot traced (temperature, top_k,
  top_p), and the sampled tokens feed the next iteration's input as a
  device array — the token feedback loop never touches the host.
- ``prefill_chunk``: ``serve.prefill_chunk`` tokens of ONE sequence
  (padded; the pad tail writes to the null block), interleaved with
  decode so a long prompt never stalls in-flight decodes.  With
  ``serve.prefill_batch > 1`` one iteration instead prefills up to
  that many chunks from DISTINCT waiting sequences in a single
  dispatched program (rows padded to the [prefill_batch,
  prefill_chunk] geometry — trace count stays 1; the head projects
  only each row's last valid token, the one row whose logits anyone
  reads).
- ``sample_first`` / ``set_slot``: sample the first token from the
  final prefill chunk's logits and splice it into the decode carry —
  tiny jitted ops, no readback.
- ``cow``: copy one pool block's k/v to another across all layers —
  the copy-on-write step behind a fully-cached prompt (see admit()).

Prefix cache (``serve.prefix_cache`` — kv_cache.PrefixIndex): admit()
maps the longest token-hash-chain match of a new prompt onto resident
blocks (refcount++ — zero recompute, zero copies) and starts prefill
past them; when the match covers the WHOLE prompt, the last matched
block is copy-on-written into a private block and only the final
prompt token re-runs (its logits are needed to sample the first output
token; its k/v write lands in the private copy, never the shared
block), so a warm prompt's TTFT is one final-chunk dispatch.  Blocks
register in the index as their prefill chunk completes, which means a
live sequence's prompt blocks are matchable immediately — concurrent
requests behind the same system prompt share from the first one that
prefilled it, not the first one that finished.

Host reads happen only at lag ``serve.decode_depth - 1`` through the
in-flight ring (the PR-5 lagged-readback pattern): iteration i's
sampled tokens are fetched while iteration i+k is dispatching, so the
per-token host sync sits off the critical path.  Consequences the
engine handles:

- a sequence is noticed finished (eos / max_new) up to k iterations
  late; the extra garbage tokens are dropped on the host;
- its blocks are freed DEFERRED — only after every dispatched
  iteration that could still write through the old block table has
  resolved — so a freed block can never alias a live sequence's cache
  (tested: test_block_free_never_aliases_live_blocks).

Admission therefore reserves ``prompt + max_new + decode_depth``
token slots of blocks up front: the overhang covers in-flight
iterations that keep writing after the finish condition.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchacc_tpu.config import ConfigError
from torchacc_tpu.models import block
from torchacc_tpu.models.transformer import (
    MIXER_KINDS,
    embed_ids,
    head_logits,
    kind_cfg,
    layer_kinds,
    pattern_period,
)
from torchacc_tpu.obs import tracing
from torchacc_tpu.ops._common import on_tpu
from torchacc_tpu.ops.paged_attention import (
    index_query_tile,
    indexer_scores,
    latent_paged_attention,
    latent_query_tile,
    paged_attention,
    query_tile,
    select_topk,
)
from torchacc_tpu.resilience.chaos import failpoint
from torchacc_tpu.serve.kv_cache import (
    BlockPool,
    PrefixIndex,
    WindowBlocks,
    blocks_needed,
    make_pools,
    num_window_blocks,
    window_blocks_bound,
)
from torchacc_tpu.utils.logger import logger
from torchacc_tpu.utils.metrics import counters


# every ModelConfig field serving has been audited against — the
# rejection below is effectively an ALLOWLIST.  The block's arithmetic is
# the model's own (models/block.py): a field it reads serves as it
# trains.  What stays serving's own, and what this list guards, is the
# attention core over the paged cache (_attend/_attend_latent: scale,
# window, softcap, alibi), the cache row (serve/kv_cache.py) and the
# layer loop (_forward: stacks, patterns, pipeline): a field added to
# ModelConfig after this audit raises at engine construction instead of
# being silently ignored there (decoding tokens that diverge from
# generate() with no error).  If those three would have to read a new
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
    # PR-26 audit: latent attention (_attend_latent), the two layer
    # stacks (_forward) and the sigmoid/grouped router, shared experts
    # and held-expert share of moe_dispatch='grouped' (_moe ->
    # models/moe.moe_ffn, the module's own definition)
    "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "first_dense_layers", "moe_intermediate_size",
    "moe_scoring", "moe_n_group", "moe_topk_group", "moe_route_scale",
    "moe_router_bias", "moe_shared_experts", "moe_router_width",
    "moe_first_expert",
    # PR-30 audit: two kinds of latent layer under one layer_pattern
    # (_attend_sparse / _attend_window, the three pools of
    # serve/kv_cache.py, _forward's scan over periods), the latents'
    # rescale and the headwise gate (models/mla.py)
    "index_topk", "index_n_heads", "index_head_dim", "swa_num_heads",
    "swa_kv_lora_rank", "swa_q_lora_rank", "swa_qk_nope_head_dim",
    "swa_qk_rope_head_dim", "swa_v_head_dim", "mla_lora_rescale",
    "attn_gate",
    # PR-33 audit: windowed and full grouped-query layers under one
    # layer_pattern (_attend's window kind over the sliding layers' own
    # k/v pools, _forward's scan over periods); rope_kinds reaches the
    # block through models/transformer.kind_cfg (a kind without rope
    # computes under pos_emb='none', which block.qkv reads)
    "rope_kinds",
    # PR-42 audit: layers of ONE mixer each (_forward_mixers walks
    # mixer_pattern: 'attention' layers on the k/v pools through _attend,
    # 'moe' layers through models/moe.moe_ffn — whose experts follow
    # `activation`, swiglu or relu2, and whose shared expert may have its
    # own width —, 'mamba' layers through models/mamba2 over the state
    # pools of serve/kv_cache.py, by slot); the ssm_* sizes reach only
    # models/mamba2 and the state pools' shapes
    "mixer_pattern", "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups",
    "ssm_conv", "ssm_chunk", "moe_shared_intermediate_size",
})


#: ``(kinds of the leading dense layers, kinds of one period)`` of a
#: model whose layer_pattern names two kinds of layer (the name
#: chipbench/layouts reads it under)
_period = pattern_period


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
        dense, period = _period(cfg)
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


def _upload(host_mirror: np.ndarray) -> jax.Array:
    """Device copy of a host array the scheduler goes on mutating in
    place.  The CPU backend may alias a numpy buffer instead of copying
    it, and an in-flight step would then read the mutation; a private
    copy that nothing else touches makes the alias harmless."""
    return jnp.asarray(host_mirror.copy())


#: leaves of an expert layer's ``moe`` tree that the layer scan does not
#: slice (PagedDecoder._forward)
_EXPERT_STACKS = ("experts/gate", "experts/up", "experts/down")


class PagedDecoder:
    """The jitted device steps: the model's forward on raw params over
    the paged pool — ``embed_ids`` / ``head_logits`` and the block of
    models/block.py, the definitions the module's own apply runs, with
    the attention core and the layer loop that are serving's own."""

    def __init__(self, cfg, serve_cfg, attention_impl: Optional[str] = None):
        _check_supported(cfg)
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        # resolve 'auto' once, here, so a block size or a head size the
        # kernel cannot tile is a typed error at construction and not a
        # lowering failure inside the first request
        impl = attention_impl or cfg.attention_impl
        if impl == "auto":
            impl = "pallas" if on_tpu() else "xla"
        # two kinds of layer under one pattern (_check_supported admits
        # no pattern otherwise): latent ones, or grouped-query ones
        self.two_kinds = bool(cfg.layer_pattern)
        self.latent_kinds = bool(cfg.swa_kv_lora_rank)
        # layers of one mixer each, state-space layers among them: their
        # state lives by slot, beside the attention layers' paged pool
        self.mixers = bool(cfg.mixer_pattern)
        if self.mixers:
            if serve_cfg.prefix_cache:
                raise NotImplementedError(
                    "the serving engine does not yet support prefix "
                    "sharing with state-space layers (serve.prefix_cache "
                    "with a mixer_pattern: a cached block holds keys and "
                    "values, the recurrent state at its end is kept "
                    "nowhere, so a shared prefix could not be resumed)")
            if ("mamba" in cfg.mixer_pattern and serve_cfg.prefill_chunk
                    > cfg.ssm_chunk and serve_cfg.prefill_chunk
                    % cfg.ssm_chunk):
                raise ConfigError(
                    f"serve.prefill_chunk={serve_cfg.prefill_chunk} is not "
                    f"whole sub-chunks of the state-space scan "
                    f"(ssm_chunk={cfg.ssm_chunk})")
        if self.two_kinds:
            if serve_cfg.prefix_cache:
                raise NotImplementedError(
                    "the serving engine does not yet support prefix "
                    "sharing across window layers (serve.prefix_cache "
                    "with windowed layers: a window layer's blocks are "
                    "freed as the window passes, so a cached prefix has "
                    "no rows left to share there)")
            of_kind = kind_cfg
            if self.latent_kinds:
                from torchacc_tpu.models.mla import kind_config as of_kind
            self._full_cfg = of_kind(cfg, "global")
            self._win_cfg = of_kind(cfg, "sliding")
        if impl == "pallas":
            for t in (1, serve_cfg.prefill_chunk):
                try:
                    if self.latent_kinds:
                        f, w = self._full_cfg, self._win_cfg
                        latent_query_tile(
                            f.num_heads, f.kv_lora_rank, f.qk_rope_head_dim,
                            serve_cfg.block_size, t, cfg.dtype, True)
                        latent_query_tile(
                            w.num_heads, w.kv_lora_rank, w.qk_rope_head_dim,
                            serve_cfg.block_size, t, cfg.dtype)
                        index_query_tile(
                            cfg.index_n_heads, cfg.index_head_dim,
                            serve_cfg.block_size, t, cfg.dtype)
                    elif cfg.kv_lora_rank:
                        latent_query_tile(
                            cfg.num_heads, cfg.kv_lora_rank,
                            cfg.qk_rope_head_dim, serve_cfg.block_size, t,
                            cfg.dtype)
                    else:
                        query_tile(cfg.num_heads, cfg.kv_heads,
                                   cfg.head_size, serve_cfg.block_size, t,
                                   cfg.dtype)
                except ValueError as e:
                    raise ConfigError(
                        f"serve.block_size={serve_cfg.block_size}, "
                        f"serve.prefill_chunk={serve_cfg.prefill_chunk} "
                        f"with {cfg.kv_heads} kv heads of head size "
                        f"{cfg.head_size}: {e}") from e
        self.impl = impl
        self.block_size = serve_cfg.block_size
        self.chunk = serve_cfg.prefill_chunk
        self.max_slots = serve_cfg.max_slots
        # pools are donated: every step consumes and returns them, so
        # XLA updates the one preallocated buffer in place.  all_greedy
        # is static: the all-greedy trace (the serving default) skips
        # the two full-vocab sampling sorts entirely — argmax only —
        # while the mixed trace keeps the one-program-per-request-mix
        # property; both advance the slot PRNG keys identically, so
        # flipping between variants cannot drift a sampled stream
        # (win_tables, an optional last argument, is None but for a model
        # with window layers: their block table)
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1, 2),
                               static_argnums=(9,))
        # is_final is static: the non-final trace skips the vocab head
        # entirely (its logits are discarded), the final trace keeps
        # the full-chunk head so first-token numerics are unchanged
        self._prefill = jax.jit(self._prefill_impl, donate_argnums=(1,),
                                static_argnums=(6,))
        # batched multi-sequence prefill: ONE trace for any mix of
        # final/non-final/padded rows (the head projects only the
        # gathered last-valid row of each sequence — [PB, H] x [H, V],
        # a decode-step-sized matmul, so there is no non-final trace to
        # skip it)
        self._prefill_batch = jax.jit(self._prefill_batch_impl,
                                      donate_argnums=(1,))
        self._sample_first = jax.jit(self._sample_first_impl)
        self._set_slot = jax.jit(self._set_slot_impl, donate_argnums=(0,))
        # copy-on-write: clone one pool block across all layers (the
        # fully-cached-prompt path in Scheduler.admit)
        self._cow = jax.jit(self._cow_impl, donate_argnums=(0,))

    # -- model forward ------------------------------------------------------

    def _layer(self, p, layer, x, pools, positions, tables, ctx_lens, blk,
               off, valid=None, expert_stacks=None, kind="",
               expert_layer=None):
        """Decoder layer ``layer`` over the paged cache: the model's own
        block (models/block.py) on this layer's raw tree ``p``, with the
        two halves that are serving's own.  The attention is
        grouped-query over a k and a v pool or latent over one pool
        (``cfg.kv_lora_rank``), the feed-forward the model's MLP or the
        held-expert layer (the layer's tree holds ``mlp`` or ``moe``).
        ``pools`` are the whole stacks; ``blk``/``off`` [S, T] name the
        pool slot every token writes its row to (the null block for
        masked tokens); ``ctx_lens`` is the post-write context length
        per slot; ``valid`` [S, T] marks the real tokens (the expert
        layer routes no others); ``expert_stacks`` the expert kernels of
        ALL expert layers (:meth:`_forward` keeps them off the scan),
        read by the grouped matmul at this layer's index among them
        (``expert_layer``; None = ``layer`` less the dense ones).  In a
        model of two kinds of layer ``kind`` names this layer's
        ('global': the full layers' pools — under an indexed selection
        in a latent model —, 'sliding': the window layers' pools);
        ``layer`` is then its index among the layers of its kind,
        ``tables`` and ``blk`` pairs ``(full, window)``.  Returns ``(x, pools, load)``, ``load`` the expert
        layer's counts or None."""
        cfg = self.cfg
        if kind:
            cfg = self._full_cfg if kind == "global" else self._win_cfg
            which = int(kind == "sliding")
            tables, blk = tables[which], blk[which]
        if cfg.num_experts and "moe" not in p:
            # a leading dense layer of an expert model: TransformerLM
            # gives that stack's blocks this config too
            cfg = dataclasses.replace(cfg, num_experts=0)
        attend = (functools.partial(self._attend, cfg=cfg, kind=kind)
                  if not cfg.kv_lora_rank else
                  {"": self._attend_latent, "global": self._attend_sparse,
                   "sliding": self._attend_window}[kind])
        # what the halves leave besides their output: the updated pools,
        # the expert layer's counts (all traced in this layer's own trace)
        left = {"load": None}

        # the named scopes are registered device scopes (obs/tracing.py
        # DEVICE_SCOPES): a profiler trace reads each part's device
        # time under the same names the training step's modules carry
        def norm(name, t, cfg=cfg):
            with jax.named_scope(name):
                return block.tree_norm(cfg, p)(name, t)

        def attention(h):
            out, left["pools"] = attend(p["attn"], layer, h, pools,
                                        positions, tables, ctx_lens, blk, off)
            return out

        def ffn(h2):
            if not cfg.num_experts:
                with jax.named_scope("mlp"):
                    return block.mlp(cfg, h2, block.tree_proj(cfg, p["mlp"]))
            from torchacc_tpu.models.moe import moe_ffn
            s_, t_, hd = h2.shape
            y, _, _, left["load"] = moe_ffn(
                cfg, {**p["moe"], **expert_stacks},
                h2.reshape(s_ * t_, hd),
                None if valid is None else valid.reshape(-1),
                layer=(layer - cfg.first_dense_layers
                       if expert_layer is None else expert_layer))
            return y.reshape(s_, t_, hd)

        x = block.block(cfg, x, norm, attention, ffn)
        return x, left["pools"], left["load"]

    def _attend(self, attn, layer, h, pools, positions, tables, ctx_lens,
                blk, off, *, cfg, kind=""):
        """Grouped-query attention of the normed ``h`` over the k and v
        pools [L, NB, BS, KH*D]: ``(output before the residual,
        pools)``.  ``cfg`` is the layer's own (a kind's window and rope);
        in a model of two kinds the pools are ``(k, v)`` of the global
        layers then ``(k, v)`` of the sliding ones, and a 'sliding'
        layer's kernel call carries its own name and scope: a profile
        reads the two kinds apart."""
        at = 2 * (kind == "sliding")
        kp, vp = pools[at:at + 2]
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
        scope, name = (("window_paged_attn", "window_paged_attention") if at
                       else ("paged_attn", "paged_attention"))
        with jax.named_scope(scope):
            out = paged_attention(
                q, kp, vp, tables, ctx_lens, positions[:, 0], layer=layer,
                scale=cfg.query_scale, window=cfg.window,
                logit_softcap=cfg.attn_logit_softcap, impl=self.impl,
                name=name)
        with jax.named_scope("o_proj"):
            return proj("o_proj", out), pools[:at] + (kp, vp) + pools[at + 2:]

    def _attend_latent(self, attn, layer, h, pools, positions, tables,
                       ctx_lens, blk, off):
        """Latent attention in the absorbed form (models/mla.py) over
        the one pool [L, NB, BS, W]: a token banks the row
        ``[c_kv | rope(k_pe)]`` (padded to W lanes), written in place
        like a k row; the kernel reads it as key and value of every
        head; ``W_kvb`` is folded into the query and the output."""
        from torchacc_tpu.models import mla

        cfg = self.cfg
        (pool,) = pools
        with jax.named_scope("mla_q"):
            q_nope, q_pe = mla.project_q(cfg, attn, h, positions)
            q_lat = mla.absorb_q(cfg, attn, q_nope)
        pool = self._bank_latent(cfg, attn, pool, layer, h, positions, blk,
                                 off)
        with jax.named_scope("latent_attn"):
            o_lat = latent_paged_attention(
                q_lat, q_pe.astype(q_lat.dtype), pool, tables, ctx_lens,
                positions[:, 0], layer=layer, scale=mla.query_scale(cfg),
                impl=self.impl)
        with jax.named_scope("o_proj"):
            return mla.project_out(
                cfg, attn, mla.expand_out(cfg, attn, o_lat)), (pool,)

    def _bank_latent(self, cfg, attn, pool, layer, h, positions, blk, off):
        """Project this chunk's latent rows and write them in place:
        ``pool`` with ``[c_kv | rope(k_pe) | padding]`` at (layer, blk,
        off)."""
        from torchacc_tpu.models import mla
        s_, t_ = h.shape[:2]
        with jax.named_scope("mla_kv"):
            c_kv, k_pe = mla.project_latent(cfg, attn, h, positions)
            row = jnp.concatenate([c_kv, k_pe], axis=-1)
            row = jnp.pad(row, ((0, 0), (0, 0),
                                (0, pool.shape[-1] - row.shape[-1])))
        with jax.named_scope("kv_write"):
            return pool.at[layer, blk.reshape(-1), off.reshape(-1)].set(
                row.reshape(s_ * t_, -1).astype(pool.dtype))

    def _gated_out(self, cfg, attn, h, o_lat):
        """Latent outputs -> the block's attention output: ``W_kvb^V``,
        the headwise gate, ``W_o``."""
        from torchacc_tpu.models import mla
        with jax.named_scope("attn_gate"):
            out = mla.head_gate(cfg, attn, h,
                                mla.expand_out(cfg, attn, o_lat))
        with jax.named_scope("o_proj"):
            return mla.project_out(cfg, attn, out)

    def _attend_sparse(self, attn, layer, h, pools, positions, tables,
                       ctx_lens, blk, off):
        """A 'global' layer of a model of two latent kinds: latent
        attention over the ``index_topk`` cached positions its indexer
        scores highest.  The token banks its latent row in the full
        layers' pool and ONE index key in the index-key pool, same block
        and offset; the indexer kernel scores every visible position of
        the slot, :func:`select_topk` finds the exact k best, and the
        latent kernel attends them.  While no slot holds more than k
        positions the selection is every position and the search for the
        k-th best is skipped (its cache writes are not)."""
        from torchacc_tpu.models import mla

        cfg = self._full_cfg
        pool, keys, win = pools
        s_, t_ = h.shape[:2]
        with jax.named_scope("mla_q"):
            c_q = mla.latent_q(cfg, attn, h)
            q_nope, q_pe = mla.project_q(cfg, attn, h, positions, c_q)
            q_lat = mla.absorb_q(cfg, attn, q_nope)
        pool = self._bank_latent(cfg, attn, pool, layer, h, positions, blk,
                                 off)
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
                q_start, layer=layer, impl=self.impl)
        with jax.named_scope("index_topk"):
            thr, tie_hi = jax.lax.cond(
                jnp.max(ctx_lens) <= cfg.index_topk,
                lambda sc: (jnp.full(sc.shape[:2], -jnp.inf, jnp.float32),
                            jnp.zeros(sc.shape[:2], jnp.int32)),
                lambda sc: select_topk(sc, cfg.index_topk), scores)
        with jax.named_scope("sparse_latent_attn"):
            o_lat = latent_paged_attention(
                q_lat, q_pe.astype(q_lat.dtype), pool, tables, ctx_lens,
                q_start, layer=layer, scale=mla.query_scale(cfg),
                impl=self.impl, selection=(scores, thr, tie_hi),
                name="sparse_latent_attention")
        return self._gated_out(cfg, attn, h, o_lat), (pool, keys, win)

    def _attend_window(self, attn, layer, h, pools, positions, tables,
                       ctx_lens, blk, off):
        """A 'sliding' layer of a model of two latent kinds: latent
        attention of its own sizes over ``cfg.window`` positions back,
        in the window layers' pool through their own table (entries
        before the window are 0: freed, never read)."""
        from torchacc_tpu.models import mla

        cfg = self._win_cfg
        pool, keys, win = pools
        with jax.named_scope("mla_q"):
            q_nope, q_pe = mla.project_q(cfg, attn, h, positions)
            q_lat = mla.absorb_q(cfg, attn, q_nope)
        win = self._bank_latent(cfg, attn, win, layer, h, positions, blk,
                                off)
        with jax.named_scope("window_latent_attn"):
            o_lat = latent_paged_attention(
                q_lat, q_pe.astype(q_lat.dtype), win, tables, ctx_lens,
                positions[:, 0], layer=layer, scale=mla.query_scale(cfg),
                impl=self.impl, window=cfg.window[0],
                name="window_latent_attention")
        return self._gated_out(cfg, attn, h, o_lat), (pool, keys, win)

    def _forward_periods(self, params, pools, x, positions, tables, ctx_lens,
                         blk, off, valid):
        """The layer loop of a model of two kinds of layer: one scan over
        the leading dense layers (all of one kind), then one over the
        PERIODS of the pattern — the body runs a period's layers one
        after another, each position of the period its own stacked tree
        ``params['layers']['p<k>']`` [periods, ...] with its expert
        stacks kept off ``xs`` (see :meth:`_forward`), every pool on the
        carry.  A layer's index in its kind's pools counts the
        layers of that kind before it."""
        cfg = self.cfg
        dense, period = pattern_period(cfg)
        n_periods = (cfg.num_layers - len(dense)) // len(period)
        per_kind = {k: period.count(k) for k in ("global", "sliding")}
        first = {k: dense.count(k) for k in per_kind}
        before = [{k: period[:i].count(k) for k in per_kind}
                  for i in range(len(period))]
        stacks, layers = [], {}
        for i in range(len(period)):
            tree = params["layers"][f"p{i}"]
            moe = tree["block"]["moe"]
            stacks.append({k: moe[k].astype(cfg.dtype)
                           for k in _EXPERT_STACKS})
            layers[f"p{i}"] = {**tree, "block": {**tree["block"], "moe": {
                k: v for k, v in moe.items() if k not in _EXPERT_STACKS}}}

        def dense_body(carry, per):
            x, pools = carry
            p_l, i = per
            x, pools, _ = self._layer(
                p_l["block"], i, x, pools, positions, tables, ctx_lens, blk,
                off, valid, kind=dense[0])
            return (x, pools), None

        def body(carry, per):
            x, pools = carry
            p_l, n = per
            load = 0
            for i, kind in enumerate(period):
                x, pools, one = self._layer(
                    p_l[f"p{i}"]["block"],
                    first[kind] + n * per_kind[kind] + before[i][kind], x,
                    pools,
                    positions, tables, ctx_lens, blk, off, valid,
                    expert_stacks=stacks[i], kind=kind, expert_layer=n)
                load = load + one
            return (x, pools), load

        with jax.named_scope("layers"):
            (x, pools), _ = jax.lax.scan(
                dense_body, (x, pools),
                (params["dense_layers"],
                 jnp.arange(len(dense), dtype=jnp.int32)))
            (x, pools), load = jax.lax.scan(
                body, (x, pools),
                (layers, jnp.arange(n_periods, dtype=jnp.int32)))
        return pools, x, jnp.sum(load, axis=0)

    def _forward_mixers(self, params, pools, x, positions, tables, ctx_lens,
                        blk, off, valid, state):
        """The layer walk of a ``mixer_pattern`` model: its layers one
        after another in the published order (no period to scan), each
        ONE mixer under a pre-norm (models/block.mixer_block) on its
        kind's stacked tree ``params['layers'][kind]`` at a static index.
        ``pools`` are ``(k, v, conv, ssm)``: an 'attention' layer reads
        and writes the first two through :meth:`_attend`, a 'mamba' layer
        the last two through models/mamba2 — ``state`` says how: None for
        a decode step (slot i's state at index i, ``valid[:, 0]`` the
        slots that decode), else ``(slots [R], fresh [R], n_valid [R])``
        of a prefill's rows.  Nothing copies a stack: an XLA dot reads
        its layer's slice where it lies, the grouped matmul and the scan
        kernel take the whole stack (or pool) and the layer's index."""
        from torchacc_tpu.models import mamba2
        from torchacc_tpu.models.moe import moe_ffn

        cfg = self.cfg
        stacks = {kind: tree["block"]
                  for kind, tree in params["layers"].items()}
        expert_stacks = {}
        if "moe" in stacks:
            moe = stacks["moe"]["moe"]
            expert_stacks = {k: moe[k].astype(cfg.dtype)
                             for k in _EXPERT_STACKS if k in moe}
            stacks["moe"] = {**stacks["moe"], "moe": {
                k: v for k, v in moe.items() if k not in _EXPERT_STACKS}}
        seen = dict.fromkeys(MIXER_KINDS, 0)
        load = None
        with jax.named_scope("layers"):
            for kind in layer_kinds(cfg):
                i = seen[kind]
                seen[kind] += 1
                p = jax.tree.map(lambda a, i=i: a[i], stacks[kind])
                left = {"pools": pools, "load": None}

                def norm(name, t, ncfg, p=p):
                    with jax.named_scope("ln1"):
                        return block.tree_norm(ncfg, p)(name, t)

                def attention(h, p=p, i=i, left=left):
                    out, kv = self._attend(
                        p["attn"], i, h, left["pools"][:2], positions,
                        tables, ctx_lens, blk, off, cfg=cfg)
                    left["pools"] = kv + left["pools"][2:]
                    return out

                def experts(h, p=p, i=i, left=left):
                    s_, t_, hd = h.shape
                    y, _, _, left["load"] = moe_ffn(
                        cfg, {**p["moe"], **expert_stacks},
                        h.reshape(s_ * t_, hd),
                        None if valid is None else valid.reshape(-1),
                        layer=i)
                    return y.reshape(s_, t_, hd)

                def mamba(h, p=p, i=i, left=left):
                    k_, v_, conv, ssm = left["pools"]
                    with jax.named_scope("ssm_mixer"):
                        if state is None:
                            out, conv, ssm = mamba2.mixer_step(
                                cfg, p["mixer"], h, conv, ssm, i,
                                valid[:, 0], impl=self.impl)
                        else:
                            out, conv, ssm = mamba2.mixer_chunk(
                                cfg, p["mixer"], h, conv, ssm, i, *state,
                                impl=self.impl)
                    left["pools"] = (k_, v_, conv, ssm)
                    return out

                x = block.mixer_block(
                    cfg, x, norm, {"attention": attention, "moe": experts,
                                   "mamba": mamba}[kind],
                    routed=kind == "moe")
                pools = left["pools"]
                if left["load"] is not None:
                    load = left["load"] if load is None \
                        else load + left["load"]
        return pools, x, load

    def _forward(self, params, pools, ids, positions, tables, ctx_lens,
                 blk, off, valid, state=None):
        """(pools', hidden [S, T, H], load): embed -> layer scan(s).  The
        stacked pools ride the scan's CARRY with the residual — each
        layer writes its rows in place and the kernel reads its pages
        through the layer index, so nothing slices a layer out of the
        stack or puts it back; ``xs`` are the stacked params and the
        layer index.  An expert model's three expert kernel stacks
        [L, E, in, out] are NOT on ``xs``: a scan hands its body a slice
        of every ``xs`` leaf, and a custom call's operand cannot absorb
        that slice, so XLA would copy each layer's 336 MiB stacks into a
        second buffer before every grouped matmul (PERF.md PR 27).  The
        body closes over them whole — loop invariants of the ``while`` —
        and the kernel reads its layer through an index, like the pools.
        The split is made here, at trace time, on the jitted function's
        own argument: ``params`` stays the pytree it is and no weight is
        copied.  The head projection is the caller's: decode
        projects every slot's single row, prefill projects ONLY the
        last valid row (the full-chunk head would be a C x hidden x
        vocab matmul that is discarded for every row but one).  A model
        with leading dense layers runs two scans over its two stacked
        trees ('dense_layers', then 'layers'), the pool on both carries,
        the layer index counting on; ``load`` is the expert layers'
        counts summed (int32[3], models/moe.held_experts_ffn) or None
        for a model without them.  ``state`` is a ``mixer_pattern``
        model's alone (:meth:`_forward_mixers`)."""
        with jax.named_scope("embed"):
            x = embed_ids(self.cfg, params, ids, positions)
        if self.two_kinds:
            return self._forward_periods(params, pools, x, positions, tables,
                                         ctx_lens, blk, off, valid)
        if self.mixers:
            return self._forward_mixers(params, pools, x, positions, tables,
                                        ctx_lens, blk, off, valid, state)

        layers, expert_stacks = params["layers"], None
        moe = layers["block"].get("moe")
        if moe is not None:
            # in cfg.dtype the kernel wants them: a no-op on weights cast
            # to serving precision, one conversion outside the scan
            # otherwise
            expert_stacks = {k: moe[k].astype(self.cfg.dtype)
                             for k in _EXPERT_STACKS}
            layers = {**layers, "block": {**layers["block"], "moe": {
                k: v for k, v in moe.items() if k not in _EXPERT_STACKS}}}

        def body(carry, per):
            x, pools = carry
            p_l, layer = per
            x, pools, load = self._layer(
                p_l["block"], layer, x, pools, positions, tables, ctx_lens,
                blk, off, valid, expert_stacks)
            return (x, pools), load

        n_dense, n = self.cfg.first_dense_layers, self.cfg.num_layers
        with jax.named_scope("layers"):
            if n_dense:
                (x, pools), _ = jax.lax.scan(
                    body, (x, pools),
                    (params["dense_layers"],
                     jnp.arange(n_dense, dtype=jnp.int32)))
            (x, pools), load = jax.lax.scan(
                body, (x, pools),
                (layers, jnp.arange(n_dense, n, dtype=jnp.int32)))
        return pools, x, (None if load is None else jnp.sum(load, axis=0))

    # -- sampling -----------------------------------------------------------

    def _sample_slots(self, logits, keys, temp, top_k, top_p):
        """Per-slot sampling with TRACED (temperature, top_k, top_p) —
        one compiled program for any request mix (the static-arg
        variant in models/generate._sample would recompile per
        combination).  temperature <= 0 is exact greedy (argmax),
        token-identical to generate()'s."""
        v = logits.shape[-1]
        greedy = jnp.argmax(logits, axis=-1)
        l = logits / jnp.maximum(temp, 1e-6)[:, None]
        # top-k: the k-th largest as cutoff, k <= 0 or >= vocab = off
        sorted_l = jnp.sort(l, axis=-1)[:, ::-1]
        kidx = jnp.clip(
            jnp.where((top_k <= 0) | (top_k >= v), v, top_k) - 1, 0, v - 1)
        kth = jnp.take_along_axis(sorted_l, kidx[:, None], axis=-1)
        l = jnp.where(l < kth, -jnp.inf, l)
        # nucleus on the k-truncated logits (generate._sample order);
        # the argmax is always kept so top_p <= 0 degrades to greedy
        sorted2 = jnp.sort(l, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted2, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p[:, None]
        keep = keep.at[:, 0].set(True)
        pth = jnp.min(jnp.where(keep, sorted2, jnp.inf), axis=-1,
                      keepdims=True)
        # top_p >= 1 is OFF (generate._sample skips it statically) —
        # without the guard, f32 cumsum rounding to >= 1.0 early can
        # truncate tail tokens even at the default top_p=1.0
        l = jnp.where((l < pth) & (top_p[:, None] < 1.0), -jnp.inf, l)
        sampled = jax.vmap(jax.random.categorical)(keys, l)
        return jnp.where(temp <= 0, greedy, sampled).astype(jnp.int32)

    # -- jitted steps -------------------------------------------------------

    def _decode_impl(self, params, pools, carry, tables, seq_lens, active,
                     temp, top_k, top_p, all_greedy, win_tables=None):
        """One decode token for every slot.  ``seq_lens`` is the banked
        length BEFORE this token; free slots (active=False) run on the
        null block and their sampled tokens are ignored by the host."""
        bs = self.block_size
        tok = carry["tok"]
        positions = seq_lens[:, None]

        def block_of(table):
            return jnp.where(
                active,
                jnp.take_along_axis(table, (seq_lens // bs)[:, None],
                                    axis=1)[:, 0],
                0)
        blk = block_of(tables)
        off = jnp.where(active, seq_lens % bs, 0)
        ctx = jnp.where(active, seq_lens + 1, 0)
        if win_tables is None:
            # (a mixer_pattern model's decode step reads its slots' state
            # by slot index: _forward's state=None)
            pools, x, load = self._forward(params, pools, tok[:, None],
                                           positions, tables, ctx,
                                           blk[:, None], off[:, None],
                                           active[:, None])
        else:
            pools, x, load = self._forward(
                params, pools, tok[:, None], positions, (tables, win_tables),
                ctx, (blk[:, None], block_of(win_tables)[:, None]),
                off[:, None], active[:, None])
        with jax.named_scope("head"):
            logits = head_logits(self.cfg, params, x)
        with jax.named_scope("sample"):
            split = jax.vmap(jax.random.split)(carry["key"])
            if all_greedy:
                toks = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            else:
                toks = self._sample_slots(logits[:, 0], split[:, 1], temp,
                                          top_k, top_p)
        return pools, {"tok": toks, "key": split[:, 0]}, toks, load

    def _prefill_impl(self, params, pools, table_row, t0, tokens, n_valid,
                      is_final, win_row=None, slot=None):
        """One chunk of ONE sequence: bank k/v for tokens
        [t0, t0 + n_valid) and return the last valid row's logits (the
        first-token sampling input when this is the final chunk;
        non-final chunks skip the C x hidden x vocab head matmul — its
        output is 100% discarded — and return None).  The pad tail
        writes to the null block and its positions clamp to the newest
        real position (keeps learned-position table lookups in range
        and longrope's max(positions) regime switch exact)."""
        bs, c = self.block_size, self.chunk
        i = jnp.arange(c, dtype=jnp.int32)
        valid = i < n_valid
        pos = t0 + i
        last_pos = jnp.maximum(t0 + n_valid - 1, 0)
        positions = jnp.where(valid, pos, last_pos)[None]          # [1, C]
        blk = jnp.where(valid, table_row[pos // bs], 0)
        off = jnp.where(valid, pos % bs, 0)
        ctx = (t0 + n_valid)[None]
        if win_row is None:
            # a mixer_pattern model's chunk starts from its slot's state,
            # from zero where the chunk is the request's first
            state = (None if slot is None else
                     (slot[None], (t0 == 0)[None], n_valid[None]))
            pools, x, load = self._forward(params, pools, tokens[None],
                                           positions, table_row[None], ctx,
                                           blk[None], off[None], valid[None],
                                           state)
        else:
            win_blk = jnp.where(valid, win_row[pos // bs], 0)
            pools, x, load = self._forward(
                params, pools, tokens[None], positions,
                (table_row[None], win_row[None]), ctx,
                (blk[None], win_blk[None]), off[None], valid[None])
        if not is_final:
            return pools, None, load
        with jax.named_scope("head"):
            if self.mixers:
                # the last valid row alone through the head: a chunk's
                # logits over this family's vocabulary are 256 MiB of
                # float32 beside pools that leave no such room
                row = jnp.take_along_axis(
                    x, jnp.maximum(n_valid - 1, 0)[None, None, None], axis=1)
                return pools, head_logits(self.cfg, params, row)[0, 0], load
            logits = head_logits(self.cfg, params, x)
            last = jnp.take_along_axis(
                logits[0], jnp.maximum(n_valid - 1, 0)[None, None],
                axis=0)[0]                                         # [V]
        return pools, last, load

    def _prefill_batch_impl(self, params, pools, table_rows, t0s, tokens,
                            n_valids, win_rows=None, slots=None):
        """One chunk each of up to ``prefill_batch`` DISTINCT sequences
        in one program: ``table_rows`` [PB, MB], ``t0s``/``n_valids``
        [PB] (0 valid = padded row: runs on the null block, output
        discarded), ``tokens`` [PB, C].  Returns the last valid row's
        logits per sequence [PB, V] — the only rows anyone reads (final
        rows sample their first token from them; non-final and padded
        rows are ignored by the host), so the head is a [PB, H] x
        [H, V] matmul, not the full-chunk head, and final-vs-non-final
        needs no static flag: trace count is 1.  ``slots`` [PB] are the
        rows' slots in a ``mixer_pattern`` model's state pools."""
        bs, c = self.block_size, self.chunk
        i = jnp.arange(c, dtype=jnp.int32)[None, :]              # [1, C]
        valid = i < n_valids[:, None]                            # [PB, C]
        pos = t0s[:, None] + i
        last_pos = jnp.maximum(t0s + n_valids - 1, 0)[:, None]
        positions = jnp.where(valid, pos, last_pos)              # [PB, C]
        blk = jnp.where(
            valid, jnp.take_along_axis(table_rows, pos // bs, axis=1), 0)
        off = jnp.where(valid, pos % bs, 0)
        ctx = t0s + n_valids                                     # [PB]
        if win_rows is not None:
            blk = (blk, jnp.where(valid, jnp.take_along_axis(
                win_rows, pos // bs, axis=1), 0))
            table_rows = (table_rows, win_rows)
        # (a mixer_pattern model: the rows' slots — the null slot for a
        # padded row, which starts fresh and is read by no one)
        state = (None if slots is None else
                 (slots, (t0s == 0) | (n_valids == 0), n_valids))
        pools, x, load = self._forward(params, pools, tokens, positions,
                                       table_rows, ctx, blk, off, valid,
                                       state)
        with jax.named_scope("head"):
            last = jnp.take_along_axis(
                x, jnp.maximum(n_valids - 1, 0)[:, None, None], axis=1)
            logits = head_logits(self.cfg, params, last)         # [PB, 1, V]
        return pools, logits[:, 0], load

    def _cow_impl(self, pools, src, dst):
        """Copy block ``src``'s rows into block ``dst`` across every
        layer of every pool (blocks are dim 1 of [L, NB, BS, row]) — the
        copy-on-write behind a fully-cached prompt: the final prompt
        token must re-run (its logits seed the first sampled token) and
        its k/v write needs a block this sequence owns; everything
        before it stays shared."""
        return tuple(p.at[:, dst].set(p[:, src]) for p in pools)

    def _sample_first_impl(self, logits, key, temp, top_k, top_p):
        with jax.named_scope("sample"):
            return self._sample_slots(logits[None], key[None], temp[None],
                                      top_k[None], top_p[None])[0]

    def _set_slot_impl(self, carry, slot, token, key):
        return {"tok": carry["tok"].at[slot].set(token),
                "key": carry["key"].at[slot].set(key)}


@dataclasses.dataclass
class Sequence:
    """Host-side runtime state of one admitted request."""

    sid: int
    prompt: np.ndarray                       # int32 [P]
    max_new: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: Optional[int] = None
    seed: int = 0
    # 'priority' policy inputs: higher priority = more urgent;
    # deadline is ABSOLUTE host monotonic time (engine.submit converts
    # the request's relative deadline_s), inf = none
    priority: int = 0
    deadline: float = float("inf")
    # streaming: called as on_token(token, t_monotonic) when the lagged
    # ring resolves each token (<= decode_depth - 1 iterations after
    # dispatch) — engine.submit(..., on_token=...) plumbs it here
    on_token: Any = None
    # end-to-end trace id (engine.submit assigns it): rides every serve
    # span this request participates in — `trace` on its own spans
    # (queue/admit/single prefill), `traces` on the batched ones
    # (batched prefill, decode, deliver) — and surfaces in
    # RequestResult.trace_id
    trace_id: str = ""
    # runtime
    slot: int = -1
    blocks: List[int] = dataclasses.field(default_factory=list)
    prefilled: int = 0
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False
    finish_reason: str = ""
    key: Any = None                          # host-held PRNG key
    # prefix-cache runtime (admit() fills these)
    block_keys: Optional[List[bytes]] = None  # chain key per full block
    registered: int = 0                      # prompt blocks indexed so far
    cached_tokens: int = 0                   # prompt tokens NOT recomputed
    shared_blocks: int = 0                   # blocks reused via refcount
    cow: bool = False                        # fully-cached prompt path
    # metrics timestamps (all on time.monotonic; engine fills t_submit)
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first_dispatch: float = 0.0            # first token sampled on device
    t_first_token: float = 0.0               # ... and delivered to the host
    t_finish: float = 0.0
    token_times: List[float] = dataclasses.field(default_factory=list)
    # the same way to the first token counted in Scheduler.step() calls
    # (Scheduler._step_idx: the running step's index; between two steps
    # the next one's), always on: what a request waits in steps does not
    # depend on what a step costs
    step_submit: int = 0
    step_admit: int = 0
    step_first: int = -1                     # the step of the last chunk
    prefill_programs: int = 0                # programs that ran a chunk of it
    # expert-layer counts of this request's prefill programs, handed to
    # the ring with its first token (device arrays; empty without experts)
    loads: List[Any] = dataclasses.field(default_factory=list)
    # window layers' blocks held now: logical block -> pool block
    # (kv_cache.WindowBlocks; empty for a model without window layers)
    win_blocks: Dict[int, int] = dataclasses.field(default_factory=dict)
    # positions a full layer attended, positions cached for it and
    # positions a window layer attended in this request's prefill
    # programs (Scheduler._note_selected; None without such layers)
    selected: Any = None

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    # The first token's way, from the stamps alone (0 for a stage the
    # request never reached): queue_s + prefill_s + first_token_lag_s ==
    # ttft_s, and wait_steps >= prefill_programs >= 1 once it is out.

    @property
    def queue_s(self) -> float:
        """submit -> slot."""
        return max(self.t_admit - self.t_submit, 0.0)

    @property
    def queue_steps(self) -> int:
        """submit -> slot in steps: 0 = admitted ahead of the first step
        after submit; each one above it is a step that ran with the
        request still queued (no free slot, or a reservation refused)."""
        return max(self.step_admit - self.step_submit, 0)

    @property
    def prefill_s(self) -> float:
        """slot -> first token sampled on device: the prompt's own
        chunks and the turns other prompts' chunks took."""
        return max(self.t_first_dispatch - self.t_admit, 0.0)

    @property
    def first_token_lag_s(self) -> float:
        """last chunk dispatched (its token sampled on device) ->
        delivered: what the device still owed then (the host runs
        ``decode_depth - 1`` iterations ahead), that chunk's program
        and the one blocking fetch."""
        return max(self.t_first_token - self.t_first_dispatch, 0.0)

    @property
    def ttft_s(self) -> float:
        """submit -> first token delivered."""
        return max(self.t_first_token - self.t_submit, 0.0)

    @property
    def wait_steps(self) -> int:
        """Scheduler steps from the first after submit to the one that
        ran the prompt's last chunk, both counted."""
        return max(self.step_first - self.step_submit + 1, 0)


def priority_key(seq: "Sequence", now: float, aging_s: float):
    """'priority' policy ordering — the ONE home for the semantics, so
    admission (engine._admit) and prefill order (scheduler.
    _prefill_candidates) can never drift apart: effective class
    descending (declared class + 1 per ``aging_s`` seconds waited — the
    starvation bound: any request eventually outranks any fixed class),
    then earliest deadline, then arrival."""
    eff = seq.priority + (int((now - seq.t_submit) / aging_s)
                          if aging_s > 0 else 0)
    return (-eff, seq.deadline, seq.sid)


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unresolved iteration in the readback ring."""

    kind: str                                # 'decode' | 'first'
    tokens: Any                              # device array
    slots: List[Tuple[int, Sequence]] = dataclasses.field(
        default_factory=list)                # decode snapshot
    seq: Optional[Sequence] = None           # 'first' entries
    iter_idx: int = -1                       # decode iteration index
    t_dispatch: float = 0.0
    # expert-layer counts of the step(s) behind this entry: int32[3]
    # device arrays (models/moe.held_experts_ffn), one a program — a
    # decode step's own, or every prefill chunk's of a 'first' entry
    loads: List[Any] = dataclasses.field(default_factory=list)
    # Scheduler._note_selected's counts of the step(s) behind this entry
    # (None for a model without an indexed selection)
    selected: Any = None
    # a mixer_pattern model's decode step: (slots whose state it read and
    # wrote, positions its attention layers' queries attended); else None
    state: Any = None


class Scheduler:
    """Slot + block bookkeeping and the iteration loop.

    One ``step()`` = (at most) one prefill chunk + one batched decode
    step + ring resolution down to ``decode_depth - 1`` in flight.
    """

    def __init__(self, model_cfg, params, serve_cfg,
                 attention_impl: Optional[str] = None, blocked=None):
        self.cfg = model_cfg
        self.serve_cfg = serve_cfg
        self.params = params
        self.blocked = blocked               # optional BlockedMeter
        self.decoder = PagedDecoder(model_cfg, serve_cfg, attention_impl)
        # shared-prefix KV reuse: the index maps token-hash chains to
        # resident blocks; the pool refcounts them and parks refcount-0
        # indexed blocks in its cached LRU instead of freeing
        self.prefix = (PrefixIndex(serve_cfg.block_size)
                       if serve_cfg.prefix_cache else None)
        self.pool = BlockPool(serve_cfg.num_blocks, index=self.prefix)
        # (k, v) stacks, or the one latent stack: a tuple either way,
        # donated to and returned by every step
        self.pools = make_pools(model_cfg, serve_cfg)
        s = serve_cfg.max_slots
        # table width bounds the LONGEST admissible sequence, not the
        # pool: the attention cost per decode token scales with table
        # width (the fallback gathers [S, MB*BS] per layer; the kernel
        # runs MB grid steps per slot/head), so sizing it num_blocks-1
        # would make growing the pool for more concurrency inflate
        # every slot's per-token cost.  The model's position reach
        # (max_seq_len) plus the in-flight overhang is the natural
        # bound; submit() rejects anything needing more.
        self.max_blocks_per_seq = min(
            serve_cfg.num_blocks - 1,
            blocks_needed(model_cfg.max_seq_len + serve_cfg.decode_depth,
                          serve_cfg.block_size))
        self.tables = np.zeros((s, self.max_blocks_per_seq), np.int32)
        # a model with window layers: their blocks, held only while the
        # window reaches them, and their table
        self.window = None
        if self.decoder.two_kinds:
            self.window = WindowBlocks(
                num_window_blocks(model_cfg, serve_cfg),
                serve_cfg.block_size, model_cfg.window[0],
                window_blocks_bound(model_cfg.window[0],
                                    serve_cfg.prefill_chunk,
                                    serve_cfg.block_size))
            self.win_tables = np.zeros_like(self.tables)
            self._dev_win = None
        # a model with state-space layers: bytes one slot's state takes
        # in all of them (what a decode step reads and writes a slot)
        self._ssm_layers = self._slot_state_bytes = 0
        if self.decoder.mixers and "mamba" in model_cfg.mixer_pattern:
            from torchacc_tpu.models import mamba2
            self._ssm_layers = layer_kinds(model_cfg).count("mamba")
            self._slot_state_bytes = (self._ssm_layers
                                      * mamba2.state_bytes(model_cfg))
        self.seq_lens = np.zeros((s,), np.int32)
        self.active = np.zeros((s,), bool)
        self.temp = np.zeros((s,), np.float32)
        self.top_k = np.zeros((s,), np.int32)
        self.top_p = np.ones((s,), np.float32)
        self.slot_seq: List[Optional[Sequence]] = [None] * s
        self.carry = {
            "tok": jnp.zeros((s,), jnp.int32),
            "key": jnp.asarray(
                np.stack([np.asarray(jax.random.PRNGKey(i))
                          for i in range(s)]), jnp.uint32),
        }
        self._ring: "collections.deque[_InFlight]" = collections.deque()
        self._iter = 0            # decode iterations dispatched
        self._step_idx = 0        # step() calls completed
        self._resolved = 0        # decode iterations resolved
        self._deferred: List[Tuple[int, List[int]]] = []
        # the same for an evicted sequence's window-layer blocks
        self._deferred_window: List[Tuple[int, List[int]]] = []
        # newly finished sequences, drained by the engine each step —
        # completion accounting stays O(finished this step), never a
        # scan over every request the process has served
        self.finished: List[Sequence] = []
        # device copies of the membership-stable host arrays (tables,
        # active, sampling params), re-uploaded only when admission /
        # prefill-completion / eviction dirties them — seq_lens changes
        # every decode iteration and is always uploaded fresh
        self._dev_stable = None

    # -- admission ----------------------------------------------------------

    def blocks_for(self, seq: Sequence) -> int:
        """Blocks reserved at admission: prompt + max_new + the
        in-flight overhang (a finished slot keeps writing for up to
        decode_depth iterations before the host notices)."""
        return blocks_needed(
            seq.prompt_len + seq.max_new + self.serve_cfg.decode_depth,
            self.serve_cfg.block_size)

    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slot_seq):
            if s is None:
                return i
        return None

    def min_fresh_blocks(self, seq: Sequence) -> int:
        """Cheapest POSSIBLE fresh-block need (best case: every full
        prompt block is a prefix hit) — the engine's O(Q) admission
        early-exit bound.  No hashing, so it may be optimistic; only
        ``admit`` itself is authoritative."""
        total = self.blocks_for(seq)
        if self.prefix is None:
            return total
        return max(1, total - seq.prompt_len // self.serve_cfg.block_size)

    def can_admit(self, seq: Sequence) -> bool:
        return (self.free_slot() is not None
                and self.pool.can_alloc(self.blocks_for(seq))
                and (self.window is None or self.window.can_reserve()))

    def admit(self, seq: Sequence) -> bool:
        """Give ``seq`` a decode slot + its whole block reservation, or
        return False with NO state change (all-or-nothing; the engine
        retries next iteration).  With the prefix cache on, the longest
        token-hash-chain match replaces that many fresh blocks with
        refcounted shared ones and prefill starts past them."""
        with tracing.span("serve/admit", sid=seq.sid,
                          trace=seq.trace_id) as sp:
            ok = self._admit_impl(seq)
            if not ok:
                # the ring keeps SUCCESSFUL admissions only: a saturated
                # engine re-attempts its queue head every iteration, and
                # one admitted=False span per retry would evict the
                # useful spans from the bounded ring exactly when an
                # operator exports it (failed-admission pressure is
                # visible as serve_queue_depth + kv_pool_free_blocks
                # instead).  A profiler trace shows the attempt, marked.
                sp.discard()
                sp.set(admitted=0)
                return False
            # the queue wait, known only now (submit -> slot admission)
            queue_s = seq.queue_s if seq.t_submit else 0.0
            sp.set(admitted=1, cached_tokens=seq.cached_tokens,
                   queue_ms=queue_s * 1e3, **self.blocks_by_kind())
            if self._ssm_layers:
                # the slot's recurrent state restarts with the request:
                # its first chunk reads zeros, not the last tenant's state
                sp.set(state_reset=1)
        if seq.t_submit and tracing.enabled():
            now = time.perf_counter()
            tracing.record_span("serve/queue", now - queue_s, now,
                                sid=seq.sid, trace=seq.trace_id)
        return True

    def blocks_by_kind(self) -> Dict[str, int]:
        """Blocks in use by kind of layer (and, where window layers free
        theirs as the window passes, how many went back that way)."""
        out = {"blocks_full": self.pool.in_use}
        if self.window is not None:
            out.update(blocks_window=self.window.pool.in_use,
                       window_blocks_freed=self.window.freed)
        return out

    def _advance_window(self, seq: Sequence, first_query: int,
                        upto: int) -> None:
        """Before a program whose queries of ``seq`` are positions
        [first_query, upto): the window layers' table row holds the
        blocks those queries see and write, and no others."""
        if self.window.advance(seq.win_blocks, self.win_tables[seq.slot],
                               first_query, upto):
            self._dev_win = None

    def _before_prefill(self, seq: Sequence, t0: int, n: int) -> None:
        """A model with window layers, before a prefill program of
        ``seq`` over positions [t0, t0 + n): its window table row, and
        the positions the request's prefill has worked through so far."""
        self._advance_window(seq, t0, t0 + n)
        seq.selected = self._note_selected(t0, n) + (
            0 if seq.selected is None else seq.selected)

    def _note_selected(self, t0: int, n: int) -> np.ndarray:
        """:meth:`_note_attended` for queries at positions [t0, t0 + n):
        query t sees t + 1 cached positions."""
        return self._note_attended(
            np.arange(t0 + 1, t0 + n + 1, dtype=np.int64))

    def _note_attended(self, seen: np.ndarray) -> np.ndarray:
        """(positions a full layer attends, positions cached for it,
        positions a window layer attends) for queries that see ``seen``
        cached positions each: a full layer attends all of them, or
        ``index_topk`` at most under an indexed selection, a window
        layer those in its window."""
        topk = self.cfg.index_topk
        return np.array([
            (np.minimum(seen, topk) if topk else seen).sum(), seen.sum(),
            np.minimum(seen, self.cfg.window[0] + 1).sum()])

    def _admit_impl(self, seq: Sequence) -> bool:
        slot = self.free_slot()
        if slot is None:
            return False
        if self.window is not None and not self.window.can_reserve():
            return False
        total = self.blocks_for(seq)
        shared: List[int] = []
        cow_src: Optional[int] = None
        if self.prefix is not None:
            # hash once, not per attempt: a queued request re-attempts
            # admission every engine iteration while it waits for blocks
            if seq.block_keys is None:
                seq.block_keys = self.prefix.keys(seq.prompt)
            shared = self.prefix.match(seq.block_keys)
            if shared and (len(shared) * self.serve_cfg.block_size
                           >= seq.prompt_len):
                # fully cached prompt: the final token must still run
                # (its logits seed the first sampled token) and its k/v
                # write needs a block this sequence owns — copy-on-write
                # the last matched block, share the rest
                cow_src = shared.pop()
        # pin the match BEFORE alloc: alloc may evict cached refcount-0
        # blocks to cover the grant, and it must not reclaim the match
        for b in shared:
            self.pool.share(b)
        if cow_src is not None:
            self.pool.share(cow_src)
        fresh = self.pool.alloc(total - len(shared))
        if fresh is None:
            # roll back the pins — admission never partially grants
            self.pool.free(shared)
            if cow_src is not None:
                self.pool.free([cow_src])
            return False
        blocks = shared + fresh
        if self.window is not None:
            self.window.reserve()
            self.win_tables[slot, :] = 0
            self._dev_win = None
        seq.slot = slot
        seq.blocks = blocks
        seq.key = jax.random.PRNGKey(seq.seed)
        seq.t_admit = time.monotonic()
        seq.step_admit = self._step_idx
        cached = len(shared) * self.serve_cfg.block_size
        if cow_src is not None:
            # dst is fresh[0] == table index len(shared): the copy sits
            # exactly where the popped match sat.  Device program order
            # makes the copy read src before any later program could
            # recycle it, so the pin can drop right after dispatch.
            self.pools = self.decoder._cow(
                self.pools, jnp.asarray(cow_src, jnp.int32),
                jnp.asarray(fresh[0], jnp.int32))
            self.pool.free([cow_src])
            cached = seq.prompt_len - 1
            seq.cow = True
            counters.inc("cow_copies")
        seq.prefilled = cached
        seq.cached_tokens = cached
        seq.shared_blocks = len(shared)
        seq.registered = len(shared)
        if cached:
            # engine.stats() aggregates the per-sequence fields at
            # completion; these global counters are the operator's
            # process-wide degradation/observability surface
            counters.inc("prefix_hits")
            if shared:
                counters.inc("prefix_blocks_reused", len(shared))
        self.slot_seq[slot] = seq
        self.tables[slot, :] = 0
        self.tables[slot, :len(blocks)] = blocks
        self.seq_lens[slot] = cached
        self.active[slot] = False          # decode starts after prefill
        self.temp[slot] = seq.temperature
        self.top_k[slot] = seq.top_k
        self.top_p[slot] = seq.top_p
        self._dev_stable = None
        return True

    def flush_prefix_cache(self) -> int:
        """Drop every cached prefix block + index entry; returns the
        block count.  The weight-swap seam (engine.load_params): k/v
        banked under old weights must never satisfy a prompt served
        under new ones.  The caller guarantees no live sequences."""
        if self.prefix is None:
            return 0
        return self.pool.flush_cached()

    # -- the iteration ------------------------------------------------------

    def _prefill_candidates(self) -> List[Sequence]:
        """Up to ``prefill_batch`` distinct sequences with prompt left
        to prefill, most-urgent first ('priority' policy: class then
        deadline — the same order admission used; otherwise arrival)."""
        cands = [s for s in self.slot_seq
                 if s is not None and not s.finished
                 and s.prefilled < s.prompt_len]
        if not cands:
            return []
        if self.serve_cfg.policy == "priority":
            # the same effective class admission uses, so a request
            # that aged past a higher class keeps its precedence once
            # both occupy slots
            now = time.monotonic()
            aging = self.serve_cfg.priority_aging_s
            cands.sort(key=lambda s: priority_key(s, now, aging))
        else:
            cands.sort(key=lambda s: s.sid)
        return cands[:self.serve_cfg.prefill_batch]

    def step(self) -> bool:
        """One engine iteration.  Returns True when any device work was
        dispatched (False = idle: nothing admitted, prefilling or
        decoding)."""
        did = False
        seqs = self._prefill_candidates()
        if seqs:
            if len(seqs) == 1:
                # a lone prefilling sequence (prefill_batch == 1, or
                # the steady-state trickle under a bigger batch) takes
                # the single-sequence program — no pad rows burning
                # prefill_batch x the FLOPs on the null block
                self._prefill_one(seqs[0])
            else:
                self._prefill_batched(seqs)
            did = True
        if self.active.any():
            self._decode_once()
            did = True
        # lagged resolution: keep at most decode_depth - 1 in flight
        while len(self._ring) >= self.serve_cfg.decode_depth:
            self._resolve_one()
        if not did:
            # nothing in flight can mature on its own — resolve one
            # entry so finishes/evictions make progress
            if self._ring:
                self._resolve_one()
                did = True
        self._release_matured()
        self._step_idx += 1
        return did

    def _prefill_one(self, seq: Sequence) -> None:
        c = self.serve_cfg.prefill_chunk
        t0 = seq.prefilled
        chunk = seq.prompt[t0:t0 + c]
        n_valid = int(chunk.shape[0])
        if n_valid < c:
            chunk = np.pad(chunk, (0, c - n_valid))
        final = (t0 + n_valid) >= seq.prompt_len
        win, state = (), {}
        if self.window is not None:
            self._before_prefill(seq, t0, n_valid)
            win = (_upload(self.win_tables[seq.slot]),)
        if self.decoder.mixers:
            state = {"slot": jnp.asarray(seq.slot, jnp.int32)}
        with tracing.span("serve/prefill", sid=seq.sid, t0=t0,
                          tokens=n_valid, batched=False,
                          trace=seq.trace_id):
            self.pools, last_logits, load = self.decoder._prefill(
                self.params, self.pools, _upload(self.tables[seq.slot]),
                jnp.asarray(t0, jnp.int32), jnp.asarray(chunk, jnp.int32),
                jnp.asarray(n_valid, jnp.int32), final, *win, **state)
        if load is not None:
            seq.loads.append(load)
        seq.prefill_programs += 1
        seq.prefilled += n_valid
        self.seq_lens[seq.slot] = seq.prefilled
        self._register_prefix(seq)
        if seq.prefilled >= seq.prompt_len:
            self._seed_first_token(seq, last_logits)

    def _prefill_batched(self, seqs: List[Sequence]) -> None:
        """One chunk each of up to ``prefill_batch`` sequences in a
        single dispatched program.  Short rows pad to [prefill_batch,
        prefill_chunk] (pad rows run on the null block, outputs
        discarded) so the program traces exactly once."""
        pb = self.serve_cfg.prefill_batch
        c = self.serve_cfg.prefill_chunk
        tables = np.zeros((pb, self.max_blocks_per_seq), np.int32)
        t0s = np.zeros((pb,), np.int32)
        toks = np.zeros((pb, c), np.int32)
        n_valids = np.zeros((pb,), np.int32)
        taken = []
        for r, seq in enumerate(seqs):
            t0 = seq.prefilled
            chunk = seq.prompt[t0:t0 + c]
            n = int(chunk.shape[0])
            tables[r] = self.tables[seq.slot]
            t0s[r] = t0
            toks[r, :n] = chunk
            n_valids[r] = n
            taken.append(n)
            if self.window is not None:
                self._before_prefill(seq, t0, n)
        win, state = (), {}
        if self.window is not None:
            win_rows = np.zeros_like(tables)
            for r, seq in enumerate(seqs):
                win_rows[r] = self.win_tables[seq.slot]
            win = (jnp.asarray(win_rows),)
        if self.decoder.mixers:
            # a padded row runs on the null slot, behind the real ones
            slots = np.full((pb,), self.serve_cfg.max_slots, np.int32)
            slots[:len(seqs)] = [seq.slot for seq in seqs]
            state = {"slots": jnp.asarray(slots)}
        with tracing.span("serve/prefill", batched=True,
                          sids=[s.sid for s in seqs],
                          traces=[s.trace_id for s in seqs],
                          tokens=int(sum(taken))):
            self.pools, logits, load = self.decoder._prefill_batch(
                self.params, self.pools, jnp.asarray(tables),
                jnp.asarray(t0s), jnp.asarray(toks), jnp.asarray(n_valids),
                *win, **state)
        if load is not None:
            seqs[0].loads.append(load)       # one program, counted once
        for r, seq in enumerate(seqs):
            seq.prefill_programs += 1
            seq.prefilled += taken[r]
            self.seq_lens[seq.slot] = seq.prefilled
            self._register_prefix(seq)
            if seq.prefilled >= seq.prompt_len:
                self._seed_first_token(seq, logits[r])

    def _register_prefix(self, seq: Sequence) -> None:
        """Index every newly completed FULL prompt block so later (and
        concurrent) prompts can share it.  First writer wins: blocks
        whose chain key is already mapped (the shared match itself, the
        COW copy, a concurrent identical prompt) stay private."""
        if self.prefix is None or not seq.block_keys:
            return
        n_full = min(seq.prefilled, seq.prompt_len) \
            // self.serve_cfg.block_size
        while seq.registered < n_full:
            i = seq.registered
            self.prefix.register(seq.block_keys[i], seq.blocks[i])
            seq.registered += 1

    def _seed_first_token(self, seq: Sequence, last_logits) -> None:
        """Final prefill chunk done: sample the first generated token
        on device and splice it into the decode carry — no readback;
        the host learns it through the ring like any other token."""
        seq.key, sub = jax.random.split(seq.key)
        tok = self.decoder._sample_first(
            last_logits, sub,
            jnp.asarray(seq.temperature, jnp.float32),
            jnp.asarray(seq.top_k, jnp.int32),
            jnp.asarray(seq.top_p, jnp.float32))
        seq.key, slot_key = jax.random.split(seq.key)
        self.carry = self.decoder._set_slot(
            self.carry, jnp.asarray(seq.slot, jnp.int32), tok,
            slot_key.astype(jnp.uint32))
        self.active[seq.slot] = True
        self._dev_stable = None
        seq.step_first = self._step_idx
        seq.t_first_dispatch = time.monotonic()
        self._ring.append(_InFlight(
            kind="first", tokens=tok, seq=seq, loads=seq.loads,
            selected=seq.selected, t_dispatch=seq.t_first_dispatch))
        seq.loads, seq.selected = [], None

    def _dev_stable_arrays(self):
        if self._dev_stable is None:
            self._dev_stable = tuple(
                _upload(x) for x in (self.tables, self.active, self.temp,
                                     self.top_k, self.top_p))
        return self._dev_stable

    def _decode_once(self) -> None:
        # serve chaos seam (resilience/chaos.py): crash-mid-decode
        # (ChaosPlan.kill -> SIGKILL with sequences in flight — the
        # journal-replay gate) and decode-loop hang (ChaosPlan.hang ->
        # the serve_liveness health check flips, the supervisor probe
        # kills).  One global `is None` check when no plan is active.
        failpoint("serve.decode", iter=self._iter)
        snapshot = [(i, s) for i, s in enumerate(self.slot_seq)
                    if self.active[i] and s is not None]
        win, selected = (), None
        if self.window is not None:
            for slot, seq in snapshot:
                n = int(self.seq_lens[slot])
                self._advance_window(seq, n, n + 1)
            selected = self._note_attended(
                self.seq_lens[[slot for slot, _ in snapshot]].astype(
                    np.int64) + 1)           # cached positions a query
            if self._dev_win is None:
                self._dev_win = _upload(self.win_tables)
            win = (self._dev_win,)
        state = None
        if self.decoder.mixers:
            # every decoding slot's state is read and written once a
            # state-space layer; an attention layer's query sees the
            # slot's cached positions and its own
            state = (len(snapshot), int(self.seq_lens[
                [slot for slot, _ in snapshot]].sum()) + len(snapshot))
        tables, active, temp, top_k, top_p = self._dev_stable_arrays()
        all_greedy = bool((self.temp[self.active] <= 0.0).all())
        # per-request trace ids on the batched span: built only while
        # tracing records (the list comprehension must cost nothing on
        # the disabled hot path)
        _traces = ([s.trace_id for _, s in snapshot]
                   if tracing.enabled() else None)
        with tracing.span("serve/decode", iter=self._iter,
                          slots=len(snapshot), traces=_traces):
            self.pools, self.carry, toks, load = self.decoder._decode(
                self.params, self.pools, self.carry,
                tables, _upload(self.seq_lens),
                active, temp, top_k, top_p, all_greedy, *win)
        # host mirror: every active slot banked one more token
        self.seq_lens[self.active] += 1
        self._ring.append(_InFlight(
            kind="decode", tokens=toks, slots=snapshot,
            loads=[] if load is None else [load], selected=selected,
            state=state, iter_idx=self._iter, t_dispatch=time.monotonic()))
        self._iter += 1

    # -- resolution / eviction ----------------------------------------------

    def _record(self, seq: Sequence, token: int, now: float) -> None:
        if seq.finished:
            return                 # lagged garbage after finish
        if not seq.out_tokens:
            seq.t_first_token = now
        seq.out_tokens.append(token)
        seq.token_times.append(now)
        if seq.on_token is not None:
            # streaming delivery: the callback sees each token at
            # resolution time — <= decode_depth - 1 iterations after
            # its dispatch, never a garbage post-finish token.  A
            # raising callback is disabled, not allowed to corrupt the
            # ring resolution for every other request.
            try:
                seq.on_token(token, now)
            except Exception:
                logger.exception(
                    f"on_token callback for request {seq.sid} raised; "
                    f"disabling the stream callback for this request")
                seq.on_token = None
        if seq.eos_id is not None and token == seq.eos_id:
            self._finish(seq, "eos", now)
        elif len(seq.out_tokens) >= seq.max_new:
            self._finish(seq, "length", now)

    def _finish(self, seq: Sequence, reason: str, now: float) -> None:
        seq.finished = True
        seq.finish_reason = reason
        seq.t_finish = now
        self.finished.append(seq)
        self._evict(seq)

    def preempt(self, seq: Sequence, now: float) -> None:
        """Evict an ADMITTED sequence before its natural finish (the
        engine's opt-in ``serve.preempt_deadlines`` sweep): typed
        ``finish_reason='preempted'`` with whatever tokens resolved so
        far, blocks released through the same deferred-free path as any
        eviction.  Safe mid-flight by the existing machinery: lagged
        ring entries for the evicted slot drop in :meth:`_record`'s
        post-finish guard, and the deferred free holds the blocks until
        every already-dispatched iteration resolves."""
        if seq.finished:
            return
        self._finish(seq, "preempted", now)

    def _evict(self, seq: Sequence) -> None:
        slot = seq.slot
        if slot < 0:
            return
        self.slot_seq[slot] = None
        self.active[slot] = False
        self.tables[slot, :] = 0
        self.seq_lens[slot] = 0
        seq.slot = -1
        self._dev_stable = None
        # DEFERRED free: iterations dispatched before this point may
        # still write through the old table — release only once every
        # decode iteration < self._iter has resolved
        if self.window is not None:
            self.win_tables[slot, :] = 0
            self._dev_win = None
            self._deferred_window.append(
                (self._iter, list(seq.win_blocks.values())))
        self._deferred.append((self._iter, seq.blocks))
        seq.blocks, seq.win_blocks = [], {}
        self._release_matured()

    def _release_matured(self) -> None:
        ring_empty = not any(e.kind == "decode" for e in self._ring)
        keep = []
        for after, blocks in self._deferred:
            if self._resolved >= after or ring_empty:
                self.pool.free(blocks)
            else:
                keep.append((after, blocks))
        self._deferred = keep
        if self.window is not None:
            keep = []
            for after, blocks in self._deferred_window:
                if self._resolved >= after or ring_empty:
                    self.window.release(blocks)
                else:
                    keep.append((after, blocks))
            self._deferred_window = keep

    def _resolve_one(self) -> None:
        entry = self._ring.popleft()
        # stream-delivery span: token readback (the lagged blocking
        # fetch) + per-request recording incl. on_token callbacks
        _traces = None
        if tracing.enabled():
            _traces = ([entry.seq.trace_id] if entry.kind == "first"
                       else [s.trace_id for _, s in entry.slots])
        with tracing.span("serve/deliver", kind=entry.kind,
                          traces=_traces) as deliver:
            # the (only) blocking fetch: deliver's time outside this
            # span is the host's own work, inside it the device's
            with tracing.span("serve/wait"):
                if self.blocked is not None:
                    with self.blocked.blocked():
                        toks = np.asarray(entry.tokens)
                else:
                    toks = np.asarray(entry.tokens)
            if entry.loads and deliver.live:
                # the steps' expert-layer counts: computed with the
                # tokens just fetched, so reading them waits on nothing
                pairs, largest, hit = np.sum(
                    [np.asarray(x) for x in entry.loads], axis=0)
                layer_steps = len(entry.loads) * (
                    layer_kinds(self.cfg).count("moe")
                    if self.cfg.mixer_pattern else
                    self.cfg.num_layers - self.cfg.first_dense_layers)
                deliver.set(
                    moe_pairs=int(pairs), moe_max=int(largest),
                    moe_hit=int(hit), moe_layer_steps=layer_steps,
                    moe_slots=layer_steps * self.cfg.num_experts)
            if entry.selected is not None and deliver.live:
                # the queries behind these tokens, one layer of each
                # kind: positions a full layer attended and had cached,
                # positions a window layer attended
                att, cached, win_att = (int(x) for x in entry.selected)
                if self.cfg.index_topk:
                    deliver.set(sel_attended=att, sel_cached=cached,
                                win_attended=win_att)
                else:
                    deliver.set(ctx_attended=cached, win_attended=win_att)
            if self._ssm_layers and deliver.live:
                deliver.set(ssm_layers=self._ssm_layers)
                if entry.state is not None:
                    # a decode step over state-space layers: the state
                    # bytes it read and wrote (every decoding slot's, once
                    # a layer) beside the positions an attention layer
                    # attended
                    slots, attended = entry.state
                    deliver.set(
                        state_bytes=2 * slots * self._slot_state_bytes,
                        ctx_attended=attended)
            now = time.monotonic()
            if entry.kind == "first":
                seq = entry.seq
                self._record(seq, int(toks), now)
                if deliver.live and seq.out_tokens:
                    # what this request's wait for its first token was
                    # made of (Sequence's stamps; docs/observability.md)
                    deliver.set(
                        sid=seq.sid,
                        prefill_programs=seq.prefill_programs,
                        queue_steps=seq.queue_steps,
                        wait_steps=seq.wait_steps,
                        queue_ms=seq.queue_s * 1e3,
                        prefill_ms=seq.prefill_s * 1e3,
                        lag_ms=seq.first_token_lag_s * 1e3,
                        ttft_ms=seq.ttft_s * 1e3)
            else:
                for slot, seq in entry.slots:
                    self._record(seq, int(toks[slot]), now)
                self._resolved = entry.iter_idx + 1
        self._release_matured()

    def drain(self) -> None:
        """Resolve every in-flight iteration (engine shutdown / idle)."""
        while self._ring:
            self._resolve_one()
        self._release_matured()

    @property
    def pending(self) -> int:
        return len(self._ring)

    def busy(self) -> bool:
        return (any(s is not None for s in self.slot_seq)
                or bool(self._ring))
