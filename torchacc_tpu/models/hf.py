"""HuggingFace model ingestion: torch state_dict -> torchacc_tpu params.

The reference accelerates HF models in place via monkeypatching
(utils/patch.py:61-301, qwen_patch.py, accelerate_hf_trainer.py) because
it shares torch's module system.  The TPU-native framework instead
*converts*: an HF checkpoint's weights are mapped onto the zoo's
:class:`TransformerLM` layout (scan-stacked layers), after which every
framework feature (FSDP/TP/PP/CP shardings, Pallas kernels, remat,
checkpointing) applies with zero model-specific code.

Supported families: Llama (1/2/3, incl. 3.1's banded rope scaling),
Qwen2 (qkv bias), Qwen3 (qk-norm), Mistral (sliding window), Gemma v1
(1+w RMSNorm, geglu, scaled embeddings), Gemma2/3 (layer patterns,
sandwich norms, softcaps), Mixtral and Qwen3-MoE (top-k sparse MoE -> models/moe.py, incl. the
un-renormalised combine-weight convention), OLMo2 (post-norm placement,
flat-projection qk-norm), Phi-3/3.5/4-mini (packed qkv/gate_up weights,
longrope, partial rotary) — the reference's patched set
(utils/patch.py:224-301) plus the Qwen3/Gemma/Mixtral/OLMo2/Phi-3
families.  Rope scaling: linear, llama3, longrope, yarn (others fail
loudly).  GPT-2 (the reference's own CLM benchmark model,
benchmarks/transformer.py) converts too: learned positions, biased
LayerNorms, packed Conv1D qkv, gelu_new, tied head — plus llama
attention_bias/mlp_bias variants.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Mapping, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from torchacc_tpu.models.transformer import ModelConfig


def config_from_hf(hf_config: Any, **overrides) -> ModelConfig:
    """ModelConfig from a transformers PretrainedConfig (llama/qwen2/
    mistral/gemma family)."""
    get = lambda n, d=None: getattr(hf_config, n, d)
    mt = get("model_type")
    kw = dict(
        vocab_size=get("vocab_size"),
        hidden_size=get("hidden_size"),
        num_layers=get("num_hidden_layers"),
        num_heads=get("num_attention_heads"),
        num_kv_heads=get("num_key_value_heads", get("num_attention_heads")),
        head_dim=get("head_dim"),
        intermediate_size=get("intermediate_size"),
        max_seq_len=get("max_position_embeddings", 4096),
        rope_theta=float(get("rope_theta", 10000.0)),
        norm_eps=float(get("rms_norm_eps", 1e-5)),
        qkv_bias=bool(get("attention_bias", False) or mt == "qwen2"),
        # llama's attention_bias puts a bias on o_proj too (qwen2's qkv
        # bias does NOT); mlp_bias is llama's separate knob
        o_bias=bool(get("attention_bias", False)),
        mlp_bias=bool(get("mlp_bias", False)),
        tie_embeddings=bool(get("tie_word_embeddings", False)),
    )
    if mt == "gemma":
        # Gemma v1: zero-centred RMSNorm (1 + w), gated tanh-GELU MLP
        # (gelu_pytorch_tanh), sqrt(hidden)-scaled embeddings, explicit
        # head_dim (7b: 256 != hidden/heads), tied head
        kw.update(norm="rmsnorm1p", activation="geglu", embed_scale=True)
    if mt == "gemma2":
        # Gemma2 adds to v1: sandwich norms (post-attention and
        # post-feedforward), alternating sliding/global attention
        # (HF Gemma2Attention: even layers sliding), attention-score
        # soft-capping, and a fixed query scale
        # (query_pre_attn_scalar ** -0.5 instead of head_dim ** -0.5)
        kw.update(
            norm="rmsnorm1p", activation="geglu", embed_scale=True,
            sandwich_norms=True, layer_pattern=("sliding", "global"),
            attn_logit_softcap=float(get("attn_logit_softcapping") or 0.0),
            query_scale=float(get("query_pre_attn_scalar",
                                  kw.get("head_dim") or 256)) ** -0.5)
    if mt in ("gemma3", "gemma3_text"):
        # Gemma3: gemma2's sandwich norms + 5:1 sliding/global pattern,
        # per-layer-type rope bases (local 10k on sliding layers, global
        # rope_theta on full layers), qk-norm, no score soft-capping
        kw.update(
            norm="rmsnorm1p", activation="geglu", embed_scale=True,
            sandwich_norms=True, qk_norm=True,
            layer_pattern=_pattern_from_layer_types(
                get("layer_types"),
                sliding_window_pattern=get("sliding_window_pattern")),
            rope_local_theta=float(get("rope_local_base_freq", 10000.0)),
            query_scale=float(get("query_pre_attn_scalar",
                                  kw.get("head_dim") or 256)) ** -0.5)
        rs = get("rope_scaling")
        if rs:
            rt = rs.get("rope_type", rs.get("type"))
            if rt != "linear":
                raise NotImplementedError(
                    f"gemma3 rope_scaling type {rt!r} is not implemented "
                    "(linear is)")
            # linear scaling on the GLOBAL rotary only (sliding layers
            # reset to 1 in pattern_cfg) — real gemma3 >=4B checkpoints
            # ship factor 8
            kw["rope_scale"] = float(rs["factor"])
    if mt == "gpt2":
        # GPT-2 class: learned positions, biased LayerNorms, gelu_new
        # MLP, packed Conv1D qkv, biases on every projection, tied head.
        # GPT2Config's attribute_map already aliases hidden_size /
        # num_attention_heads / num_hidden_layers /
        # max_position_embeddings onto n_embd / n_head / n_layer /
        # n_positions, so the generic reads above populated them.
        act = get("activation_function", "gelu_new")
        if act not in ("gelu_new", "gelu_pytorch_tanh"):
            # our 'gelu' is the tanh approximation; exact-erf gelu or
            # relu variants would convert silently wrong
            raise NotImplementedError(
                f"gpt2 activation_function {act!r} is not implemented "
                f"(gelu_new is)")
        kw.update(norm="layernorm", activation="gelu",
                  pos_emb="learned", qkv_bias=True, o_bias=True,
                  mlp_bias=True,
                  norm_eps=float(get("layer_norm_epsilon", 1e-5)))
        if get("n_inner"):
            kw["intermediate_size"] = int(get("n_inner"))
    if mt == "starcoder2":
        # StarCoder2 (3B/7B/15B): rope + GQA + biased LayerNorms +
        # NON-gated gelu_pytorch_tanh MLP named c_fc/c_proj + one
        # use_bias knob driving qkv/o/mlp biases; 7B/15B configs carry
        # sliding_window (picked up by the generic read below)
        act = get("hidden_act", "gelu_pytorch_tanh")
        if act not in ("gelu_pytorch_tanh", "gelu_new"):
            raise NotImplementedError(
                f"starcoder2 hidden_act {act!r} is not implemented "
                f"(gelu_pytorch_tanh is)")
        bias = bool(get("use_bias", True))
        kw.update(norm="layernorm", activation="gelu",
                  qkv_bias=bias, o_bias=bias, mlp_bias=bias,
                  norm_eps=float(get("norm_epsilon", 1e-5)))
    if mt == "gpt_neox":
        # GPT-NeoX / Pythia: TWO-norm parallel residual
        # (x + attn(ln1(x)) + mlp(ln2(x)) when use_parallel_residual,
        # the pythia default), packed per-head [q|k|v] attention, exact
        # erf gelu, biases everywhere, partial rotary via rotary_pct
        act = get("hidden_act", "gelu")
        if act not in ("gelu", "gelu_new", "gelu_pytorch_tanh",
                       "gelu_fast"):
            raise NotImplementedError(
                f"gpt_neox hidden_act {act!r} is not implemented")
        nx_bias = bool(get("attention_bias", True))
        kw.update(norm="layernorm",
                  activation="gelu_exact" if act == "gelu" else "gelu",
                  parallel_block=bool(get("use_parallel_residual", True)),
                  parallel_block_shared_norm=False,
                  # attention_bias gates qkv/dense; the MLP linears are
                  # unconditionally biased in HF GPTNeoXMLP
                  qkv_bias=nx_bias, o_bias=nx_bias, mlp_bias=True,
                  norm_eps=float(get("layer_norm_eps", 1e-5)),
                  rope_theta=float(get("rotary_emb_base",
                                       get("rope_theta", 10000.0) or
                                       10000.0) or 10000.0))
        prf = float(get("rotary_pct", 1.0) or 1.0)
        if prf != 1.0:
            kw["partial_rotary"] = prf
    if mt == "nemotron":
        # Nemotron: layernorm1p ((1+w) scale + bias over a mean-centred
        # norm), NON-gated square-relu MLP (up/down names), partial
        # rotary; llama attention names
        act = get("hidden_act", "relu2")
        if act != "relu2":
            raise NotImplementedError(
                f"nemotron hidden_act {act!r} is not implemented "
                f"(relu2 is)")
        kw.update(norm="layernorm1p", activation="relu2",
                  norm_eps=float(get("norm_eps", 1e-5)))
        prf = float(get("partial_rotary_factor", 0.5) or 1.0)
        if prf != 1.0:
            kw["partial_rotary"] = prf
    if mt == "cohere":
        # Cohere / Command-R: PARALLEL residual with ONE shared BIASLESS
        # LayerNorm, gated silu MLP (llama names), tied embeddings, and
        # a logit_scale multiplier (applied by scaling the final-normed
        # hidden — every head path inherits it)
        if get("use_qk_norm", False):
            raise NotImplementedError(
                "cohere use_qk_norm=True (per-head LayerNorm q/k) is "
                "not implemented")
        kw.update(parallel_block=True, norm="layernorm", norm_bias=False,
                  norm_eps=float(get("layer_norm_eps", 1e-5)),
                  logit_scale=float(get("logit_scale", 1.0) or 1.0),
                  rope_interleaved=True)
    if mt == "phi":
        # Phi-1/1.5/2: PARALLEL residual (x + attn(ln(x)) + mlp(ln(x)),
        # one shared biased LayerNorm, no ln2), partial rotary,
        # gelu_new fc1/fc2 MLP, biases everywhere INCLUDING the lm_head
        act = get("hidden_act", "gelu_new")
        if act not in ("gelu_new", "gelu_pytorch_tanh"):
            raise NotImplementedError(
                f"phi hidden_act {act!r} is not implemented (gelu_new is)")
        if kw.get("tie_embeddings"):
            # HF ties only lm_head.weight; its bias would survive in the
            # state dict with no tied-head slot to land in — converting
            # would silently drop it
            raise NotImplementedError(
                "phi with tie_word_embeddings=True is not supported "
                "(the biased lm_head cannot ride the tied head)")
        kw.update(norm="layernorm", activation="gelu", parallel_block=True,
                  qkv_bias=True, o_bias=True, mlp_bias=True, head_bias=True,
                  norm_eps=float(get("layer_norm_eps", 1e-5)),
                  partial_rotary=float(get("partial_rotary_factor", 0.5)))
    if mt == "phi3":
        # Phi-3/3.5/4-mini: llama-style pre-norm block with PACKED
        # qkv_proj / gate_up_proj weights (split at conversion);
        # phi-4-mini's partial rotary and the 128k variants' 'longrope'
        # scaling are both supported (the generic rope chain below)
        prf = float(get("partial_rotary_factor", 1.0) or 1.0)
        if prf != 1.0:
            kw["partial_rotary"] = prf
    if mt == "olmo2":
        # OLMo2 (the modern revision of the reference's example-notebook
        # family, examples/train_olmo.ipynb): llama MLP + POST-norm
        # residual placement (x + norm(f(x)), no pre-norms) and RMSNorm
        # over the FLAT q/k projections
        kw.update(qk_norm=True, qk_norm_proj=True, norm_placement="post")
    if mt == "qwen3":
        # Qwen3: llama layout + per-head-dim RMSNorm on q/k before rope
        # (same q_norm/k_norm tensors as gemma3, but with the standard
        # RMSNorm — cfg.norm stays 'rmsnorm') and explicit head_dim; no
        # qkv bias (unlike qwen2)
        kw.update(qk_norm=True)
    if mt == "qwen3_moe":
        # Qwen3-MoE (30B-A3B family): qwen3 attention + per-expert
        # llama FFNs at moe_intermediate_size; norm_topk_prob picks the
        # combine-weight convention
        if int(get("decoder_sparse_step", 1) or 1) != 1 \
                or get("mlp_only_layers"):
            raise NotImplementedError(
                "qwen3_moe mixed dense/sparse layer schedules "
                "(decoder_sparse_step != 1 / mlp_only_layers) are not "
                "implemented")
        kw.update(
            qk_norm=True,
            num_experts=int(get("num_experts")),
            num_experts_per_tok=int(get("num_experts_per_tok", 2)),
            router_aux_weight=float(get("router_aux_loss_coef", 0.001)),
            intermediate_size=int(get("moe_intermediate_size")),
            moe_renorm_topk=bool(get("norm_topk_prob", False)))
    if mt == "dots3_note":
        # dots3-note: the axk1 family's latent attention and held-expert
        # layers (same keys, read below), with two kinds of attention
        # layer named by `layer_types`: 'full_attention' layers select
        # the `index_topk` best cached positions with a learned indexer,
        # 'sliding_attention' layers are latent attention of their own
        # sizes (`swa_*`) over `sliding_window_size` positions, the token
        # itself counted.  Every layer's pattern entry is written out
        # (no shorter period: the leading dense layer is a full one).
        types_ = list(get("layer_types") or [])
        n = int(overrides.get("num_layers", kw["num_layers"]))
        if len(types_) < n:
            raise ValueError(
                f"dots3_note layer_types names {len(types_)} layers, "
                f"num_layers is {n}")
        if get("swa_attention_gate_type",
               get("attention_gate_type")) != get("attention_gate_type"):
            raise NotImplementedError(
                "dots3_note with different gates on the two layer kinds")
        if get("rope_scaling"):
            raise NotImplementedError("dots3_note with rope_scaling")
        kw.update(
            layer_pattern=tuple(
                "sliding" if t == "sliding_attention" else "global"
                for t in types_[:n]),
            window=(int(get("sliding_window_size")) - 1, -1),
            rope_local_theta=float(get("swa_rope_theta")),
            index_topk=int(get("index_topk")),
            index_n_heads=int(get("index_n_heads")),
            index_head_dim=int(get("index_head_dim")),
            swa_num_heads=int(get("swa_num_attention_heads")),
            swa_kv_lora_rank=int(get("swa_kv_lora_rank")),
            swa_q_lora_rank=int(get("swa_q_lora_rank")),
            swa_qk_nope_head_dim=int(get("swa_qk_nope_head_dim")),
            swa_qk_rope_head_dim=int(get("swa_qk_rope_head_dim")),
            swa_v_head_dim=int(get("swa_v_head_dim")),
            mla_lora_rescale=bool(get("apply_mla_qkv_lora_rescale", False)),
            attn_gate=get("attention_gate_type") or "none")
    if mt in ("axk1", "dots3_note"):
        # A.X-K1 (skt): multi-head latent attention, `first_k_dense_replace`
        # leading dense layers, then expert layers (sigmoid scores,
        # group-limited top-k, normalised weights times
        # routed_scaling_factor, shared experts).  `n_routed_experts` is
        # the number of experts THIS program holds; an expert-parallel
        # share states the router's published width and its first held
        # expert beside it (`router_n_experts`, `first_held_expert`;
        # absent = every expert is held).  `topk_method` "none" is read
        # as: no selection-bias term ("noaux_tc" adds one).
        if int(get("moe_layer_freq", 1) or 1) != 1:
            raise NotImplementedError(
                "axk1 with moe_layer_freq != 1 (dense layers between the "
                "expert layers) is not implemented")
        if get("hidden_act", "silu") != "silu":
            raise NotImplementedError("axk1 hidden_act must be silu")
        if get("topk_method", "none") not in ("none", "noaux_tc"):
            raise NotImplementedError(
                f"axk1 topk_method {get('topk_method')!r} is not "
                f"implemented ('none' and 'noaux_tc' are)")
        held = int(get("n_routed_experts"))
        kw.update(
            head_dim=None, qkv_bias=False, o_bias=False,
            rope_interleaved=True,
            kv_lora_rank=int(get("kv_lora_rank")),
            q_lora_rank=int(get("q_lora_rank") or 0),
            qk_nope_head_dim=int(get("qk_nope_head_dim")),
            qk_rope_head_dim=int(get("qk_rope_head_dim")),
            v_head_dim=int(get("v_head_dim")),
            first_dense_layers=int(get("first_k_dense_replace", 0) or 0),
            num_experts=held,
            num_experts_per_tok=int(get("num_experts_per_tok")),
            moe_intermediate_size=int(get("moe_intermediate_size")),
            moe_scoring=get("scoring_func", "softmax"),
            moe_n_group=int(get("n_group", 1) or 1),
            moe_topk_group=int(get("topk_group", 1) or 1),
            moe_route_scale=float(get("routed_scaling_factor", 1.0)),
            moe_renorm_topk=bool(get("norm_topk_prob", True)),
            moe_router_bias=get("topk_method", "none") == "noaux_tc",
            moe_shared_experts=int(get("n_shared_experts", 0) or 0),
            moe_router_width=int(get("router_n_experts", held)),
            moe_first_expert=int(get("first_held_expert", 0)),
            moe_dispatch="grouped")
        # softmax scale: head dim ** -0.5, times yarn's mscale**2 over
        # ALL dims (the family's attention folds it into the scale, the
        # generic yarn branch below keeps the cos/sin factor)
        import math as _m
        scale = (kw["qk_nope_head_dim"] + kw["qk_rope_head_dim"]) ** -0.5
        rs = get("rope_scaling") or {}
        if rs.get("mscale_all_dim") and float(rs.get("factor", 1.0)) > 1.0:
            scale *= (0.1 * float(rs["mscale_all_dim"])
                      * _m.log(float(rs["factor"])) + 1.0) ** 2
        # two kinds of layer: each kind's head size gives its own scale
        kw["query_scale"] = None if mt == "dots3_note" else scale
    if mt == "exaone_moe":
        # K-EXAONE (LG AI Research): EXAONE 4.0's block under experts.
        # Grouped-query attention with RMSNorm over each head of q and k
        # before rope, the norms on the sublayers' OUTPUTS (post
        # placement); `layer_types` mixes 'sliding_attention' layers
        # (rope, `sliding_window` positions, the token itself counted)
        # with 'full_attention' layers that carry NO rotary embedding;
        # `mlp_layer_types` is `first_k_dense_replace` dense layers, then
        # expert layers: sigmoid scores, selection by score + a
        # per-expert bias, normalised weights times
        # routed_scaling_factor, shared experts.  `num_experts` is the
        # number of experts THIS program holds (`router_n_experts`,
        # `first_held_expert` beside it state an expert-parallel share,
        # as for axk1).  The multi-token-prediction block
        # (`num_nextn_predict_layers`, a drafter) is not loaded.
        types_ = list(get("layer_types") or [])
        n = int(overrides.get("num_layers", kw["num_layers"]))
        n_dense = int(get("first_k_dense_replace", 0) or 0)
        mlp_types = list(get("mlp_layer_types") or [])
        if len(types_) < n:
            raise ValueError(
                f"exaone_moe layer_types names {len(types_)} layers, "
                f"num_layers is {n}")
        if mlp_types != (["dense"] * n_dense
                         + ["sparse"] * (len(mlp_types) - n_dense)):
            raise NotImplementedError(
                "exaone_moe with dense layers anywhere but in front "
                "(mlp_layer_types against first_k_dense_replace)")
        if get("hidden_act", "silu") != "silu":
            raise NotImplementedError("exaone_moe hidden_act must be silu")
        rp = get("rope_parameters") or {}
        if rp.get("rope_type", "default") != "default":
            raise NotImplementedError(
                f"exaone_moe rope_type {rp.get('rope_type')!r}")
        held = int(get("num_experts"))
        kw.update(
            rope_theta=float(rp.get("rope_theta", kw["rope_theta"])),
            qkv_bias=False, o_bias=False, qk_norm=True,
            norm_placement="post",
            layer_pattern=tuple(
                "sliding" if t == "sliding_attention" else "global"
                for t in types_[:n]),
            rope_kinds=("sliding",),
            first_dense_layers=n_dense,
            num_experts=held,
            num_experts_per_tok=int(get("num_experts_per_tok")),
            moe_intermediate_size=int(get("moe_intermediate_size")),
            moe_scoring=get("scoring_func", "sigmoid"),
            moe_n_group=int(get("n_group", 1) or 1),
            moe_topk_group=int(get("topk_group", 1) or 1),
            moe_route_scale=float(get("routed_scaling_factor", 1.0)),
            moe_renorm_topk=bool(get("norm_topk_prob", True)),
            moe_router_bias=True,
            moe_shared_experts=int(get("num_shared_experts", 0) or 0),
            moe_router_width=int(get("router_n_experts", held)),
            moe_first_expert=int(get("first_held_expert", 0)),
            moe_dispatch="grouped")
    if mt == "mellum":
        # Mellum 2 (JetBrains): the Qwen3-MoE block — grouped-query
        # attention with RMSNorm over each head of q and k before rope
        # (the family's block has it without a config key), pre-norm,
        # every MLP `sparse`: softmax scores over `num_experts`, the
        # `num_experts_per_tok` largest, renormalised (`norm_topk_prob`),
        # no shared expert — under `layer_types` that mix
        # 'sliding_attention' layers (`sliding_window` positions, plain
        # rope) with 'full_attention' layers whose rope is YaRN-stretched:
        # `rope_parameters` holds one section a kind.  The experts run on
        # the dropless grouped path, which a train step spreads over
        # 'ep' (models/moe.routed_experts).  The multi-token-prediction
        # head the release describes has no key here and is not built.
        types_ = list(get("layer_types") or [])
        n = int(overrides.get("num_layers", kw["num_layers"]))
        if len(types_) < n:
            raise ValueError(
                f"mellum layer_types names {len(types_)} layers, "
                f"num_layers is {n}")
        if any(t != "sparse" for t in (get("mlp_layer_types") or [])[:n]):
            raise NotImplementedError(
                "mellum with dense MLP layers (mlp_layer_types)")
        if get("hidden_act", "silu") != "silu":
            raise NotImplementedError("mellum hidden_act must be silu")
        rp = get("rope_parameters") or {}
        full = rp.get("full_attention") or {}
        slide = rp.get("sliding_attention") or {}
        if slide.get("rope_type", "default") != "default" \
                or full.get("rope_type", "default") not in ("default",
                                                            "yarn"):
            raise NotImplementedError(
                f"mellum rope types {slide.get('rope_type')!r} (sliding) "
                f"/ {full.get('rope_type')!r} (full)")
        theta = float(full.get("rope_theta", kw["rope_theta"]))
        local = float(slide.get("rope_theta", theta))
        kw.update(
            rope_theta=theta,
            rope_local_theta=None if local == theta else local,
            qk_norm=True,
            layer_pattern=tuple(
                "sliding" if t == "sliding_attention" else "global"
                for t in types_[:n]),
            num_experts=int(get("num_experts")),
            num_experts_per_tok=int(get("num_experts_per_tok")),
            moe_intermediate_size=int(get("moe_intermediate_size")),
            moe_scoring="softmax",
            moe_renorm_topk=bool(get("norm_topk_prob", True)),
            # the family's load-balance loss is one mean over the layers;
            # the program SUMS the layers' terms
            router_aux_weight=float(get("router_aux_loss_coef", 0.001)) / n,
            moe_dispatch="grouped")
        if full.get("rope_type") == "yarn":
            af = full.get("attention_factor")
            kw.update(
                rope_yarn=(
                    float(full["factor"]),
                    float(full.get("original_max_position_embeddings")
                          or kw["max_seq_len"]),
                    float(full.get("beta_fast") or 32.0),
                    float(full.get("beta_slow") or 1.0),
                    None if af is None else float(af),
                    bool(full.get("truncate", True))),
                rope_yarn_kinds=("global",))
    if mt == "nemotron_h":
        # Nemotron-H / Nemotron 3 (NVIDIA): every layer is ONE mixer under
        # a pre-norm, x + mixer(RMSNorm(x)), named by a character of
        # `hybrid_override_pattern`: 'M' a Mamba-2 mixer (mamba_num_heads
        # heads of mamba_head_dim, n_groups groups, ssm_state_size,
        # conv_kernel; d_inner is heads x head_dim, not expand x hidden),
        # 'E' routed experts (sigmoid scores, a selection bias,
        # renormalised top-k times routed_scaling_factor, one shared
        # expert of its own width; relu2 FFNs of two matrices), '*'
        # grouped-query attention WITHOUT a rotary embedding (the
        # nemotron_h attention applies none; `rope_theta` stays unread).
        # `n_routed_experts` is the number of experts THIS program holds
        # (`router_n_experts`, `first_held_expert` beside it state an
        # expert-parallel share, as for axk1).
        kinds = {"M": "mamba", "E": "moe", "*": "attention"}
        pattern = str(get("hybrid_override_pattern") or "")
        n = int(overrides.get("num_layers", kw["num_layers"]))
        if set(pattern) - set(kinds):
            raise NotImplementedError(
                f"nemotron_h layers {sorted(set(pattern) - set(kinds))} "
                f"('-': a dense MLP layer) are not implemented; 'M', 'E' "
                f"and '*' are")
        if len(pattern) < n:
            raise ValueError(
                f"nemotron_h hybrid_override_pattern names {len(pattern)} "
                f"layers, num_layers is {n}")
        if get("mlp_hidden_act", "relu2") != "relu2" \
                or get("mamba_hidden_act", "silu") != "silu":
            raise NotImplementedError(
                "nemotron_h with mlp_hidden_act other than relu2 or "
                "mamba_hidden_act other than silu")
        if (get("mamba_proj_bias", False) or get("use_bias", False)
                or get("attention_bias", False) or get("mlp_bias", False)
                or not get("use_conv_bias", True)):
            raise NotImplementedError(
                "nemotron_h with projection biases, or without the "
                "convolution's bias")
        held = int(get("n_routed_experts"))
        shared = int(get("n_shared_experts", 0) or 0)
        kw.update(
            mixer_pattern=tuple(kinds[c] for c in pattern[:n]),
            pos_emb="none", qkv_bias=False, o_bias=False, mlp_bias=False,
            norm_eps=float(get("layer_norm_epsilon",
                               get("norm_eps", kw["norm_eps"]))),
            activation="relu2",
            ssm_heads=int(get("mamba_num_heads")),
            ssm_head_dim=int(get("mamba_head_dim")),
            ssm_state=int(get("ssm_state_size")),
            ssm_groups=int(get("n_groups", 1) or 1),
            ssm_conv=int(get("conv_kernel", 4)),
            ssm_chunk=int(get("chunk_size", 128)),
            num_experts=held,
            num_experts_per_tok=int(get("num_experts_per_tok")),
            moe_intermediate_size=int(get("moe_intermediate_size")),
            moe_shared_experts=shared,
            moe_shared_intermediate_size=(
                int(get("moe_shared_expert_intermediate_size"))
                if shared and get("moe_shared_expert_intermediate_size")
                else None),
            moe_scoring="sigmoid",
            moe_n_group=int(get("n_group", 1) or 1),
            moe_topk_group=int(get("topk_group", 1) or 1),
            moe_route_scale=float(get("routed_scaling_factor", 1.0)),
            moe_renorm_topk=bool(get("norm_topk_prob", True)),
            moe_router_bias=True,
            moe_router_width=int(get("router_n_experts", held)),
            moe_first_expert=int(get("first_held_expert", 0)),
            moe_dispatch="grouped")
    if mt == "mixtral":
        # Mixtral 8x7B/8x22B: llama attention + top-k sparse MoE MLP.
        # HF routes softmax-then-topk-then-renormalise, which equals the
        # zoo's topk-then-softmax exactly (softmax is monotonic, and
        # renormalising the selected probs reproduces softmax over the
        # selected logits) — so logits match with dense dispatch.
        kw.update(
            num_experts=int(get("num_local_experts")),
            num_experts_per_tok=int(get("num_experts_per_tok", 2)),
            router_aux_weight=float(get("router_aux_loss_coef", 0.01)))
    if mt not in ("gemma3", "gemma3_text"):
        # generic rope_scaling (gemma3 parses its own above): 'linear'
        # divides positions, 'llama3' is Llama-3.1's frequency banding,
        # 'longrope' is Phi-3.5/4's per-dim divisors, 'yarn' is the
        # qwen 128k recipe.  Anything else fails LOUDLY — silently
        # dropping a scaling would make long-context logits quietly
        # wrong.
        rs = get("rope_scaling")
        if rs:
            rt = rs.get("rope_type", rs.get("type", "default"))
            if rt == "linear":
                kw["rope_scale"] = float(rs["factor"])
            elif rt == "llama3":
                kw["rope_llama3"] = (
                    float(rs["factor"]),
                    float(rs["low_freq_factor"]),
                    float(rs["high_freq_factor"]),
                    float(rs["original_max_position_embeddings"]))
            elif rt == "longrope":
                # Phi-3.5/4 128k: per-dim divisors.  HF semantics: the
                # original context comes from the CONFIG ATTR when
                # present (factor = max_pos / orig); otherwise orig =
                # max_pos and the rs-level 'factor' drives the default
                # attention factor.  Compute that default HERE so _rope
                # never has to guess the effective factor.
                import math as _m
                attr_orig = get("original_max_position_embeddings")
                orig = float(attr_orig or kw["max_seq_len"])
                f_eff = (kw["max_seq_len"] / orig if attr_orig
                         else float(rs.get("factor") or 1.0))
                af = rs.get("attention_factor")
                if af is None:
                    af = (1.0 if f_eff <= 1.0
                          else _m.sqrt(1.0 + _m.log(f_eff)
                                       / _m.log(orig)))
                kw["rope_longrope"] = (
                    tuple(float(x) for x in rs["short_factor"]),
                    tuple(float(x) for x in rs["long_factor"]),
                    orig, float(af))
            elif rt == "yarn":
                # qwen 128k variants.  Fallbacks mirror HF
                # _compute_yarn_parameters exactly: original_max falls
                # back to max_position_embeddings (NOT divided by
                # factor, and the top-level config attr is not
                # consulted); beta defaults use `or` (an explicit null
                # still means 32/1)
                orig = float(rs.get("original_max_position_embeddings")
                             or kw["max_seq_len"])
                af = rs.get("attention_factor")
                if af is None and rs.get("mscale") \
                        and rs.get("mscale_all_dim"):
                    # the mscale variant: the cos/sin factor is the ratio
                    # of the two (HF _compute_yarn_parameters)
                    import math as _m
                    f = float(rs["factor"])
                    ms = lambda m: (1.0 if f <= 1.0  # noqa: E731
                                    else 0.1 * float(m) * _m.log(f) + 1.0)
                    af = ms(rs["mscale"]) / ms(rs["mscale_all_dim"])
                kw["rope_yarn"] = (
                    float(rs["factor"]), orig,
                    float(rs.get("beta_fast") or 32.0),
                    float(rs.get("beta_slow") or 1.0),
                    None if af is None else float(af),
                    bool(rs.get("truncate", True)))
            elif rt != "default":
                raise NotImplementedError(
                    f"rope_scaling type {rt!r} is not implemented "
                    f"(linear, llama3, longrope and yarn are)")
    if get("final_logit_softcapping"):
        kw["logit_softcap"] = float(get("final_logit_softcapping"))
    if get("sliding_window") and get("use_sliding_window", True):
        # HF sliding masks attend iff kv > q - sliding_window (inclusive
        # count = sliding_window); our window=(left, right) attends
        # kv >= q - left (count = left + 1) -> left = sliding_window - 1
        kw["window"] = (int(get("sliding_window")) - 1, -1)
    kw.update(overrides)
    return ModelConfig(**kw)


def _pattern_from_layer_types(layer_types,
                              sliding_window_pattern=None
                              ) -> Tuple[str, ...]:
    """Shortest cyclic layer_pattern reproducing HF's per-layer
    ``layer_types`` list (gemma3: 5 sliding + 1 full).  Pre-4.53
    transformers gemma3 configs expose ``sliding_window_pattern=p``
    (every p-th layer global) instead of ``layer_types``."""
    if not layer_types:
        if sliding_window_pattern:
            p = int(sliding_window_pattern)
            return ("sliding",) * (p - 1) + ("global",)
        raise ValueError("layer_types missing from the HF config")
    kinds = tuple("sliding" if t == "sliding_attention" else "global"
                  for t in layer_types)
    n = len(kinds)
    for period in range(1, n):
        if n % period == 0 and kinds == kinds[:period] * (n // period):
            return kinds[:period]
    return kinds  # no shorter period: one full cycle


def _t(x) -> np.ndarray:
    if hasattr(x, "detach"):
        x = x.detach().cpu().float().numpy()
    return np.asarray(x)


def _params_from_gpt2(state_dict, cfg: ModelConfig, dtype):
    """GPT-2 state dict -> TransformerLM params.  GPT-2 uses Conv1D
    layers whose weights are already [in, out] (no transpose), a packed
    c_attn with COLUMNS [q | k | v], and biases everywhere."""
    L, h = cfg.num_layers, cfg.hidden_size
    nh, d = cfg.num_heads, cfg.head_size
    f = cfg.ffn_size

    def get(name):
        for prefix in ("transformer.", ""):
            if prefix + name in state_dict:
                return _t(state_dict[prefix + name])
        raise KeyError(f"missing weight {name!r} in state_dict")

    def stack(fmt, transform):
        return np.stack([transform(get(fmt.format(i=i))) for i in range(L)])

    # one fetch + torch->numpy conversion of each packed c_attn per
    # layer (gpt2-xl's is ~29 MB); slice the cached array three ways
    qw, kw_, vw, qb, kb, vb = ([] for _ in range(6))
    for i in range(L):
        w = get(f"h.{i}.attn.c_attn.weight")   # [h, 3h], cols [q|k|v]
        b = get(f"h.{i}.attn.c_attn.bias")
        qw.append(w[:, :h].reshape(h, nh, d))
        kw_.append(w[:, h:2 * h].reshape(h, nh, d))
        vw.append(w[:, 2 * h:].reshape(h, nh, d))
        qb.append(b[:h].reshape(nh, d))
        kb.append(b[h:2 * h].reshape(nh, d))
        vb.append(b[2 * h:].reshape(nh, d))
    attn = {
        "q_proj": {"kernel": np.stack(qw), "bias": np.stack(qb)},
        "k_proj": {"kernel": np.stack(kw_), "bias": np.stack(kb)},
        "v_proj": {"kernel": np.stack(vw), "bias": np.stack(vb)},
        "o_proj": {"kernel": stack("h.{i}.attn.c_proj.weight",
                                   lambda w: w.reshape(nh, d, h)),
                   "bias": stack("h.{i}.attn.c_proj.bias", lambda b: b)},
    }
    block = {
        "attn": attn,
        "mlp": {
            "up_proj": {"kernel": stack("h.{i}.mlp.c_fc.weight",
                                        lambda w: w.reshape(h, f)),
                        "bias": stack("h.{i}.mlp.c_fc.bias", lambda b: b)},
            "down_proj": {"kernel": stack("h.{i}.mlp.c_proj.weight",
                                          lambda w: w.reshape(f, h)),
                          "bias": stack("h.{i}.mlp.c_proj.bias",
                                        lambda b: b)},
        },
        "ln1": {"scale": stack("h.{i}.ln_1.weight", lambda w: w),
                "bias": stack("h.{i}.ln_1.bias", lambda b: b)},
        "ln2": {"scale": stack("h.{i}.ln_2.weight", lambda w: w),
                "bias": stack("h.{i}.ln_2.bias", lambda b: b)},
    }
    params: Dict[str, Any] = {
        "embed_tokens": {"embedding": get("wte.weight")},
        "pos_embed": get("wpe.weight")[:cfg.max_seq_len],
        "layers": {"block": block},
        "final_norm": {"scale": get("ln_f.weight"),
                       "bias": get("ln_f.bias")},
    }
    import jax
    return jax.tree.map(lambda a: jnp.asarray(a, dtype), params)


def _params_from_neox(state_dict, cfg: ModelConfig, dtype):
    """GPT-NeoX state dict -> TransformerLM params: ``gpt_neox.``
    prefix, packed per-head ``attention.query_key_value`` ([q|k|v] rows
    PER HEAD — not the phi3 whole-tensor split), ``attention.dense``,
    ``mlp.dense_h_to_4h/dense_4h_to_h``, biased LayerNorms, top-level
    ``embed_out`` head."""
    L, h = cfg.num_layers, cfg.hidden_size
    nh, d = cfg.num_heads, cfg.head_size

    def get(name):
        for prefix in ("gpt_neox.", ""):
            if prefix + name in state_dict:
                return _t(state_dict[prefix + name])
        raise KeyError(f"missing weight {name!r} in state_dict")

    def stack(fmt, transform):
        return np.stack([transform(get(fmt.format(i=i))) for i in range(L)])

    qw, kw_, vw, qb, kb, vb = ([] for _ in range(6))
    for i in range(L):
        w = get(f"layers.{i}.attention.query_key_value.weight")
        w3 = w.reshape(nh, 3 * d, h)          # rows per head: [q|k|v]
        # -> [h, nh, d] kernels / [nh, d] biases
        qw.append(w3[:, :d, :].transpose(2, 0, 1))
        kw_.append(w3[:, d:2 * d, :].transpose(2, 0, 1))
        vw.append(w3[:, 2 * d:, :].transpose(2, 0, 1))
        if cfg.qkv_bias:   # attention_bias=False checkpoints ship none
            b3 = get(f"layers.{i}.attention.query_key_value.bias"
                     ).reshape(nh, 3 * d)
            qb.append(b3[:, :d])
            kb.append(b3[:, d:2 * d])
            vb.append(b3[:, 2 * d:])
    attn = {
        "q_proj": {"kernel": np.stack(qw)},
        "k_proj": {"kernel": np.stack(kw_)},
        "v_proj": {"kernel": np.stack(vw)},
        "o_proj": {"kernel": stack("layers.{i}.attention.dense.weight",
                                   lambda w: w.T.reshape(nh, d, h))},
    }
    if cfg.qkv_bias:
        attn["q_proj"]["bias"] = np.stack(qb)
        attn["k_proj"]["bias"] = np.stack(kb)
        attn["v_proj"]["bias"] = np.stack(vb)
    if cfg.o_bias:
        attn["o_proj"]["bias"] = stack(
            "layers.{i}.attention.dense.bias", lambda b: b)
    block = {
        "attn": attn,
        "mlp": {
            "up_proj": {"kernel": stack(
                "layers.{i}.mlp.dense_h_to_4h.weight", lambda w: w.T),
                "bias": stack("layers.{i}.mlp.dense_h_to_4h.bias",
                              lambda b: b)},
            "down_proj": {"kernel": stack(
                "layers.{i}.mlp.dense_4h_to_h.weight", lambda w: w.T),
                "bias": stack("layers.{i}.mlp.dense_4h_to_h.bias",
                              lambda b: b)},
        },
        "ln1": {"scale": stack("layers.{i}.input_layernorm.weight",
                               lambda w: w),
                "bias": stack("layers.{i}.input_layernorm.bias",
                              lambda b: b)},
        "ln2": {"scale": stack(
            "layers.{i}.post_attention_layernorm.weight", lambda w: w),
            "bias": stack("layers.{i}.post_attention_layernorm.bias",
                          lambda b: b)},
    }
    params: Dict[str, Any] = {
        "embed_tokens": {"embedding": get("embed_in.weight")},
        "layers": {"block": block},
        "final_norm": {"scale": get("final_layer_norm.weight"),
                       "bias": get("final_layer_norm.bias")},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": _t(state_dict["embed_out.weight"]).T}
    import jax
    return jax.tree.map(lambda a: jnp.asarray(a, dtype), params)


#: GPT-NeoX attention tensors live at ``layers.<i>.attention.
#: query_key_value.weight`` (optionally under a ``gpt_neox.`` prefix).
#: Anchoring on the ``layers.<i>.`` prefix matters: Falcon-style
#: checkpoints name theirs ``h.<i>.self_attention.query_key_value.
#: weight``, which a bare ``endswith("attention.query_key_value.
#: weight")`` also matches — dispatching those through the NeoX layout
#: would silently mis-convert (wrong transpose + fused-qkv split).
_NEOX_QKV_RE = re.compile(
    r"(?:^|\.)layers\.\d+\.attention\.query_key_value\.weight$")


def _is_neox_state_dict(state_dict: Mapping[str, Any]) -> bool:
    """True only for the GPT-NeoX tensor layout (see ``_NEOX_QKV_RE``);
    Falcon-style ``self_attention.query_key_value`` keys do NOT
    qualify."""
    return any(_NEOX_QKV_RE.search(k) for k in state_dict)


def params_from_hf_state_dict(
    state_dict: Mapping[str, Any],
    cfg: ModelConfig,
    dtype=None,
) -> Dict[str, Any]:
    """Map an HF llama/qwen2-style state_dict to TransformerLM params.

    HF linear weights are [out, in]; flax kernels are [in, out] (and
    DenseGeneral splits heads), so weights are transposed/reshaped.
    Layers are stacked on a leading dim for scan-over-layers.
    GPT-2 checkpoints (Conv1D packed weights, ``transformer.``-prefixed
    names) take their own mapping.
    """
    dtype = dtype or cfg.param_dtype
    if cfg.kv_lora_rank:
        raise NotImplementedError(
            "converting a latent-attention (axk1) checkpoint is not "
            "implemented: config_from_hf builds the model, the weight "
            "mapping (kv_b_proj split into kv_b_k / kv_b_v, the two "
            "layer stacks, a held share of the experts) is not written")
    # the Conv1D-packed c_attn is specific to the gpt2 layout (GPT-J /
    # GPT-Neo also have wte but different attention naming — those are
    # unsupported and will fail on their attention tensors loudly)
    if any(k.endswith("attn.c_attn.weight") for k in state_dict):
        return _params_from_gpt2(state_dict, cfg, dtype)
    if _is_neox_state_dict(state_dict):
        return _params_from_neox(state_dict, cfg, dtype)
    L = cfg.num_layers
    h = cfg.hidden_size
    nh, nk, d = cfg.num_heads, cfg.kv_heads, cfg.head_size

    def get(name):
        for prefix in ("model.", ""):
            key = prefix + name
            if key in state_dict:
                return _t(state_dict[key])
        raise KeyError(f"missing weight {name!r} in state_dict")

    def stack(fmt, transform):
        return np.stack([transform(get(fmt.format(i=i))) for i in range(L)])

    qkv = lambda w, heads: w.T.reshape(h, heads, d)

    def has(name):
        return any(p + name in state_dict for p in ("model.", ""))

    if has("layers.0.self_attn.qkv_proj.weight"):
        # Phi-3 packed attention: qkv_proj rows are [q | k | v]
        qr, kr = nh * d, nk * d
        attn = {
            "q_proj": {"kernel": stack(
                "layers.{i}.self_attn.qkv_proj.weight",
                lambda w: qkv(w[:qr], nh))},
            "k_proj": {"kernel": stack(
                "layers.{i}.self_attn.qkv_proj.weight",
                lambda w: qkv(w[qr:qr + kr], nk))},
            "v_proj": {"kernel": stack(
                "layers.{i}.self_attn.qkv_proj.weight",
                lambda w: qkv(w[qr + kr:], nk))},
        }
    else:
        attn = {
            "q_proj": {"kernel": stack("layers.{i}.self_attn.q_proj.weight",
                                       lambda w: qkv(w, nh))},
            "k_proj": {"kernel": stack("layers.{i}.self_attn.k_proj.weight",
                                       lambda w: qkv(w, nk))},
            "v_proj": {"kernel": stack("layers.{i}.self_attn.v_proj.weight",
                                       lambda w: qkv(w, nk))},
        }
    # phi names the output projection self_attn.dense
    o_name = ("dense" if has("layers.0.self_attn.dense.weight")
              else "o_proj")
    attn["o_proj"] = {"kernel": stack(
        f"layers.{{i}}.self_attn.{o_name}.weight",
        lambda w: w.T.reshape(nh, d, h))}
    if cfg.qkv_bias:
        for name, heads in (("q_proj", nh), ("k_proj", nk), ("v_proj", nk)):
            attn[name]["bias"] = stack(
                f"layers.{{i}}.self_attn.{name}.bias",
                lambda b, heads=heads: b.reshape(heads, d))
    if cfg.o_bias:
        attn["o_proj"]["bias"] = stack(
            f"layers.{{i}}.self_attn.{o_name}.bias", lambda b: b)
    if cfg.qk_norm:
        attn["q_norm"] = {"scale": stack(
            "layers.{i}.self_attn.q_norm.weight", lambda w: w)}
        attn["k_norm"] = {"scale": stack(
            "layers.{i}.self_attn.k_norm.weight", lambda w: w)}

    # OLMo2 post-norm placement renames both block norms; decide the
    # source tensors once so pre/post stay in one place
    post = cfg.norm_placement == "post"
    ln1_src = ("layers.{i}.post_attention_layernorm.weight" if post
               else "layers.{i}.input_layernorm.weight")
    ln2_src = ("layers.{i}.post_feedforward_layernorm.weight" if post
               else "layers.{i}.post_attention_layernorm.weight")
    block = {
        "attn": attn,
        "ln1": {"scale": stack(ln1_src, lambda w: w)},
    }
    if cfg.num_experts > 0:
        # Sparse MoE -> MoEMlp: router [e, h] -> [h, e] kernel; expert
        # FFNs stack [L, e, ...] to the zoo's expert-major layout.
        # Mixtral names them block_sparse_moe.{gate, experts.j.w1/w3/w2};
        # qwen3_moe uses mlp.{gate, experts.j.gate_proj/up_proj/down_proj}
        E = cfg.num_experts
        # one detector shared with the streaming path so the two cannot
        # diverge on a future naming style
        from torchacc_tpu.models.hf_stream import _detect_moe_style
        if _detect_moe_style(state_dict) == "qwen":
            moe_mod, wg, wu, wd = ("mlp", "gate_proj", "up_proj",
                                   "down_proj")
        else:
            moe_mod, wg, wu, wd = "block_sparse_moe", "w1", "w3", "w2"

        def experts_stack(wn):
            return np.stack([
                np.stack([
                    get(f"layers.{i}.{moe_mod}.experts.{j}."
                        f"{wn}.weight").T
                    for j in range(E)]) for i in range(L)])

        block["moe"] = {
            "router": {"kernel": stack(
                "layers.{{i}}.{}.gate.weight".format(moe_mod),
                lambda w: w.T)},
            "experts/gate": experts_stack(wg),
            "experts/up": experts_stack(wu),
            "experts/down": experts_stack(wd),
        }
    elif has("layers.0.mlp.gate_up_proj.weight"):
        # Phi-3 packed MLP: gate_up_proj rows are [gate | up]
        inter = cfg.intermediate_size
        block["mlp"] = {
            "gate_proj": {"kernel": stack(
                "layers.{i}.mlp.gate_up_proj.weight",
                lambda w: w[:inter].T)},
            "up_proj": {"kernel": stack(
                "layers.{i}.mlp.gate_up_proj.weight",
                lambda w: w[inter:].T)},
            "down_proj": {"kernel": stack(
                "layers.{i}.mlp.down_proj.weight", lambda w: w.T)},
        }
    elif has("layers.0.mlp.c_fc.weight") or has("layers.0.mlp.fc1.weight"):
        # NON-gated MLPs: StarCoder2 names them c_fc/c_proj, phi fc1/fc2
        # (activation='gelu' builds no gate_proj)
        up_n, dn_n = (("c_fc", "c_proj")
                      if has("layers.0.mlp.c_fc.weight")
                      else ("fc1", "fc2"))
        block["mlp"] = {
            "up_proj": {"kernel": stack(
                f"layers.{{i}}.mlp.{up_n}.weight", lambda w: w.T)},
            "down_proj": {"kernel": stack(
                f"layers.{{i}}.mlp.{dn_n}.weight", lambda w: w.T)},
        }
        if cfg.mlp_bias:
            block["mlp"]["up_proj"]["bias"] = stack(
                f"layers.{{i}}.mlp.{up_n}.bias", lambda b: b)
            block["mlp"]["down_proj"]["bias"] = stack(
                f"layers.{{i}}.mlp.{dn_n}.bias", lambda b: b)
    else:
        # gated (llama) MLPs carry gate/up/down; non-gated models that
        # keep the up/down names (nemotron relu2) just drop the gate
        gated = cfg.activation in ("swiglu", "geglu")
        names = (("gate_proj", "up_proj", "down_proj") if gated
                 else ("up_proj", "down_proj"))
        block["mlp"] = {
            nm: {"kernel": stack(
                f"layers.{{i}}.mlp.{nm}.weight", lambda w: w.T)}
            for nm in names}
        if cfg.mlp_bias:
            for nm in names:
                block["mlp"][nm]["bias"] = stack(
                    f"layers.{{i}}.mlp.{nm}.bias", lambda b: b)
    if cfg.sandwich_norms:
        # gemma2 norm naming: post_attention_layernorm is the POST-attn
        # sandwich norm; the pre-mlp norm is pre_feedforward_layernorm
        block["ln1_post"] = {"scale": stack(
            "layers.{i}.post_attention_layernorm.weight", lambda w: w)}
        block["ln2"] = {"scale": stack(
            "layers.{i}.pre_feedforward_layernorm.weight", lambda w: w)}
        block["ln2_post"] = {"scale": stack(
            "layers.{i}.post_feedforward_layernorm.weight", lambda w: w)}
    elif not cfg.parallel_block:      # phi's parallel block has no ln2
        block["ln2"] = {"scale": stack(ln2_src, lambda w: w)}
    # phi names the final norm final_layernorm
    fn_src = ("final_layernorm" if has("final_layernorm.weight")
              else "norm")
    params: Dict[str, Any] = {
        "embed_tokens": {"embedding": get("embed_tokens.weight")},
        "layers": {"block": block},
        "final_norm": {"scale": get(f"{fn_src}.weight")},
    }
    if cfg.norm in ("layernorm", "layernorm1p") and cfg.norm_bias:
        # biased LayerNorms (StarCoder2/phi): same source names, .bias
        block["ln1"]["bias"] = stack(
            ln1_src.replace(".weight", ".bias"), lambda b: b)
        if "ln2" in block:
            block["ln2"]["bias"] = stack(
                ln2_src.replace(".weight", ".bias"), lambda b: b)
        params["final_norm"]["bias"] = get(f"{fn_src}.bias")
    if not cfg.tie_embeddings:
        # lm_head lives at the top level in HF models
        head = state_dict.get("lm_head.weight")
        if head is None:
            raise KeyError("lm_head.weight missing and tie_embeddings=False")
        params["lm_head"] = {"kernel": _t(head).T}
        if cfg.head_bias:
            params["lm_head"]["bias"] = _t(state_dict["lm_head.bias"])

    import jax
    return jax.tree.map(lambda a: jnp.asarray(a, dtype), params)


def load_hf_model(model_or_path: Any, **config_overrides
                  ) -> Tuple[ModelConfig, Dict[str, Any]]:
    """(ModelConfig, params) from a transformers model instance or a
    local checkpoint path."""
    if isinstance(model_or_path, str):
        import transformers
        model = transformers.AutoModelForCausalLM.from_pretrained(
            model_or_path)
    else:
        model = model_or_path
    cfg = config_from_hf(model.config, **config_overrides)
    params = params_from_hf_state_dict(model.state_dict(), cfg)
    return cfg, params
