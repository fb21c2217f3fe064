"""Decoder-only transformer LM family (flax.linen).

The reference accelerates existing torch models (GPT-2 via HF CLM
benchmarks/transformer.py:33-220, Llama/Qwen via transformers patches
utils/patch.py:224-301, qwen_patch.py).  The TPU-native framework ships
its own model zoo instead of monkeypatching: one configurable module
covers the GPT-2 class (learned positions, LayerNorm, GELU) and the
Llama/Qwen class (RoPE, RMSNorm, SwiGLU, GQA, optional qkv bias).
HF-trained weights are ingested by the converter in models/hf.py.

Layers are stacked with ``nn.scan`` (single compiled block, layer dim on
every param) — this keeps compile time O(1) in depth and gives pipeline
parallelism a natural stage-stacked layout.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import operator
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchacc_tpu.models import block
from torchacc_tpu.models.block import Norm
from torchacc_tpu.ops.attn import attention


@dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: Optional[int] = None      # None = MHA; < num_heads = GQA
    head_dim: Optional[int] = None          # None = hidden/heads
    intermediate_size: Optional[int] = None  # None = 4x hidden (gelu) / llama rule
    max_seq_len: int = 2048
    pos_emb: str = "rope"                   # 'rope' | 'learned' | 'alibi'
    # 'rmsnorm1p' is the Gemma variant: effective scale is (1 + w) with
    # w zero-initialised (HF GemmaRMSNorm)
    # 'rmsnorm' | 'layernorm' | 'rmsnorm1p' | 'layernorm1p' (nemotron:
    # zero-centred (1+w) scale AND bias over a mean-subtracted norm)
    norm: str = "rmsnorm"
    # 'geglu' is Gemma's gated tanh-GELU (gelu_pytorch_tanh on the gate);
    # 'gelu' (tanh approx), 'gelu_exact' (gpt-neox erf) and 'relu2'
    # (nemotron square-relu) are NON-gated 2-matrix MLPs
    activation: str = "swiglu"  # swiglu | gelu | geglu | relu2 | gelu_exact
    # Gemma multiplies token embeddings by sqrt(hidden_size)
    embed_scale: bool = False
    # Gemma2 final-logit soft-capping: logits = c * tanh(logits / c);
    # 0 disables.  Applied in the plain head, the fused-CE head
    # (ops/fused.py) and the 1F1B last-stage head alike.
    logit_softcap: float = 0.0
    # phi-2-style parallel residual: x + attn(ln1(x)) + mlp(ln1(x)) —
    # ONE shared pre-norm, no ln2 (HF PhiDecoderLayer / CohereDecoderLayer).
    # parallel_block_shared_norm=False is GPT-NeoX's variant: the mlp
    # branch reads its OWN pre-norm (x + attn(ln1(x)) + mlp(ln2(x)))
    parallel_block: bool = False
    parallel_block_shared_norm: bool = True
    head_bias: bool = False                 # bias on the lm_head (phi-2)
    norm_bias: bool = True                  # layernorm bias (False: cohere)
    rope_interleaved: bool = False          # cohere pairwise rope layout
    # Cohere logit multiplier; applied by SCALING the final-normed hidden
    # (logits*s == (x*s)@W), so every head path — plain, fused-CE,
    # tp-vocab-parallel, pp decode — inherits it from one place
    logit_scale: float = 1.0
    qkv_bias: bool = False                  # Qwen2 style
    o_bias: bool = False                    # bias on o_proj (llama
    #                                         attention_bias covers it;
    #                                         qwen2's does not)
    mlp_bias: bool = False                  # biases on the mlp denses
    tie_embeddings: bool = False
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16               # activation dtype
    param_dtype: Any = jnp.float32
    # how the stacked layers are APPLIED: True = one lax.scan, False =
    # a Python-unrolled loop over static slices, None = the framework
    # chooses (train/accelerate.apply_config_to_model, from the mesh);
    # readers go through scans_layers(), which takes None as the scan
    scan_layers: Optional[bool] = None
    remat: bool = False                     # remat each block (memory.gc)
    remat_policy: str = "nothing"           # see utils/remat.py
    # selective remat (reference gc_cls/gc_cnt, utils/checkpoint.py:67-81):
    # remat_cls picks WHICH submodules remat ('Block' = the whole decoder
    # layer; 'Attention' / 'Mlp' / 'MoEMlp' remat only that part);
    # remat_cnt remats only the first N layers (None = all).
    remat_cls: Optional[Tuple[str, ...]] = None
    remat_cnt: Optional[int] = None
    attention_impl: str = "auto"
    window: Tuple[int, int] = (-1, -1)      # sliding-window attention
    # Gemma2-style attention-score soft-capping: scores = c * tanh(s/c)
    # applied after the q-scale, before mask/softmax; 0 disables.
    # Implemented by both the Pallas kernel and the XLA attention.
    attn_logit_softcap: float = 0.0
    # query scaling override: None = head_dim ** -0.5; Gemma2 sets
    # query_pre_attn_scalar ** -0.5
    query_scale: Optional[float] = None
    # Gemma2 sandwich norms: extra RMSNorms AFTER attention and mlp
    # (HF post_attention_layernorm / post_feedforward_layernorm), adding
    # ln1_post / ln2_post params to each block
    sandwich_norms: bool = False
    # Gemma3 qk-norm: per-head-dim RMSNorm on q and k after projection,
    # before rope (adds q_norm / k_norm params to each attention)
    qk_norm: bool = False
    # OLMo2 variant of qk_norm: the RMSNorm runs over the FLAT q/k
    # projection (heads*head_dim jointly, one scale vector per
    # projection) instead of per-head-dim
    qk_norm_proj: bool = False
    # 'pre' (llama: x + f(norm(x))) or 'post' (OLMo2: x + norm(f(x)));
    # gemma2's sandwich_norms composes with 'pre' only
    norm_placement: str = "pre"
    # Llama-3.1 frequency-banded rope scaling (HF rope_type='llama3'):
    # (factor, low_freq_factor, high_freq_factor, original_max_pos)
    rope_llama3: Optional[Tuple[float, float, float, float]] = None
    # Phi-3.5/4 'longrope': (short_factor, long_factor,
    # original_max_pos, attention_factor) — per-dim inv_freq divisors,
    # long set active once positions exceed original_max_pos, cos/sin
    # scaled by attention_factor (None = HF's sqrt(1+ln(s)/ln(orig)))
    rope_longrope: Optional[Tuple[Tuple[float, ...], Tuple[float, ...],
                                  float, Optional[float]]] = None
    # fraction of head_dim that rotates (phi-4-mini: 0.75); the
    # remaining dims pass through rope untouched
    partial_rotary: float = 1.0
    # YaRN (qwen 128k variants): (factor, original_max_pos, beta_fast,
    # beta_slow, attention_factor, truncate) — NTK-by-parts inv_freq
    # interpolation with a linear ramp between the correction dims,
    # cos/sin scaled by the attention factor (None = HF's
    # 0.1*ln(factor)+1 for factor > 1, else 1)
    rope_yarn: Optional[Tuple[float, float, float, float,
                              Optional[float], bool]] = None
    # Gemma3 dual rope bases: 'sliding' pattern layers use this theta
    # (local 10k) while 'global' layers use cfg.rope_theta (1M);
    # None = every layer uses cfg.rope_theta
    rope_local_theta: Optional[float] = None
    # linear rope position scaling (HF rope_scaling type 'linear'):
    # rope sees positions / rope_scale.  Under a gemma3 layer_pattern
    # the factor applies to GLOBAL layers only (sliding layers reset to
    # 1, matching HF's unscaled local rotary)
    rope_scale: float = 1.0
    # heterogeneous per-layer attention (gemma2/3): a cycle of
    # 'sliding' (uses cfg.window) | 'global' (full attention) applied as
    # layer i -> pattern[i % len]. None = every layer uses cfg.window.
    # Layers stay structurally identical (the pattern is param-free), so
    # the canonical stacked layout and checkpoints are unchanged;
    # execution uses the per-layer loop (scan_layers is ignored).
    layer_pattern: Optional[Tuple[str, ...]] = None
    # the layer_pattern kinds whose layers carry the rotary embedding;
    # None = every layer does.  ('sliding',) is the exaone_moe family's
    # arrangement: rope on the windowed layers, NO position signal at all
    # on the global ones (pattern_cfg gives those pos_emb='none')
    rope_kinds: Optional[Tuple[str, ...]] = None
    # the layer_pattern kinds whose rotary embedding takes rope_yarn;
    # None = every layer that rotates does.  ('global',) is the mellum
    # family's arrangement: the full-attention layers stretch their
    # frequencies, the windowed ones keep the plain ones (pattern_cfg
    # gives those rope_yarn=None)
    rope_yarn_kinds: Optional[Tuple[str, ...]] = None
    # KV-cache decode mode (models/generate.py): __call__ consumes one
    # token per step, appending rotated k / raw v into the 'cache'
    # collection and attending over the filled prefix
    decode: bool = False
    # KV-cache length; None = max_seq_len.  generate() sets it to
    # prompt_len + max_new_tokens so short generations do not allocate
    # (or attend over) a max_seq_len-sized cache
    cache_len: Optional[int] = None
    # post-softmax attention dropout (reference flash_attn.py:418-423);
    # active only when the caller passes deterministic=False + a seed
    attn_dropout: float = 0.0
    # quantized forward matmuls (ops/quantized_matmul.py): the selected
    # dense sites run int8/fp8 with delayed per-tensor activation
    # scaling (amax history in the 'quant' collection) + just-in-time
    # per-channel weight scales; 'none' = bitwise legacy semantics.
    # Composes with the scan, unrolled and overlap_fsdp layer paths;
    # NOT with pp, layer_pattern, remat_cnt splits, or decode (the
    # guards in __call__ raise; generate() strips quant — inference
    # runs in the compute dtype).
    quant: str = "none"                     # 'none' | 'int8' | 'fp8'
    quant_sites: Tuple[str, ...] = ("attn", "mlp")
    quant_amax_history_len: int = 16
    quant_impl: str = "auto"                # 'auto' | 'pallas' | 'xla'
    # FSDP comm/compute overlap (PerfConfig.overlap_fsdp): run the
    # layers as the unrolled loop with the all-gather of layer i+1's
    # params issued before layer i's compute consumes its own —
    # decomposing the FSDP boundary so XLA can overlap the gather with
    # the compute ladder (parallel/sharding.fsdp_gather_params)
    overlap_fsdp: bool = False
    # context parallelism: attention runs in a shard_map region with the
    # sequence dim sharded over ('sp', 'spu') — see ops/context_parallel
    context_parallel: bool = False
    # pipeline parallelism: the layer stack runs as a circulating-micro-
    # batch pipeline over the 'pp' mesh axis — see parallel/pp.py
    pp_size: int = 1
    pp_num_micro: int = 1
    # interleaved pipeline: V non-adjacent layer chunks per stage
    # (Megatron virtual pipeline; parallel/pp.py virtual_stages)
    pp_virtual: int = 1
    # logical-axis rule table for activation sharding constraints; None =
    # parallel.sharding.DEFAULT_RULES (accelerate() injects make_rules(cfg))
    logical_axis_rules: Optional[Tuple] = None
    # 1F1B vocab-parallel head (pp_1f1b_forward_sum_count): False
    # restores the round-3 behavior of pinning the head weights
    # replicated inside the pipeline region
    tp_vocab_head: bool = True
    # MoE (0 = dense). See models/moe.py.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    router_aux_weight: float = 0.01   # switch-style load-balance loss weight
    # capacity-dispatch mechanism (models/moe.py): 'einsum' = one-hot
    # dispatch/combine einsums (MXU-friendly at small n*e*cap), 'sort' =
    # argsort/scatter (no [n, e, cap] materialisation — the Mixtral-scale
    # answer), 'auto' = sort above ~2^24 dispatch elements.  'grouped'
    # (capacity-free only) is the dropless sparse path: the (token,
    # expert) pairs on held experts sorted by expert through a grouped
    # matmul, FLOPs by the routed pairs
    moe_dispatch: str = "auto"
    # True (mixtral): softmax over the selected top-k logits (equals
    # HF's softmax-then-topk-then-renormalise).  False (qwen3-moe with
    # norm_topk_prob=false): combine weights are the UN-renormalised
    # full-softmax probs of the selected experts.
    moe_renorm_topk: bool = True
    # None = exact capacity-free dense dispatch (every token through
    # every expert — right for small e).  A float (e.g. 1.25) switches
    # to switch-transformer capacity dispatch: per-expert buffers of
    # ceil(cf * k * tokens / e) slots, FLOPs independent of e; tokens
    # over capacity are dropped (combine weight 0).
    moe_capacity_factor: Optional[float] = None
    # -- latent attention + held-expert MoE (models/mla.py, models/moe.py;
    # the 'axk1' family of models/hf.py) -----------------------------------
    # Multi-head latent attention: kv_lora_rank > 0 replaces the q/k/v
    # projections by low-rank ones (q through q_lora_rank, k/v through one
    # shared kv_lora_rank latent) with a head split of qk_nope_head_dim
    # un-rotated + qk_rope_head_dim rotated dims (the rotated key is ONE
    # head shared by all) and values of v_head_dim; query_scale carries
    # the softmax scale (yarn's mscale**2 folded in by the ingest)
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the first N layers are dense MLPs of intermediate_size, the rest
    # expert layers: two stacked trees ('dense_layers', 'layers'), each
    # scanned; expert FFNs are moe_intermediate_size wide (None =
    # intermediate_size)
    first_dense_layers: int = 0
    moe_intermediate_size: Optional[int] = None
    # router: 'softmax' (mixtral/qwen3, above) | 'sigmoid' scores with
    # group-limited top-k (groups of width/moe_n_group, a group's score =
    # its two largest, the moe_topk_group best groups stay), weights
    # renormalised (moe_renorm_topk) and times moe_route_scale;
    # moe_router_bias adds a selection-only bias to the scores
    moe_scoring: str = "softmax"
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_route_scale: float = 1.0
    moe_router_bias: bool = False
    # SwiGLU experts every token passes through, fused into one FFN of
    # moe_shared_experts * moe_intermediate_size
    moe_shared_experts: int = 0
    # expert-parallel share: the router scores moe_router_width experts
    # (None = num_experts) and this program holds num_experts of them
    # from moe_first_expert on, adding only their terms (moe_dispatch
    # 'grouped': dropless sort + grouped matmul; the other chips' terms
    # and the exchange are not this program's)
    moe_router_width: Optional[int] = None
    moe_first_expert: int = 0
    # -- two kinds of latent layer under one layer_pattern (the
    # 'dots3_note' family of models/hf.py; models/mla.kind_config) -------
    # 'global' layers are the latent attention above plus a learned
    # selection: an indexer (index_n_heads heads of index_head_dim over
    # one cached key a token) scores every cached position and attention
    # runs over the index_topk best (all of them up to index_topk).
    # 'sliding' layers are latent attention of their OWN sizes (swa_*)
    # over cfg.window, with rope base rope_local_theta.  layer_pattern
    # names every layer ('global' | 'sliding', num_layers entries).
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    swa_num_heads: int = 0
    swa_kv_lora_rank: int = 0
    swa_q_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    # normalised latents times sqrt(hidden / rank) (the scale correction
    # of low-rank projections); False = 1
    mla_lora_rescale: bool = False
    # 'headwise': the attention output of head h times sigmoid(x W_g)[h],
    # the gate read from the layer's normed input, before o_proj
    attn_gate: str = "none"
    # -- layers of ONE mixer each (the 'nemotron_h' family of
    # models/hf.py) ---------------------------------------------------------
    # mixer_pattern names every layer's single mixer: 'mamba' (a Mamba-2
    # state-space mixer, models/mamba2.py), 'moe' (the held-expert layer)
    # or 'attention' (grouped-query attention); a layer computes
    # x + mixer(norm(x)) (models/block.mixer_block).  The parameters are
    # one stacked tree a kind, 'layers/<kind>' [layers of that kind, ...].
    # Serving only (PagedDecoder walks the pattern; the module's forward
    # and the trainer refuse it).
    mixer_pattern: Optional[Tuple[str, ...]] = None
    # the state-space mixer: ssm_heads heads of ssm_head_dim channels
    # (d_inner = their product), a state of ssm_state values a channel,
    # ssm_groups groups of heads sharing B and C, a causal depthwise
    # convolution of width ssm_conv before the recurrence, and the
    # sub-chunk of the chunked scan (ops/ssm_scan.py)
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # width of the fused shared-expert FFN where it is not
    # moe_shared_experts * the routed experts' width (None = that)
    moe_shared_intermediate_size: Optional[int] = None

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def router_width(self) -> int:
        return self.moe_router_width or self.num_experts

    @property
    def expert_ffn_size(self) -> int:
        return self.moe_intermediate_size or self.ffn_size

    @property
    def shared_ffn_size(self) -> int:
        return (self.moe_shared_intermediate_size
                or self.moe_shared_experts * self.expert_ffn_size)

    @property
    def ffn_size(self) -> int:
        if self.intermediate_size is not None:
            return self.intermediate_size
        if self.activation in ("swiglu", "geglu"):
            # llama sizing: 2/3 * 4h, rounded up to a multiple of 256
            # (keeps the matmul dims MXU-tile friendly).  Pass
            # intermediate_size explicitly to pin an exact width.
            return ((8 * self.hidden_size // 3) + 255) // 256 * 256
        return 4 * self.hidden_size

    def num_params(self) -> int:
        """Analytic parameter count (for MFU math) — exact per family:
        biases (qkv/o/mlp/head), sandwich and qk norms, parallel-block
        norm counts, and biased LayerNorms are all accounted."""
        h, v = self.hidden_size, self.vocab_size
        d = self.head_size
        if self.mixer_pattern:
            # one mixer and one norm a layer, untied or tied head
            from torchacc_tpu.models.mamba2 import param_count as mamba
            mats = 3 if self.activation in ("swiglu", "geglu") else 2
            per_kind = {
                "mamba": mamba(self),
                "attention": 2 * h * d * (self.num_heads + self.kv_heads),
                "moe": (mats * h * (self.num_experts * self.expert_ffn_size
                                    + self.shared_ffn_size)
                        + h * self.router_width
                        + (self.router_width if self.moe_router_bias else 0))}
            return (v * h * (1 if self.tie_embeddings else 2) + h
                    + sum(per_kind[k] + h for k in layer_kinds(self)))
        emb = v * h + (self.max_seq_len * h if self.pos_emb == "learned" else 0)
        attn = h * (self.num_heads * d) + h * (2 * self.kv_heads * d) \
            + (self.num_heads * d) * h
        if self.qkv_bias:
            attn += (self.num_heads + 2 * self.kv_heads) * d
        if self.o_bias:
            attn += h
        if self.qk_norm:
            attn += ((self.num_heads + self.kv_heads) * d
                     if self.qk_norm_proj else 2 * d)
        if self.activation in ("swiglu", "geglu"):
            mlp = 3 * h * self.ffn_size
            if self.mlp_bias:
                mlp += 2 * self.ffn_size + h
        else:
            mlp = 2 * h * self.ffn_size
            if self.mlp_bias:
                mlp += self.ffn_size + h
        if self.kv_lora_rank:
            qk = self.qk_nope_head_dim + self.qk_rope_head_dim
            ql, r = self.q_lora_rank, self.kv_lora_rank
            attn = ((h * ql + ql + ql * self.num_heads * qk) if ql
                    else h * self.num_heads * qk)
            attn += (h * (r + self.qk_rope_head_dim) + r
                     + r * self.num_heads
                     * (self.qk_nope_head_dim + self.v_head_dim)
                     + self.num_heads * self.v_head_dim * h)
        if self.swa_kv_lora_rank:
            # per-kind latent attention: return early with the sum over
            # the pattern (gate and indexer projections counted)
            from torchacc_tpu.models.mla import attn_param_count
            attn = None
        dense_mlp = mlp
        if self.num_experts > 0:
            # the held experts, the router's whole width, shared experts
            per = (mlp if self.moe_intermediate_size is None
                   else mlp // self.ffn_size * self.expert_ffn_size)
            mlp = (per * (self.num_experts + self.moe_shared_experts)
                   + h * self.router_width
                   + (self.router_width if self.moe_router_bias else 0))
        norm_size = (2 * h
                     if self.norm in ("layernorm", "layernorm1p")
                     and self.norm_bias else h)
        per_block = (1 if self.parallel_block
                     and self.parallel_block_shared_norm
                     else (4 if self.sandwich_norms else 2))
        norms = (per_block * self.num_layers + 1) * norm_size
        out = 0 if self.tie_embeddings else v * h
        if self.head_bias:
            out += v
        nd = self.first_dense_layers
        attn_all = (self.num_layers * attn if attn is not None else
                    sum(attn_param_count(self, i)
                        for i in range(self.num_layers)))
        return (emb + attn_all + nd * dense_mlp
                + (self.num_layers - nd) * mlp + norms + out)


def softcap(logits: jax.Array, cap: float) -> jax.Array:
    """Gemma2 logit soft-capping ``c * tanh(logits / c)``; cap <= 0 is a
    no-op.  The single definition keeps the plain, fused-CE and 1F1B
    heads bit-identical."""
    if cap <= 0.0:
        return logits
    return jnp.tanh(logits / cap) * cap


def scale_hidden(cfg: "ModelConfig", xn: jax.Array) -> jax.Array:
    """Apply cohere's logit_scale to the final-normed hidden
    (logits * s == (x * s) @ W), so every head path — the module tail,
    ``head_logits`` (pp decode), the 1F1B head, and the fused-CE path
    fed by ``return_hidden`` — inherits the multiplier from ONE
    definition (same no-drift rationale as :func:`softcap`)."""
    if cfg.logit_scale == 1.0:
        return xn
    return xn * jnp.asarray(cfg.logit_scale, xn.dtype)


def alibi_slopes(num_heads: int) -> Tuple[float, ...]:
    """Standard ALiBi per-head slopes (geometric 2^(-8i/n) with the
    paper's interpolation for non-power-of-two head counts) — the same
    table the reference's models pass as ``alibi_slopes``."""
    import math

    def pow2(n):
        start = 2.0 ** (-8.0 / n)
        return [start ** (i + 1) for i in range(n)]

    if math.log2(num_heads).is_integer():
        return tuple(pow2(num_heads))
    m = 2 ** math.floor(math.log2(num_heads))
    return tuple(pow2(m) + pow2(2 * m)[0::2][:num_heads - m])


def _layer_seed(dropout_seed, layer_idx):
    """Decorrelate dropout across layers: mix the layer index into the
    seed (the hash itself only sees batch/head/q/k coordinates)."""
    s = jnp.asarray(dropout_seed, jnp.int32).astype(jnp.uint32)
    li = jnp.asarray(layer_idx, jnp.int32).astype(jnp.uint32)
    return (s + li * jnp.uint32(0x9E3779B9)).astype(jnp.int32)


def quant_site_on(cfg: "ModelConfig", site: str) -> bool:
    """Whether a dense ``site`` ('attn' | 'mlp' | 'head') runs the
    quantized matmul.  Decode always runs the plain dense (generate()
    strips quant anyway — inference is compute-dtype); the param
    layouts are identical either way, so this only picks execution."""
    return (cfg.quant != "none" and site in cfg.quant_sites
            and not cfg.decode)


def _quant_dense(cfg: "ModelConfig", name, features, axis, use_bias):
    """The quantized drop-in for an ``nn.DenseGeneral``/``nn.Dense``
    site: identical param names/shapes/init (same RNG stream, same
    checkpoints), quantized forward, delayed-scaling amax history in
    the 'quant' collection (ops/quantized_matmul.QuantDenseGeneral)."""
    from torchacc_tpu.ops.quantized_matmul import QuantDenseGeneral
    return QuantDenseGeneral(
        features=features, axis=axis, use_bias=use_bias, name=name,
        dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        kernel_init=nn.initializers.normal(0.02),
        quant=cfg.quant, quant_impl=cfg.quant_impl,
        amax_history_len=cfg.quant_amax_history_len)


def _site_proj(cfg: "ModelConfig", site: str, sites):
    """The block's ``proj`` verb (models/block.py) for a Flax module:
    ``sites`` maps a parameter's name to ``(features, axis, use_bias)``;
    the verb creates the dense of that name and applies it — the
    quantized one where dense ``site`` runs quantized."""
    def proj(name, t):
        features, axis, use_bias = sites[name]
        if quant_site_on(cfg, site):
            return _quant_dense(cfg, name, features, axis, use_bias)(t)
        return nn.DenseGeneral(
            features=features, axis=axis, use_bias=use_bias, name=name,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02))(t)
    return proj


def _layout_hint(cfg: "ModelConfig"):
    """The block's ``hint`` verb for training: the megatron TP
    activation layout under the config's logical-axis rules."""
    from torchacc_tpu.parallel.sharding import (
        DEFAULT_RULES,
        activation_constraint,
    )
    rules = cfg.logical_axis_rules or DEFAULT_RULES
    return lambda t, axes: activation_constraint(t, axes, rules)


class Attention(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, dropout_seed=None):
        cfg = self.cfg
        d = cfg.head_size
        proj = _site_proj(cfg, "attn", {
            "q_proj": ((cfg.num_heads, d), -1, cfg.qkv_bias),
            "k_proj": ((cfg.kv_heads, d), -1, cfg.qkv_bias),
            "v_proj": ((cfg.kv_heads, d), -1, cfg.qkv_bias),
            "o_proj": (cfg.hidden_size, (-2, -1), cfg.o_bias)})
        hint = _layout_hint(cfg)
        q, k, v = block.qkv(
            cfg, x, positions, proj,
            norm=lambda name, t: Norm(cfg, name=name)(t), hint=hint)
        slopes = (jnp.asarray(alibi_slopes(cfg.num_heads), jnp.float32)
                  if cfg.pos_emb == "alibi" else None)

        # -- KV cache (prefill writes the prompt's k/v; decode appends
        # one position and attends over the filled prefix).  Not created
        # at init so checkpoints/params stay cache-free. ----------------
        if self.has_variable("cache", "k") or (
                self.is_mutable_collection("cache")
                and not self.is_initializing()):
            b, s = x.shape[0], x.shape[1]
            max_len = cfg.cache_len or cfg.max_seq_len
            ck = self.variable("cache", "k", jnp.zeros,
                               (b, max_len, cfg.kv_heads, d), cfg.dtype)
            cv = self.variable("cache", "v", jnp.zeros,
                               (b, max_len, cfg.kv_heads, d), cfg.dtype)
            cidx = self.variable("cache", "idx",
                                 lambda: jnp.zeros((), jnp.int32))
            if cfg.decode:
                pos = cidx.value
                ck.value = jax.lax.dynamic_update_slice(
                    ck.value, k.astype(cfg.dtype), (0, pos, 0, 0))
                cv.value = jax.lax.dynamic_update_slice(
                    cv.value, v.astype(cfg.dtype), (0, pos, 0, 0))
                if cfg.context_parallel:
                    # keep the slot dim sp-sharded through the decode
                    # scan (see the prefill-side constraint below)
                    ck.value = hint(ck.value, ("batch", "seq", None, None))
                    cv.value = hint(cv.value, ("batch", "seq", None, None))
                cidx.value = pos + s
                # ragged (left-padded) prompts: prefill banked per-slot
                # validity in the 'seg' cache; decode-appended tokens are
                # always real.  Segment equality masks each row's pad
                # slots out of the attention.
                qseg = kvseg = None
                if self.has_variable("cache", "seg"):
                    cseg = self.variable("cache", "seg", jnp.ones,
                                         (b, max_len), jnp.int32)
                    cseg.value = jax.lax.dynamic_update_slice(
                        cseg.value, jnp.ones((b, s), jnp.int32), (0, pos))
                    qseg = jnp.ones((b, s), jnp.int32)
                    kvseg = cseg.value
                # the query's TRUE position is pos while it sits at row 0
                # of a [1, kv_len] score matrix: q_offset re-aligns the
                # geometry so the shared mask/bias machinery gives exact
                # causal (<= pos), sliding-window, and ALiBi behavior over
                # the filled prefix (positions > pos hold zeros and fall
                # outside the causal mask).  kv_len comes from the LIVE
                # cache (a pre-existing cache may be sized differently
                # than this cfg's cache_len).
                from torchacc_tpu.ops.attention import attention_reference
                kv_len = ck.value.shape[1]
                out = attention_reference(
                    q, ck.value, cv.value, causal=True, window=cfg.window,
                    scale=cfg.query_scale, alibi_slopes=slopes,
                    q_segment_ids=qseg, kv_segment_ids=kvseg,
                    q_offset=pos - (kv_len - s),
                    logit_softcap=cfg.attn_logit_softcap)
                return proj("o_proj", out)   # cfg.decode: never quantized
            # prefill: bank the prompt's (rotated) k / v, then fall
            # through to the normal attention below
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k.astype(cfg.dtype), (0, 0, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v.astype(cfg.dtype), (0, 0, 0, 0))
            if cfg.context_parallel:
                # long-context decode: the cache's SLOT dim shards over
                # the sequence axes, so per-device cache memory is
                # cache_len/sp — the point of cp decode.  Decode's
                # single-token DUS and the partial-softmax attention
                # over the sharded slots are GSPMD-handled.
                ck.value = hint(ck.value, ("batch", "seq", None, None))
                cv.value = hint(cv.value, ("batch", "seq", None, None))
            cidx.value = jnp.asarray(s, jnp.int32)
            if segment_ids is not None:
                # ragged (left-padded) prompts: bank per-slot validity so
                # decode can mask each row's pad slots (slots past the
                # prompt default to 1 = real, written again at decode)
                cseg = self.variable("cache", "seg", jnp.ones,
                                     (b, max_len), jnp.int32)
                cseg.value = jax.lax.dynamic_update_slice(
                    cseg.value, segment_ids.astype(jnp.int32), (0, 0))
        # per-layer decorrelation already happened in TransformerLM
        # (seeds_xs = _layer_seed(seed, arange(L)))
        dropout_p, seed = 0.0, None
        if cfg.attn_dropout > 0.0 and dropout_seed is not None:
            dropout_p = cfg.attn_dropout
            seed = dropout_seed
        if cfg.context_parallel:
            # scale and score softcap are both elementwise on the
            # pre-softmax scores, so the ring/ulysses LSE merge is exact
            # with them (each chunk caps the same per-score values the
            # global computation would)
            from torchacc_tpu.ops.context_parallel import cp_attention
            out = cp_attention(q, k, v, causal=True, window=cfg.window,
                               scale=cfg.query_scale,
                               logit_softcap=cfg.attn_logit_softcap,
                               q_segment_ids=segment_ids,
                               kv_segment_ids=segment_ids,
                               alibi_slopes=slopes, dropout_p=dropout_p,
                               dropout_seed=seed,
                               impl=cfg.attention_impl)
        else:
            out = attention(q, k, v, causal=True, window=cfg.window,
                            scale=cfg.query_scale,
                            q_segment_ids=segment_ids,
                            kv_segment_ids=segment_ids,
                            alibi_slopes=slopes, dropout_p=dropout_p,
                            dropout_seed=seed,
                            impl=cfg.attention_impl,
                            logit_softcap=cfg.attn_logit_softcap)
        return proj("o_proj", out)


class Mlp(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        proj = _site_proj(cfg, "mlp", {
            name: (features, -1, cfg.mlp_bias) for name, features in (
                ("gate_proj", cfg.ffn_size), ("up_proj", cfg.ffn_size),
                ("down_proj", cfg.hidden_size))})
        return block.mlp(cfg, x, proj, hint=_layout_hint(cfg))


def _sub_remat(cfg: ModelConfig) -> bool:
    """True when remat applies to selected submodules inside the block
    (reference gc_cls semantics, utils/checkpoint.py:67-81) rather than
    to the whole decoder layer."""
    return bool(cfg.remat and cfg.remat_cls and "Block" not in cfg.remat_cls)


def _block_remat(cfg: ModelConfig) -> bool:
    return bool(cfg.remat and not _sub_remat(cfg))


class Block(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, dropout_seed=None):
        cfg = self.cfg
        attn_cls, mlp_cls = Attention, Mlp
        if cfg.kv_lora_rank:
            from torchacc_tpu.models.mla import MlaAttention
            attn_cls = MlaAttention
        if cfg.num_experts > 0:
            from torchacc_tpu.models.moe import MoEMlp
            mlp_cls = MoEMlp
        if _sub_remat(cfg):
            from torchacc_tpu.utils.remat import remat_policy
            pol = remat_policy(cfg.remat_policy)
            if "Attention" in cfg.remat_cls:
                attn_cls = nn.remat(attn_cls, policy=pol, prevent_cse=False)
            if mlp_cls.__name__ in cfg.remat_cls or "Mlp" in cfg.remat_cls:
                mlp_cls = nn.remat(mlp_cls, policy=pol, prevent_cse=False)
        return block.block(
            cfg, x,
            norm=lambda name, t, cfg=cfg: Norm(cfg, name=name)(t),
            attention=lambda h: attn_cls(cfg, name="attn")(
                h, positions, segment_ids, dropout_seed),
            ffn=lambda h: mlp_cls(
                cfg, name="moe" if cfg.num_experts > 0 else "mlp")(h))


class ScanBlock(nn.Module):
    """Block adapted to nn.scan's (carry, xs) -> (carry, out) signature;
    ``seed`` is the per-layer dropout seed (scanned xs) or None."""
    cfg: ModelConfig

    @nn.compact
    def __call__(self, carry, seed):
        x, positions, segment_ids = carry
        x = Block(self.cfg, name="block")(x, positions, segment_ids,
                                          dropout_seed=seed)
        return (x, positions, segment_ids), None


def scans_layers(cfg: "ModelConfig") -> bool:
    """Whether ``cfg.scan_layers`` says scan — the ONE reader of the
    field's truth.  ``None`` (nobody chose: a bare ``TransformerLM``,
    ``models.generate``, anything outside ``accelerate()``) is the
    scan; read as plain truth it would unroll every bare model and
    every pp stage."""
    return cfg.scan_layers is None or bool(cfg.scan_layers)


def layer_loop(cfg: "ModelConfig") -> str:
    """``'scan'`` or ``'unrolled'``: how a train step of this config
    applies its layers (the branches of ``TransformerLM.__call__`` in
    their order; decode and initialisation always scan).  The Trainer's
    start-up line and its ``train/dispatch`` span carry the word."""
    if cfg.first_dense_layers:
        return "scan"
    if cfg.layer_pattern or (cfg.overlap_fsdp and cfg.pp_size <= 1):
        return "unrolled"
    return "scan" if scans_layers(cfg) else "unrolled"


def pp_block_appliers(cfg: "ModelConfig", wrap):
    """(apply_block_or_slots, unroll_stage) for the pp pipelines.

    Uniform models wrap ONE ``_raw_block_fn``; a ``layer_pattern``
    (gemma2/3) yields one wrapped fn per chunk slot so each slot applies
    its own static config inside the unrolled stage body — the pattern
    period must divide the per-stage chunk (num_layers / pp / virtual)
    so slot j's kind is the same on every stage and virtual chunk.
    ``wrap`` adapts the raw ``fn(p, carry, seed)`` to the pipeline's
    applier signature (the gpipe and 1f1b callers differ)."""
    unroll = not scans_layers(cfg)
    if not cfg.layer_pattern:
        return wrap(_raw_block_fn(cfg)), unroll
    plen = len(cfg.layer_pattern)
    per_stage = cfg.num_layers // (cfg.pp_size * cfg.pp_virtual)
    if per_stage % plen:
        raise ValueError(
            f"layer_pattern of period {plen} does not divide the "
            f"per-stage chunk of {per_stage} layers (num_layers "
            f"{cfg.num_layers} / pp {cfg.pp_size} / virtual "
            f"{cfg.pp_virtual}): slot kinds would differ across "
            f"stages.  Choose pp_size x virtual_stages so each chunk "
            f"holds whole pattern repeats.")
    # with plen | per_stage, global layer s*per_stage + j has kind
    # pattern[j % plen] on every stage s — slot fns are stage-invariant
    return tuple(wrap(_raw_block_fn(pattern_cfg(cfg, j)))
                 for j in range(per_stage)), True


def _raw_block_fn(block_cfg, with_load: bool = False):
    """``fn(p, carry, seed) -> (carry, aux)`` applying ONE block via raw
    ``ScanBlock.apply``.  The raw apply drops sown intermediates unless
    the collection is mutable, so the MoE router aux is collected
    explicitly and returned — the single place this subtlety lives (the
    pp / unrolled / split-remat paths all build on it).  ``with_load``:
    ``aux`` is ``(aux, load)``, the layer's sown expert load beside it
    (``moe_load`` int32[5], models/moe.routed_experts), so a per-layer
    loop can stack and sow the counts as ``nn.scan`` does."""
    def fn(p, carry, s):
        (new_carry, _), vs = ScanBlock(block_cfg).apply(
            {"params": p}, carry, s, mutable=["intermediates"])
        aux = _sown_aux_sum(vs)
        if with_load:
            aux = (aux, _sown(vs, "moe_load")[0])
        return new_carry, aux
    return fn


def _raw_block_fn_quant(block_cfg):
    """quant-threading variant of :func:`_raw_block_fn`:
    ``fn(p, q, carry, s) -> (carry, aux, q_new)``.  The per-layer
    delayed-scaling state goes in and the mutated history comes out, so
    the unrolled / overlap_fsdp paths carry it explicitly (nn.scan's
    ``variable_axes={'quant': 0}`` does the same job on the scan
    path)."""
    def fn(p, q, carry, s):
        (new_carry, _), vs = ScanBlock(block_cfg).apply(
            {"params": p, "quant": q}, carry, s,
            mutable=["intermediates", "quant"])
        return new_carry, _sown_aux_sum(vs), vs["quant"]
    return fn


class TransformerLM(nn.Module):
    """The LM.  ``__call__(input_ids, positions?, segment_ids?) -> logits``.

    positions default to arange; segment_ids enable packed sequences
    (reference varlen-by-position-ids path ops/flash_attn.py:173-216).
    """
    cfg: ModelConfig

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None,
                 return_hidden=False, dropout_seed=None,
                 moe_aux_row_weights=None):
        """``moe_aux_row_weights`` [B] (MoE x GPipe only): per-row
        weight count_m / count_total of the row's micro-batch.  Rides
        the pipeline ring with its micro so each tick's router aux is
        weighted by its own micro's valid-token share — the SAME
        convention as 1F1B and the grad-accum loop (VERDICT r3 weak-7);
        None keeps the unweighted micro mean."""
        cfg = self.cfg
        if cfg.mixer_pattern:
            raise NotImplementedError(
                "mixer_pattern (layers of one state-space, expert or "
                "attention mixer each) runs through ServeEngine and "
                "models.generate on the serving layout ('layers/<kind>' "
                "stacks); the module's forward and init do not build it")
        # Attention dropout is active iff the caller supplies a seed
        # (train steps do; eval/inference omit it — the deterministic
        # story).  One base seed fans out to per-layer seeds here.
        seeds_xs = None
        if cfg.attn_dropout > 0.0 and dropout_seed is not None:
            seeds_xs = _layer_seed(
                dropout_seed, jnp.arange(cfg.num_layers, dtype=jnp.int32))
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        emb = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                       dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                       embedding_init=nn.initializers.normal(0.02))
        pos_table = (self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (cfg.max_seq_len, cfg.hidden_size), cfg.param_dtype)
            if cfg.pos_emb == "learned" else None)
        x = _embed_extras(cfg, emb(input_ids), positions, pos_table)

        block_cls = ScanBlock
        if _block_remat(cfg):
            from torchacc_tpu.utils.remat import remat_policy
            block_cls = nn.remat(
                ScanBlock, policy=remat_policy(cfg.remat_policy),
                prevent_cse=False)
        # remat_cnt (reference gc_cnt): remat only the first N layers
        split_n = None
        if (cfg.remat and cfg.remat_cnt is not None
                and 0 <= cfg.remat_cnt < cfg.num_layers and cfg.pp_size == 1):
            split_n = cfg.remat_cnt
        # ONE canonical param layout: layers are always initialised via
        # nn.scan, so the stacked [L, ...] tree (partitioned over the
        # 'layers' logical axis) is the layout regardless of scan_layers
        # — checkpoints are portable between the two execution paths.
        # scan_layers picks how the layers are APPLIED: True = lax.scan
        # over the stack (compile time flat in depth; policy-saved
        # residuals and weight gradients stack [L, ...] through
        # dynamic-update-slices fused into the matmuls that produce
        # them — the scan-stacking tax), False = Python-unrolled loop
        # over static slices (separate per-layer buffers; compile time
        # grows with depth; under ZeRO-3 nothing holds back the gathers
        # of later layers, which then live at once).  Who chooses:
        # accelerate() from the mesh (apply_config_to_model) — unrolled
        # where every device holds the layer parameters whole, the scan
        # otherwise; None outside it is the scan (scans_layers).  The
        # chip's numbers for both: PERF.md section 6, PR 38.  The decode/cache
        # path ALWAYS applies via plain scan — the cache collection only
        # flows through scan_mod's variable_axes (raw .apply in the
        # unrolled/split paths would silently drop prefill cache
        # writes), and decode compute is trivial either way.
        cache_live = cfg.decode or self.is_mutable_collection("cache")
        use_scan_apply = scans_layers(cfg) or cache_live
        quant_on = cfg.quant != "none"
        if quant_on and not self.is_initializing():
            # the quantized sites' delayed-scaling state threads through
            # the scan / unrolled / overlap paths only; the pp regions
            # and the decode cache path apply blocks via raw param trees
            # that do not carry (or would silently drop) the 'quant'
            # collection — keep those failures loud
            if cfg.pp_size > 1:
                raise NotImplementedError(
                    "quant != 'none' does not compose with pipeline "
                    "parallelism (config.validate rejects it too)")
            if cfg.layer_pattern:
                raise NotImplementedError(
                    "quant != 'none' does not compose with "
                    "layer_pattern models yet")
            if cache_live:
                raise NotImplementedError(
                    "quant != 'none' decode must go through "
                    "models.generate (it strips quant — inference runs "
                    "in the compute dtype)")
            if (split_n is not None and scans_layers(cfg)
                    and not cfg.overlap_fsdp):
                # overlap_fsdp forces the unrolled loop below, which
                # honors remat_cnt AND threads quant — only the
                # split-SCAN path cannot
                raise NotImplementedError(
                    "quant != 'none' with memory.gc_cnt requires "
                    "scan_layers=False (the split-scan path does not "
                    "thread the delayed-scaling state)")
        # FSDP overlap: force the unrolled loop with the in-fn param
        # gather (see the branch below); quant threads through it.
        # layer_pattern would silently skip the overlap branch — reject
        # loudly instead of letting a user benchmark a no-op (pp is
        # already rejected by Config.validate; decode skips silently by
        # design: a single-token step has no ladder to overlap)
        if (cfg.overlap_fsdp and cfg.layer_pattern
                and not self.is_initializing()):
            raise NotImplementedError(
                "perf.overlap_fsdp does not compose with layer_pattern "
                "models (the pattern's per-layer loop does not take "
                "the overlap path) — disable one of the two")
        overlap_active = (cfg.overlap_fsdp and not cache_live
                          and cfg.pp_size <= 1 and not cfg.layer_pattern)
        def stack(stack_cfg, length, name):
            return nn.scan(
                block_cls,
                variable_axes={"params": 0, "intermediates": 0, "cache": 0,
                               "quant": 0},
                split_rngs={"params": True},
                length=length,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(stack_cfg, name=name)

        n_dense = cfg.first_dense_layers
        scan_mod = (None if n_dense
                    else stack(cfg, cfg.num_layers, "layers"))
        # every application path below runs under the registered device
        # scope "layers" (obs/tracing.py DEVICE_SCOPES): the loop's own
        # ops — per-layer slices of the stack, the scan's saved-residual
        # stacking — read under it in a profiler trace, the blocks'
        # parts under their module names inside it
        if n_dense:
            # two kinds of layer, two stacked trees: 'dense_layers'
            # (plain MLPs) then 'layers' (expert layers), each one scan —
            # no per-layer branch inside a scan, and each tree's leaves
            # keep one shape
            if (cfg.pp_size > 1 or cfg.layer_pattern or quant_on
                    or cfg.overlap_fsdp or seeds_xs is not None
                    or not 0 < n_dense < cfg.num_layers):
                raise NotImplementedError(
                    "first_dense_layers (leading dense layers before the "
                    "expert layers) runs as two plain layer scans: it "
                    "needs 0 < first_dense_layers < num_layers and does "
                    "not compose with pp, layer_pattern, quant, "
                    "overlap_fsdp or attention dropout")
            dense_cfg = dataclasses.replace(
                cfg, num_experts=0, first_dense_layers=0,
                num_layers=n_dense)
            carry = (x, positions, segment_ids)
            with jax.named_scope("layers"):
                carry, _ = stack(dense_cfg, n_dense, "dense_layers")(
                    carry, None)
                carry, _ = stack(cfg, cfg.num_layers - n_dense, "layers")(
                    carry, None)
            x = carry[0]
        elif self.is_initializing():
            with jax.named_scope("layers"):
                (x, _, _), _ = scan_mod((x, positions, segment_ids),
                                        seeds_xs)
        elif cfg.layer_pattern and cfg.pp_size <= 1:
            # heterogeneous layers (gemma2-style sliding/global
            # alternation): the pattern is param-free, so params keep the
            # canonical stacked layout; execution is a per-layer python
            # loop with each layer's own static cfg (lax.scan cannot
            # vary a static window across iterations).  Composes with
            # GSPMD sharding (dp/fsdp/tp); under pp the pattern runs
            # through the unrolled stage body instead (the pp branch
            # below) and decode/cache goes through generate()'s pattern
            # path.
            if cache_live:
                raise NotImplementedError(
                    "layer_pattern decode must go through "
                    "models.generate (its pattern-aware cached path); "
                    "direct .apply with a mutable cache is unsupported")
            layer_params = self.variables["params"]["layers"]
            aux_total = jnp.zeros((), jnp.float32)
            carry = (x, positions, segment_ids)
            from torchacc_tpu.utils.remat import remat_policy as _rp
            loads = []
            with_load = cfg.num_experts > 0 and cfg.moe_dispatch == "grouped"
            for i in range(cfg.num_layers):
                fn = _raw_block_fn(pattern_cfg(cfg, i), with_load)
                if _block_remat(cfg):
                    fn = jax.checkpoint(fn, policy=_rp(cfg.remat_policy),
                                        prevent_cse=False)
                with jax.named_scope("layers"):
                    p_i = jax.tree.map(lambda a, i=i: a[i], layer_params)
                    s_i = None if seeds_xs is None else seeds_xs[i]
                    carry, aux = fn(p_i, carry, s_i)
                if with_load:
                    aux, load = aux
                    loads.append(load)
                aux_total = aux_total + aux
            if cfg.num_experts > 0:
                self.sow("intermediates", "moe_aux_loss", aux_total)
            if loads:
                self.sow("intermediates", "moe_load", jnp.stack(loads))
            x = carry[0]
        elif cfg.pp_size > 1:
            # pipeline path: drive the stacked layer params through the
            # pp-stage pipeline (init traced scan_mod so params exist
            # with the stacked layout); scan_layers picks whether each
            # stage scans or unrolls its layer chunk
            if cache_live:
                # the raw in-region block apply never threads the flax
                # cache collection — prefill writes would silently drop.
                # pp decode has its own path (generate()'s stage ring /
                # pattern dispatch); keep the failure loud here.
                raise NotImplementedError(
                    "pipeline-parallel decode must go through "
                    "models.generate; direct .apply with a mutable "
                    "cache is unsupported under pp")
            from torchacc_tpu.parallel.pp import pipeline_blocks
            layer_params = self.variables["params"]["layers"]
            moe_on = cfg.num_experts > 0
            if seeds_xs is not None:
                # per-layer seeds ride the stacked pytree so each
                # pp stage sees its own layers' seeds
                stacked = {"p": layer_params, "s": seeds_xs}
                unpack = lambda ps: (ps["p"], ps["s"])
            else:
                stacked = layer_params
                unpack = lambda p: (p, None)

            aux_weighted = moe_on and moe_aux_row_weights is not None
            carry0 = (x, positions, segment_ids)
            if aux_weighted:
                # weight rider: travels the ring with its micro, so each
                # tick weights its aux by the RESIDENT micro's
                # valid-token share (rows of a micro share one value)
                carry0 = carry0 + (
                    moe_aux_row_weights.astype(jnp.float32),)

            def mk_apply(_block):
                def apply_one(ps, carry):
                    p, s = unpack(ps)
                    if aux_weighted:
                        new_carry, aux = _block(p, carry[:3], s)
                        return new_carry + (carry[3],), aux * carry[3][0]
                    new_carry, aux = _block(p, carry, s)
                    # aux_from_block=moe_on below: only then does the
                    # pipeline expect (carry, aux)
                    return (new_carry, aux) if moe_on else new_carry
                return apply_one

            apply_arg, unroll = pp_block_appliers(cfg, mk_apply)
            from torchacc_tpu.utils.remat import remat_policy
            with jax.named_scope("layers"):
                res = pipeline_blocks(
                    apply_arg, stacked, carry0,
                    pp_size=cfg.pp_size, num_micro=cfg.pp_num_micro,
                    virtual_stages=cfg.pp_virtual,
                    remat=cfg.remat,
                    remat_policy=(remat_policy(cfg.remat_policy)
                                  if cfg.remat else None),
                    aux_from_block=moe_on,
                    unroll_stage=unroll)
            if moe_on:
                x, aux_total = res
                if aux_weighted:
                    # each tick already weighted its aux by the resident
                    # micro's count_m / count_total (the weight rider),
                    # so aux_total IS sum_m aux_m * count_m / count_tot
                    # — the trainer's aux_weight * aux * count term then
                    # equals the 1F1B / grad-accum convention exactly
                    self.sow("intermediates", "moe_aux_loss", aux_total)
                else:
                    # unweighted micro mean: equal to the weighted form
                    # whenever micros carry equal valid-token counts
                    # (packed/full batches); the trainer passes row
                    # weights whenever labels are available
                    self.sow("intermediates", "moe_aux_loss",
                             aux_total / cfg.pp_num_micro)
            else:
                x = res
        elif not use_scan_apply or overlap_active:
            # unrolled application from the stacked layout: static
            # per-layer slices keep each layer's policy-saved residuals
            # as SEPARATE buffers, so the step's autodiff carries no
            # [L, ...] DUS stacking (the scan-stacking tax: PERF.md
            # section 6, PR 38, and the ledger's line of that PR for the
            # one-chip train cells).  Honors remat_cnt: layers past
            # split_n run without remat.
            #
            # overlap_fsdp rides this loop: each layer's block fn FIRST
            # constrains its param slice to REPLICATED (an explicit
            # all-gather under GSPMD — parallel/sharding.
            # fsdp_gather_params).  The gather's only operand is the
            # stacked param slice — data-independent of every other
            # layer's compute — so XLA's scheduler is free to overlap
            # layer i+1's all-gather with layer i's compute ladder (the
            # ASPLOS'23 decomposition; XLA schedules by data flow, not
            # program order).  The gather lives INSIDE the
            # jax.checkpoint region: residuals stay the fsdp-SHARDED
            # slices (remat re-gathers in backward — standard ZeRO-3
            # memory behavior), never a per-layer replicated copy.  The
            # backward mirror is each layer's weight cotangent
            # resharding back into the fsdp-sharded stack independently
            # of older layers' backward compute.
            from torchacc_tpu.utils.remat import remat_policy
            layer_params = self.variables["params"]["layers"]
            cfg_off = dataclasses.replace(cfg, remat=False)

            # block-level quant state exists only when an in-block site
            # ('attn'/'mlp') is quantized; a head-only quant_sites
            # leaves the blocks plain (the head's own QuantDenseGeneral
            # at the module tail threads through normal flax mutation)
            quant_blocks = quant_on and (
                quant_site_on(cfg, "attn") or quant_site_on(cfg, "mlp"))
            with_load = (not quant_blocks and cfg.num_experts > 0
                         and cfg.moe_dispatch == "grouped")
            raw_gc = (_raw_block_fn_quant(cfg) if quant_blocks
                      else _raw_block_fn(cfg, with_load))
            raw_plain = (_raw_block_fn_quant(cfg_off) if quant_blocks
                         else _raw_block_fn(cfg_off, with_load))
            if overlap_active:
                from torchacc_tpu.parallel.sharding import (
                    DEFAULT_RULES,
                    fsdp_gather_params,
                    fsdp_gather_specs,
                )
                # per-leaf target specs = each weight's layout minus
                # its fsdp dim, so the gather unshard-s ONLY the ZeRO-3
                # axis and megatron tp/ep dims stay sharded; falls back
                # to fully-replicated for trees the axes rules don't
                # know (custom modules)
                try:
                    g_specs = fsdp_gather_specs(
                        jax.tree.map(lambda a: a[0], layer_params),
                        cfg.logical_axis_rules or DEFAULT_RULES)
                except ValueError as e:
                    # fully-replicated fallback also un-shards tp/ep
                    # dims — fine on fsdp/dp-only meshes, a per-layer
                    # memory+collective cost under tensor parallelism;
                    # say so instead of degrading silently
                    from torchacc_tpu.utils.logger import logger
                    logger.warning(
                        "overlap_fsdp: param tree has no axes-rule "
                        f"coverage ({e}); gathering layers to fully "
                        "replicated — under tensor parallelism this "
                        "also un-shards the megatron dims per layer")
                    g_specs = None

                def _gathered(fn):
                    def wrapped(p, *rest):
                        return fn(fsdp_gather_params(p, g_specs), *rest)
                    return wrapped
                raw_gc = _gathered(raw_gc)
                raw_plain = _gathered(raw_plain)
            if _block_remat(cfg):
                raw_gc = jax.checkpoint(
                    raw_gc, policy=remat_policy(cfg.remat_policy),
                    prevent_cse=False)
            layer_quant = None
            if quant_blocks:
                if "quant" not in self.variables \
                        or "layers" not in self.variables["quant"]:
                    raise ValueError(
                        "quant != 'none' but no 'quant' collection was "
                        "passed to apply() — thread TrainState.quant "
                        "(the Trainer does this automatically)")
                layer_quant = self.variables["quant"]["layers"]

            slice_i = lambda tree, i: jax.tree.map(
                lambda a, i=i: a[i], tree)
            carry = (x, positions, segment_ids)
            aux_total = jnp.zeros((), jnp.float32)
            new_quant, loads = [], []
            n_gc = cfg.num_layers if split_n is None else split_n
            for i in range(cfg.num_layers):
                fn = raw_gc if (i < n_gc and cfg.remat) else raw_plain
                with jax.named_scope("layers"):
                    p_i = slice_i(layer_params, i)
                    seed_i = None if seeds_xs is None else seeds_xs[i]
                    if quant_blocks:
                        carry, aux, q_i = fn(p_i, slice_i(layer_quant, i),
                                             carry, seed_i)
                        new_quant.append(q_i)
                    else:
                        carry, aux = fn(p_i, carry, seed_i)
                if with_load:
                    aux, load = aux
                    loads.append(load)
                aux_total = aux_total + aux
            if loads:
                self.sow("intermediates", "moe_load", jnp.stack(loads))
            if quant_blocks and self.is_mutable_collection("quant"):
                self.put_variable(
                    "quant", "layers",
                    jax.tree.map(lambda *a: jnp.stack(a), *new_quant))
            if cfg.num_experts > 0:
                self.sow("intermediates", "moe_aux_loss", aux_total)
            x = carry[0]
        elif split_n is not None and not cache_live:
            # split the stacked params: first remat_cnt layers run with
            # remat semantics, the rest without.  cache_live falls
            # through to plain scan below: this path's raw .apply would
            # drop prefill cache writes (remat does not change values,
            # so eval/prefill under scan is correct regardless of
            # remat_cnt).
            from torchacc_tpu.utils.remat import remat_policy
            layer_params = self.variables["params"]["layers"]
            head = jax.tree.map(lambda p: p[:split_n], layer_params)
            tail = jax.tree.map(lambda p: p[split_n:], layer_params)
            cfg_off = dataclasses.replace(cfg, remat=False)

            _gc, _plain = _raw_block_fn(cfg), _raw_block_fn(cfg_off)
            apply_gc = lambda ps, carry: _gc(ps[0], carry, ps[1])
            apply_plain = lambda ps, carry: _plain(ps[0], carry, ps[1])
            if _block_remat(cfg):
                apply_gc = jax.checkpoint(
                    apply_gc, policy=remat_policy(cfg.remat_policy),
                    prevent_cse=False)

            def seg(fn, stack, lo, hi, carry):
                if seeds_xs is None:
                    return jax.lax.scan(
                        lambda c, p: fn((p, None), c), carry, stack)
                return jax.lax.scan(
                    lambda c, ps: fn(ps, c), carry,
                    (stack, seeds_xs[lo:hi]))

            carry = (x, positions, segment_ids)
            aux_total = jnp.zeros((), jnp.float32)
            if split_n > 0:
                with jax.named_scope("layers"):
                    carry, aux = seg(apply_gc, head, 0, split_n, carry)
                aux_total = aux_total + jnp.sum(aux)
            if split_n < cfg.num_layers:
                with jax.named_scope("layers"):
                    carry, aux = seg(apply_plain, tail, split_n,
                                     cfg.num_layers, carry)
                aux_total = aux_total + jnp.sum(aux)
            if cfg.num_experts > 0:
                self.sow("intermediates", "moe_aux_loss", aux_total)
            x = carry[0]
        else:
            with jax.named_scope("layers"):
                (x, _, _), _ = scan_mod((x, positions, segment_ids),
                                        seeds_xs)

        x = scale_hidden(cfg, Norm(cfg, name="final_norm")(x))
        if return_hidden:
            # fused linear+CE path (ops/fused.py): the caller applies the
            # head matmul chunk-by-chunk inside the loss
            return x
        if cfg.tie_embeddings:
            if cfg.head_bias:
                # the tied path projects via emb.attend — no bias param
                # exists to apply; converting silently would drop it
                raise ValueError(
                    "head_bias does not compose with tie_embeddings "
                    "(the tied head has no bias parameter)")
            if quant_site_on(cfg, "head"):
                # the tied head projects through emb.attend — there is
                # no lm_head dense to quantize; a silent no-op would
                # let a user benchmark head quantization that never ran
                raise ValueError(
                    "quant_sites includes 'head' but tie_embeddings "
                    "projects through the embedding table — drop "
                    "'head' from quant_sites (the tied head stays in "
                    "the compute dtype)")
            logits = emb.attend(x)
        elif quant_site_on(cfg, "head"):
            # the MATERIALISED head only: the trainer's fused-CE path
            # computes the head inside the chunked loss and stays in
            # the compute dtype (docs/performance.md)
            logits = _quant_dense(cfg, "lm_head", cfg.vocab_size, -1,
                                  cfg.head_bias)(x)
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=cfg.head_bias,
                              name="lm_head",
                              dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                              kernel_init=nn.initializers.normal(0.02))(x)
        return softcap(logits.astype(jnp.float32), cfg.logit_softcap)


def loss_sum_count(logits: jax.Array, labels: jax.Array,
                   loss_mask: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Next-token cross entropy: (sum over valid tokens, valid count).

    -100 labels are ignored (HF convention the reference benchmarks rely
    on).  Returning sum+count separately lets gradient accumulation
    weight micro-batches by token count — exact big-batch equivalence
    even when padding makes counts uneven.
    """
    valid = labels != -100
    if loss_mask is not None:
        valid = valid & (loss_mask != 0)
    safe_labels = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    token_ll = jnp.take_along_axis(logp, safe_labels[..., None], axis=-1)[..., 0]
    total = jnp.sum(jnp.where(valid, -token_ll, 0.0))
    count = jnp.sum(valid).astype(jnp.float32)
    return total, count


def loss_fn(logits: jax.Array, labels: jax.Array,
            loss_mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token cross entropy (see loss_sum_count)."""
    total, count = loss_sum_count(logits, labels, loss_mask)
    return total / jnp.maximum(count, 1.0)


def _embed_extras(cfg: ModelConfig, x: jax.Array, positions: jax.Array,
                  pos_table) -> jax.Array:
    """Shared embedding front-end conventions (Gemma sqrt(hidden) scale
    in the compute dtype, learned position add) — one definition so the
    1F1B raw-params path cannot drift from TransformerLM.__call__."""
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, cfg.dtype)
    if cfg.pos_emb == "learned":
        x = x + pos_table.astype(cfg.dtype)[positions]
    return x


def embed_ids(cfg: ModelConfig, params, ids: jax.Array,
              positions: jax.Array) -> jax.Array:
    """Shared raw-params embedding front end, :func:`head_logits`'
    counterpart: table lookup in the compute dtype, then
    :func:`_embed_extras`."""
    emb = params["embed_tokens"]["embedding"]
    return _embed_extras(cfg, emb[ids].astype(cfg.dtype), positions,
                         params.get("pos_embed"))


def pattern_cfg(cfg: ModelConfig, i: int) -> ModelConfig:
    """The effective per-layer config under ``cfg.layer_pattern``:
    layer i takes pattern[i % len] (:func:`kind_cfg`).  Identity when no
    pattern."""
    if not cfg.layer_pattern:
        return cfg
    return kind_cfg(cfg, cfg.layer_pattern[i % len(cfg.layer_pattern)])


def kind_cfg(cfg: ModelConfig, kind: str) -> ModelConfig:
    """The config a ``layer_pattern`` layer of ``kind`` computes under:
    'sliding' keeps cfg.window, 'global' lifts it to full attention; a
    kind outside ``cfg.rope_kinds`` (where those are named) has no
    rotary embedding, one outside ``cfg.rope_yarn_kinds`` the plain
    frequencies."""
    if kind in MIXER_KINDS and cfg.mixer_pattern:
        # a mixer_pattern layer: the kinds differ in what they own, not
        # in the config they compute under
        return cfg
    if kind not in ("sliding", "global"):
        raise ValueError(
            f"layer_pattern entries must be 'sliding' | 'global', got "
            f"{kind!r}")
    if kind == "global":
        cfg = dataclasses.replace(cfg, window=(-1, -1))
    elif cfg.rope_local_theta is not None:
        # gemma3 dual rope: sliding layers use the local base frequency,
        # UNSCALED (HF applies rope_scaling to the global rotary only)
        cfg = dataclasses.replace(cfg, rope_theta=cfg.rope_local_theta,
                                  rope_scale=1.0)
    if cfg.rope_kinds is not None and kind not in cfg.rope_kinds:
        if cfg.pos_emb != "rope":
            raise ValueError("rope_kinds names the layers that carry "
                             "rope: it needs pos_emb='rope'")
        cfg = dataclasses.replace(cfg, pos_emb="none")
    if cfg.rope_yarn_kinds is not None and kind not in cfg.rope_yarn_kinds:
        cfg = dataclasses.replace(cfg, rope_yarn=None)
    return cfg


#: what a ``mixer_pattern`` layer's single mixer can be
MIXER_KINDS = ("mamba", "moe", "attention")


def layer_kinds(cfg: ModelConfig):
    """The kind of every layer, in order: its ``mixer_pattern`` entry, or
    its ``layer_pattern`` one."""
    if cfg.mixer_pattern:
        if len(cfg.mixer_pattern) < cfg.num_layers:
            raise ValueError(
                f"mixer_pattern names {len(cfg.mixer_pattern)} layers, "
                f"num_layers is {cfg.num_layers}")
        return list(cfg.mixer_pattern[:cfg.num_layers])
    return [cfg.layer_pattern[i % len(cfg.layer_pattern)]
            for i in range(cfg.num_layers)]


def pattern_period(cfg: ModelConfig):
    """``(kinds of the leading dense layers, kinds of one period)`` of a
    model whose ``layer_pattern`` names every layer beside
    ``first_dense_layers``: the layers after the dense ones repeat the
    shortest period that divides them (all of them, where none does)."""
    kinds = layer_kinds(cfg)
    dense, rest = kinds[:cfg.first_dense_layers], \
        kinds[cfg.first_dense_layers:]
    for n in range(1, len(rest) + 1):
        if len(rest) % n == 0 and rest == rest[:n] * (len(rest) // n):
            return dense, rest[:n]
    return dense, rest


class LayerAt(NamedTuple):
    """Where one layer lies: the path of its stacked ``tree`` in the
    parameters, its index ``at`` in that stack, its ``kind``
    (:func:`layer_kinds`; '' in a model of one kind) and its index
    ``of_kind`` among the layers of its kind: its row in whatever is kept
    a kind (the serving pools)."""

    tree: Tuple[str, ...]
    at: Any
    kind: str
    of_kind: Any

    def stack(self, params):
        """The stacked tree ``{'block': ...}`` this layer lies in."""
        return functools.reduce(operator.getitem, self.tree, params)


class LayerRun(NamedTuple):
    """Consecutive layers of the plan.  A ``scanned`` run repeats its
    ``body`` ``repeats`` times, every body layer in a stacked tree of its
    own with one entry a repetition; any other holds its layers at the
    static indices its body names."""

    body: Tuple[LayerAt, ...]
    repeats: int
    scanned: bool

    def layers(self, n=0):
        """The body's layers at repetition ``n`` (an int, or the scan's
        counter, which is where they lie in their stacks)."""
        if not self.scanned:
            return list(self.body)
        per = collections.Counter(e.kind for e in self.body)
        return [e._replace(at=n, of_kind=e.of_kind + n * per[e.kind])
                for e in self.body]


def layer_plan(cfg: ModelConfig) -> Tuple[LayerRun, ...]:
    """How a model's layers are stacked, every layer in order, in runs:
    the ONE place that says it (chipbench/layouts builds these trees;
    :func:`layer_tree`, through it models/generate.py, and the serving
    decoder's walk read them from here).  The canonical ``layers``
    [L, ...] is one scanned run with a body of one; leading dense layers
    are a run of their own over ``dense_layers``; a ``layer_pattern``
    beside dense layers is scanned a period at a time, one stack a
    position of the period, ``layers/p<k>`` [periods, ...].  A
    ``mixer_pattern`` (one stack a kind of mixer, ``layers/<kind>``) has
    no period, a ``layer_pattern`` on the canonical stack no two layers
    of one config in a row: both are walked at static indices."""
    n, nd = cfg.num_layers, cfg.first_dense_layers
    patterned = bool(cfg.mixer_pattern or cfg.layer_pattern)
    kinds = layer_kinds(cfg) if patterned else [""] * n
    periodic = bool(cfg.layer_pattern and nd)
    period = len(pattern_period(cfg)[1]) if periodic else 1
    seen, flat = collections.Counter(), []
    for i, kind in enumerate(kinds):
        if cfg.mixer_pattern:
            tree, at = ("layers", kind), seen[kind]
        elif i < nd:
            tree, at = ("dense_layers",), i
        elif periodic:
            tree, at = ("layers", f"p{(i - nd) % period}"), (i - nd) // period
        else:
            tree, at = ("layers",), i - nd
        flat.append(LayerAt(tree, at, kind, seen[kind]))
        seen[kind] += 1
    if cfg.mixer_pattern or (cfg.layer_pattern and not nd):
        return (LayerRun(tuple(flat), 1, False),)
    runs = []
    if nd:
        # leading dense layers scan where they are of one kind
        scan = len(set(kinds[:nd])) == 1
        runs.append(LayerRun(tuple(flat[:1 if scan else nd]),
                             nd if scan else 1, scan))
    runs.append(LayerRun(tuple(flat[nd:nd + period]), (n - nd) // period,
                         True))
    return tuple(runs)


def planned_layers(cfg: ModelConfig):
    """Every layer of :func:`layer_plan`, in order."""
    return [e for run in layer_plan(cfg) for n in range(run.repeats)
            for e in run.layers(n)]


def layer_tree(cfg: ModelConfig, params, i: int):
    """Layer ``i``'s raw tree ``{'block': ...}`` and the config its block
    computes under, out of the stacked parameters as :func:`layer_plan`
    lays them out."""
    at = planned_layers(cfg)[i]
    block_cfg = cfg if cfg.mixer_pattern else pattern_cfg(cfg, i)
    if i < cfg.first_dense_layers:
        block_cfg = dataclasses.replace(block_cfg, num_experts=0,
                                        first_dense_layers=0)
    return jax.tree.map(lambda a: a[at.at], at.stack(params)), block_cfg


def head_logits(cfg: ModelConfig, params, x: jax.Array) -> jax.Array:
    """Shared raw-params head tail: final norm -> vocab projection ->
    softcap, numerically identical to TransformerLM.__call__'s tail
    (Dense/attend both cast operands to cfg.dtype) — one definition so
    raw-params consumers (the pp decode path, models/generate.py)
    cannot drift from the module."""
    xn = scale_hidden(cfg, Norm(cfg).apply(
        {"params": params["final_norm"]}, x))
    w = (params["embed_tokens"]["embedding"].T if cfg.tie_embeddings
         else params["lm_head"]["kernel"])
    logits = jnp.einsum("bsh,hv->bsv", xn.astype(cfg.dtype),
                        w.astype(cfg.dtype))
    if cfg.head_bias:
        logits = logits + params["lm_head"]["bias"].astype(cfg.dtype)
    return softcap(logits.astype(jnp.float32), cfg.logit_softcap)


def _micro_seed(base, micro_idx):
    """Decorrelate dropout across pipeline micro-batches (a different odd
    constant than _layer_seed's so layer/micro mixes cannot collide)."""
    b = jnp.asarray(base, jnp.int32).astype(jnp.uint32)
    m = jnp.asarray(micro_idx, jnp.int32).astype(jnp.uint32)
    return (b + m * jnp.uint32(0x85EBCA6B)).astype(jnp.int32)


def _sown(vs, name: str):
    """Every sown intermediate whose path holds ``name``, out of a raw
    .apply's mutated variables."""
    paths = jax.tree_util.tree_flatten_with_path(
        vs.get("intermediates", {}))[0]
    return [v for path, v in paths if name in jax.tree_util.keystr(path)]


def _sown_aux_sum(vs) -> jax.Array:
    """Sum every sown '*aux_loss*' intermediate (MoE router load-balance,
    models/moe.py)."""
    vals = [jnp.sum(v) for v in _sown(vs, "aux_loss")]
    return sum(vals) if vals else jnp.zeros((), jnp.float32)


def sown_expert_load(vs):
    """The expert layers' sown load, int32 [expert layers, 5] (pairs on
    held experts, the busiest one's, held experts that drew a pair, the
    busiest shard's pairs, its sorted buffers' rows;
    models/moe.routed_experts), or None for a model that sows none."""
    vals = _sown(vs, "moe_load")
    if not vals:
        return None
    return jnp.concatenate([v.reshape(-1, v.shape[-1]) for v in vals],
                           axis=0)


class _MicroBatchView(dict):
    """Batch view handed to a custom Trainer loss inside the 1F1B last
    stage.  Only ``labels`` exists there — the other batch leaves never
    enter the pipeline region — so turn an unknown-key lookup into an
    actionable error instead of a bare trace-time KeyError."""

    def __missing__(self, key):
        raise KeyError(
            f"batch[{key!r}] is not available inside the 1f1b pipeline "
            "region: a custom loss under pp.schedule='1f1b' runs in the "
            "last stage and sees {'labels': ...} only.  Losses needing "
            "other batch leaves should use pp.schedule='gpipe', whose "
            "loss runs outside the region.")

    # dict.get() bypasses __missing__, so batch.get('attention_mask')
    # would silently hand a custom loss None; raise the same curated
    # error instead.  (`in` keeps plain membership so a loss can branch
    # on availability.)
    def get(self, key, default=None):
        if not dict.__contains__(self, key):
            self.__missing__(key)
        return dict.get(self, key, default)


def pp_1f1b_forward_sum_count(cfg: ModelConfig, params, input_ids,
                              positions=None, segment_ids=None,
                              labels=None, pp_axis: str = "pp",
                              dropout_seed=None, use_fused_ce=False,
                              custom_loss=None):
    """(loss_sum, count) for a zoo model under the 1F1B pipeline schedule.

    The 1F1B schedule (parallel/pp.py pipeline_loss_1f1b; reference
    pp/schedule.py:156-227) fuses final-norm + head + loss into the last
    stage so each micro-batch's backward starts as soon as its forward
    finishes.  That means the loss cannot be computed OUTSIDE model.apply
    the way the GPipe path does — this function replaces the trainer's
    forward for pp.schedule == '1f1b'.  Embedding (+ learned positions)
    runs outside the region, replicated over 'pp', exactly like the
    GPipe path; gradients flow into it through the pipeline's dx.

    Compositions:

    - ``use_fused_ce``: the last-stage head runs the chunked fused
      linear+CE (ops/fused.py) instead of materialising [mb, s, V] f32
      logits — the same memory win the non-PP trainer gets.
    - ``dropout_seed``: attention dropout inside the schedule.  Each
      micro-batch's seed rides the ppermute ring with its activations
      (so the B sub-tick's recompute regenerates the identical mask),
      mixed per micro (_micro_seed) and per layer (_layer_seed).
    - MoE: per-stage router aux losses fold into the loss with
      per-micro weights ``router_aux_weight * count_m`` — the same
      convention as the trainer's gradient-accumulation loop (each
      micro weighted by its valid-token count).
    """
    from torchacc_tpu.parallel.pp import pipeline_loss_1f1b
    from torchacc_tpu.train.trainer import shift_labels

    b, s = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    emb_table = params["embed_tokens"]["embedding"]
    x = _embed_extras(cfg, emb_table[input_ids].astype(cfg.dtype),
                      positions, params.get("pos_embed"))
    if labels is None:
        labels = shift_labels(input_ids, segment_ids)

    stacked = params["layers"]
    head_params = {"final_norm": params["final_norm"]}
    if cfg.tie_embeddings:
        head_params["embed"] = emb_table
    else:
        head_params["lm_head"] = params["lm_head"]

    M = cfg.pp_num_micro
    dropout_on = cfg.attn_dropout > 0.0 and dropout_seed is not None
    moe_on = cfg.num_experts > 0

    riders = (positions, segment_ids)
    layer_xs = None
    if dropout_on:
        # seed rider: every row of micro-batch m carries _micro_seed(m);
        # the pipeline's [B] -> [M, mb] reshape makes it per-micro
        micro_of_row = jnp.arange(b, dtype=jnp.int32) // max(b // M, 1)
        riders = riders + (_micro_seed(dropout_seed, micro_of_row),)
        layer_xs = jnp.arange(cfg.num_layers, dtype=jnp.int32)

    aux_scale = None
    if moe_on:
        labels_m = labels.reshape((M, b // M) + labels.shape[1:])
        count_m = jnp.sum(labels_m != -100, axis=(1, 2)).astype(jnp.float32)
        aux_scale = cfg.router_aux_weight * count_m

    def mk_apply(raw):
        # raw = _raw_block_fn(per-layer cfg): one block apply returning
        # (carry, aux_sum); this wrapper adds the 1F1B-specific riders
        # (per-micro dropout seed travels the ring in the carry)
        def apply_block(p, carry, layer_idx=None):
            if dropout_on:
                inner, seed_row = carry[:-1], carry[-1]
                seed = _layer_seed(seed_row[0], layer_idx)
            else:
                inner, seed = carry, None
            new_c, aux = raw(p, inner, seed)
            if dropout_on:
                new_c = tuple(new_c) + (seed_row,)
            return (new_c, aux) if moe_on else new_c
        return apply_block

    # uniform models: one applier; layer_pattern: per-slot appliers with
    # each slot's static cfg (forces the unrolled stage body)
    apply_block, unroll_stage = pp_block_appliers(cfg, mk_apply)

    def _pin_logits(logits):
        """Pin the in-region [mb, s, V] logits' VOCAB dim un-sharded: a
        vocab dim GSPMD auto-shards over 'tp' puts tp collectives inside
        the pp-manual tick body, which trips an XLA SPMD-partitioner
        CHECK (spmd_partitioner_util.cc:495) whenever a data axis is
        also live (same issue as the head-weight pin in parallel/pp.py
        head_vjp).  Batch stays on the data axes; seq is left
        unconstrained (sp may shard it)."""
        from jax.sharding import PartitionSpec as _P

        from torchacc_tpu.config import DATA_AXES
        mesh = jax.sharding.get_abstract_mesh()
        data = tuple(a for a in DATA_AXES
                     if mesh is not None and a in getattr(mesh, "shape", {}))
        return jax.lax.with_sharding_constraint(
            logits, _P(data or None, _P.UNCONSTRAINED, None))

    # Vocab-parallel head: with a live tp axis the head weight, its grad
    # and the head matmul stay 1/tp per device via hand-written manual
    # collectives (ops/fused.py fused_linear_cross_entropy_tp) — the
    # GSPMD-auto alternative trips the SPMD-partitioner CHECK inside the
    # pp-manual region (see _pin_logits).  Falls back to the replicated
    # pin for custom losses (which need full logits) and non-divisible
    # vocabs.  cfg.tp_vocab_head is the escape hatch back to the pinned
    # (replicated) head.
    _mesh = jax.sharding.get_abstract_mesh()
    _tp_ext = int(getattr(_mesh, "shape", {}).get("tp", 1) or 1)
    # neither chunked-CE variant carries a bias term — head_bias models
    # (phi) take the materialised-logits paths below, mirroring the
    # trainer's fused-CE gate
    tp_head = (cfg.tp_vocab_head and _tp_ext > 1 and custom_loss is None
               and cfg.vocab_size % _tp_ext == 0 and not cfg.head_bias)
    use_fused_ce = use_fused_ce and not cfg.head_bias

    def head_loss(hp, y, lab):
        xn = scale_hidden(cfg, Norm(cfg).apply(
            {"params": hp["final_norm"]}, y))
        w = (hp["embed"].T if cfg.tie_embeddings
             else hp["lm_head"]["kernel"])
        hb = (hp["lm_head"]["bias"].astype(jnp.float32)
              if cfg.head_bias else None)
        if tp_head:
            from torchacc_tpu.ops.fused import fused_linear_cross_entropy_tp
            return fused_linear_cross_entropy_tp(
                xn, w, lab, tp_axis="tp",
                logit_softcap=cfg.logit_softcap)
        if custom_loss is not None:
            # user loss(logits, batch) -> (sum, count) | scalar, applied
            # per micro-batch in the last stage (reference: the PP
            # executor aggregates any stage-computed loss,
            # pp/executor.py:283-321).  The batch view here carries the
            # micro's labels; losses needing other batch leaves should
            # use the gpipe schedule, whose loss runs outside the region.
            logits = jnp.einsum("bsh,hv->bsv", xn.astype(jnp.float32),
                                w.astype(jnp.float32))
            logits = _pin_logits(logits if hb is None else logits + hb)
            res = custom_loss(softcap(logits, cfg.logit_softcap),
                              _MicroBatchView(labels=lab))
            if isinstance(res, tuple):
                return res
            return res, jnp.ones((), jnp.float32)
        if use_fused_ce:
            from torchacc_tpu.ops.fused import fused_linear_cross_entropy
            # scan_free: this runs inside the last-stage lax.cond, where
            # a lax.scan's WhileThunk would desynchronize XLA:CPU's
            # collective rendezvous (see ops/fused.py docstring)
            return fused_linear_cross_entropy(
                xn, w, lab, logit_softcap=cfg.logit_softcap,
                scan_free=True)
        logits = jnp.einsum("bsh,hv->bsv", xn.astype(jnp.float32),
                            w.astype(jnp.float32))
        logits = _pin_logits(logits if hb is None else logits + hb)
        return loss_sum_count(softcap(logits, cfg.logit_softcap), lab)

    # tells the 1F1B executor's head_vjp to SKIP its replicated-head pin:
    # the tp-aware head consumes the tp-sharded weight directly (a
    # replicated copy would force an all-gather each tick and a reshard
    # at the inner shard_map boundary)
    head_loss.tp_aware = tp_head

    return pipeline_loss_1f1b(
        apply_block, head_loss, stacked, head_params, x, riders, labels,
        layer_xs, aux_scale, cfg.pp_size, M, pp_axis, moe_on,
        unroll_stage, cfg.pp_virtual)
