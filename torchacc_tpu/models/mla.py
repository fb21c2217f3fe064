"""Multi-head latent attention (MLA).

Keys and values come from one low-rank latent a token: ``[c_kv | k_pe] =
x W_kva`` (``kv_lora_rank`` + ``qk_rope_head_dim`` values), ``c_kv``
RMS-normed, ``k_pe`` rotated and shared by every head; a head's key is
``[c_kv W_kvb,h^K | k_pe]`` and its value ``c_kv W_kvb,h^V``.  Queries go
through their own low-rank pair (``q_lora_rank``; 0 = one plain
projection).  Two forms of the same arithmetic live here:

- expanded (:class:`MlaAttention`, the module's plain forward): build
  every head's k and v and run ordinary causal attention;
- absorbed (:func:`absorb_q` / :func:`expand_out`, the serving decoder):
  cache only the row ``[c_kv | rope(k_pe)]``, fold ``W_kvb^K`` into the
  query (``q~_h = q_nope,h W_kvb,h^K``) and ``W_kvb^V`` into the output
  (``o_h = (P c_kv) W_kvb,h^V``), so attention reads the latent rows as
  one shared key/value head of ``kv_lora_rank + qk_rope_head_dim`` lanes
  (ops/paged_attention.latent_paged_attention).

The projections are pure functions of the raw parameter tree, shared by
both forms.  The softmax scale is ``cfg.query_scale`` (the ingest folds
yarn's ``mscale**2`` into it).

A model may mix TWO kinds of latent layer (``cfg.layer_pattern``,
``cfg.swa_*``; the 'dots3_note' family): 'global' layers of the sizes
above with a learned selection in front of the attention (an indexer:
:func:`index_query`, :func:`index_key`, :func:`index_weights`; scores
``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``, attention over the
``cfg.index_topk`` best cached positions), and 'sliding' layers of their
own ranks, head count, head sizes and rope base over ``cfg.window``.
:func:`kind_config` gives the config a layer of one kind computes under
(the kind's sizes in the plain fields), so every function here serves
both.  Both kinds may rescale their normalised latents
(``cfg.mla_lora_rescale``) and gate each head's output
(``cfg.attn_gate``, :func:`head_gate`).
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchacc_tpu.models.block import _rope, tree_norm, tree_proj


def layer_kind(cfg, layer: int) -> str:
    """'global' | 'sliding' for a model of two latent kinds, else ''."""
    if not (cfg.swa_kv_lora_rank and cfg.layer_pattern):
        return ""
    return cfg.layer_pattern[layer % len(cfg.layer_pattern)]


def kind_config(cfg, kind: str):
    """The config a layer of ``kind`` computes under: a 'sliding' layer's
    own sizes and rope base in the plain fields, no indexer; a 'global'
    layer (or a model of one kind) is ``cfg`` with no window."""
    if kind == "sliding":
        return dataclasses.replace(
            cfg, num_heads=cfg.swa_num_heads, num_kv_heads=cfg.swa_num_heads,
            kv_lora_rank=cfg.swa_kv_lora_rank,
            q_lora_rank=cfg.swa_q_lora_rank,
            qk_nope_head_dim=cfg.swa_qk_nope_head_dim,
            qk_rope_head_dim=cfg.swa_qk_rope_head_dim,
            v_head_dim=cfg.swa_v_head_dim, index_topk=0,
            rope_theta=cfg.rope_local_theta or cfg.rope_theta)
    if kind == "global":
        return dataclasses.replace(cfg, window=(-1, -1))
    return cfg


def attn_param_count(cfg, layer: int) -> int:
    """Parameters of layer ``layer``'s attention in a model of two
    latent kinds (projections, latent norms, gate, indexer)."""
    kind = layer_kind(cfg, layer)
    c = kind_config(cfg, kind)
    h, nh = c.hidden_size, c.num_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    n = (h * c.q_lora_rank + c.q_lora_rank + c.q_lora_rank * nh * qk
         + h * (c.kv_lora_rank + c.qk_rope_head_dim) + c.kv_lora_rank
         + c.kv_lora_rank * nh * (c.qk_nope_head_dim + c.v_head_dim)
         + nh * c.v_head_dim * h)
    if c.attn_gate == "headwise":
        n += h * nh
    if c.index_topk:
        ni, di = c.index_n_heads, c.index_head_dim
        n += c.q_lora_rank * ni * di + h * di + 2 * di + h * ni
    return n


def _lora_scale(cfg, rank: int) -> float:
    return (cfg.hidden_size / rank) ** 0.5 if cfg.mla_lora_rescale else 1.0


def latent_q(cfg, attn, h):
    """``c_q = alpha_q RMSNorm(h W_qa)`` [b, s, q_lora]: the latent both
    the query heads and the indexer's heads are projected from."""
    c_q = tree_norm(cfg, attn)("q_a_norm",
                               tree_proj(cfg, attn)("q_a_proj", h))
    if cfg.mla_lora_rescale:
        c_q = c_q * _lora_scale(cfg, cfg.q_lora_rank)
    return c_q


def project_q(cfg, attn, h, positions, c_q=None):
    """``(q_nope [b, s, H, nope], q_pe [b, s, H, rope])``, q_pe rotated.
    ``c_q`` is :func:`latent_q`'s value where the caller has it."""
    proj = tree_proj(cfg, attn)
    if cfg.q_lora_rank:
        if c_q is None:
            c_q = latent_q(cfg, attn, h)
        q = proj("q_b_proj", c_q)
    else:
        q = proj("q_proj", h)
    q_nope, q_pe = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    return q_nope, _rope(q_pe, q_pe, positions, cfg)[0]


def _index_rope(cfg, x, positions):
    """Rope on the first ``qk_rope_head_dim`` of the indexer's
    ``index_head_dim`` dims, the layer's own base and pair layout."""
    part = dataclasses.replace(
        cfg, partial_rotary=cfg.qk_rope_head_dim / cfg.index_head_dim)
    return _rope(x, x, positions, part)[0]


def index_query(cfg, attn, c_q, positions):
    """The indexer's queries ``rope(c_q W_Iq)`` [b, s, nI, dI]."""
    return _index_rope(cfg, tree_proj(cfg, attn)("index_q", c_q), positions)


def index_key(cfg, attn, h, positions):
    """The ONE index key a token banks: ``rope(LayerNorm(h W_Ik))``
    [b, s, dI]."""
    ln = dataclasses.replace(cfg, norm="layernorm", norm_bias=True)
    k = tree_norm(ln, attn)("index_k_norm",
                            tree_proj(cfg, attn)("index_k", h))
    return _index_rope(cfg, k[:, :, None, :], positions)[:, :, 0]


def index_weights(cfg, attn, h):
    """The heads' weights ``(h W_Iw) nI^-1/2 dI^-1/2`` [b, s, nI], f32."""
    w = tree_proj(cfg, attn)("index_w", h).astype(jnp.float32)
    return w * (cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5)


def head_gate(cfg, attn, h, out):
    """``out`` [b, s, H, v] times the headwise gate ``sigmoid(h W_g)``
    (``cfg.attn_gate``; 'none' returns ``out``)."""
    if cfg.attn_gate == "none":
        return out
    if cfg.attn_gate != "headwise":
        raise ValueError(f"attn_gate must be 'none' | 'headwise', got "
                         f"{cfg.attn_gate!r}")
    g = jax.nn.sigmoid(
        tree_proj(cfg, attn)("gate_proj", h).astype(jnp.float32))
    return (out.astype(jnp.float32) * g[..., None]).astype(out.dtype)


def project_latent(cfg, attn, h, positions):
    """``(c_kv [b, s, R] normed, k_pe [b, s, rope] rotated)``: the row a
    latent cache banks is their concatenation."""
    ckv = tree_proj(cfg, attn)("kv_a_proj", h)
    c_kv, k_pe = jnp.split(ckv, [cfg.kv_lora_rank], axis=-1)
    c_kv = tree_norm(cfg, attn)("kv_a_norm", c_kv)
    if cfg.mla_lora_rescale:
        c_kv = c_kv * _lora_scale(cfg, cfg.kv_lora_rank)
    k_pe = k_pe[:, :, None, :]
    return c_kv, _rope(k_pe, k_pe, positions, cfg)[0][:, :, 0]


def absorb_q(cfg, attn, q_nope):
    """``q~_h = q_nope,h W_kvb,h^K``: [b, s, H, nope] -> [b, s, H, R]."""
    return jnp.einsum("bshn,rhn->bshr", q_nope.astype(cfg.dtype),
                      attn["kv_b_k"]["kernel"].astype(cfg.dtype))


def expand_out(cfg, attn, o_lat):
    """``o_h = o~_h W_kvb,h^V``: [b, s, H, R] -> [b, s, H, v]."""
    return jnp.einsum("bshr,rhv->bshv", o_lat.astype(cfg.dtype),
                      attn["kv_b_v"]["kernel"].astype(cfg.dtype))


def project_out(cfg, attn, out):
    """``concat_h(o_h) W_o``: [b, s, H, v] -> [b, s, hidden]."""
    return jnp.einsum("bshv,hvd->bsd", out.astype(cfg.dtype),
                      attn["o_proj"]["kernel"].astype(cfg.dtype))


def query_scale(cfg) -> float:
    return (cfg.query_scale if cfg.query_scale is not None else
            (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5)


def expanded_attention(cfg, attn, h, positions, segment_ids=None):
    """The expanded form on the raw tree ``attn``: [b, s, hidden] ->
    [b, s, hidden] (the attention block's output before the residual)."""
    from torchacc_tpu.ops.attention import attention_reference

    with jax.named_scope("mla_q"):
        q_nope, q_pe = project_q(cfg, attn, h, positions)
    with jax.named_scope("mla_kv"):
        c_kv, k_pe = project_latent(cfg, attn, h, positions)
        k_nope = jnp.einsum("bsr,rhn->bshn", c_kv.astype(cfg.dtype),
                            attn["kv_b_k"]["kernel"].astype(cfg.dtype))
        v = jnp.einsum("bsr,rhv->bshv", c_kv.astype(cfg.dtype),
                       attn["kv_b_v"]["kernel"].astype(cfg.dtype))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                      k_nope.shape[:3] + k_pe.shape[-1:])],
            axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    out = attention_reference(q, k, v, causal=True, scale=query_scale(cfg),
                              q_segment_ids=segment_ids,
                              kv_segment_ids=segment_ids)
    with jax.named_scope("o_proj"):
        return project_out(cfg, attn, out)


class _Scale(nn.Module):
    """Holds one norm ``scale`` (the tree a :class:`Norm` would hold)."""
    size: int
    param_dtype: object

    @nn.compact
    def __call__(self):
        return {"scale": self.param("scale", nn.initializers.ones,
                                    (self.size,), self.param_dtype)}


class MlaAttention(nn.Module):
    """The attention block of an MLA model, expanded form.  Holds the
    parameters (kernels ``[in, heads, dim]`` like :class:`Attention`'s)
    and computes on the raw tree, so the serving decoder's absorbed
    form reads the same leaves."""
    cfg: object  # ModelConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, dropout_seed=None):
        cfg = self.cfg
        if self.has_variable("cache", "k") or (
                self.is_mutable_collection("cache")
                and not self.is_initializing()) or cfg.decode:
            raise NotImplementedError(
                "latent-attention models decode through "
                "torchacc_tpu.serve.ServeEngine (a latent paged cache); "
                "the module's dense-cache decode path is not implemented")
        from torchacc_tpu.models.moe import _Kernel

        h, nh, r = cfg.hidden_size, cfg.num_heads, cfg.kv_lora_rank
        nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        kernel = lambda name, *shape: _Kernel(  # noqa: E731
            shape, cfg.param_dtype, name=name)()
        scale = lambda name, size: _Scale(  # noqa: E731
            size, cfg.param_dtype, name=name)()
        attn = {}
        if cfg.q_lora_rank:
            attn["q_a_proj"] = kernel("q_a_proj", h, cfg.q_lora_rank)
            attn["q_a_norm"] = scale("q_a_norm", cfg.q_lora_rank)
            attn["q_b_proj"] = kernel("q_b_proj", cfg.q_lora_rank, nh,
                                      nope + rope)
        else:
            attn["q_proj"] = kernel("q_proj", h, nh, nope + rope)
        attn["kv_a_proj"] = kernel("kv_a_proj", h, r + rope)
        attn["kv_a_norm"] = scale("kv_a_norm", r)
        attn["kv_b_k"] = kernel("kv_b_k", r, nh, nope)
        attn["kv_b_v"] = kernel("kv_b_v", r, nh, vd)
        attn["o_proj"] = kernel("o_proj", nh, vd, h)
        return expanded_attention(cfg, attn, x, positions, segment_ids)
