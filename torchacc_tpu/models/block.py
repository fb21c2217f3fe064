"""What a decoder block computes, written once.

Which projections, in which order, which norm where, qk-norm flat or per
head, the rope scale, which activation, where the residuals add:
:func:`qkv`, :func:`mlp` and :func:`block` say it, against two verbs a
caller supplies — ``proj(name, x)``, apply the projection whose
parameters are named ``name``, and ``norm(name, x, cfg=cfg)``, apply the
norm named ``name`` — and two closures for the block's halves,
``attention(h)`` and ``ffn(h)``, which the caller builds from ``qkv`` /
``mlp`` and its own attention core (a layer of a single mixer:
:func:`mixer_block`).  ``TransformerLM``'s modules
(models/transformer.py) implement the verbs with Flax submodules that
create and apply the parameter; the serving decoder (serve/scheduler.py)
and latent attention (models/mla.py) with :func:`tree_proj` /
:func:`tree_norm` over a raw tree that already holds it.  Nothing here
knows of either caller.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name


def _rope(q: jax.Array, k: jax.Array, positions: jax.Array,
          cfg) -> Tuple[jax.Array, jax.Array]:
    """Rotary embeddings, llama convention (half-split, not interleaved —
    matches HF transformers so converted weights agree).

    Scaling variants (all from the per-layer cfg, so gemma3's dual-base
    pattern composes):

    - ``rope_llama3`` — Llama-3.1 frequency banding: long wavelengths
      divide by ``factor``, short ones stay, the band between
      interpolates smoothly.  Every 3.1+ release ships this.
    - ``rope_longrope`` — Phi-3.5/4: per-dim inv_freq divisors with the
      LONG set activating once any position exceeds the original
      context (a traced switch: both static sets are built, jnp.where
      selects), and cos/sin scaled by the attention factor.  The
      ``jnp.max(positions)`` is a reduction that can lower to a small
      collective when positions are sharded (cp) — measured harmless
      (compiles+runs under pp×dp, 1f1b and cp-ring;
      test_longrope_composes_with_parallelism) and CSE dedupes it in
      the unrolled-layer path; revisit only if a partitioner change
      breaks that test.
    - ``partial_rotary`` < 1 — only the first ``d * partial`` head dims
      rotate (phi-4-mini: 0.75); the rest pass through.
    """
    import math as _math

    d = q.shape[-1]
    rot_d = int(d * cfg.partial_rotary)
    theta = cfg.rope_theta
    freqs = 1.0 / (theta ** (jnp.arange(0, rot_d, 2, dtype=jnp.float32)
                             / rot_d))
    scale = jnp.float32(1.0)
    if cfg.rope_llama3 is not None:
        factor, lo, hi, old_len = cfg.rope_llama3
        wavelen = 2.0 * _math.pi / freqs
        low_wl, high_wl = old_len / lo, old_len / hi
        smooth = (old_len / wavelen - lo) / (hi - lo)
        scaled = jnp.where(wavelen > low_wl, freqs / factor, freqs)
        smoothed = ((1.0 - smooth) / factor + smooth) * freqs
        freqs = jnp.where((wavelen >= high_wl) & (wavelen <= low_wl),
                          smoothed, scaled)
    if cfg.rope_yarn is not None:
        # YaRN NTK-by-parts (HF _compute_yarn_parameters): interpolate
        # per-dim between the original freqs (short wavelengths) and
        # position-interpolated freqs (long), with a linear ramp
        # between the beta_fast/beta_slow correction dims
        factor, old_len, bfast, bslow, attn_f, truncate = cfg.rope_yarn

        def corr_dim(beta):
            return (rot_d * _math.log(old_len / (beta * 2 * _math.pi))
                    / (2 * _math.log(theta)))

        low, high = corr_dim(bfast), corr_dim(bslow)
        if truncate:
            low, high = _math.floor(low), _math.ceil(high)
        low, high = max(low, 0), min(high, rot_d - 1)
        if low == high:
            high += 0.001  # HF's singularity guard
        ramp = jnp.clip(
            (jnp.arange(rot_d // 2, dtype=jnp.float32) - low)
            / (high - low), 0.0, 1.0)
        mask = 1.0 - ramp                       # 1 = keep original
        freqs = (freqs / factor) * (1.0 - mask) + freqs * mask
        if attn_f is None:
            attn_f = (1.0 if factor <= 1.0
                      else 0.1 * _math.log(factor) + 1.0)
        scale = jnp.float32(attn_f)
    if cfg.rope_longrope is not None:
        short_f, long_f, old_len, attn_f = cfg.rope_longrope
        short = freqs / jnp.asarray(short_f, jnp.float32)
        long = freqs / jnp.asarray(long_f, jnp.float32)
        # HF switches factor sets when the sequence grows past the
        # original context; positions are traced, so build both static
        # sets and select (one jnp.where, no retrace)
        is_long = jnp.max(positions) + 1 > old_len
        freqs = jnp.where(is_long, long, short)
        if attn_f is None:
            s = cfg.max_seq_len / old_len
            attn_f = (1.0 if s <= 1.0
                      else _math.sqrt(1.0 + _math.log(s)
                                      / _math.log(old_len)))
        scale = jnp.float32(attn_f)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [b,s,rd/2]
    cos = (jnp.cos(angles) * scale)[:, :, None, :]
    sin = (jnp.sin(angles) * scale)[:, :, None, :]

    def rot(x):
        xf = x.astype(jnp.float32)
        xr, xp = xf[..., :rot_d], xf[..., rot_d:]
        if cfg.rope_interleaved:
            # cohere: dims pair as (even, odd) instead of llama's half
            # split; rotate each pair and restore the interleaving
            x1, x2 = xr[..., 0::2], xr[..., 1::2]
            out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                            axis=-1).reshape(xr.shape)
        else:
            x1, x2 = jnp.split(xr, 2, axis=-1)
            out = jnp.concatenate(
                [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        if rot_d < d:
            out = jnp.concatenate([out, xp], axis=-1)
        return out.astype(x.dtype)

    return rot(q), rot(k)


class Norm(nn.Module):
    cfg: object  # ModelConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        xf = x.astype(jnp.float32)
        if cfg.norm in ("rmsnorm", "rmsnorm1p"):
            one_p = cfg.norm == "rmsnorm1p"
            # Gemma convention: weight stored as w, effective scale 1 + w,
            # zero-initialised (HF GemmaRMSNorm)
            scale = self.param(
                "scale",
                nn.initializers.zeros if one_p else nn.initializers.ones,
                (x.shape[-1],), cfg.param_dtype)
            y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                   + cfg.norm_eps)
            sf = scale.astype(jnp.float32)
            if one_p:
                sf = 1.0 + sf
            return (y * sf).astype(cfg.dtype)
        one_p = cfg.norm == "layernorm1p"   # nemotron: stored w, scale 1+w
        scale = self.param(
            "scale", nn.initializers.zeros if one_p else nn.initializers.ones,
            (x.shape[-1],), cfg.param_dtype)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
        sf = scale.astype(jnp.float32)
        y = y * (1.0 + sf if one_p else sf)
        if cfg.norm_bias:   # cohere's LayerNorm carries no bias
            bias = self.param("bias", nn.initializers.zeros,
                              (x.shape[-1],), cfg.param_dtype)
            y = y + bias.astype(jnp.float32)
        return y.astype(cfg.dtype)


def qkv(cfg, h, positions, proj, norm, hint=lambda x, logical_axes: x):
    """``(q, k, v)`` of one attention layer, heads split out
    ``[b, s, heads, d]``: the three projections, qk-norm in either
    variant, rope and its scale.  ``hint(x, logical_axes)`` is the
    caller's activation-layout constraint (training shards heads over
    'tp'; the default leaves ``x`` alone)."""
    q = proj("q_proj", h)
    k = proj("k_proj", h)
    v = proj("v_proj", h)
    # megatron TP activation layout: heads sharded on 'tp'
    q = hint(q, ("batch", "seq", "heads", None))
    k = hint(k, ("batch", "seq", "heads", None))
    v = hint(v, ("batch", "seq", "heads", None))
    if cfg.qk_norm:
        if cfg.qk_norm_proj:
            # OLMo2: RMSNorm over the FLAT projection (heads*d
            # jointly, scale of nh*d / nk*d) before the head split's
            # rope — HF Olmo2Attention norms the projection output
            bq, sq_ = q.shape[:2]
            q = norm("q_norm", q.reshape(bq, sq_, -1)).reshape(q.shape)
            k = norm("k_norm", k.reshape(bq, sq_, -1)).reshape(k.shape)
        else:
            # Gemma3/Qwen3: per-head-dim RMSNorm on q and k after
            # projection, BEFORE rope (HF q_norm/k_norm)
            q = norm("q_norm", q)
            k = norm("k_norm", k)
    if cfg.pos_emb == "rope":
        rp = (positions.astype(jnp.float32) / cfg.rope_scale
              if cfg.rope_scale != 1.0 else positions)
        q, k = _rope(q, k, rp, cfg)
    # names for the selective-remat policies (utils/remat.py): saving
    # post-rope q/k/v means the backward recomputes only the cheap
    # norms/elementwise ops, never the projections or the rope
    q = checkpoint_name(q, "qkv_proj")
    k = checkpoint_name(k, "qkv_proj")
    v = checkpoint_name(v, "qkv_proj")
    return q, k, v


def mlp(cfg, x, proj, hint=lambda x, logical_axes: x):
    """The dense feed-forward half's output (before the residual add)."""
    if cfg.activation in ("swiglu", "geglu"):
        # named so 'save_attn_mlp' can save the ffn-width projections
        # (recompute becomes elementwise-only) while 'save_attn' leaves
        # them unsaved — they are the dominant activation cost
        gate = checkpoint_name(proj("gate_proj", x), "mlp_gate_up")
        up = checkpoint_name(proj("up_proj", x), "mlp_gate_up")
        # geglu = Gemma's gelu_pytorch_tanh gate (nn.gelu default is
        # the tanh approximation)
        act = nn.silu if cfg.activation == "swiglu" else nn.gelu
        h = act(gate) * up
    else:
        up = checkpoint_name(proj("up_proj", x), "mlp_gate_up")
        if cfg.activation == "relu2":   # nemotron: square(relu(x))
            h = jnp.square(nn.relu(up))
        elif cfg.activation == "gelu_exact":   # gpt-neox erf gelu
            h = nn.gelu(up, approximate=False)
        else:
            h = nn.gelu(up)
    # megatron TP: ffn hidden sharded on 'tp' (column-parallel out)
    h = hint(h, ("batch", "seq", "mlp"))
    return proj("down_proj", h)


def block(cfg, x, norm, attention, ffn):
    """One decoder block: ``attention(h)`` and ``ffn(h)`` are its two
    halves' outputs before the residual; the norms' placement, the
    parallel form and the residual adds are decided here."""
    post = cfg.norm_placement == "post"
    if post and cfg.sandwich_norms:
        raise ValueError("norm_placement='post' (OLMo2) does not "
                         "compose with sandwich_norms (gemma2)")
    if cfg.norm_placement not in ("pre", "post"):
        raise ValueError(f"norm_placement must be 'pre' | 'post', "
                         f"got {cfg.norm_placement!r}")
    if cfg.parallel_block:
        # phi-2: both sublayers read ONE shared pre-norm and the
        # residual adds them together; no ln2 exists
        if post or cfg.sandwich_norms:
            raise ValueError("parallel_block (phi) does not compose "
                             "with norm_placement='post' or "
                             "sandwich_norms")
        n = norm("ln1", x)
        attn_out = attention(n)
        n_mlp = (n if cfg.parallel_block_shared_norm
                 else norm("ln2", x))   # gpt-neox
        mlp_out = ffn(n_mlp)
        return (x + checkpoint_name(attn_out, "attn_out")
                + checkpoint_name(mlp_out, "mlp_out"))
    attn_out = attention(x if post else norm("ln1", x))
    if cfg.sandwich_norms:
        # Gemma2: post-attention norm before the residual add
        attn_out = norm("ln1_post", attn_out)
    if post:
        # OLMo2: the sublayer OUTPUT is normed (no pre-norm at all)
        attn_out = norm("ln1", attn_out)
    # names referenced by the 'offload_dots' remat policy (utils/remat.py)
    h = x + checkpoint_name(attn_out, "attn_out")
    # the grouped expert layer routes in float32: its norm hands it
    # float32 (a bf16-rounded router input flips near-tied experts)
    ln2_cfg = (dataclasses.replace(cfg, dtype=jnp.float32)
               if cfg.num_experts > 0 and cfg.moe_dispatch == "grouped"
               else cfg)
    mlp_out = ffn(h if post else norm("ln2", h, ln2_cfg))
    if cfg.sandwich_norms:
        mlp_out = norm("ln2_post", mlp_out)
    if post:
        mlp_out = norm("ln2", mlp_out)
    return h + checkpoint_name(mlp_out, "mlp_out")


def mixer_block(cfg, x, norm, mixer, routed=False):
    """A layer of ONE mixer (``cfg.mixer_pattern``): ``x + mixer(ln(x))``,
    whatever the mixer is — state-space, attention or experts.  A
    ``routed`` mixer (the grouped expert layer) is handed float32, as in
    :func:`block`: a bf16-rounded router input flips near-tied experts."""
    norm_cfg = (dataclasses.replace(cfg, dtype=jnp.float32) if routed
                else cfg)
    return x + checkpoint_name(mixer(norm("ln", x, norm_cfg)), "mixer_out")


def tree_proj(cfg, tree):
    """The ``proj`` verb over a raw tree ``{name: {kernel[, bias]}}``,
    numerically the module's ``nn.DenseGeneral`` (operands in
    ``cfg.dtype``).  An input with its heads split out ``[b, t, heads,
    d]`` contracts both (o_proj)."""
    def proj(name, x):
        kernel = tree[name]["kernel"]
        if x.ndim == 4:
            x = x.reshape(*x.shape[:2], -1)
            kernel = kernel.reshape(-1, kernel.shape[-1])
        y = jnp.einsum("bth,h...->bt...", x.astype(cfg.dtype),
                       kernel.astype(cfg.dtype))
        bias = tree[name].get("bias")
        if bias is not None:
            y = y + bias.astype(cfg.dtype)
        return y
    return proj


def tree_norm(cfg, tree):
    """The ``norm`` verb over a raw tree ``{name: {scale[, bias]}}``."""
    def norm(name, x, cfg=cfg):
        return Norm(cfg).apply({"params": tree[name]}, x)
    return norm
