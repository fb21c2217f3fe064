"""Plain reference for the ``mla_moe_decoder`` family (A.X-K1).

Written from the equations of the configuration's source, in
``jax.numpy`` and float32 at ``highest`` precision; no kernels, no cache,
no batching, nothing imported from the program.  Weights come from
``chipbench.weights.mla_moe_decoder`` in the canonical layout there.

The block (pre-norm, RMSNorm): ``h = x + attn(norm1 x)``, ``y = h +
ffn(norm2 h)``; the first ``first_k_dense_replace`` layers' ffn is a
SwiGLU MLP, the others' the expert layer.

- Attention, EXPANDED form.  ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb``
  -> heads x [q_nope | q_pe]; ``[c_kv | k_pe] = x W_kva``, ``c_kv =
  RMSNorm(c_kv)``, ``[k_nope | v] = c_kv W_kvb`` a head, ``k_pe`` one
  head shared by all.  Rotary on q_pe and k_pe over INTERLEAVED pairs
  (dims 2i, 2i+1) with yarn frequencies; the cos/sin factor is
  ``mscale(f, mscale) / mscale(f, mscale_all_dim)``.  Scores ``(q_nope .
  k_nope + q_pe . k_pe) * (nope + rope)^-1/2 * m^2``, ``m = 0.1 *
  mscale_all_dim * ln f + 1``; causal softmax; ``o = concat_h(P v_h) W_o``.
- Expert layer.  ``s = sigmoid(x W_g)`` over the router's published
  width; groups of adjacent experts, a group's score the sum of its two
  largest ``s``; the ``topk_group`` best groups stay; top-k of ``s``
  inside them; ``w = route_scale * s_sel / (sum s_sel + 1e-20)``.
  ``ffn(x) = shared(x) + sum_i w_i E_i(x)`` — of which this chip's share
  holds experts ``[first_held_expert, + n_routed_experts)`` and adds only
  their terms: what the absent experts would add is left out, here as in
  the program.

Departures from a textbook forward, all about memory (the reference is
handed the program's own bf16 values, 11-12 GB of a 16 GB chip) and none
about the arithmetic: attention runs one head at a time, the dense MLP
one block of its width at a time, the routed part one held expert at a
time, the head one block of vocabulary rows at a time; each block is
upcast alone.

``dot`` is the one seam (see ``dense_decoder.lower_precision_dot``): the
control swaps it, the router's product included.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.dense_decoder import (  # noqa: F401
    _f32_dot,
    lower_precision_dot,
    rmsnorm,
)

FFN_BLOCK = 2048         # columns of a dense MLP upcast at a time
VOCAB_BLOCK = 8192       # rows of the vocabulary upcast at a time


def sizes_of(published: dict) -> dict:
    """The sizes the block needs, from the source's config keys (and the
    two keys that state the chip's share of the experts)."""
    p = published
    rs = p["rope_scaling"]
    held = p["n_routed_experts"]
    if p["scoring_func"] != "sigmoid" or p["topk_method"] != "none":
        raise SystemExit("chipbench: the mla_moe_decoder reference is "
                         "written for sigmoid scores without a selection "
                         "bias")
    return dict(
        heads=p["num_attention_heads"], q_lora=p["q_lora_rank"],
        kv_lora=p["kv_lora_rank"], nope=p["qk_nope_head_dim"],
        rope=p["qk_rope_head_dim"], v_dim=p["v_head_dim"],
        eps=float(p["rms_norm_eps"]), theta=float(p["rope_theta"]),
        yarn_factor=float(rs["factor"]),
        yarn_original=float(rs["original_max_position_embeddings"]),
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        dense_layers=p["first_k_dense_replace"],
        router_width=p.get("router_n_experts", held), held=held,
        first_held=p.get("first_held_expert", 0),
        n_group=p["n_group"], topk_group=p["topk_group"],
        top_k=p["num_experts_per_tok"],
        route_scale=float(p["routed_scaling_factor"]),
        norm_topk=bool(p["norm_topk_prob"]))


def _mscale(factor, m):
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def yarn_frequencies(sizes):
    """Per-pair rotary frequencies [rope / 2]: NTK-by-parts between the
    original and the position-interpolated frequencies."""
    d, theta = sizes["rope"], sizes["theta"]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))

    def correction(beta):
        return (d * math.log(sizes["yarn_original"] / (beta * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(sizes["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction(sizes["yarn_beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inv / sizes["yarn_factor"] * ramp + inv * (1.0 - ramp)


def rope(x, positions, sizes):
    """Rotary embedding over interleaved pairs.  x: [S, heads, rope]."""
    ang = positions.astype(jnp.float32)[:, None] * yarn_frequencies(sizes)
    factor = (_mscale(sizes["yarn_factor"], sizes["yarn_mscale"])
              / _mscale(sizes["yarn_factor"], sizes["yarn_mscale_all_dim"]))
    cos, sin = (jnp.cos(ang) * factor)[:, None], (jnp.sin(ang)
                                                  * factor)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attention(x, lw, sizes, positions, dot):
    s = x.shape[0]
    r, nope = sizes["kv_lora"], sizes["nope"]
    c_q = rmsnorm(dot(x, lw["wq_a"]), lw["q_norm"], sizes["eps"])
    ckv = dot(x, lw["wkv_a"])
    c_kv = rmsnorm(ckv[:, :r], lw["kv_norm"], sizes["eps"])
    k_pe = rope(ckv[:, None, r:], positions, sizes)[:, 0]        # [S, rope]
    m = _mscale(sizes["yarn_factor"], sizes["yarn_mscale_all_dim"])
    scale = (nope + sizes["rope"]) ** -0.5 * m * m
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def one_head(acc, hw):
        wq, wk, wv, wo = hw          # [Q, nope+rope] [R, nope] [R, v] [v, H]
        q = dot(c_q, wq)
        q_pe = rope(q[:, None, nope:], positions, sizes)[:, 0]
        scores = (dot(q[:, :nope], dot(c_kv, wk).T)
                  + dot(q_pe, k_pe.T)) * scale
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return acc + dot(dot(p, dot(c_kv, wv)), wo), None

    heads_first = lambda w: jnp.moveaxis(w, 1, 0)  # noqa: E731
    out, _ = jax.lax.scan(
        one_head, jnp.zeros((s, lw["wo"].shape[-1]), jnp.float32),
        (heads_first(lw["wq_b"]), heads_first(lw["wkv_b_k"]),
         heads_first(lw["wkv_b_v"]), lw["wo"]))
    return out


def swiglu(x, w_gate, w_up, w_down, dot, block=None):
    """SwiGLU, ``block`` columns of its width at a time."""
    f = w_gate.shape[1]
    if not block or f % block or f == block:
        return dot(jax.nn.silu(dot(x, w_gate)) * dot(x, w_up), w_down)

    def part(acc, i):
        cols = lambda w: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, i * block, block, axis=1)
        ff = jax.nn.silu(dot(x, cols(w_gate))) * dot(x, cols(w_up))
        return acc + dot(ff, jax.lax.dynamic_slice_in_dim(
            w_down, i * block, block, axis=0)), None

    out, _ = jax.lax.scan(part, jnp.zeros((x.shape[0], w_down.shape[1]),
                                          jnp.float32),
                          jnp.arange(f // block))
    return out


def route(scores, sizes):
    """Group-limited top-k on ``scores`` [S, E]: ``(sel [S, k], w [S, k],
    margin [S])``.  ``margin`` says how far the token is from a routing
    that would change what THIS chip computes: the smallest move of a
    score (or of a group's score) that puts a held expert into or out
    of the selection.  A program that computes in bfloat16 cannot be
    held to the reference's choice between two experts whose scores tie
    to its precision; the comparison reads a position only where the
    margin is wide (``limits.serve.route_margin``)."""
    s, e = scores.shape
    g, k, tg = sizes["n_group"], sizes["top_k"], sizes["topk_group"]
    per = e // g
    group_score = jnp.sum(jnp.sort(scores.reshape(s, g, per),
                                   axis=-1)[..., -2:], axis=-1)
    by_group = jnp.argsort(-group_score, axis=-1, stable=True)
    kept = jnp.zeros((s, g), bool).at[
        jnp.arange(s)[:, None], by_group[:, :tg]].set(True)
    eligible = jnp.repeat(kept, per, axis=1)
    masked = jnp.where(eligible, scores, -jnp.inf)
    order = jnp.argsort(-masked, axis=-1, stable=True)
    sel = order[:, :k]
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if sizes["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)

    # -- the margin, over the held experts and the groups they lie in --
    ranked = jnp.take_along_axis(masked, order, axis=-1)
    lowest_in, best_out = ranked[:, k - 1:k], ranked[:, k:k + 1]
    held = sizes["first_held"] + jnp.arange(sizes["held"])
    chosen = jnp.any(sel[:, :, None] == held[None, None, :], axis=1)
    expert_margin = jnp.where(chosen, scores[:, held] - best_out,
                              lowest_in - scores[:, held])
    expert_margin = jnp.where(eligible[:, held], expert_margin, jnp.inf)
    groups = sorted({(sizes["first_held"] + i) // per
                     for i in range(sizes["held"])})
    group_margin = jnp.full((s, 1), jnp.inf)
    if tg < g:
        ranked_g = -jnp.sort(-group_score, axis=-1)
        groups = jnp.asarray(groups)
        group_margin = jnp.where(
            kept[:, groups], group_score[:, groups] - ranked_g[:, tg:tg + 1],
            ranked_g[:, tg - 1:tg] - group_score[:, groups])
    margin = jnp.minimum(jnp.min(expert_margin, axis=-1),
                         jnp.min(group_margin, axis=-1))
    return sel, w * sizes["route_scale"], margin


def expert_layer(x, lw, sizes, dot):
    """``(shared(x) + the held experts' terms of sum_i w_i E_i(x),
    routing margin [S])``."""
    sel, w, margin = route(jax.nn.sigmoid(dot(x, lw["router"])), sizes)
    held = sizes["first_held"] + jnp.arange(sizes["held"])
    # combine[t, e]: token t's weight on held expert e (0 = not chosen)
    combine = jnp.sum(jnp.where(sel[:, :, None] == held[None, None, :],
                                w[:, :, None], 0.0), axis=1)

    def one_expert(acc, ew):
        w_gate, w_up, w_down, c = ew
        return acc + c[:, None] * swiglu(x, w_gate, w_up, w_down, dot), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (lw["e_gate"], lw["e_up"], lw["e_down"], combine.T))
    return (swiglu(x, lw["s_gate"], lw["s_up"], lw["s_down"], dot) + routed,
            margin)


def block(x, lw, sizes, positions, dot):
    """One layer: ``(y, routing margin [S])`` (inf for a dense layer)."""
    eps = sizes["eps"]
    h = x + attention(rmsnorm(x, lw["ln1"], eps), lw, sizes, positions, dot)
    n = rmsnorm(h, lw["ln2"], eps)
    if "router" in lw:
        y, margin = expert_layer(n, lw, sizes, dot)
        return h + y, margin
    return h + swiglu(n, lw["w_gate"], lw["w_up"], lw["w_down"], dot,
                      FFN_BLOCK), jnp.full((x.shape[0],), jnp.inf)


def hidden(weights, sizes, ids, dot=_f32_dot):
    """Final-norm hidden states of one row and each position's narrowest
    routing margin over the layers.  ids: [S] -> ([S, H], [S])."""
    positions = jnp.arange(ids.shape[0])
    layer = lambda x, lw: block(x, lw, sizes, positions, dot)  # noqa: E731
    x = weights["embed"][ids].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, weights["dense"])
    x, margins = jax.lax.scan(layer, x, weights["moe"])
    return (rmsnorm(x, weights["final_norm"], sizes["eps"]),
            jnp.min(margins, axis=0))


def logits_and_margin_at(weights, sizes, ids, positions, dot=_f32_dot):
    """Float32 logits of one row at ``positions`` and the routing margin
    there.  -> ([len(positions), V], [len(positions)])"""
    h, margin = hidden(weights, sizes, ids, dot)
    h, margin = h[positions], margin[positions]
    head = weights["head"]
    v = head.shape[1]
    if v % VOCAB_BLOCK or v == VOCAB_BLOCK:
        return dot(h, head), margin
    parts = jax.lax.map(
        lambda i: dot(h, jax.lax.dynamic_slice_in_dim(
            head, i * VOCAB_BLOCK, VOCAB_BLOCK, axis=1)),
        jnp.arange(v // VOCAB_BLOCK))                    # [blocks, P, VB]
    return jnp.moveaxis(parts, 0, 1).reshape(h.shape[0], v), margin


def logits_at(weights, sizes, ids, positions, dot=_f32_dot):
    return logits_and_margin_at(weights, sizes, ids, positions, dot)[0]
