"""Plain reference for the ``gqa_window_softmax_moe_decoder`` family
(Mellum2-12B-A2.5B): forward, loss, gradients and AdamW.

Written from the layer equations of ISSUE 46 (this repo), in
``jax.numpy`` and float32 at ``highest`` precision; no kernels, no
sorting, no sharding, nothing imported from the program.  Weights come
from ``chipbench.weights.gqa_window_softmax_moe_decoder`` in the
canonical layout there.

- Block (pre-norm): ``h = x + Attn_l(RMSNorm(x))``, ``y = h +
  MoE_l(RMSNorm(h))``, eps ``rms_norm_eps``; final RMSNorm; untied head.
- ``Attn_l``: ``q = x W_q`` (NH heads of D), ``k = x W_k``, ``v = x W_v``
  (KH heads), no bias; RMSNorm over each head's D values on q and on k
  before rope; rope in the half-split layout (dims i and i + D/2 rotate
  together).  A SLIDING layer (``layer_types[l] == 'sliding_attention'``):
  inverse frequencies ``theta^(-2i/D)``, visible ``i - sliding_window < j
  <= i``.  A FULL layer: YaRN inverse frequencies — ``theta^(-2i/D)``
  divided by ``factor`` where the ramp is 1 and kept where it is 0, the
  ramp linear in the pair index between the correction dims of
  ``beta_fast`` and ``beta_slow`` rotations over the original context
  (the low one rounded down, the high one up) — with cos and sin times
  ``attention_factor``; visible ``j <= i``.  Scores ``q . k / sqrt(D)``;
  each group of NH / KH query heads reads one key-value head; ``W_o``.
- ``MoE_l``: ``p = softmax(h W_r)`` over the experts in float32; the
  ``num_experts_per_tok`` largest; ``w_i = p_i / sum of the chosen p``;
  ``sum_i w_i W_down,i(silu(W_gate,i h) * W_up,i h)``.  No shared expert,
  nothing dropped.
- Objective of a batch: mean next-token cross-entropy + ``AUX_COEF`` x
  the mean over layers and rows of ``E x sum_e f_e P_e``, ``f_e`` the
  share of the row's ``S x k`` (token, expert) pairs on expert e, ``P_e``
  the row's mean ``p_e``.  This sum is what ``train_readings`` reports
  as a step's loss, and what ``Trainer.fit`` logs.

Departures from the published description (``assumed`` in
``configs/mellum2-12b-a2.5b-instruct.json`` says why each): the per-head
qk-norm, the coefficient and the per-row scope of the load-balance term
are the family's (Qwen3-MoE), not keys of the row's config; the
multi-token-prediction head the release mentions has no key and is not
computed.

Departures from a textbook forward, all about memory and none about the
arithmetic: attention runs one key-value head and one block of
``Q_BLOCK`` queries at a time, every expert works every position of the
row, one expert at a time, with weight 0 on the positions that did not
choose it (no gather), the head and the softmax go a block of positions
at a time (``dense_decoder``), each under ``jax.checkpoint``; a batch is
taken a row at a time.

``dot`` is the one seam (``dense_decoder.lower_precision_dot``): the
control swaps it for every product but the router's, which stays in
float32 as the program states it (a router in the control's precision
would choose other experts and fail for that alone).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.dense_decoder import (  # noqa: F401
    _Static,
    _adamw,
    _ce_block,
    _f32_dot,
    _scale,
    _subtract,
    CE_BLOCK,
    embed,
    leaf_norms,
    lower_precision_dot,
    rmsnorm,
)

Q_BLOCK = 1024           # queries per attention block
AUX_COEF = 0.001         # the load-balance term's weight (assumed)


def sizes_of(published: dict) -> dict:
    """The sizes the block needs, from the source's config keys."""
    rope = published["rope_parameters"]
    full = rope["full_attention"]
    return dict(
        heads=published["num_attention_heads"],
        kv_heads=published["num_key_value_heads"],
        head_dim=published["head_dim"],
        eps=float(published["rms_norm_eps"]),
        window=int(published["sliding_window"]),
        layer_types=tuple(published["layer_types"]),
        theta_sliding=float(rope["sliding_attention"]["rope_theta"]),
        theta_full=float(full["rope_theta"]),
        yarn=(float(full["factor"]),
              float(full["original_max_position_embeddings"]),
              float(full["beta_fast"]), float(full["beta_slow"]),
              float(full["attention_factor"]))
        if full.get("rope_type") == "yarn" else None,
        experts=published["num_experts"],
        top_k=published["num_experts_per_tok"],
        renorm=bool(published["norm_topk_prob"]))


def inv_freq(sizes, full: bool):
    """``(inverse frequencies [D/2], factor on cos and sin)`` of a
    layer's rope."""
    d = sizes["head_dim"]
    theta = sizes["theta_full"] if full else sizes["theta_sliding"]
    plain = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if not full or sizes["yarn"] is None:
        return plain, 1.0
    factor, original, beta_fast, beta_slow, attention_factor = sizes["yarn"]

    def correction_dim(rotations):
        # the pair index whose wavelength makes ``rotations`` turns over
        # the original context
        return (d * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    # ramp 0: short wavelengths, kept (extrapolated); ramp 1: long ones,
    # divided by the factor (interpolated)
    return plain / factor * ramp + plain * (1.0 - ramp), attention_factor


def rope(x, positions, inv, scale):
    """Half-split rotary embedding.  x: [S, heads, D]."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block_attention(q, k, v, q_pos, window, dot):
    """Softmax attention of one kv head and one block of queries.
    q: [Q, G, D] at positions ``q_pos``; k, v: [S, D] at 0..S-1;
    ``window`` None on a full layer."""
    nq, g, d = q.shape
    s = k.shape[0]
    scores = dot(q.reshape(nq * g, d), k.T).reshape(nq, g, s) / jnp.sqrt(
        jnp.float32(d))
    j = jnp.arange(s)[None, :]
    visible = j <= q_pos[:, None]
    if window is not None:
        visible &= j > q_pos[:, None] - window
    scores = jnp.where(visible[:, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return dot(p.reshape(nq * g, s), v).reshape(nq, g, d)


def attention(x, lw, sizes, full: bool, dot):
    s = x.shape[0]
    nh, kh, d = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
    eps = sizes["eps"]
    positions = jnp.arange(s)
    inv, scale = inv_freq(sizes, full)
    q = rmsnorm(dot(x, lw["wq"]).reshape(s, nh, d), lw["q_norm"], eps)
    k = rmsnorm(dot(x, lw["wk"]).reshape(s, kh, d), lw["k_norm"], eps)
    q, k = rope(q, positions, inv, scale), rope(k, positions, inv, scale)
    v = dot(x, lw["wv"]).reshape(s, kh, d)
    g = nh // kh
    window = None if full else sizes["window"]
    qb = min(Q_BLOCK, s)
    assert s % qb == 0, (s, qb)
    nb = s // qb

    @jax.checkpoint
    def one(args):
        qi, ki, vi, pos = args
        return _block_attention(qi, ki, vi, pos, window, dot)

    def head(args):
        qh, kh_, vh = args               # [S, G, D], [S, D], [S, D]
        out = jax.lax.map(
            lambda a: one((a[0], kh_, vh, a[1])),
            (qh.reshape(nb, qb, g, d), positions.reshape(nb, qb)))
        return out.reshape(s, g, d)

    outs = jax.lax.map(head, (q.reshape(s, kh, g, d).transpose(1, 0, 2, 3),
                              k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return dot(outs.transpose(1, 0, 2, 3).reshape(s, nh * d), lw["wo"])


def route(h, router, sizes):
    """``(probabilities [S, E], combine weights [S, E])``: the weights
    are ``w_i`` on the chosen experts and 0 elsewhere."""
    p = jax.nn.softmax(_f32_dot(h, router), axis=-1)
    top, sel = jax.lax.top_k(p, sizes["top_k"])
    if sizes["renorm"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return p, jnp.zeros_like(p).at[rows, sel].set(top)


def moe(h, lw, sizes, dot):
    """``(sum_i w_i E_i(h), E x sum_e f_e P_e of this row)``."""
    p, combine = route(h, lw["router"], sizes)

    @jax.checkpoint
    def one(args):
        w_gate, w_up, w_down, w = args
        out = dot(jax.nn.silu(dot(h, w_gate)) * dot(h, w_up), w_down)
        return w[:, None] * out

    # the sum is outside the checkpoint: the backward then keeps no
    # running sum an expert, only what it was handed
    y, _ = jax.lax.scan(lambda acc, args: (acc + one(args), None),
                        jnp.zeros_like(h),
                        (lw["e_gate"], lw["e_up"], lw["e_down"], combine.T))
    chosen = jax.lax.stop_gradient(combine > 0).astype(jnp.float32)
    share = jnp.sum(chosen, axis=0) / (h.shape[0] * sizes["top_k"])
    aux = sizes["experts"] * jnp.sum(share * jnp.mean(p, axis=0))
    return y, aux


def block(x, lw, sizes, full: bool, dot):
    eps = sizes["eps"]
    h = x + attention(rmsnorm(x, lw["ln1"], eps), lw, sizes, full, dot)
    y, aux = moe(rmsnorm(h, lw["ln2"], eps), lw, sizes, dot)
    return h + y, aux


def hidden(weights, sizes, ids, dot=_f32_dot):
    """``(final-norm hidden states [S, H], the layers' mean load-balance
    term)`` of one row."""
    x = embed(weights, ids)
    depth = weights["layers"]["ln1"].shape[0]
    aux = 0.0
    for i in range(depth):
        full = sizes["layer_types"][i] == "full_attention"
        lw = jax.tree.map(lambda a, i=i: a[i], weights["layers"])
        x, a = jax.checkpoint(
            lambda x_, lw_, full=full: block(x_, lw_, sizes, full, dot))(
                x, lw)
        aux = aux + a / depth
    return rmsnorm(x, weights["final_norm"], sizes["eps"]), aux


def logits_at(weights, sizes, ids, positions, dot=_f32_dot):
    """Float32 logits of one row at ``positions``."""
    h, _ = hidden(weights, sizes, ids, dot)
    return dot(h[positions], weights["head"])


def row_objective_sum(weights, sizes, ids, dot=_f32_dot):
    """One row's share of the batch objective times the batch's count of
    targets: its summed next-token cross-entropy (S - 1 targets) +
    ``AUX_COEF`` x its load-balance term x (S - 1)."""
    h, aux = hidden(weights, sizes, ids, dot)
    labels = jnp.concatenate([ids[1:], jnp.full((1,), -1, ids.dtype)])
    n = -(-h.shape[0] // CE_BLOCK)
    pad = n * CE_BLOCK - h.shape[0]
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(n, CE_BLOCK, -1)
    labels = jnp.pad(labels, (0, pad), constant_values=-1).reshape(
        n, CE_BLOCK)
    one = jax.checkpoint(
        lambda hl: _ce_block(hl[0], weights["head"], hl[1], dot))
    ce = jnp.sum(jax.lax.map(one, (h, labels)))
    return ce + AUX_COEF * aux * (ids.shape[0] - 1)


@functools.lru_cache(maxsize=None)
def _row_grad(sizes, dot):
    def fn(w, ids, acc):
        val, g = jax.value_and_grad(
            lambda w_: row_objective_sum(w_, dict(sizes), ids, dot))(w)
        return val, jax.tree.map(jnp.add, acc, g)
    return jax.jit(fn, donate_argnums=2)


def loss_and_grads(weights, sizes, batch, dot=_f32_dot):
    """The batch objective and its gradient, row by row."""
    fn = _row_grad(_Static(sizes), dot)
    total = 0.0
    grads = jax.tree.map(jnp.zeros_like, weights)
    for row in batch:
        val, grads = fn(weights, jnp.asarray(row), grads)
        total = total + val
    count = float(batch.shape[0] * (batch.shape[1] - 1))
    return total / count, _scale(grads, count)


def train_readings(make_weights, sizes, batches, opt, dot=_f32_dot):
    """Follow ``len(batches)`` AdamW steps from ``make_weights()``: the
    objective of each step, the per-leaf norm of the first gradient and
    of the parameters' change after the last step
    (``dense_decoder.train_readings``)."""
    p = make_weights()
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        loss, g = loss_and_grads(p, sizes, batch, dot)
        losses.append(float(loss))
        if i == 0:
            grad_norms = leaf_norms(g)
        p, m, v = _adamw(p, g, m, v, step=i + 1, opt=_Static(opt))
        del g
    del m, v
    return dict(losses=losses, grad_norms=grad_norms,
                delta_norms=leaf_norms(_subtract(p, make_weights())))
