"""Plain reference for the ``gqa_window_moe_decoder`` family
(K-EXAONE-236B-A23B).

Written from the layer equations of ISSUE 33 (this repo), in
``jax.numpy`` and float32 at ``highest`` precision; no kernels, no cache,
no batching, nothing imported from the program.  Weights come from
``chipbench.weights.gqa_window_moe_decoder`` in the canonical layout
there.  RMSNorm with eps ``rms_norm_eps``, SwiGLU MLPs, untied head.

- Block, layer l: ``a = Attn_l(x)``, ``h = x + RMSNorm(a)``, ``m =
  FFN_l(h)``, ``y = h + RMSNorm(m)``: the norms sit on the sublayers'
  OUTPUTS, the sublayers read the residual stream as it is.
- ``Attn_l``: ``q = x W_q`` (NH heads of D), ``k = x W_k``, ``v = x W_v``
  (KH heads), no bias; RMSNorm over each head's D values on q and on k,
  before any rope; on a SLIDING layer rope with base ``rope_theta`` over
  the whole head, on a GLOBAL layer no rotary at all; scores ``q . k
  D^-1/2``; softmax over the visible positions: global ``j <= i``,
  sliding ``i - window < j <= i`` (``sliding_window`` positions, the
  query's own among them); each group of NH / KH query heads reads one
  key-value head; ``W_o``, no bias.
- ``FFN_l``, l < ``first_k_dense_replace``: a SwiGLU of
  ``intermediate_size``.  Else ``s = sigmoid(h W_r)`` over the router's
  published width; the ``num_experts_per_tok`` experts of largest ``s +
  b`` (``b`` a per-expert selection bias); weights ``w_i =
  routed_scaling_factor * s_i / sum of the chosen s``; ``sum_i w_i E_i(h)
  + E_shared(h)`` — of which this chip's share holds the experts
  ``[first_held_expert, + num_experts)`` and adds only their terms: what
  the absent experts would add is left out, here as in the program.
- Final RMSNorm, head.

Departures from the published description (``assumed`` in
``configs/k-exaone-236b-a23b.json`` says why each): the norm placement,
the per-head qk-norm, no rope on global layers, the window's inclusive
reading (``i - j < sliding_window``), the selection bias, and the rotary
pair layout (half-split: dims i and i + D/2 rotate together) are the
family's published block (EXAONE 4.0), not keys of the row's config; the
multi-token-prediction block (``num_nextn_predict_layers``) is not
computed — it is a drafter beside the 48-layer trunk, not part of it.

Every position gets a MARGIN: how far a selection score is from moving a
held expert into or out of the selection, the narrowest over the expert
layers (``mla_sparse_window_moe_decoder.route``).  A program in bfloat16
cannot be held to the reference's choice where two experts tie to its
precision; ``drivers/serve_closed_loop_routed.py`` reads the widest logit
gap over the positions whose margin is at least
``limits.serve.route_margin`` and the p95 over all of them.

Departures from a textbook forward, all about memory and time and none
about the arithmetic: the row is padded to whole blocks of ``ROWS``
positions and worked a block of rows at a time, in loops that stop after
the last block that holds a real position; a global layer's block of
queries sees the row's keys up to the next of ``KEY_WIDTHS`` fixed widths
past its own end, under a mask (a sliding layer's the ``ROWS + window -
1`` keys it can reach), one query head at a time; every held expert
works every row of a block, one expert at a time, with weight 0 on the
rows that did not choose it (no gather of "the rows that chose it": at
seeded weights the norm on the attention's output makes neighbouring
tokens' router inputs alike, so one held expert draws most of a block
and another none); the dense MLP goes a block of its width at a time,
the vocabulary one block at a time, each upcast alone.

``dot`` is the one seam (``dense_decoder.lower_precision_dot``): the
control swaps it, the router's product included.
``lower_precision_dot`` also names two WRONG forwards in float32 that
``correct`` has to catch — the two errors this family invites:
'no_window' (every layer attends its whole context: a sliding layer
keeps its rope and loses its window) and 'rope_global' (the global
layers rotate q and k like the sliding ones).  And one WITNESS,
'bfloat16': these equations with the operands of every product rounded
to bfloat16 — the precision the program states; it has to pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import dense_decoder as _dense
from chipbench.reference.dense_decoder import (  # noqa: F401
    _f32_dot,
    rmsnorm,
    rope,
)
from chipbench.reference.mla_moe_decoder import FFN_BLOCK, swiglu
from chipbench.reference.mla_sparse_window_moe_decoder import (
    _bf16_dot,
    _Wrong,
    route,
)

ROWS = 2048              # positions a block (tests shrink it)
KEY_WIDTHS = 4           # fixed key widths a global layer's block chooses from
VOCAB_BLOCK = 8192       # most vocabulary rows upcast at a time
WRONG = ("no_window", "rope_global")


def lower_precision_dot(name: str):
    if name in WRONG:
        return _Wrong(name)
    if name == "bfloat16":
        return _bf16_dot
    return _dense.lower_precision_dot(name)


def sizes_of(published: dict) -> dict:
    p = published
    rp = p.get("rope_parameters") or {}
    if (p["scoring_func"] != "sigmoid" or p.get("n_group", 1) != 1
            or rp.get("rope_type", "default") != "default"):
        raise SystemExit("chipbench: the gqa_window_moe_decoder reference "
                         "is written for sigmoid scores with a selection "
                         "bias, no groups, no rope scaling")
    held = p["num_experts"]
    heads = p["num_attention_heads"]
    return dict(
        heads=heads, kv_heads=p.get("num_key_value_heads") or heads,
        head_dim=p.get("head_dim") or p["hidden_size"] // heads,
        eps=float(p["rms_norm_eps"]), theta=float(rp["rope_theta"]),
        window=int(p["sliding_window"]),
        kinds=tuple("sliding" if t == "sliding_attention" else "full"
                    for t in p["layer_types"]),
        dense_layers=int(p["first_k_dense_replace"]),
        router_width=p.get("router_n_experts", held), held=held,
        first_held=p.get("first_held_expert", 0),
        top_k=p["num_experts_per_tok"],
        route_scale=float(p["routed_scaling_factor"]),
        norm_topk=bool(p["norm_topk_prob"]))


def _blocks(fn, carry, n):
    """``fn(block index, carry)`` over the blocks that hold a real
    position (``n`` of them real in all)."""
    return jax.lax.fori_loop(0, (n + ROWS - 1) // ROWS, fn, carry)


def _rows(a, i, size=None):
    return jax.lax.dynamic_slice_in_dim(a, i * ROWS, size or ROWS, axis=0)


def _put(a, rows, i):
    return jax.lax.dynamic_update_slice_in_dim(a, rows, i * ROWS, axis=0)


def heads_attention(rows, k, v, mask, lw, sizes, positions, roped, dot):
    """``concat_h(softmax(q_h . k_g(h)) v_g(h)) W_o`` of a block of
    queries ``rows`` [R, H] over the keys ``k`` and values ``v`` [K, KH,
    D] under ``mask`` [R, K], one query head at a time.  -> [R, H]"""
    d = sizes["head_dim"]
    group = sizes["heads"] // sizes["kv_heads"]

    def one_head(acc, hw):
        wq, wo, g = hw                                  # [H, D] [D, H] []
        q = rmsnorm(dot(rows, wq), lw["q_norm"], sizes["eps"])
        if roped:
            q = rope(q[:, None], positions, sizes["theta"])[:, 0]
        k_g = jax.lax.dynamic_index_in_dim(k, g, axis=1, keepdims=False)
        v_g = jax.lax.dynamic_index_in_dim(v, g, axis=1, keepdims=False)
        scores = dot(q, k_g.T) * d ** -0.5
        # (a padded row past the real positions may see no key: its
        # probabilities are 0, not 0 / 0)
        scores = jnp.where(mask, scores, -jnp.inf)
        top = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), -1e30)
        e = jnp.where(mask, jnp.exp(scores - top), 0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        return acc + dot(dot(p, v_g), wo), None

    out, _ = jax.lax.scan(
        one_head, jnp.zeros((rows.shape[0], lw["wo"].shape[-1]), jnp.float32),
        (jnp.moveaxis(lw["wq"], 1, 0), lw["wo"],
         jnp.arange(sizes["heads"]) // group))
    return out


def expert_layer(x, lw, layer, sizes, dot, real):
    """``(shared(x) + the held experts' terms, routing margin)``; the
    leaves of ``lw`` are stacked over the layers of their stack and read
    at ``layer``, one expert at a time.  ``real`` [S] marks the rows that
    are positions of the request (padding reaches no expert)."""
    sel, w, margin = route(jax.nn.sigmoid(dot(x, lw["router"][layer])),
                           lw["router_bias"][layer], sizes)
    held = sizes["first_held"] + jnp.arange(sizes["held"])
    combine = jnp.sum(jnp.where(sel[:, :, None] == held[None, None, :],
                                w[:, :, None], 0.0), axis=1)
    combine = jnp.where(real[:, None], combine, 0.0)

    def one_expert(e, acc):
        # every row through every held expert, weight 0 where it did not
        # choose it: a seed's router sends one held expert most of a
        # block's rows and another none, so no fixed room for "the rows
        # that chose it" is safe (my chip runs, PR 33)
        return acc + combine[:, e, None] * swiglu(
            x, lw["e_gate"][layer, e], lw["e_up"][layer, e],
            lw["e_down"][layer, e], dot)

    routed = jax.lax.fori_loop(0, sizes["held"], one_expert,
                               jnp.zeros_like(x))
    return (swiglu(x, lw["s_gate"][layer], lw["s_up"][layer],
                   lw["s_down"][layer], dot) + routed, margin)


_EXPERT_LEAVES = ("router", "router_bias", "e_gate", "e_up", "e_down",
                  "s_gate", "s_up", "s_down")


def layer_forward(x, margin, stack, layer, kind, sizes, n, dot):
    """One layer of ``kind`` over the row ``x`` [T, H] (``n`` real
    positions): the new row and the positions' margins so far."""
    eps, t = sizes["eps"], x.shape[0]
    kh, d = sizes["kv_heads"], sizes["head_dim"]
    lw = {name: leaf[layer] for name, leaf in stack.items()
          if name not in _EXPERT_LEAVES}
    departure = getattr(dot, "departure", "")
    windowed = kind == "sliding" and departure != "no_window"
    roped = kind == "sliding" or departure == "rope_global"
    reach = sizes["window"] - 1 if windowed else 0

    # -- what every position leaves for the later ones to read -----------
    def keep(i, kept):
        pos = i * ROWS + jnp.arange(ROWS)
        rows = _rows(x, i)
        k = rmsnorm(dot(rows, lw["wk"].reshape(-1, kh * d)).reshape(
            ROWS, kh, d), lw["k_norm"], eps)
        if roped:
            k = rope(k, pos, sizes["theta"])
        v = dot(rows, lw["wv"].reshape(-1, kh * d)).reshape(ROWS, kh, d)
        # a sliding layer's buffers have `reach` empty rows in front
        return tuple(jax.lax.dynamic_update_slice_in_dim(
            a, b, reach + i * ROWS, axis=0) for a, b in zip(kept, (k, v)))

    kept = _blocks(keep, tuple(jnp.zeros((reach + t, kh, d), jnp.float32)
                               for _ in range(2)), n)

    # -- a block of queries: attention, then the feed-forward half -------
    def block(i, carry):
        out, margin = carry
        pos = i * ROWS + jnp.arange(ROWS)
        rows = _rows(x, i)
        if windowed:
            # the keys a block can reach: its own rows and `reach` before
            k, v = (_rows(a, i, ROWS + reach) for a in kept)
            key_pos = i * ROWS - reach + jnp.arange(ROWS + reach)
            mask = ((key_pos[None, :] >= 0) & (key_pos[None, :] < n)
                    & (key_pos[None, :] <= pos[:, None])
                    & (key_pos[None, :] > pos[:, None] - sizes["window"]))
            attn_out = heads_attention(rows, k, v, mask, lw, sizes, pos,
                                       roped, dot)
        else:
            def over(width):
                # the keys [0, width): every key this block can see
                k, v = (a[:width] for a in kept)
                key_pos = jnp.arange(width)
                mask = ((key_pos[None, :] <= pos[:, None])
                        & (key_pos[None, :] < n))
                return heads_attention(rows, k, v, mask, lw, sizes, pos,
                                       roped, dot)
            widths = sorted({-(-(t * (j + 1) // KEY_WIDTHS) // ROWS) * ROWS
                             for j in range(KEY_WIDTHS)})
            which = jnp.searchsorted(jnp.asarray(widths), (i + 1) * ROWS)
            attn_out = jax.lax.switch(
                which, [lambda w=w: over(w) for w in widths])
        # rows past the real positions stay 0: they are later layers'
        # (masked) keys, and 0 x anything finite is 0
        real = (pos < n)[:, None]
        h = jnp.where(real, rows + rmsnorm(attn_out, lw["ln1"], eps), 0.0)
        if "router" in stack:
            y, route_margin = expert_layer(h, stack, layer, sizes, dot,
                                           real[:, 0])
            margin = _put(margin, jnp.minimum(_rows(margin, i),
                                              route_margin), i)
        else:
            y = swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], dot,
                       FFN_BLOCK)
        y = jnp.where(real, h + rmsnorm(y, lw["ln2"], eps), 0.0)
        return _put(out, y, i), margin

    return _blocks(block, (jnp.zeros_like(x), margin), n)


def hidden(weights, sizes, ids, n, dot=_f32_dot):
    """Final-norm hidden states of one row of ``n`` real positions and
    each position's margin.  ids: [S] -> ([S, H], [S])"""
    s = ids.shape[0]
    t = -(-s // ROWS) * ROWS
    x = weights["embed"][jnp.pad(ids, (0, t - s))].astype(jnp.float32)
    margin = jnp.full((t,), jnp.inf)
    periods = sorted((name for name in weights if name[0] == "p"
                      and name[1:].isdigit()), key=lambda p: int(p[1:]))
    at = 0                   # the layer's index in the model
    for layer in range(weights["dense"]["ln1"].shape[0]):
        x, margin = layer_forward(x, margin, weights["dense"], layer,
                                  sizes["kinds"][at], sizes, n, dot)
        at += 1
    for period in range(weights[periods[0]]["ln1"].shape[0]):
        for name in periods:
            x, margin = layer_forward(x, margin, weights[name], period,
                                      sizes["kinds"][at], sizes, n, dot)
            at += 1
    return rmsnorm(x, weights["final_norm"], sizes["eps"])[:s], margin[:s]


def logits_and_margin_at(weights, sizes, ids, positions, dot=_f32_dot):
    """Float32 logits of one row at ``positions`` and the margin there;
    the row's real positions end at the last one asked for.
    -> ([len(positions), V], [len(positions)])"""
    h, margin = hidden(weights, sizes, ids, jnp.max(positions) + 1, dot)
    h, margin = h[positions], margin[positions]
    head = weights["head"]
    v = head.shape[1]
    block = max(b for b in range(1, min(v, VOCAB_BLOCK) + 1) if v % b == 0)
    parts = jax.lax.map(
        lambda i: dot(h, jax.lax.dynamic_slice_in_dim(
            head, i * block, block, axis=1)), jnp.arange(v // block))
    return jnp.moveaxis(parts, 0, 1).reshape(h.shape[0], v), margin


def logits_at(weights, sizes, ids, positions, dot=_f32_dot):
    return logits_and_margin_at(weights, sizes, ids, positions, dot)[0]
