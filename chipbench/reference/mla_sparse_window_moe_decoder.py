"""Plain reference for the ``mla_sparse_window_moe_decoder`` family
(dots3-note-prev).

Written from the layer equations of ISSUE 30 (this repo), in
``jax.numpy`` and float32 at ``highest`` precision; no kernels, no cache,
no batching, nothing imported from the program.  Weights come from
``chipbench.weights.mla_sparse_window_moe_decoder`` in the canonical
layout there.  ``x = RMSNorm(h)``, pre-norm residual blocks, SwiGLU MLPs,
untied head; rotary embeddings over INTERLEAVED pairs, no scaling.

- FULL layer (expanded form).  ``c_q = a_q RMSNorm(x W_qa)``, ``q_h = c_q
  W_qb,h`` -> [nope | rope]; ``[c_kv | k_pe] = x W_kva``, ``c_kv = a_kv
  RMSNorm(c_kv)``, ``k_pe = rope(k_pe)`` one head shared by all; ``k_s,h =
  [c_kv,s W_kb,h^K | k_pe,s]``, ``v_s,h = c_kv,s W_kb,h^V``; ``a =
  sqrt(hidden / rank)`` (``apply_mla_qkv_lora_rescale``).  Indexer:
  ``qI_j = rope(c_q W_Iq,j)``, ``kI_s = rope(LayerNorm(x_s W_Ik))`` (rope
  on the first ``qk_rope_head_dim`` dims of ``index_head_dim``), ``w = (x
  W_Iw) nI^-1/2 dI^-1/2``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
  kI[s])`` for ``s <= t``; ``S_t`` = the ``index_topk`` positions of
  largest ``I[t, .]`` (all while ``t < index_topk``; ties to the lower
  position: a stable descending sort).  ``o_h = sum_{s in S_t} softmax_s(
  q_h . k_s,h (nope + rope)^-1/2) v_s,h``; ``g = sigmoid(x W_g)``; ``out =
  concat_h(g_h o_h) W_o``.
- SLIDING layer: the same without indexer, its own sizes (``swa_*``) and
  rope base, ``S_t = {s : t - window < s <= t}``.
- Expert layer: ``p = sigmoid(x W_r)``, selection by ``p + b``, top-k,
  weights ``p`` over the selected normalised to sum 1 times the route
  scale, plus the shared expert; this chip's share adds the terms of the
  experts ``[first_held_expert, + n_routed_experts)`` only.

Every position gets a MARGIN: the lesser of its routing margin (how far
a score is from moving a held expert into or out of the selection, over
the expert layers) and its selection margin (how far ``I``'s
``index_topk``-th value lies above the next, as a share of the standard
deviation of the row's visible scores, over the full layers; infinite
while everything is selected) times ``SELECTION_MARGIN_UNIT``.  A
program in bfloat16 cannot be held to the reference's choice where
either ties to its precision; ``drivers/serve_closed_loop_routed.py``
reads the widest logit gap over the positions whose margin is at least
``limits.serve.route_margin`` and the p95 over all of them (the cell's
own driver, ``serve_closed_loop_selected``, reads a control the same
way).

Departures from a textbook forward, all about memory and time and none
about the arithmetic: the row is padded to whole blocks of ``ROWS``
positions and worked a block of rows at a time, in loops that stop after
the last block that holds a real position (a request of 5k tokens in a
row padded to 33k costs 5k tokens' work); a full layer's block of queries sees
the row's keys up to the next of ``KEY_WIDTHS`` fixed widths past its own
end, under a mask (a sliding layer's the ``ROWS + window`` keys it can
reach), one head at a time; a held expert works the rows of the block
that chose it (gathered to a fixed room of ``ROWS / 8`` rows where a
block is large: eight times the mean of a block of 2,048 rows, 16 held
of 256 experts, top-8; a block that sends an expert more poisons its
output with NaN, so the comparison fails loudly and is never wrong
silently); the vocabulary goes one block at a time, each upcast alone.

``dot`` is the one seam (``dense_decoder.lower_precision_dot``): the
control swaps it, the router's and the indexer's products included.
``lower_precision_dot`` also names two WRONG forwards in float32 that
``correct`` has to catch (``chipbench/tests``): 'recent' attends the
``index_topk`` most recent positions instead of the indexer's, 'no_gate'
drops the headwise gate.  And one WITNESS, 'bfloat16': these equations
with the operands of every product rounded to bfloat16 (sums in
float32, everything between the products in float32) — the precision
the program states.  It is no control: it has to pass, and to read
about what the program reads (``drivers/serve_closed_loop_selected``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import dense_decoder as _dense
from chipbench.reference.dense_decoder import _f32_dot, rmsnorm  # noqa: F401
from chipbench.reference.mla_moe_decoder import swiglu

ROWS = 2048              # positions a block (tests shrink it)
KEY_WIDTHS = 4           # fixed key widths a full layer's block chooses from
VOCAB_BLOCK = 8192       # most vocabulary rows upcast at a time
# the selection margin in units of the routing margin: at the cell's
# `route_margin` 0.005 a position goes unread where its k-th and (k+1)-th
# scores lie within 1.7e-6 of the row's spread - a tie to float32
# precision.  A bfloat16 tie (some 5e-3 of the spread: a dozen or two of
# a query's 2,048 positions change sides) cannot be left unread: every
# position of a long context has one, and the positions that change sides
# carry the attention weight of any one of 2,048, so the comparison reads
# them and holds their effect to its limits (PERF.md section 2, PR 30)
SELECTION_MARGIN_UNIT = 3000.0


class _Wrong:
    """The float32 ``dot`` under the name of a forward that departs from
    the equations (see the module docstring)."""

    def __init__(self, departure):
        self.departure = departure

    def __call__(self, a, b):
        return _f32_dot(a, b)


def _bf16_dot(a, b):
    """Operands rounded to bfloat16, products and sums in float32: one
    pass on the MXU (DEFAULT precision rounds float32 operands to
    bfloat16, which these already are), a float32 product on a CPU."""
    a, b = (x.astype(jnp.bfloat16).astype(jnp.float32) for x in (a, b))
    return jnp.dot(a, b, precision=jax.lax.Precision.DEFAULT)


def lower_precision_dot(name: str):
    if name in ("recent", "no_gate"):
        return _Wrong(name)
    if name == "bfloat16":
        return _bf16_dot
    return _dense.lower_precision_dot(name)


def sizes_of(published: dict) -> dict:
    p = published
    if p["scoring_func"] != "sigmoid" or p.get("rope_scaling") or \
            p["topk_method"] != "noaux_tc" or p.get("n_group", 1) != 1:
        raise SystemExit("chipbench: the mla_sparse_window_moe_decoder "
                         "reference is written for sigmoid scores with a "
                         "selection bias, no groups, no rope scaling")
    held = p["n_routed_experts"]

    def kind(pre, theta):
        return dict(heads=p[pre + "num_attention_heads"],
                    q_lora=p[pre + "q_lora_rank"],
                    kv_lora=p[pre + "kv_lora_rank"],
                    nope=p[pre + "qk_nope_head_dim"],
                    rope=p[pre + "qk_rope_head_dim"],
                    v_dim=p[pre + "v_head_dim"], theta=float(theta))
    return dict(
        full=kind("", p["rope_theta"]),
        sliding=kind("swa_", p["swa_rope_theta"]),
        hidden=p["hidden_size"], eps=float(p["rms_norm_eps"]),
        window=int(p["sliding_window_size"]),
        index_heads=p["index_n_heads"], index_dim=p["index_head_dim"],
        index_topk=int(p["index_topk"]),
        rescale=bool(p["apply_mla_qkv_lora_rescale"]),
        gate=p["attention_gate_type"],
        router_width=p.get("router_n_experts", held), held=held,
        first_held=p.get("first_held_expert", 0),
        top_k=p["num_experts_per_tok"],
        route_scale=float(p["routed_scaling_factor"]),
        norm_topk=bool(p["norm_topk_prob"]))


def rope(x, positions, theta, dims=None):
    """Rotary embedding over interleaved pairs of the first ``dims``
    dims (all by default).  x: [S, heads, D]."""
    d = dims or x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0:d:2], x[..., 1:d:2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                    axis=-1).reshape(x.shape[:-1] + (d,))
    return jnp.concatenate([out, x[..., d:]], axis=-1)


def layernorm(x, scale, bias, eps):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32))


def _alpha(sizes, rank):
    return (sizes["hidden"] / rank) ** 0.5 if sizes["rescale"] else 1.0


def _blocks(fn, carry, n):
    """``fn(block index, carry)`` over the blocks that hold a real
    position (``n`` of them real in all)."""
    return jax.lax.fori_loop(0, (n + ROWS - 1) // ROWS, fn, carry)


def _rows(a, i, size=None):
    return jax.lax.dynamic_slice_in_dim(a, i * ROWS, size or ROWS, axis=0)


def _put(a, rows, i):
    return jax.lax.dynamic_update_slice_in_dim(a, rows, i * ROWS, axis=0)


def latents(xn, lw, sizes, kind, positions, dot):
    """What a layer keeps of every position, from its normed input:
    ``(c_kv, k_pe[, kI])``."""
    k = sizes[kind]
    r = k["kv_lora"]
    ckv = dot(xn, lw["wkv_a"])
    c_kv = rmsnorm(ckv[:, :r], lw["kv_norm"], sizes["eps"]) * _alpha(sizes, r)
    k_pe = rope(ckv[:, None, r:], positions, k["theta"])[:, 0]
    if kind == "sliding":
        return c_kv, k_pe
    k_idx = layernorm(dot(xn, lw["wi_k"]), lw["ik_scale"], lw["ik_bias"],
                      sizes["eps"])
    return c_kv, k_pe, rope(k_idx[:, None], positions, k["theta"],
                            k["rope"])[:, 0]


def index_scores(xn, c_q, k_idx, lw, sizes, positions, dot):
    """``I`` [rows, keys] of a block of queries over every key (no
    mask)."""
    ni, di = sizes["index_heads"], sizes["index_dim"]
    q = dot(c_q, lw["wi_q"].reshape(c_q.shape[1], ni * di))
    q = rope(q.reshape(-1, ni, di), positions, sizes["full"]["theta"],
             sizes["full"]["rope"])
    w = dot(xn, lw["wi_w"]) * (ni ** -0.5 * di ** -0.5)

    def one_head(acc, qw):
        q_j, w_j = qw
        return acc + w_j[:, None] * jax.nn.relu(dot(q_j, k_idx.T)), None

    out, _ = jax.lax.scan(
        one_head, jnp.zeros((xn.shape[0], k_idx.shape[0]), jnp.float32),
        (jnp.moveaxis(q, 1, 0), w.T))
    return out


def select(scores, visible, k):
    """``(chosen [rows, keys] bool, margin [rows])``: the ``k`` visible
    positions of largest score, ties to the lower position, and how far
    the k-th lies above the next as a share of the visible scores'
    standard deviation (inf where all visible positions are chosen)."""
    if scores.shape[1] <= k:
        return visible, jnp.full((scores.shape[0],), jnp.inf)
    masked = jnp.where(visible, scores, -jnp.inf)
    order = jnp.argsort(-masked, axis=-1, stable=True)
    rows = jnp.arange(scores.shape[0])[:, None]
    chosen = jnp.zeros(scores.shape, bool).at[rows, order[:, :k]].set(True)
    ranked = jnp.take_along_axis(masked, order[:, :k + 1], axis=-1)
    count = jnp.sum(visible, axis=-1)
    mean = jnp.sum(jnp.where(visible, scores, 0.0), axis=-1) / count
    var = jnp.sum(jnp.where(visible, jnp.square(scores - mean[:, None]),
                            0.0), axis=-1) / count
    margin = jnp.where(count > k, (ranked[:, k - 1] - ranked[:, k])
                       / jnp.sqrt(var + 1e-30), jnp.inf)
    return chosen & visible, margin


def heads_attention(c_q, c_kv, k_pe, mask, gate, lw, k, positions, dot):
    """``concat_h(g_h softmax(q_h . k_h) v_h) W_o`` of a block of
    queries over the keys ``(c_kv, k_pe)`` under ``mask``, expanded, one
    head at a time.  -> [rows, hidden]"""
    nope = k["nope"]
    scale = (nope + k["rope"]) ** -0.5

    def one_head(acc, hw):
        wq, wk, wv, wo, g = hw
        q = dot(c_q, wq)
        q_pe = rope(q[:, None, nope:], positions, k["theta"])[:, 0]
        scores = (dot(q[:, :nope], dot(c_kv, wk).T)
                  + dot(q_pe, k_pe.T)) * scale
        # (a padded row past the real positions may see no key: its
        # probabilities are 0, not 0 / 0)
        scores = jnp.where(mask, scores, -jnp.inf)
        top = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), -1e30)
        e = jnp.where(mask, jnp.exp(scores - top), 0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        return acc + dot(dot(p, dot(c_kv, wv)) * g[:, None], wo), None

    first = lambda w: jnp.moveaxis(w, 1, 0)  # noqa: E731
    out, _ = jax.lax.scan(
        one_head, jnp.zeros((c_q.shape[0], lw["wo"].shape[-1]), jnp.float32),
        (first(lw["wq_b"]), first(lw["wkv_b_k"]), first(lw["wkv_b_v"]),
         lw["wo"], gate.T))
    return out


def route(scores, bias, sizes):
    """``(sel [S, k], w [S, k], margin [S])``: top-k of ``scores + bias``,
    weights the unbiased scores of the selected; ``margin`` the smallest
    move of a selection score that puts a held expert into or out of
    the selection."""
    k = sizes["top_k"]
    choice = scores + bias.astype(jnp.float32)
    order = jnp.argsort(-choice, axis=-1, stable=True)
    sel = order[:, :k]
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if sizes["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    ranked = jnp.take_along_axis(choice, order, axis=-1)
    lowest_in, best_out = ranked[:, k - 1:k], ranked[:, k:k + 1]
    held = sizes["first_held"] + jnp.arange(sizes["held"])
    chosen = jnp.any(sel[:, :, None] == held[None, None, :], axis=1)
    margin = jnp.where(chosen, choice[:, held] - best_out,
                       lowest_in - choice[:, held])
    return sel, w * sizes["route_scale"], jnp.min(margin, axis=-1)


def expert_layer(x, lw, layer, sizes, dot, real=None):
    """``(shared(x) + the held experts' terms, routing margin)``; the
    leaves of ``lw`` are stacked over the layers of their stack and read
    at ``layer``, one expert at a time.  ``real`` [S] marks the rows that
    are positions of the request (padding reaches no expert)."""
    sel, w, margin = route(jax.nn.sigmoid(dot(x, lw["router"][layer])),
                           lw["router_bias"][layer], sizes)
    held = sizes["first_held"] + jnp.arange(sizes["held"])
    combine = jnp.sum(jnp.where(sel[:, :, None] == held[None, None, :],
                                w[:, :, None], 0.0), axis=1)
    if real is not None:
        combine = jnp.where(real[:, None], combine, 0.0)
    rows = x.shape[0]
    room = rows if rows <= 256 else rows // 8

    def one_expert(e, acc):
        c = combine[:, e]
        # the rows that chose this expert first, in their own order
        order = jnp.argsort(c <= 0.0, stable=True)[:room]
        y = swiglu(x[order], lw["e_gate"][layer, e], lw["e_up"][layer, e],
                   lw["e_down"][layer, e], dot)
        acc = acc.at[order].add(c[order][:, None] * y)
        return jnp.where(jnp.sum(c > 0.0) > room, jnp.nan, acc)

    routed = jax.lax.fori_loop(0, sizes["held"], one_expert,
                               jnp.zeros_like(x))
    return (swiglu(x, lw["s_gate"][layer], lw["s_up"][layer],
                   lw["s_down"][layer], dot) + routed, margin)


_EXPERT_LEAVES = ("router", "router_bias", "e_gate", "e_up", "e_down",
                  "s_gate", "s_up", "s_down")


def layer_forward(x, margin, stack, layer, sizes, n, dot):
    """One layer over the row ``x`` [T, H] (``n`` real positions): the
    new row and the positions' margins so far."""
    kind = "full" if "wi_q" in stack else "sliding"
    k, eps, t = sizes[kind], sizes["eps"], x.shape[0]
    lw = {name: leaf[layer] for name, leaf in stack.items()
          if name not in _EXPERT_LEAVES}
    departure = getattr(dot, "departure", "")
    reach = sizes["window"] - 1 if kind == "sliding" else 0

    # -- what every position leaves for the later ones to read -----------
    def keep(i, kept):
        pos = i * ROWS + jnp.arange(ROWS)
        new = latents(rmsnorm(_rows(x, i), lw["ln1"], eps), lw, sizes, kind,
                      pos, dot)
        # a sliding layer's buffers have `reach` empty rows in front
        return tuple(jax.lax.dynamic_update_slice_in_dim(
            a, b, reach + i * ROWS, axis=0) for a, b in zip(kept, new))

    widths = (k["kv_lora"], k["rope"]) + (
        (sizes["index_dim"],) if kind == "full" else ())
    kept = _blocks(keep, tuple(jnp.zeros((reach + t, w), jnp.float32)
                               for w in widths), n)

    # -- a block of queries: attention, then the feed-forward half -------
    def block(i, carry):
        out, margin = carry
        pos = i * ROWS + jnp.arange(ROWS)
        rows = _rows(x, i)
        xn = rmsnorm(rows, lw["ln1"], eps)
        c_q = rmsnorm(dot(xn, lw["wq_a"]), lw["q_norm"], eps) * _alpha(
            sizes, k["q_lora"])
        gate = (jnp.ones((ROWS, k["heads"]), jnp.float32)
                if sizes["gate"] != "headwise" or departure == "no_gate"
                else jax.nn.sigmoid(dot(xn, lw["w_og"])))
        if kind == "full":
            def over(width):
                # the keys [0, width): every key this block can see
                c_kv, k_pe, k_idx = (a[:width] for a in kept)
                key_pos = jnp.arange(width)
                visible = ((key_pos[None, :] <= pos[:, None])
                           & (key_pos[None, :] < n))
                if departure == "recent":
                    mask = visible & (key_pos[None, :]
                                      > pos[:, None] - sizes["index_topk"])
                    sel_margin = jnp.full((ROWS,), jnp.inf)
                else:
                    mask, sel_margin = select(
                        index_scores(xn, c_q, k_idx, lw, sizes, pos, dot),
                        visible, sizes["index_topk"])
                return heads_attention(c_q, c_kv, k_pe, mask, gate, lw, k,
                                       pos, dot), sel_margin
            widths = sorted({-(-(t * (j + 1) // KEY_WIDTHS) // ROWS) * ROWS
                             for j in range(KEY_WIDTHS)})
            which = jnp.searchsorted(jnp.asarray(widths), (i + 1) * ROWS)
            attn_out, sel_margin = jax.lax.switch(
                which, [lambda w=w: over(w) for w in widths])
            margin = _put(margin, jnp.minimum(
                _rows(margin, i), sel_margin * SELECTION_MARGIN_UNIT), i)
        else:
            # the keys a block can reach: its own rows and `reach` before
            c_kv, k_pe = (_rows(a, i, ROWS + reach) for a in kept)
            key_pos = i * ROWS - reach + jnp.arange(ROWS + reach)
            mask = ((key_pos[None, :] >= 0) & (key_pos[None, :] < n)
                    & (key_pos[None, :] <= pos[:, None])
                    & (key_pos[None, :] > pos[:, None] - sizes["window"]))
            attn_out = heads_attention(c_q, c_kv, k_pe, mask, gate, lw, k,
                                       pos, dot)
        # rows past the real positions stay 0: they are later layers'
        # (masked) keys, and 0 x anything finite is 0
        real = (pos < n)[:, None]
        h = jnp.where(real, rows + attn_out, 0.0)
        hn = rmsnorm(h, lw["ln2"], eps)
        if "router" in stack:
            y, route_margin = expert_layer(hn, stack, layer, sizes, dot,
                                           real[:, 0])
            margin = _put(margin, jnp.minimum(_rows(margin, i),
                                              route_margin), i)
        else:
            y = swiglu(hn, lw["w_gate"], lw["w_up"], lw["w_down"], dot)
        return _put(out, jnp.where(real, h + y, 0.0), i), margin

    return _blocks(block, (jnp.zeros_like(x), margin), n)


def hidden(weights, sizes, ids, n, dot=_f32_dot):
    """Final-norm hidden states of one row of ``n`` real positions and
    each position's margin.  ids: [S] -> ([S, H], [S])"""
    s = ids.shape[0]
    t = -(-s // ROWS) * ROWS
    x = weights["embed"][jnp.pad(ids, (0, t - s))].astype(jnp.float32)
    margin = jnp.full((t,), jnp.inf)
    periods = sorted(name for name in weights if name[0] == "p"
                     and name[1:].isdigit())
    for layer in range(weights["dense"]["ln1"].shape[0]):
        x, margin = layer_forward(x, margin, weights["dense"], layer, sizes,
                                  n, dot)
    for period in range(weights[periods[0]]["ln1"].shape[0]):
        for name in periods:
            x, margin = layer_forward(x, margin, weights[name], period,
                                      sizes, n, dot)
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
