"""Plain reference for the ``ssm_attn_moe_decoder`` family
(NVIDIA-Nemotron-3-Nano-30B-A3B).

Written from the layer equations of ISSUE 42 (this repo), in
``jax.numpy`` and float32 at ``highest`` precision; no kernels, no cache,
no batching, no chunked scan, nothing imported from the program.  Weights
come from ``chipbench.weights.ssm_attn_moe_decoder`` in the canonical
layout there.  Every layer is ONE mixer: ``x <- x + mixer(RMSNorm(x))``,
eps ``layer_norm_epsilon``; the kind is a character of
``hybrid_override_pattern``.  A final RMSNorm, an untied head.

- ``M`` (Mamba-2), on the normed ``u``: ``[z | xBC | dt] = u W_in``
  (``d_inner = mamba_num_heads * mamba_head_dim`` | ``d_inner + 2 G N`` |
  heads); ``xBC_t = silu(b + sum_j w_j xBC_{t - (K-1) + j})`` over the
  ``K = conv_kernel`` last inputs, zeros before the row's start; split
  ``x [Hm, P]``, ``B [G, N]``, ``C [G, N]``; ``delta = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; THE RECURRENCE, ONE TOKEN AT A TIME
  under ``lax.scan``: ``S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x)
  B_t`` (head h reads group ``h // (Hm / G)``), ``y_t = S_t C_t + D
  x_t``; then ``y <- RMSNorm_grouped(y * silu(z))`` over ``G`` groups of
  ``d_inner / G`` (the gate BEFORE the norm), ``out = y W_out``.
- ``*``: grouped-query attention, ``num_attention_heads`` query heads on
  ``num_key_value_heads`` key-value heads of ``head_dim``, no bias, NO
  rotary embedding, causal, scale ``head_dim^-1/2``.
- ``E``: ``s = sigmoid(u W_r)``; the ``num_experts_per_tok`` experts of
  largest ``s + b``; weights ``routed_scaling_factor * s_i / sum of the
  chosen s``; expert ``i``: ``W_down,i relu(W_up,i u)^2``; plus the shared
  expert of the same form — of which this chip's share holds the experts
  ``[first_held_expert, + n_routed_experts)`` and adds only their terms
  (what the absent experts would add is left out, here as in the
  program).

Departures from the published description (``assumed`` in
``configs/nemotron-3-nano-30b-a3b.json`` says why each): ``d_inner`` from
the heads and not from ``expand``, no rotary embedding although the
config carries ``rope_theta``, no limit on the time step, the selection
bias, the state in float32.

Every position gets a MARGIN: how far a selection score is from moving a
held expert into or out of the selection, the narrowest over the expert
layers (``mla_sparse_window_moe_decoder.route``); the driver reads the
widest logit gap over the positions whose margin is at least
``limits.serve.route_margin`` and the p95 over all of them.

Departures from a textbook forward, all about memory and time and none
about the arithmetic: the row is padded to whole blocks of ``ROWS``
positions and worked a block at a time, in loops that stop after the
last block that holds a real position (the recurrence runs a block's
tokens one by one and hands its state to the next block's); every held
expert works every row of a block with weight 0 on the rows that did not
choose it; the vocabulary goes one block at a time.

``dot`` is the one seam (``dense_decoder.lower_precision_dot``): the
control swaps it, the router's product included; the recurrence itself
has no product of two matrices and stays in float32.
``lower_precision_dot`` also names two WRONG forwards in float32 that
``correct`` has to catch — 'no_conv' (the convolution sees the current
input alone: its taps on the earlier inputs are dropped) and
'no_gate_norm' (the grouped norm after the gate is dropped) — and one
WITNESS, 'bfloat16': these equations with the operands of every product
rounded to bfloat16, the precision the program states; it has to pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import dense_decoder as _dense
from chipbench.reference.dense_decoder import _f32_dot, rmsnorm  # noqa: F401
from chipbench.reference.mla_sparse_window_moe_decoder import (
    _bf16_dot,
    _Wrong,
    route,
)

ROWS = 512               # positions a block (tests shrink it)
VOCAB_BLOCK = 8192       # most vocabulary rows upcast at a time
WRONG = ("no_conv", "no_gate_norm")
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


def lower_precision_dot(name: str):
    if name in WRONG:
        return _Wrong(name)
    if name == "bfloat16":
        return _bf16_dot
    return _dense.lower_precision_dot(name)


def sizes_of(published: dict) -> dict:
    p = published
    if (p.get("n_group", 1) != 1 or p["mlp_hidden_act"] != "relu2"
            or p["mamba_hidden_act"] != "silu" or p["mamba_proj_bias"]
            or not p["use_conv_bias"] or p["attention_bias"]
            or set(p["hybrid_override_pattern"]) - set(KINDS)):
        raise SystemExit("chipbench: the ssm_attn_moe_decoder reference is "
                         "written for layers M, E and * without projection "
                         "biases, relu2 experts, no expert groups")
    held = p["n_routed_experts"]
    hm = p["mamba_num_heads"]
    return dict(
        heads=p["num_attention_heads"], kv_heads=p["num_key_value_heads"],
        head_dim=p["head_dim"], eps=float(p["layer_norm_epsilon"]),
        kinds=tuple(KINDS[c] for c in p["hybrid_override_pattern"]),
        ssm_heads=hm, ssm_head_dim=p["mamba_head_dim"],
        d_inner=hm * p["mamba_head_dim"], groups=p["n_groups"],
        state=p["ssm_state_size"], conv=p["conv_kernel"],
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


def relu2(x, w_up, w_down, dot):
    return dot(jnp.square(jax.nn.relu(dot(x, w_up))), w_down)


def _at(stack, layer):
    """One layer's leaves of a stacked tree.  The small ones are sliced
    here; a matrix is read inside the loop that multiplies by it
    (``stack[name][layer]`` there): a slice taken out here is a
    loop-invariant copy of its own, and 23 layers' copies of the expert
    stacks do not fit beside the weights (my chip run, PR 42: the
    reference's compile asked for 5.8 GiB of temporaries)."""
    return {name: (leaf if leaf.ndim > 2 else leaf[layer])
            for name, leaf in stack.items()}


def mamba_mixer(u, stack, layer, sizes, n, dot):
    """The Mamba-2 mixer of layer ``layer`` of ``stack`` over the normed
    row ``u`` [T, H] (``n`` real positions; what it returns past them is
    not read).  -> [T, H]"""
    lw = _at(stack, layer)
    t = u.shape[0]
    hm, p, g, ns, k = (sizes["ssm_heads"], sizes["ssm_head_dim"],
                       sizes["groups"], sizes["state"], sizes["conv"])
    di = sizes["d_inner"]
    cw = di + 2 * g * ns
    departure = getattr(dot, "departure", "")

    # -- the input projection, a block of rows at a time ------------------
    def project(i, out):
        return _put(out, dot(_rows(u, i), lw["in_proj"][layer]), i)

    zxbcdt = _blocks(project, jnp.zeros((t, di + cw + hm), jnp.float32), n)
    z, pre, dt = zxbcdt[:, :di], zxbcdt[:, di:di + cw], zxbcdt[:, di + cw:]

    # -- the causal depthwise convolution (zeros before the row) ----------
    w = lw["conv_w"][layer].astype(jnp.float32)
    taps = range(k - 1, k) if departure == "no_conv" else range(k)
    padded = jnp.pad(pre, ((k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(lw["conv_b"].astype(jnp.float32)
                      + sum(w[j] * padded[j:j + t] for j in taps))
    x = xbc[:, :di].reshape(t, hm, p)
    b = jnp.repeat(xbc[:, di:di + g * ns].reshape(t, g, ns), hm // g, axis=1)
    c = jnp.repeat(xbc[:, di + g * ns:].reshape(t, g, ns), hm // g, axis=1)
    delta = jax.nn.softplus(dt + lw["dt_bias"].astype(jnp.float32))
    decay = jnp.exp(-jnp.exp(lw["A_log"].astype(jnp.float32)) * delta)

    # -- the recurrence, one token at a time ------------------------------
    def token(s, per):
        x_t, b_t, c_t, delta_t, decay_t = per       # [Hm, P] [Hm, N] .. [Hm]
        s = (decay_t[:, None, None] * s
             + (delta_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    def recur(i, carry):
        s, y = carry
        s, y_rows = jax.lax.scan(
            token, s, tuple(_rows(v, i) for v in (x, b, c, delta, decay)))
        return s, _put(y, y_rows, i)

    _, y = _blocks(recur, (jnp.zeros((hm, p, ns), jnp.float32),
                           jnp.zeros((t, hm, p), jnp.float32)), n)
    y = y + lw["D"].astype(jnp.float32)[None, :, None] * x

    # -- gate, grouped norm, output projection -----------------------------
    y = y.reshape(t, di) * jax.nn.silu(z)
    if departure != "no_gate_norm":
        grouped = y.reshape(t, g, di // g)
        y = (grouped * jax.lax.rsqrt(jnp.mean(
            grouped * grouped, axis=-1, keepdims=True) + sizes["eps"])
        ).reshape(t, di)
    y = y * lw["norm"].astype(jnp.float32)

    def project_out(i, out):
        return _put(out, dot(_rows(y, i), lw["out_proj"][layer]), i)

    return _blocks(project_out, jnp.zeros_like(u), n)


def attention_mixer(u, stack, layer, sizes, n, dot):
    """Causal grouped-query attention of layer ``layer`` of ``stack``
    over the normed row ``u`` [T, H], no rotary embedding, one query head
    at a time.  -> [T, H]"""
    lw = {name: leaf[layer] for name, leaf in stack.items()}
    t = u.shape[0]
    nh, kh, d = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]

    def keep(i, kept):
        rows = _rows(u, i)
        return tuple(_put(a, dot(rows, w.reshape(-1, kh * d)).reshape(
            ROWS, kh, d), i) for a, w in zip(kept, (lw["wk"], lw["wv"])))

    k, v = _blocks(keep, tuple(jnp.zeros((t, kh, d), jnp.float32)
                               for _ in range(2)), n)
    key_pos = jnp.arange(t)

    def block(i, out):
        rows = _rows(u, i)
        pos = i * ROWS + jnp.arange(ROWS)
        mask = (key_pos[None, :] <= pos[:, None]) & (key_pos[None, :] < n)

        def one_head(acc, hw):
            wq, wo, g = hw                              # [H, D] [D, H] []
            k_g = jax.lax.dynamic_index_in_dim(k, g, axis=1, keepdims=False)
            v_g = jax.lax.dynamic_index_in_dim(v, g, axis=1, keepdims=False)
            scores = dot(dot(rows, wq), k_g.T) * d ** -0.5
            scores = jnp.where(mask, scores, -jnp.inf)
            top = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), -1e30)
            e = jnp.where(mask, jnp.exp(scores - top), 0.0)
            prob = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
            return acc + dot(dot(prob, v_g), wo), None

        attended, _ = jax.lax.scan(
            one_head, jnp.zeros((ROWS, u.shape[1]), jnp.float32),
            (jnp.moveaxis(lw["wq"], 1, 0), lw["wo"],
             jnp.arange(nh) // (nh // kh)))
        return _put(out, attended, i)

    return _blocks(block, jnp.zeros_like(u), n)


def expert_mixer(u, stack, layer, sizes, n, dot):
    """``(shared(u) + the held experts' terms [T, H], routing margin
    [T])`` of layer ``layer`` of ``stack`` over the normed row ``u``, one
    expert at a time."""
    lw = _at(stack, layer)
    t = u.shape[0]
    held = sizes["first_held"] + jnp.arange(sizes["held"])

    def block(i, carry):
        out, margin = carry
        rows = _rows(u, i)
        real = i * ROWS + jnp.arange(ROWS) < n
        sel, w, m = route(jax.nn.sigmoid(dot(rows, lw["router"][layer])),
                          lw["router_bias"], sizes)
        combine = jnp.sum(jnp.where(sel[:, :, None] == held[None, None, :],
                                    w[:, :, None], 0.0), axis=1)
        combine = jnp.where(real[:, None], combine, 0.0)

        def one_expert(e, acc):
            # (e_up lies [out, in], as the source's weight does)
            return acc + combine[:, e, None] * relu2(
                rows, lw["e_up"][layer, e].T, lw["e_down"][layer, e], dot)

        routed = jax.lax.fori_loop(0, sizes["held"], one_expert,
                                   jnp.zeros_like(rows))
        y = relu2(rows, lw["s_up"][layer], lw["s_down"][layer], dot) + routed
        return _put(out, y, i), _put(margin, m, i)

    return _blocks(block, (jnp.zeros_like(u), jnp.full((t,), jnp.inf)), n)


def hidden(weights, sizes, ids, n, dot=_f32_dot):
    """Final-norm hidden states of one row of ``n`` real positions and
    each position's margin.  ids: [S] -> ([S, H], [S])"""
    s = ids.shape[0]
    t = -(-s // ROWS) * ROWS
    x = weights["embed"][jnp.pad(ids, (0, t - s))].astype(jnp.float32)
    real = (jnp.arange(t) < n)[:, None]
    margin = jnp.full((t,), jnp.inf)
    depth = sum(stack["ln"].shape[0] for name, stack in weights.items()
                if name in KINDS.values())
    seen = dict.fromkeys(KINDS.values(), 0)
    for kind in sizes["kinds"][:depth]:
        stack, layer = weights[kind], seen[kind]
        seen[kind] += 1
        u = rmsnorm(x, stack["ln"][layer], sizes["eps"])
        if kind == "mamba":
            out = mamba_mixer(u, stack, layer, sizes, n, dot)
        elif kind == "attention":
            out = attention_mixer(u, stack, layer, sizes, n, dot)
        else:
            out, m = expert_mixer(u, stack, layer, sizes, n, dot)
            margin = jnp.minimum(margin, m)
        # rows past the real positions stay 0: finite, and read by no one
        x = jnp.where(real, x + out, 0.0)
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
