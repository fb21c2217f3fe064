"""Seeded weights of the ``gqa_window_moe_decoder`` family (grouped-query
attention with per-head qk-norm, windowed and full layers under one
pattern, a leading dense layer, then expert layers with a shared
expert), made by the benchmark and handed to both sides like the other
families'.

Canonical layout.  The leaves have the shapes the program's parameter
tree has (``layouts/gqa_window_moe_decoder.py`` moves no byte).  The
layers after the ``Ld`` dense ones repeat a PERIOD of the source's
``layer_types`` — the shortest one that divides them, all of them where
none does (depth 8: the dense sliding layer, then ONE period of seven,
``s s g s s s g``) — and every position of the period is a stack of its
own over the ``P`` periods, so each stack's leaves keep one shape::

    embed [V, H]   final_norm [H]   head [H, V]
    every stack:  ln1 ln2 [L, H]   (the norms on the sublayers' OUTPUTS)
                  wq [L, H, NH, D]   wk wv [L, H, KH, D]
                  q_norm k_norm [L, D]   (RMSNorm over each head)
                  wo [L, NH, D, H]
    dense:        w_gate w_up [Ld, H, F]   w_down [Ld, F, H]
    p0 .. :       router [P, H, E_router]   router_bias [P, E_router]
                  e_gate e_up [P, E_held, H, Fm]   e_down [P, E_held, Fm, H]
                  s_gate s_up [P, H, Fs]   s_down [P, Fs, H]

A sliding and a global layer hold the same leaves: the kinds differ in
what they compute (window, rope), not in what they own.  Every (leaf,
layer) has its own key, every leaf can be made alone (``make_leaf``);
matrices are normal with std 0.02 and norm scales 1, but for three
leaves whose seeded scale decides how the tokens ROUTE — and with that
how much work a seed's window holds (my chip runs, PR 33: at 0.02 / 1
throughout, six seeds' ``serve_tokens_per_s`` spread by 2.6%):

- ``q_norm`` / ``k_norm`` scales are 2: scores ``q . k / sqrt(D)`` of
  unit-norm heads have std 1, so every query averages its whole context;
  the norm on the attention's OUTPUT then blows that average — the same
  for every token of a long request — up to unit rms, the tokens' router
  inputs share it, and a request's tokens all choose the same experts
  (a share of 1% of common variance already doubles the busiest
  expert's load).  At scale 2 the scores have std 4 and a query attends
  a handful of positions, as a trained head does: what it reads is its
  own.
- the ``router`` matrix has std ``0.55 / sqrt(hidden_size)`` (0.007 at
  6144): its input is the residual stream itself (the norms sit on the
  sublayers' outputs), whose rms grows from 1.7 to 3.9 over the expert
  layers; at 0.02 the scores saturate (logits of std 2.7-6) and the bias
  decides everything.  This keeps the logits' std between 1 and 2.2 at
  any width.
- ``router_bias`` has std 0.005: it decides choices between near-tied
  experts (the selection scores near the threshold lie ~0.01 apart) and
  not the load (at 0.02 the busiest expert draws 1.8-4.7 times the mean).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.weights.dense_decoder import base_key  # noqa: F401
from chipbench.weights.mla_sparse_window_moe_decoder import (  # noqa: F401
    structure,
)

_TOP = ("embed", "final_norm", "head")
_ATTN = ("ln1", "ln2", "wq", "wk", "wv", "q_norm", "k_norm", "wo")
_MLP = ("w_gate", "w_up", "w_down")
_MOE = ("router", "router_bias", "e_gate", "e_up", "e_down", "s_gate",
        "s_up", "s_down")
_DRAWN_VECTORS = ("router_bias",)
# a leaf's constant value, or its std as a share of the matrices' 0.02
# (the router's: 27.5 / sqrt(hidden_size), see above)
_CONSTANT = {"q_norm": 2.0, "k_norm": 2.0}
_BIAS_STD_SHARE = 0.25
_ROUTER_STD_SHARE = 27.5


def stacks(published: dict, depth: int) -> dict:
    """stack name -> (leaf names, layers in it)."""
    dense, period, n = structure(published, depth)
    out = {"dense": (_ATTN + _MLP, len(dense))}
    for i in range(len(period)):
        out[f"p{i}"] = (_ATTN + _MOE, n)
    return out


def shapes(published: dict, depth: int) -> dict:
    """Shape of each canonical leaf for ONE layer (top leaves whole),
    keyed 'embed' or '<stack>.<leaf>'."""
    p = published
    h, v, f = p["hidden_size"], p["vocab_size"], p["intermediate_size"]
    nh = p["num_attention_heads"]
    kh = p.get("num_key_value_heads") or nh
    d = p.get("head_dim") or h // nh
    fm = p["moe_intermediate_size"]
    fs = fm * p["num_shared_experts"]
    held = p["num_experts"]
    width = p.get("router_n_experts", held)
    attn = dict(ln1=(h,), ln2=(h,), wq=(h, nh, d), wk=(h, kh, d),
                wv=(h, kh, d), q_norm=(d,), k_norm=(d,), wo=(nh, d, h))
    mlp = dict(w_gate=(h, f), w_up=(h, f), w_down=(f, h))
    moe = dict(router=(h, width), router_bias=(width,),
               e_gate=(held, h, fm), e_up=(held, h, fm),
               e_down=(held, fm, h), s_gate=(h, fs), s_up=(h, fs),
               s_down=(fs, h))
    out = {"embed": (v, h), "final_norm": (h,), "head": (h, v)}
    for stack, (names, _) in stacks(p, depth).items():
        leaves = {**attn, **(mlp if stack == "dense" else moe)}
        out.update({f"{stack}.{n}": leaves[n] for n in names})
    return out


def order(published: dict, depth: int):
    """Every leaf name, in the order that fixes each leaf's key."""
    return _TOP + tuple(f"{s}.{n}" for s, (names, _) in
                        stacks(published, depth).items() for n in names)


def make_leaf(key, published, depth, name, dtype=jnp.float32, std=0.02):
    """One canonical leaf alone: bit-identical to the same leaf of
    ``make``."""
    sh = shapes(published, depth)[name]
    layers, leaf = (), name
    if name not in _TOP:
        stack, leaf = name.split(".", 1)
        layers = (stacks(published, depth)[stack][1],)
    if len(sh) == 1 and leaf not in _DRAWN_VECTORS:
        return jnp.full(layers + sh, _CONSTANT.get(leaf, 1.0), dtype)
    key = jax.random.fold_in(key, order(published, depth).index(name))
    if leaf == "router":
        std = std * _ROUTER_STD_SHARE / math.sqrt(published["hidden_size"])
    elif leaf == "router_bias":
        std = std * _BIAS_STD_SHARE
    draw = lambda k: (jax.random.normal(k, sh, jnp.float32)  # noqa: E731
                      * std).astype(dtype)
    if not layers:
        return draw(key)
    return jax.vmap(lambda i: draw(jax.random.fold_in(key, i)))(
        jnp.arange(1, layers[0] + 1))


def make(key, published, depth, dtype=jnp.float32, std=0.02):
    """The whole canonical tree (traceable: call under ``jax.jit``)."""
    out = {n: make_leaf(key, published, depth, n, dtype, std) for n in _TOP}
    for stack, (names, _) in stacks(published, depth).items():
        out[stack] = {n: make_leaf(key, published, depth, f"{stack}.{n}",
                                   dtype, std) for n in names}
    return out


def param_count(published: dict, depth: int) -> int:
    sh = shapes(published, depth)
    return (sum(math.prod(sh[n]) for n in _TOP)
            + sum(layers * sum(math.prod(sh[f"{s}.{n}"]) for n in names)
                  for s, (names, layers) in
                  stacks(published, depth).items()))
