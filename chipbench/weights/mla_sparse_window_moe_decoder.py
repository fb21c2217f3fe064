"""Seeded weights of the ``mla_sparse_window_moe_decoder`` family (two
kinds of latent attention layer under one pattern, a leading dense
layer, then expert layers with a shared expert), made by the benchmark
and handed to both sides like the other families'.

Canonical layout.  The leaves have the shapes the program's parameter
tree has (``layouts/mla_sparse_window_moe_decoder.py`` moves no byte).
The layers after the ``Ld`` dense ones repeat a PERIOD of the source's
``layer_types`` (``full, sliding, sliding, sliding``); every position of
the period is a stack of its own over the ``P`` periods, so each stack's
leaves keep one shape::

    embed [V, H]   final_norm [H]   head [H, V]
    every stack:  ln1 ln2 [L, H]
                  wq_a [L, H, Q]  q_norm [L, Q]  wq_b [L, Q, NH, nope+rope]
                  wkv_a [L, H, R+rope]  kv_norm [L, R]
                  wkv_b_k [L, R, NH, nope]  wkv_b_v [L, R, NH, v]
                  wo [L, NH, v, H]   w_og [L, H, NH] (the headwise gate)
                  -- NH, Q, R, nope, rope, v are the layer kind's own
                  (``swa_*`` keys for a sliding layer)
    full layers:  wi_q [L, Q, nI, dI]  wi_k [L, H, dI]  ik_scale ik_bias
                  [L, dI]  wi_w [L, H, nI]        (the indexer)
    dense:        w_gate w_up [Ld, H, F]   w_down [Ld, F, H]
    p0 .. p3:     router [P, H, E_router]   router_bias [P, E_router]
                  e_gate e_up [P, E_held, H, Fm]   e_down [P, E_held, Fm, H]
                  s_gate s_up [P, H, Fs]   s_down [P, Fs, H]

Every (leaf, layer) has its own key, every leaf can be made alone
(``make_leaf``); matrices are normal with std 0.02, the router's
selection bias too (a trained ``noaux_tc`` bias is of that order), norm
scales 1, the index key's LayerNorm bias 0.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.weights.dense_decoder import base_key  # noqa: F401

_TOP = ("embed", "final_norm", "head")
_ATTN = ("ln1", "ln2", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
         "wkv_b_k", "wkv_b_v", "wo", "w_og")
_INDEXER = ("wi_q", "wi_k", "ik_scale", "ik_bias", "wi_w")
_MLP = ("w_gate", "w_up", "w_down")
_MOE = ("router", "router_bias", "e_gate", "e_up", "e_down", "s_gate",
        "s_up", "s_down")
_ZEROS = ("ik_bias",)
_DRAWN_VECTORS = ("router_bias",)


def structure(published: dict, depth: int):
    """``(kinds of the dense layers, kinds of one period, periods)`` of
    the model cut to ``depth``: 'full' | 'sliding' a layer."""
    kinds = ["sliding" if t == "sliding_attention" else "full"
             for t in published["layer_types"][:depth]]
    n_dense = int(published["first_k_dense_replace"])
    rest = kinds[n_dense:]
    if len(kinds) < depth or not rest:
        raise SystemExit(f"chipbench: depth {depth} does not fit "
                         f"layer_types / leaves no expert layer")
    for n in range(1, len(rest) + 1):
        if len(rest) % n == 0 and rest == rest[:n] * (len(rest) // n):
            return kinds[:n_dense], rest[:n], len(rest) // n
    raise AssertionError


def stacks(published: dict, depth: int) -> dict:
    """stack name -> (leaf names, layers in it)."""
    dense, period, n = structure(published, depth)
    if "sliding" in dense:
        raise SystemExit("chipbench: a sliding dense layer is not written")
    attn = lambda kind: _ATTN + (  # noqa: E731
        _INDEXER if kind == "full" else ())
    out = {"dense": (attn("full") + _MLP, len(dense))}
    for i, kind in enumerate(period):
        out[f"p{i}"] = (attn(kind) + _MOE, n)
    return out


def _attn_shapes(p: dict, kind: str) -> dict:
    h = p["hidden_size"]
    pre = "swa_" if kind == "sliding" else ""
    nh = p[pre + "num_attention_heads"]
    q, r = p[pre + "q_lora_rank"], p[pre + "kv_lora_rank"]
    nope, rope, vd = (p[pre + "qk_nope_head_dim"],
                      p[pre + "qk_rope_head_dim"], p[pre + "v_head_dim"])
    out = {"ln1": (h,), "ln2": (h,), "wq_a": (h, q), "q_norm": (q,),
           "wq_b": (q, nh, nope + rope), "wkv_a": (h, r + rope),
           "kv_norm": (r,), "wkv_b_k": (r, nh, nope),
           "wkv_b_v": (r, nh, vd), "wo": (nh, vd, h), "w_og": (h, nh)}
    if kind == "full":
        ni, di = p["index_n_heads"], p["index_head_dim"]
        out.update(wi_q=(q, ni, di), wi_k=(h, di), ik_scale=(di,),
                   ik_bias=(di,), wi_w=(h, ni))
    return out


def shapes(published: dict, depth: int) -> dict:
    """Shape of each canonical leaf for ONE layer (top leaves whole),
    keyed 'embed' or '<stack>.<leaf>'."""
    p = published
    h, v, f = p["hidden_size"], p["vocab_size"], p["intermediate_size"]
    fm = p["moe_intermediate_size"]
    fs = fm * p["n_shared_experts"]
    held = p["n_routed_experts"]
    width = p.get("router_n_experts", held)
    dense, period, _ = structure(p, depth)
    mlp = dict(w_gate=(h, f), w_up=(h, f), w_down=(f, h))
    moe = dict(router=(h, width), router_bias=(width,),
               e_gate=(held, h, fm), e_up=(held, h, fm),
               e_down=(held, fm, h), s_gate=(h, fs), s_up=(h, fs),
               s_down=(fs, h))
    out = {"embed": (v, h), "final_norm": (h,), "head": (h, v)}
    out.update({f"dense.{k}": s for k, s in
                {**_attn_shapes(p, "full"), **mlp}.items()})
    for i, kind in enumerate(period):
        out.update({f"p{i}.{k}": s for k, s in
                    {**_attn_shapes(p, kind), **moe}.items()})
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
    if leaf in _ZEROS:
        return jnp.zeros(layers + sh, dtype)
    if len(sh) == 1 and leaf not in _DRAWN_VECTORS:
        return jnp.ones(layers + sh, dtype)
    key = jax.random.fold_in(key, order(published, depth).index(name))
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
