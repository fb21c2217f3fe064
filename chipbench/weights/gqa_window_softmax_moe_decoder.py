"""Seeded weights of the ``gqa_window_softmax_moe_decoder`` family
(Mellum2-12B-A2.5B), made by the benchmark and handed to both sides
(``weights/dense_decoder.py`` says why).

Canonical layout (every per-layer leaf is stacked over layers on axis 0;
matrices are ``[in, out]``; E experts of width F)::

    embed [V, H]   final_norm [H]   head [H, V]
    layers: ln1 ln2 [L, H]   wq [L, H, NH*D]   wk wv [L, H, KH*D]
            wo [L, NH*D, H]  q_norm k_norm [L, D]   router [L, H, E]
            e_gate e_up [L, E, H, F]   e_down [L, E, F, H]

Every (leaf, layer) has its own key and every leaf can be made alone
(``make_leaf``), bit-identical to the whole tree's.  Matrices are normal
with std 0.02, norm scales 1.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.weights.dense_decoder import base_key  # noqa: F401

_TOP = ("embed", "final_norm", "head")
_LAYER = ("ln1", "ln2", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
          "router", "e_gate", "e_up", "e_down")
_ORDER = _TOP + _LAYER


def shapes(published: dict) -> dict:
    """Shape of each canonical leaf for ONE layer (top leaves whole)."""
    h, v = published["hidden_size"], published["vocab_size"]
    nh, kh = (published["num_attention_heads"],
              published["num_key_value_heads"])
    d = published["head_dim"]
    e, f = published["num_experts"], published["moe_intermediate_size"]
    return {"embed": (v, h), "final_norm": (h,), "head": (h, v),
            "ln1": (h,), "ln2": (h,), "wq": (h, nh * d), "wk": (h, kh * d),
            "wv": (h, kh * d), "wo": (nh * d, h), "q_norm": (d,),
            "k_norm": (d,), "router": (h, e), "e_gate": (e, h, f),
            "e_up": (e, h, f), "e_down": (e, f, h)}


def make_leaf(key, published, depth, name, dtype=jnp.float32, std=0.02):
    """One canonical leaf alone ('embed', or 'layers.wq' stacked over
    the depth): bit-identical to the same leaf of ``make``.  A leaf's
    key is its place in ``_ORDER``, a layer's its index from 1, drawn
    under ``vmap`` (``weights/dense_decoder._stacked`` says why)."""
    name = name.split(".", 1)[-1]
    shape = shapes(published)[name]
    lead = () if name in _TOP else (depth,)
    if len(shape) == 1:
        return jnp.ones(lead + shape, dtype)
    base = jax.random.fold_in(key, _ORDER.index(name))
    draw = lambda k: (jax.random.normal(k, shape, jnp.float32)  # noqa: E731
                      * std).astype(dtype)
    if not lead:
        return draw(base)
    return jax.vmap(lambda i: draw(jax.random.fold_in(base, i)))(
        jnp.arange(1, depth + 1))


def make(key, published, depth, dtype=jnp.float32, std=0.02):
    """The whole canonical tree (traceable: call under ``jax.jit``)."""
    out = {n: make_leaf(key, published, depth, n, dtype, std) for n in _TOP}
    out["layers"] = {n: make_leaf(key, published, depth, n, dtype, std)
                     for n in _LAYER}
    return out


def param_count(published: dict, depth: int) -> int:
    sh = shapes(published)
    size = lambda n: math.prod(sh[n])  # noqa: E731
    return (sum(size(n) for n in _TOP) + depth * sum(size(n) for n in _LAYER))
