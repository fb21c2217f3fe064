"""Seeded weights, made by the benchmark and handed to both sides.

The program gets them through ``chipbench.program`` (re-laid into its
parameter tree), the reference reads them as they are.  Neither side
makes a weight: a fault in the program's own initialiser cannot hide.

Canonical layout of the dense decoder family (every per-layer leaf is
stacked over layers on axis 0; matrices are ``[in, out]``)::

    embed [V, H]   final_norm [H]   head [H, V]
    layers: ln1 ln2 [L, H]   wq [L, H, NH*D]   wk wv [L, H, KH*D]
            wo [L, NH*D, H]  w_gate w_up [L, H, F]   w_down [L, F, H]
            q_norm [L, NH*D]  k_norm [L, KH*D]      (qk-norm models only)

Every (leaf, layer) has its own key and every leaf can be made alone
(``make_leaf``), bit-identical to the whole tree's.  Matrices are normal
with the source's ``initializer_range`` (0.02), norm scales 1.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_TOP = ("embed", "final_norm", "head")
_LAYER = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
          "q_norm", "k_norm")
_ORDER = _TOP + _LAYER


def shapes(published: dict) -> dict:
    """Shape of each canonical leaf for ONE layer (top leaves whole)."""
    h, f, v = (published["hidden_size"], published["intermediate_size"],
               published["vocab_size"])
    nh = published["num_attention_heads"]
    kh = published.get("num_key_value_heads") or nh
    d = published.get("head_dim") or h // nh
    out = {"embed": (v, h), "final_norm": (h,), "head": (h, v),
           "ln1": (h,), "ln2": (h,), "wq": (h, nh * d), "wk": (h, kh * d),
           "wv": (h, kh * d), "wo": (nh * d, h), "w_gate": (h, f),
           "w_up": (h, f), "w_down": (f, h)}
    if published["model_type"] == "olmo2":
        out.update(q_norm=(nh * d,), k_norm=(kh * d,))
    return out


def base_key(seed: int):
    """A key from any whole number up to 2**63 (the driver's are large)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def _top(key, name, shape, dtype, std):
    if len(shape) == 1:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, _ORDER.index(name))
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def _stacked(key, name, depth, shape, dtype, std):
    """A per-layer leaf for every layer, [depth, *shape]: one key a
    layer, drawn under ``vmap`` (a python ``stack`` of separately drawn
    layers sends the TPU compiler into minutes of work; PR 23)."""
    if len(shape) == 1:
        return jnp.ones((depth,) + shape, dtype)
    base = jax.random.fold_in(key, _ORDER.index(name))
    keys = jax.vmap(lambda i: jax.random.fold_in(base, i))(
        jnp.arange(1, depth + 1))
    return jax.vmap(lambda k: (jax.random.normal(k, shape, jnp.float32)
                               * std).astype(dtype))(keys)


def make_leaf(key, published, depth, name, dtype=jnp.float32, std=0.02):
    """One canonical leaf alone ('embed', or 'layers.wq' stacked over
    the depth): bit-identical to the same leaf of ``make``."""
    sh = shapes(published)
    if name in _TOP:
        return _top(key, name, sh[name], dtype, std)
    name = name.split(".", 1)[1]
    return _stacked(key, name, depth, sh[name], dtype, std)


def make(key, published, depth, dtype=jnp.float32, std=0.02):
    """The whole canonical tree (traceable: call under ``jax.jit``)."""
    sh = shapes(published)
    out = {n: _top(key, n, sh[n], dtype, std) for n in _TOP}
    out["layers"] = {n: _stacked(key, n, depth, sh[n], dtype, std)
                     for n in _LAYER if n in sh}
    return out


def param_count(published: dict, depth: int) -> int:
    sh = shapes(published)
    size = lambda n: math.prod(sh[n])  # noqa: E731
    return (sum(size(n) for n in _TOP)
            + depth * sum(size(n) for n in _LAYER if n in sh))
