"""Seeded weights of the ``mla_moe_decoder`` family (latent attention,
leading dense layers, then expert layers with a shared expert), made by
the benchmark and handed to both sides like the dense family's.

Canonical layout.  The leaves have the shapes the program's parameter
tree has, so that ``layouts/mla_moe_decoder.py`` moves no byte (11 GB of
bf16 get no second copy under the driver's one ``jit``); the reference
reshapes what it reads.  Two stacks, each stacked over its layers on
axis 0 (``Ld`` leading dense layers, ``Lm`` expert layers)::

    embed [V, H]   final_norm [H]   head [H, V]
    both stacks:  ln1 ln2 [L, H]
                  wq_a [L, H, Q]  q_norm [L, Q]  wq_b [L, Q, NH, nope+rope]
                  wkv_a [L, H, R+rope]  kv_norm [L, R]
                  wkv_b_k [L, R, NH, nope]  wkv_b_v [L, R, NH, v]
                  wo [L, NH, v, H]
    dense:        w_gate w_up [Ld, H, F]   w_down [Ld, F, H]
    moe:          router [Lm, H, E_router]
                  e_gate e_up [Lm, E_held, H, Fm]   e_down [Lm, E_held, Fm, H]
                  s_gate s_up [Lm, H, Fs]   s_down [Lm, Fs, H]

``E_held`` (``n_routed_experts`` of the configuration) experts are held
of the router's ``E_router`` (``router_n_experts``): the chip's share of
an expert-parallel deployment.  The absent experts have no weights here
or anywhere.  Every (leaf, layer) has its own key, every leaf can be
made alone (``make_leaf``); matrices are normal with std 0.02, norm
scales 1.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.weights.dense_decoder import base_key  # noqa: F401

_TOP = ("embed", "final_norm", "head")
_ATTN = ("ln1", "ln2", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
         "wkv_b_k", "wkv_b_v", "wo")
_DENSE = _ATTN + ("w_gate", "w_up", "w_down")
_MOE = _ATTN + ("router", "e_gate", "e_up", "e_down", "s_gate", "s_up",
                "s_down")
_STACKS = {"dense": _DENSE, "moe": _MOE}


def depths(published: dict, depth: int):
    """(leading dense layers, expert layers) of a model cut to ``depth``."""
    n_dense = int(published["first_k_dense_replace"])
    if not 0 < n_dense < depth:
        raise SystemExit(f"chipbench: depth {depth} leaves no expert layer "
                         f"after {n_dense} dense one(s)")
    return n_dense, depth - n_dense


def shapes(published: dict) -> dict:
    """Shape of each canonical leaf for ONE layer (top leaves whole),
    keyed 'embed' or '<stack>.<leaf>'."""
    p = published
    h, v, f = p["hidden_size"], p["vocab_size"], p["intermediate_size"]
    nh, q, r = p["num_attention_heads"], p["q_lora_rank"], p["kv_lora_rank"]
    nope, rope, vd = (p["qk_nope_head_dim"], p["qk_rope_head_dim"],
                      p["v_head_dim"])
    fm = p["moe_intermediate_size"]
    fs = fm * p["n_shared_experts"]
    held = p["n_routed_experts"]
    attn = {"ln1": (h,), "ln2": (h,), "wq_a": (h, q), "q_norm": (q,),
            "wq_b": (q, nh, nope + rope), "wkv_a": (h, r + rope),
            "kv_norm": (r,), "wkv_b_k": (r, nh, nope),
            "wkv_b_v": (r, nh, vd), "wo": (nh, vd, h)}
    dense = dict(attn, w_gate=(h, f), w_up=(h, f), w_down=(f, h))
    moe = dict(attn, router=(h, p.get("router_n_experts", held)),
               e_gate=(held, h, fm), e_up=(held, h, fm),
               e_down=(held, fm, h), s_gate=(h, fs), s_up=(h, fs),
               s_down=(fs, h))
    out = {"embed": (v, h), "final_norm": (h,), "head": (h, v)}
    out.update({f"dense.{k}": s for k, s in dense.items()})
    out.update({f"moe.{k}": s for k, s in moe.items()})
    return out


_ORDER = _TOP + tuple(f"{s}.{n}" for s, names in _STACKS.items()
                      for n in names)


def make_leaf(key, published, depth, name, dtype=jnp.float32, std=0.02):
    """One canonical leaf alone ('embed', 'moe.e_gate' stacked over the
    expert layers): bit-identical to the same leaf of ``make``."""
    sh = shapes(published)[name]
    layers = ()
    if name not in _TOP:
        n_dense, n_moe = depths(published, depth)
        layers = (n_dense if name.startswith("dense.") else n_moe,)
    if len(sh) == 1:
        return jnp.ones(layers + sh, dtype)
    key = jax.random.fold_in(key, _ORDER.index(name))
    draw = lambda k: (jax.random.normal(k, sh, jnp.float32)  # noqa: E731
                      * std).astype(dtype)
    if not layers:
        return draw(key)
    # one key a layer, drawn under vmap (a python stack of separately
    # drawn layers costs the TPU compiler minutes; PR 23)
    return jax.vmap(lambda i: draw(jax.random.fold_in(key, i)))(
        jnp.arange(1, layers[0] + 1))


def make(key, published, depth, dtype=jnp.float32, std=0.02):
    """The whole canonical tree (traceable: call under ``jax.jit``)."""
    out = {n: make_leaf(key, published, depth, n, dtype, std) for n in _TOP}
    for stack, names in _STACKS.items():
        out[stack] = {n: make_leaf(key, published, depth, f"{stack}.{n}",
                                   dtype, std) for n in names}
    return out


def param_count(published: dict, depth: int) -> int:
    sh = shapes(published)
    n_dense, n_moe = depths(published, depth)
    size = lambda n: math.prod(sh[n])  # noqa: E731
    return (sum(size(n) for n in _TOP)
            + n_dense * sum(size(f"dense.{n}") for n in _DENSE)
            + n_moe * sum(size(f"moe.{n}") for n in _MOE))
