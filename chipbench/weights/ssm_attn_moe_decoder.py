"""Seeded weights of the ``ssm_attn_moe_decoder`` family (layers of ONE
mixer each under a pre-norm: Mamba-2 state-space mixers, routed relu2
experts with a shared expert, grouped-query attention without rotary
embedding, in the order ``hybrid_override_pattern`` gives), made by the
benchmark and handed to both sides like the other families'.

Canonical layout.  The leaves have the shapes the program's parameter
tree has (``layouts/ssm_attn_moe_decoder.py`` moves no byte but the held
experts': below): one stack a KIND of layer over the layers of that kind, in the pattern's order::

    embed [V, H]   final_norm [H]   head [H, V]
    mamba:      ln [L, H]   in_proj [L, H, 2 d_inner + 2 G N + Hm]
                conv_w [L, K, d_inner + 2 G N]   conv_b [L, d_inner + 2 G N]
                dt_bias  A_log  D [L, Hm]   norm [L, d_inner]
                out_proj [L, d_inner, H]
    moe:        ln [L, H]   router [L, H, E_router]   router_bias [L, E_router]
                e_up [L, E_held, Fm, H]   e_down [L, E_held, Fm, H]
                s_up [L, H, Fs]   s_down [L, Fs, H]
    attention:  ln [L, H]   wq [L, H, NH, D]   wk wv [L, H, KH, D]
                wo [L, NH, D, H]

``e_up`` lies as the source's own ``nn.Linear`` weight does, [out, in]:
a TPU keeps an array whose last dimension is not whole 128-lane tiles
(1856) with its last two dimensions swapped, and the reference's slice
of one expert out of a [.., 2688, 1856] stack then copied the whole
stack first (sandbox compile, PR 42: 3.5 GiB beside 10.9 GiB of
weights); [.., 1856, 2688] is read where it lies.

Every (leaf, layer) has its own key, every leaf can be made alone
(``make_leaf``).  Matrices are normal with std 0.02, norm scales and the
skip ``D`` are 1; what is drawn otherwise, and why:

- ``A_log = log(U)``, ``U`` uniform in [1, 16] a head, and ``dt_bias``
  the inverse softplus of a step drawn log-uniform in [``time_step_min``,
  ``time_step_max``] (0.001 .. 0.1): the published initialisation of the
  mixer.  A head's decay a token, ``exp(-delta U)``, then spans 0.999
  (a memory of a thousand tokens) to 0.2 (a handful), so a recurrence
  that forgets too fast or too slowly, or a state that is reset or
  carried where it should not be, moves the logits — at a constant
  ``A_log`` and ``dt_bias`` every head would forget within ten tokens
  and no chunk boundary would matter.
- ``conv_w`` has std 0.5 (four taps: the convolution's output keeps its
  input's scale, so every tap matters), ``conv_b`` std 0.1.
- ``e_down``, ``s_down`` and ``out_proj`` have NO MEAN OVER THEIR INPUTS
  (each output's column of weights sums to zero).  What they multiply
  has a positive mean — ``relu(u)**2`` 0.41 of its rms, the gated
  ``silu`` channels a tenth — and a random projection turns that mean
  into ONE vector added to every token of every request.  After six
  expert layers 17% of the router input's power was common to all
  tokens, the busiest of the 128 experts drew 4-7 times the mean, the
  16 held here 194-354 pairs a chunk where 288 are due, and a decode
  step hit 62% of them or 88% by the seed (sandbox CPU, the reference at
  the published widths over 12 layers, PR 42): on the chip six seeds
  ran in two modes 1% apart (`serve_tokens_per_s` 857.6 and 865.5-869.7;
  my chip runs, PR 42, set A before this).  A trained model balances its
  router with the correction bias; seeded weights remove the term at its
  source: the common share stays at 1.5%, the busiest expert at 2.0
  times the mean (chance, at 18 pairs an expert a chunk), the hit share
  at 82-91%.
- ``router`` has std ``1 / sqrt(hidden_size)`` (0.0193 at 2688: its input
  is the normed residual, rms 1, so the logits have std 1 at any width
  — the rehearsal's toy hidden of 128 would give 0.23 at 0.02 and the
  bias would decide everything) and ``router_bias`` std 0.005: it
  decides choices between near-tied experts (the selection scores near
  the threshold lie ~0.01 apart) and not the load (PR 33's finding).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.weights.dense_decoder import base_key  # noqa: F401

_TOP = ("embed", "final_norm", "head")
_LEAVES = {
    "mamba": ("ln", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
              "norm", "out_proj"),
    "moe": ("ln", "router", "router_bias", "e_up", "e_down", "s_up",
            "s_down"),
    "attention": ("ln", "wq", "wk", "wv", "wo")}
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
_ONES = ("ln", "norm", "D", "final_norm")
# the projections that follow an activation of positive mean: drawn with
# no mean over their inputs (the module docstring says why)
_NO_COMMON_TERM = ("e_down", "s_down", "out_proj")


def kinds(published: dict, depth: int):
    """The kind of each of the first ``depth`` layers."""
    pattern = published["hybrid_override_pattern"]
    if depth > len(pattern):
        raise SystemExit(f"chipbench: depth {depth} is past the "
                         f"{len(pattern)} layers of the pattern")
    return [KINDS[c] for c in pattern[:depth]]


def stacks(published: dict, depth: int) -> dict:
    """stack name -> (leaf names, layers in it), the kinds the cut model
    has, in the order of their first layer."""
    ks = kinds(published, depth)
    return {k: (_LEAVES[k], ks.count(k)) for k in dict.fromkeys(ks)}


def shapes(published: dict, depth: int) -> dict:
    """Shape of each canonical leaf for ONE layer (top leaves whole),
    keyed 'embed' or '<stack>.<leaf>'."""
    p = published
    h, v = p["hidden_size"], p["vocab_size"]
    nh, kh, d = (p["num_attention_heads"], p["num_key_value_heads"],
                 p["head_dim"])
    hm, n, g = p["mamba_num_heads"], p["ssm_state_size"], p["n_groups"]
    di = hm * p["mamba_head_dim"]
    cw = di + 2 * g * n
    fm, fs = p["moe_intermediate_size"], p["moe_shared_expert_intermediate_size"]
    held = p["n_routed_experts"]
    width = p.get("router_n_experts", held)
    per_kind = {
        "mamba": dict(ln=(h,), in_proj=(h, di + cw + hm),
                      conv_w=(p["conv_kernel"], cw), conv_b=(cw,),
                      dt_bias=(hm,), A_log=(hm,), D=(hm,), norm=(di,),
                      out_proj=(di, h)),
        "moe": dict(ln=(h,), router=(h, width), router_bias=(width,),
                    e_up=(held, fm, h), e_down=(held, fm, h),
                    s_up=(h, fs), s_down=(fs, h)),
        "attention": dict(ln=(h,), wq=(h, nh, d), wk=(h, kh, d),
                          wv=(h, kh, d), wo=(nh, d, h))}
    out = {"embed": (v, h), "final_norm": (h,), "head": (h, v)}
    for stack, (names, _) in stacks(p, depth).items():
        out.update({f"{stack}.{n_}": per_kind[stack][n_] for n_ in names})
    return out


def order(published: dict, depth: int):
    """Every leaf name, in the order that fixes each leaf's key (the
    kinds in a fixed order, so a leaf's key does not move with the cut)."""
    return _TOP + tuple(f"{s}.{n}" for s in _LEAVES for n in _LEAVES[s])


def _draw(key, leaf, shape, published, std):
    """One layer's values of a drawn leaf, float32."""
    p = published
    if leaf == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if leaf == "dt_bias":
        lo, hi = math.log(p["time_step_min"]), math.log(p["time_step_max"])
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, lo, hi)), p["time_step_floor"])
        return step + jnp.log(-jnp.expm1(-step))       # softplus^-1
    std = {"conv_w": 0.5, "conv_b": 0.1, "router_bias": std * 0.25,
           "router": 1.0 / math.sqrt(p["hidden_size"])}.get(leaf, std)
    drawn = jax.random.normal(key, shape, jnp.float32) * std
    if leaf in _NO_COMMON_TERM:
        drawn = drawn - jnp.mean(drawn, axis=-2, keepdims=True)
    return drawn


def make_leaf(key, published, depth, name, dtype=jnp.float32, std=0.02):
    """One canonical leaf alone: bit-identical to the same leaf of
    ``make``."""
    sh = shapes(published, depth)[name]
    layers, leaf = (), name
    if name not in _TOP:
        stack, leaf = name.split(".", 1)
        layers = (stacks(published, depth)[stack][1],)
    if leaf in _ONES:
        return jnp.ones(layers + sh, dtype)
    key = jax.random.fold_in(key, order(published, depth).index(name))
    draw = lambda k: _draw(k, leaf, sh, published, std).astype(  # noqa: E731
        dtype)
    if not layers:
        return draw(key)
    # a leaf whose mean is taken has to exist whole in float32 before it
    # is rounded: one layer at a time (all 23 layers of e_down at once
    # are 7 GiB beside the 11 GiB being made; my chip run, PR 42)
    over = jax.lax.map if leaf in _NO_COMMON_TERM else (
        lambda f, xs: jax.vmap(f)(xs))
    return over(lambda i: draw(jax.random.fold_in(key, i)),
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
