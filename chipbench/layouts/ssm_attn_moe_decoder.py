"""Layout ``ssm_attn_moe_decoder``: where each canonical leaf of
``chipbench/weights/ssm_attn_moe_decoder.py`` sits in the program's
parameter tree.  The canonical shapes ARE the program's (one stacked
tree a kind of mixer under ``layers``: ``layers/mamba``, ``layers/moe``,
``layers/attention``) but for the held experts' two stacks: the program
multiplies by ``e_up`` as [in, out] (the canonical leaf lies [out, in])
and stores both at whole 128-lane tiles of the experts' width
(``models/moe.stored_expert_width``: 1856 -> 1920, zero columns of
``e_up`` and zero rows of ``e_down``, which add nothing to any product);
no other leaf is reshaped or copied."""

from __future__ import annotations

import jax.numpy as jnp

_TOP = {"embed": ("embed_tokens", "embedding"),
        "final_norm": ("final_norm", "scale"),
        "head": ("lm_head", "kernel")}
_LEAF = {"ln": ("ln", "scale"),
         "in_proj": ("mixer", "in_proj", "kernel"),
         "conv_w": ("mixer", "conv", "kernel"),
         "conv_b": ("mixer", "conv", "bias"),
         "dt_bias": ("mixer", "dt_bias"), "A_log": ("mixer", "A_log"),
         "D": ("mixer", "D"), "norm": ("mixer", "norm", "scale"),
         "out_proj": ("mixer", "out_proj", "kernel"),
         "router": ("moe", "router", "kernel"),
         "router_bias": ("moe", "router_bias"),
         # the program names its stacked expert kernels with a slash
         "e_up": ("moe", "experts/up"), "e_down": ("moe", "experts/down"),
         "s_up": ("moe", "shared", "up_proj", "kernel"),
         "s_down": ("moe", "shared", "down_proj", "kernel"),
         "wq": ("attn", "q_proj", "kernel"),
         "wk": ("attn", "k_proj", "kernel"),
         "wv": ("attn", "v_proj", "kernel"),
         "wo": ("attn", "o_proj", "kernel")}


def _path(name):
    if name in _TOP:
        return _TOP[name]
    stack, leaf = name.split(".", 1)
    return ("layers", stack, "block") + _LEAF[leaf]


def _stored(name: str, value):
    """The leaf as the program stores it: the experts' width padded to
    whole tiles (the last axis of ``e_up``, the one before it of
    ``e_down``)."""
    from torchacc_tpu.models.moe import stored_expert_width
    axis = {"moe.e_up": -1, "moe.e_down": -2}.get(name)
    if axis is None:
        return value
    if name == "moe.e_up":
        value = jnp.swapaxes(value, -1, -2)          # [out, in] -> [in, out]
    room = [(0, 0)] * value.ndim
    room[axis] = (0, stored_expert_width(value.shape[axis])
                  - value.shape[axis])
    return jnp.pad(value, room)


def to_program_params(weights: dict, mc) -> dict:
    """Canonical weights -> the program's param tree."""
    if not getattr(mc, "mixer_pattern", None):
        # a program from before PR 42 reads model_type 'nemotron_h' as a
        # plain dense decoder: stop before anything is built for it
        raise SystemExit(
            "chipbench: this program's ingest gave the ssm_attn_moe_decoder "
            "family no mixer_pattern (it does not know model_type "
            "'nemotron_h'): it cannot run this configuration")
    out: dict = {}
    flat = {n: weights[n] for n in _TOP}
    for stack, leaves in weights.items():
        if stack not in _TOP:
            flat.update({f"{stack}.{n}": v for n, v in leaves.items()})
    for name, value in flat.items():
        node = out
        *parents, last = _path(name)
        for part in parents:
            node = node.setdefault(part, {})
        node[last] = _stored(name, value)
    return out


def leaf_to_program(name: str, value, mc):
    """One canonical leaf -> ('a/b/c', value as the program has it)."""
    return "/".join(_path(name)), _stored(name, value)


def canonical_names(mc) -> dict:
    """'a/b/c' path in the program's tree -> canonical leaf name (the
    leaves of every kind the pattern of ``mc`` has)."""
    from chipbench.weights import ssm_attn_moe_decoder as w
    names = list(w._TOP) + [f"{kind}.{n}" for kind in w._LEAVES
                            if kind in mc.mixer_pattern[:mc.num_layers]
                            for n in w._LEAVES[kind]]
    return {"/".join(_path(n)): n for n in names}
