"""Layout ``gqa_window_moe_decoder``: where each canonical leaf of
``chipbench/weights/gqa_window_moe_decoder.py`` sits in the program's
parameter tree.  The canonical shapes ARE the program's
(``dense_layers``, then one stacked tree a position of the pattern's
period under ``layers``: ``layers/p0`` ..), so nothing is reshaped or
copied."""

from __future__ import annotations

_TOP = {"embed": ("embed_tokens", "embedding"),
        "final_norm": ("final_norm", "scale"),
        "head": ("lm_head", "kernel")}
_LEAF = {"ln1": ("ln1", "scale"), "ln2": ("ln2", "scale"),
         "wq": ("attn", "q_proj", "kernel"),
         "wk": ("attn", "k_proj", "kernel"),
         "wv": ("attn", "v_proj", "kernel"),
         "q_norm": ("attn", "q_norm", "scale"),
         "k_norm": ("attn", "k_norm", "scale"),
         "wo": ("attn", "o_proj", "kernel"),
         "w_gate": ("mlp", "gate_proj", "kernel"),
         "w_up": ("mlp", "up_proj", "kernel"),
         "w_down": ("mlp", "down_proj", "kernel"),
         "router": ("moe", "router", "kernel"),
         "router_bias": ("moe", "router_bias"),
         # the program names its stacked expert kernels with a slash
         "e_gate": ("moe", "experts/gate"), "e_up": ("moe", "experts/up"),
         "e_down": ("moe", "experts/down"),
         "s_gate": ("moe", "shared", "gate_proj", "kernel"),
         "s_up": ("moe", "shared", "up_proj", "kernel"),
         "s_down": ("moe", "shared", "down_proj", "kernel")}


def _path(name):
    if name in _TOP:
        return _TOP[name]
    stack, leaf = name.split(".", 1)
    top = (("dense_layers",) if stack == "dense" else ("layers", stack))
    return top + ("block",) + _LEAF[leaf]


def to_program_params(weights: dict, mc) -> dict:
    """Canonical weights -> the program's param tree (no leaf moves)."""
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
        node[last] = value
    return out


def leaf_to_program(name: str, value, mc):
    """One canonical leaf -> ('a/b/c', value as the program has it)."""
    return "/".join(_path(name)), value


def canonical_names(mc) -> dict:
    """'a/b/c' path in the program's tree -> canonical leaf name (the
    leaf names of every stack the pattern of ``mc`` gives)."""
    from chipbench.weights import gqa_window_moe_decoder as w
    types_ = [("sliding_attention" if mc.layer_pattern[
        i % len(mc.layer_pattern)] == "sliding" else "full_attention")
        for i in range(mc.num_layers)]
    _, period, _ = w.structure(
        {"layer_types": types_,
         "first_k_dense_replace": mc.first_dense_layers}, mc.num_layers)
    names = list(w._TOP) + [f"dense.{n}" for n in w._ATTN + w._MLP]
    for i in range(len(period)):
        names += [f"p{i}.{n}" for n in w._ATTN + w._MOE]
    return {"/".join(_path(n)): n for n in names}
