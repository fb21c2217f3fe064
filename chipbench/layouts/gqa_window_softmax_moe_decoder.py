"""Layout ``gqa_window_softmax_moe_decoder``: where each canonical leaf
of ``chipbench/weights/gqa_window_softmax_moe_decoder.py`` sits in the
program's parameter tree, and in what shape.  A ``layer_pattern`` with
no dense layers in front keeps the program's canonical stack
(``models/transformer.layer_plan``): every per-layer leaf under
``layers/block`` with the layers on axis 0, as the canonical leaves are,
so only the attention projections' head split is a reshape."""

from __future__ import annotations


def _layout(mc):
    nh, kh, d, h = mc.num_heads, mc.kv_heads, mc.head_size, mc.hidden_size
    top = {"embed": (("embed_tokens", "embedding"), None),
           "final_norm": (("final_norm", "scale"), None),
           "head": (("lm_head", "kernel"), None)}
    blk = ("layers", "block")
    layer = {
        "ln1": (blk + ("ln1", "scale"), None),
        "ln2": (blk + ("ln2", "scale"), None),
        "wq": (blk + ("attn", "q_proj", "kernel"), (h, nh, d)),
        "wk": (blk + ("attn", "k_proj", "kernel"), (h, kh, d)),
        "wv": (blk + ("attn", "v_proj", "kernel"), (h, kh, d)),
        "wo": (blk + ("attn", "o_proj", "kernel"), (nh, d, h)),
        "q_norm": (blk + ("attn", "q_norm", "scale"), None),
        "k_norm": (blk + ("attn", "k_norm", "scale"), None),
        "router": (blk + ("moe", "router", "kernel"), None),
        # the program names its stacked expert kernels with a slash
        "e_gate": (blk + ("moe", "experts/gate"), None),
        "e_up": (blk + ("moe", "experts/up"), None),
        "e_down": (blk + ("moe", "experts/down"), None),
    }
    return top, layer


def leaf_to_program(name: str, value, mc):
    """One canonical leaf ('embed' or 'layers.wq') -> ('a/b/c', value
    laid out as the program's tree has it)."""
    top, layer = _layout(mc)
    if name in top:
        return "/".join(top[name][0]), value
    path, shape = layer[name.split(".", 1)[1]]
    return "/".join(path), (value if shape is None else value.reshape(
        (value.shape[0],) + shape))


def to_program_params(weights: dict, mc) -> dict:
    """Canonical weights -> the program's (stacked-layer) param tree."""
    top, layer = _layout(mc)
    out: dict = {}
    flat = {n: (top[n][0], weights[n]) for n in top}
    for name, value in weights["layers"].items():
        path, shape = layer[name]
        flat[name] = (path, value if shape is None else value.reshape(
            (value.shape[0],) + shape))
    for path, value in flat.values():
        node = out
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value
    return out


def canonical_names(mc) -> dict:
    """'a/b/c' path in the program's tree -> canonical leaf name."""
    top, layer = _layout(mc)
    names = {"/".join(p): n for n, (p, _) in top.items()}
    names.update({"/".join(p): f"layers.{n}" for n, (p, _) in layer.items()})
    return names
