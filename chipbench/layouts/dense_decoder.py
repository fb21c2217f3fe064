"""Layout ``dense_decoder``: where each canonical leaf of
``chipbench/weights/dense_decoder.py`` sits in the program's (stacked
layer) parameter tree, and in what shape.  Found by the configuration's
``family`` (``spec.Cell.layout()``); a new family adds a file beside
this one."""

from __future__ import annotations


# canonical leaf -> (path in the program's tree, reshape from sizes)
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
        "w_gate": (blk + ("mlp", "gate_proj", "kernel"), None),
        "w_up": (blk + ("mlp", "up_proj", "kernel"), None),
        "w_down": (blk + ("mlp", "down_proj", "kernel"), None),
        "q_norm": (blk + ("attn", "q_norm", "scale"), None),
        "k_norm": (blk + ("attn", "k_norm", "scale"), None),
    }
    return top, layer


def to_program_params(weights: dict, mc) -> dict:
    """Canonical weights -> the program's (stacked-layer) param tree."""
    top, layer = _layout(mc)
    out: dict = {}

    def put(path, value):
        node = out
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value

    for name, (path, _) in top.items():
        put(path, weights[name])
    for name, value in weights["layers"].items():
        path, shape = layer[name]
        put(path, value if shape is None
            else value.reshape((value.shape[0],) + shape))
    return out


def leaf_to_program(name: str, value, mc):
    """One canonical leaf ('embed' or 'layers.wq') -> ('a/b/c', value
    laid out as the program's tree has it)."""
    top, layer = _layout(mc)
    if name in top:
        return "/".join(top[name][0]), value
    path, shape = layer[name.split(".", 1)[1]]
    return "/".join(path), (value if shape is None else value.reshape(
        (value.shape[0],) + shape))


def canonical_names(mc) -> dict:
    """'a/b/c' path in the program's tree -> canonical leaf name."""
    top, layer = _layout(mc)
    names = {"/".join(p): n for n, (p, _) in top.items()}
    names.update({"/".join(p): f"layers.{n}" for n, (p, _) in layer.items()})
    return names
