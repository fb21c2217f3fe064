"""``--rehearse``: the same harness and drivers at a toy size on CPU
devices, for the sandbox (wrong paths, arguments, control flow, meshes).
It changes the cell in memory; the result line it leads to carries only
``rehearsal_*`` names (``run.py``), never a metric's."""

from __future__ import annotations

import json
import os


def shrink(cell) -> None:
    with open(os.path.join(os.path.dirname(__file__),
                           "rehearsal.json")) as f:
        toy = json.load(f)
    pub = dict(cell.published)
    heads = pub["num_attention_heads"]
    kv = pub.get("num_key_value_heads") or heads
    pub.update(toy["published"])
    pub["num_key_value_heads"] = max(
        1, kv * pub["num_attention_heads"] // heads)
    if pub.get("head_dim"):
        pub["head_dim"] = pub["hidden_size"] // pub["num_attention_heads"]
    cell.published = pub
    cell.depth = toy["depth"]
    traffic = dict(cell.traffic)
    for key, value in toy["traffic"].items():
        if key in traffic:
            traffic[key] = (dict(traffic[key], **value)
                            if isinstance(value, dict) else value)
    cell.traffic = traffic
    cell.config = dict(cell.config, limits=toy["limits"])
