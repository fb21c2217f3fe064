"""The seam to the program under test, for every family: it builds the
program's ``ModelConfig`` and ``Config`` from the cell's data files.
Only this module, ``layouts/<family>.py`` and the drivers import
``torchacc_tpu``.  How one family's seeded weights are re-laid into the
program's parameter tree is ``chipbench/layouts/<family>.py``."""

from __future__ import annotations

import types

import jax.numpy as jnp

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def model_config(published: dict, depth: int, **overrides):
    """The source's config through the program's own HF ingest."""
    from torchacc_tpu.models.hf import config_from_hf
    if "param_dtype" in overrides:
        overrides["param_dtype"] = _DTYPES[overrides["param_dtype"]]
    return config_from_hf(types.SimpleNamespace(**published),
                          num_layers=depth, **overrides)


def framework_config(settings: dict, seed: int):
    """``ta.Config`` with the traffic file's dotted settings applied."""
    import torchacc_tpu as ta
    cfg = ta.Config()
    cfg.seed = int(seed) & 0x7FFFFFFF
    for dotted, value in settings.items():
        node = cfg
        *path, leaf = dotted.split(".")
        for part in path:
            node = getattr(node, part)
        if not hasattr(node, leaf):
            raise SystemExit(f"chipbench: the program's Config has no "
                             f"setting {dotted!r}")
        setattr(node, leaf, value)
    cfg.validate()
    return cfg


def optimizer(opt: dict):
    import optax
    if opt["name"] != "adamw":
        raise SystemExit(f"chipbench: unknown optimizer {opt['name']!r}")
    return optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                       eps=opt["eps"], weight_decay=opt["weight_decay"])


def flat_paths(tree) -> dict:
    """Program param tree -> {'a/b/c': leaf}."""
    import jax
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat["/".join(str(getattr(k, "key", getattr(k, "name", k)))
                      for k in path)] = leaf
    return flat
