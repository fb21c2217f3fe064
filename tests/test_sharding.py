"""Sharding-rule tests: logical axes -> PartitionSpec -> NamedSharding."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from torchacc_tpu.config import Config, DistConfig, DPConfig, FSDPConfig, TPConfig
from torchacc_tpu.parallel.mesh import build_mesh
from torchacc_tpu.parallel.sharding import (
    batch_spec,
    make_rules,
    spec_for,
    tree_shardings,
)


def test_spec_for_basic():
    rules = make_rules()
    # ZeRO-3's hidden dim over fsdp and, since PR 46, over ep too: the
    # chips that hold the experts hold the rest of the state in shares
    assert spec_for(("embed", "mlp"), rules) == P(("fsdp", "ep"), "tp")
    assert spec_for(("batch", "seq", None), rules) == P(
        ("dp", "fsdp", "ep"), ("sp", "spu"), None)
    assert spec_for(("kv",), rules) == P(None)


def test_an_expert_leafs_expert_dim_takes_ep_before_its_hidden_dim():
    """``experts/gate`` [layers, expert, embed, expert_mlp]: the expert
    dim comes first and takes 'ep', so the hidden dim keeps 'fsdp' alone
    (a mesh axis shards one dim of a leaf)."""
    rules = make_rules()
    assert spec_for(("layers", "expert", "embed", "expert_mlp"), rules) \
        == P(None, "ep", ("fsdp",), "tp")
    assert spec_for(("layers", "expert", "expert_mlp", "embed"), rules) \
        == P(None, "ep", "tp", ("fsdp",))


def test_spec_no_duplicate_mesh_axes():
    rules = make_rules()
    # 'mlp' and 'heads' both map to tp; second occurrence must drop out
    spec = spec_for(("mlp", "heads"), rules)
    assert spec == P("tp", None)


def test_batch_spec():
    assert batch_spec() == P(("dp", "fsdp", "ep"), ("sp", "spu"))


def test_tree_shardings_divisibility_and_min_size(devices):
    cfg = Config(dist=DistConfig(dp=DPConfig(size=2), fsdp=FSDPConfig(size=2),
                                 tp=TPConfig(size=2)))
    mesh = build_mesh(cfg.dist, devices=devices)
    rules = make_rules(cfg)
    abstract = {
        "w": jax.ShapeDtypeStruct((64, 128), jnp.float32),
        "scale": jax.ShapeDtypeStruct((64,), jnp.float32),
        "odd": jax.ShapeDtypeStruct((63, 128), jnp.float32),
    }
    axes = {"w": ("embed", "mlp"), "scale": ("embed",), "odd": ("embed", "mlp")}
    sh = tree_shardings(mesh, abstract, axes, rules, min_weight_size=1024)
    assert sh["w"].spec == P(("fsdp", "ep"), "tp")
    # below min_weight_size -> replicated
    assert sh["scale"].spec == P(None)
    # 63 not divisible by fsdp=2 -> that dim falls back to replicated
    assert sh["odd"].spec == P(None, "tp")


def test_tree_shardings_none_leaf_and_prefix(devices):
    import pytest
    cfg = Config(dist=DistConfig(dp=DPConfig(size=2), fsdp=FSDPConfig(size=2),
                                 tp=TPConfig(size=2)))
    mesh = build_mesh(cfg.dist, devices=devices)
    rules = make_rules(cfg)
    # None leaves (optax EmptyState slots) pass through
    abstract = {"w": jax.ShapeDtypeStruct((64, 128), jnp.float32), "empty": None}
    axes = {"w": ("embed", "mlp"), "empty": None}
    sh = tree_shardings(mesh, abstract, axes, rules)
    assert sh["empty"] is None
    # batch=6 on ('dp','fsdp')=(2,2): falls back to dp-only prefix, not replicated
    b = tree_shardings(mesh, jax.ShapeDtypeStruct((6, 16), jnp.float32),
                       ("batch", None), rules)
    assert b.spec == P(("dp",), None)
    # unknown logical axis raises
    with pytest.raises(ValueError):
        spec_for(("embd",), rules)


def test_sharded_matmul_executes(devices):
    cfg = Config(dist=DistConfig(fsdp=FSDPConfig(size=4), tp=TPConfig(size=2)))
    mesh = build_mesh(cfg.dist, devices=devices)
    rules = make_rules(cfg)
    w = jnp.ones((16, 32))
    x = jnp.ones((8, 16))
    wsh = tree_shardings(mesh, jax.ShapeDtypeStruct(w.shape, w.dtype), ("embed", "mlp"), rules)
    xsh = tree_shardings(mesh, jax.ShapeDtypeStruct(x.shape, x.dtype), ("batch", "embed"), rules)
    w = jax.device_put(w, wsh)
    x = jax.device_put(x, xsh)
    y = jax.jit(lambda a, b: a @ b)(x, w)
    np.testing.assert_allclose(np.asarray(y), np.full((8, 32), 16.0))
