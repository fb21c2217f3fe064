"""Compile the main path's Pallas kernels for a described TPU v5e.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that is described, not attached (``on-chip-measurement`` guide, §2):
what Mosaic refuses on the chip it refuses here — block shapes that do
not tile, too much VMEM, a kernel GSPMD would have to partition.
Nothing runs, so these say nothing about results or times.

The topology is described inside a module-scoped fixture (never at
import: only one process may load the TPU library, and every xdist
worker imports this file) and the compiles happen in the test's own
process.  All at Mistral-7B widths: 32 q / 8 kv heads, head_dim 128,
hidden 4096, ffn 14336.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

import torchacc_tpu.ops.flash_attention as flash_mod
import torchacc_tpu.ops.paged_attention as paged_mod
import torchacc_tpu.ops.quantized_matmul as quant_mod
from torchacc_tpu.config import ServeConfig
from torchacc_tpu.ops.attn import attention

H, KH, D, SEQ = 32, 8, 128, 4096
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip: keep it off around these
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_the_chip(monkeypatch):
    """The kernels ask ``interpret_mode()``, which sees the CPU backend
    here; steer them to the Mosaic lowering for the described chip."""
    for mod in (flash_mod, paged_mod, quant_mod):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("segments", [False, True],
                         ids=["plain", "segment_ids"])
def test_flash_fwd_bwd_compiles(one_chip, for_the_chip, segments):
    def loss(q, k, v, seg):
        kw = dict(q_segment_ids=seg, kv_segment_ids=seg) if segments else {}
        return attention(q, k, v, impl="pallas", **kw).astype(
            jnp.float32).sum()

    q = _sds((1, SEQ, H, D), BF16, one_chip)
    kv = _sds((1, SEQ, KH, D), BF16, one_chip)
    seg = _sds((1, SEQ), jnp.int32, one_chip)
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, seg)
    assert text.count("tpu_custom_call") >= 3      # fwd, dq, dkv


@pytest.mark.parametrize("t", [1, ServeConfig().prefill_chunk],
                         ids=["decode", "prefill_chunk"])
def test_paged_attention_compiles_at_default_block_size(one_chip,
                                                        for_the_chip, t):
    sc = ServeConfig()
    slots = sc.max_slots if t == 1 else 1
    mb = SEQ // sc.block_size
    pool = _sds((sc.num_blocks, KH, sc.block_size, D), BF16, one_chip)
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=one_chip)
    text = _compiled_text(
        functools.partial(paged_mod.paged_attention, impl="pallas"),
        _sds((slots, t, H, D), BF16, one_chip), pool, pool,
        i32((slots, mb)), i32((slots,)), i32((slots,)))
    assert "tpu_custom_call" in text


def test_quantized_dot_int8_compiles(one_chip, for_the_chip):
    text = _compiled_text(
        functools.partial(quant_mod.quantized_dot, fmt="int8",
                          impl="pallas"),
        _sds((2 * SEQ, 4096), BF16, one_chip),
        _sds((4096, 14336), BF16, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fsdp,tp", [(4, 1), (2, 2)],
                         ids=["fsdp4", "fsdp2_tp2"])
def test_flash_under_a_mesh_compiles(topo, for_the_chip, fsdp, tp):
    # plain jit with sharded operands is refused ("Mosaic kernels cannot
    # be automatically partitioned"); attention() wraps the kernel in a
    # shard_map over the ambient mesh
    mesh = Mesh(np.asarray(topo.devices).reshape(fsdp, tp), ("fsdp", "tp"))
    sh = NamedSharding(mesh, P("fsdp", None, "tp", None))

    def loss(q, k, v):
        return attention(q, k, v, impl="pallas").astype(jnp.float32).sum()

    q = _sds((4, SEQ, H, D), BF16, sh)
    kv = _sds((4, SEQ, KH, D), BF16, sh)
    with jax.sharding.set_mesh(mesh):
        text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    assert text.count("tpu_custom_call") >= 3


def test_paged_attention_under_a_tp_mesh_compiles(topo, for_the_chip):
    # ServeEngine(mesh=): the pool's kv heads are sharded over 'tp'
    sc = ServeConfig()
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("fsdp", "tp"))
    rep = NamedSharding(mesh, P())
    pool = _sds((sc.num_blocks, KH, sc.block_size, D), BF16,
                NamedSharding(mesh, P(None, "tp", None, None)))
    i32 = functools.partial(_sds, dtype=jnp.int32, sharding=rep)
    s, mb = sc.max_slots, SEQ // sc.block_size
    with jax.sharding.set_mesh(mesh):
        text = _compiled_text(
            functools.partial(paged_mod.paged_attention, impl="pallas"),
            _sds((s, 1, H, D), BF16,
                 NamedSharding(mesh, P(None, None, "tp", None))),
            pool, pool, i32((s, mb)), i32((s,)), i32((s,)))
    assert "tpu_custom_call" in text
