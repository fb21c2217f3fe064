"""Rehearsal of ``chip_smoke.py`` on the CPU mesh at a tiny size.

The script itself never runs a phase off the chip; its phases are plain
functions of a ``ModelConfig`` and sizes, so the control flow, the
entry points and the checks are exercised here (``on-chip-measurement``
guide §2, rehearsals 1 and 2).  Kernel presence cannot be asked of a
CPU compile, so ``require_kernels`` is off; the chip run has it on.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
import torchacc_tpu as ta  # noqa: E402
from torchacc_tpu.ops.attn import attention  # noqa: E402
from torchacc_tpu.serve import ServeEngine  # noqa: E402


def _tiny(depth=2, **kw):
    # the script's own ingest path, Mistral's shape at toy widths
    return chip_smoke.mistral_config(
        depth, hidden_size=64, intermediate_size=128, num_heads=4,
        num_kv_heads=2, vocab_size=512, **kw)


def test_mistral_config_is_the_published_one():
    mc = chip_smoke.mistral_config(32)
    assert (mc.hidden_size, mc.intermediate_size, mc.num_heads, mc.kv_heads,
            mc.head_size, mc.vocab_size, mc.num_layers) == (
        4096, 14336, 32, 8, 128, 32768, 32)
    assert mc.rope_theta == 1e6 and not mc.tie_embeddings
    assert tuple(mc.window) == (-1, -1) and mc.norm_placement == "pre"
    # one 16 GB chip: depth is cut, widths are not
    depth, why = chip_smoke.train_depth(mc, 16 * 2**30, batch=4, seq=4096)
    assert 1 <= depth < 32 and "16 B/param" in why
    depth, why = chip_smoke.serve_depth(mc, 16 * 2**30, 8 * 4096)
    assert 1 <= depth < 32 and "KV pool" in why


def test_train_phase_rehearsal(devices):
    out = chip_smoke.train_phase(
        _tiny(max_seq_len=64, scan_layers=False), batch=4, seq=64, steps=4,
        seed=0, devices=devices[:1], require_kernels=False)
    assert len(out["losses"]) == 4 and out["losses"][-1] < out["losses"][0]
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        # on the chip a step without the flash kernels fails the phase
        chip_smoke.train_phase(
            _tiny(max_seq_len=64, scan_layers=False), batch=4, seq=64,
            steps=2, seed=0, devices=devices[:1])


def test_serve_phase_rehearsal():
    out = chip_smoke.serve_phase(
        _tiny(max_seq_len=128, dtype=jnp.float32),
        prompt_lens=(5, 40, 20, 30, 9, 17), max_new=6, seed=0, block_size=8,
        num_blocks=64, max_slots=4, prefill_chunk=16, wave_steps=6,
        require_kernels=False)
    assert out["impl"] == "xla" and out["mid_decode"] >= 1
    assert out["agree"] == out["compared"] == 36
    assert all(len(t) == 6 for t in out["tokens"])


def test_four_chip_phase_rehearsal(devices):
    out = chip_smoke.multichip_phase(
        _tiny(max_seq_len=64, scan_layers=False),
        layouts=((4, 1), (2, 2)), batch=4, seq=64, steps=3, seed=0,
        devices=devices[:4], require_kernels=False)
    for label in ("fsdp=4 x tp=1", "fsdp=2 x tp=2"):
        assert out[label]["holders"] == 4
        assert out[label]["device_share"] < 0.3


def test_main_without_a_tpu_exits_nonzero_before_any_phase(monkeypatch,
                                                           capsys):
    def no_phase(*a, **kw):
        raise AssertionError("a phase ran off the chip")
    for name in ("train_phase", "serve_phase", "multichip_phase"):
        monkeypatch.setattr(chip_smoke, name, no_phase)
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    assert capsys.readouterr().out == ""      # no result line


def test_engine_rejects_a_block_size_the_kernel_cannot_tile():
    # bf16 pool: the kernel tiles multiples of 16; a typed error at
    # construction, not a lowering failure inside the first request
    model = ta.models.TransformerLM(
        _tiny(max_seq_len=64, attention_impl="pallas"))
    cfg = ta.Config()
    cfg.serve.block_size = 8
    with pytest.raises(ta.ConfigError, match="multiple of 16"):
        ServeEngine(model, None, cfg)


@pytest.mark.parametrize("case", ["plain", "segments", "dropout", "alibi"])
def test_flash_under_a_mesh_matches_the_xla_reference(devices, case):
    # the shard_map wrapper real chips need (ops/attn._sharded_flash),
    # exercised with the interpret-mode kernel on the 8-device CPU mesh:
    # values, gradients and the dropout mask's global coordinates
    b, s, h, kh, d = 4, 64, 4, 2, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kh, d)), jnp.float32)
    seg = jnp.asarray(np.repeat(np.arange(2), s // 2)[None].repeat(b, 0),
                      jnp.int32)
    kw = {"plain": {},
          "segments": dict(q_segment_ids=seg, kv_segment_ids=seg),
          "dropout": dict(dropout_p=0.2, dropout_seed=jnp.int32(7)),
          "alibi": dict(alibi_slopes=jnp.asarray([.1, .2, .3, .4]))}[case]

    def loss(impl, q, k, v):
        return (attention(q, k, v, impl=impl, **kw) ** 2).sum()

    grad = lambda impl: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda *a: loss(impl, *a), argnums=(0, 1, 2)))
    ref_val, ref_grads = grad("xla")(q, k, v)
    mesh = Mesh(np.asarray(devices).reshape(2, 2, 2), ("dp", "fsdp", "tp"))
    with jax.sharding.set_mesh(mesh):
        sh = NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None))
        val, grads = grad("pallas")(*(jax.device_put(x, sh)
                                      for x in (q, k, v)))
    np.testing.assert_allclose(val, ref_val, rtol=1e-5)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(g, r, atol=2e-5, rtol=1e-5)


def test_kernels_under_a_mesh(devices):
    # GSPMD cannot partition a Mosaic kernel: paged attention runs per
    # shard (heads over tp) and matches the gather path; the quantized
    # matmul kernel has no shard_map region and says so with a typed error
    from torchacc_tpu.ops.paged_attention import paged_attention
    from torchacc_tpu.ops.quantized_matmul import quantized_dot
    rng = np.random.default_rng(0)
    s, t, h, kh, d, bs, mb = 3, 4, 4, 2, 16, 8, 3
    nb = s * mb + 1
    q = jnp.asarray(rng.standard_normal((s, t, h, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((3, nb, bs, kh * d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((3, nb, bs, kh * d)), jnp.float32)
    tables = jnp.asarray(1 + np.arange(s * mb).reshape(s, mb), jnp.int32)
    ctx = jnp.asarray([9, 17, 24], jnp.int32)
    args = (q, kp, vp, tables, ctx, ctx - t)
    ref = paged_attention(*args, layer=2, impl="xla")
    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("fsdp", "tp"))
    with jax.sharding.set_mesh(mesh):
        got = jax.jit(lambda *a: paged_attention(
            *a[:-1], layer=a[-1], impl="pallas"))(*args, jnp.int32(2))
        with pytest.raises(ta.ConfigError, match="quant_impl='xla'"):
            quantized_dot(jnp.ones((8, 16)), jnp.ones((16, 8)), impl="pallas")
    np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)
