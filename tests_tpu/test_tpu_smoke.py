"""On-chip checks (see conftest docstring for why these exist).

Each test targets a path that CPU interpret-mode testing cannot validate:
Mosaic compilation of the Pallas flash kernel at the bench's block sizes,
execution (not just lowering) of pinned_host offload placement, the
vocab-parallel fused-CE shard_map lowering, and one end-to-end train step
plus a cached greedy decode on the real chip.  The whole file takes a
few minutes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tpu_smoke


def _xla_attention(q, k, v, *, causal, window=(-1, -1), scale=None,
                   logit_softcap=0.0):
    """f32 reference attention (materialised scores) for comparison."""
    b, sq, hq, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    scale = d ** -0.5 if scale is None else scale
    qf = q.astype(jnp.float32)
    kf = jnp.repeat(k.astype(jnp.float32), hq // hk, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), hq // hk, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    qpos = jnp.arange(sq)[:, None] + (sk - sq)
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    wl, wr = window
    if wl >= 0:
        mask &= kpos >= qpos - wl
    if wr >= 0:
        mask &= kpos <= qpos + wr
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf)


def test_flash_kernel_bench_shapes(chip):
    """Pallas flash fwd+bwd compiles under Mosaic and matches XLA at the
    HEADLINE BENCH geometry (seq 2048, head_dim 128 — the shapes whose
    block sizes the perf claims in docs/PERF.md depend on)."""
    from torchacc_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(0)
    b, s, h, d = 2, 2048, 8, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, causal=True) ** 2)

    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
        q, k, v)
    ref = _xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gf, gr):
        assert np.isfinite(np.asarray(a, np.float32)).all()
        # bf16 grads against an f32-ref: match on overall magnitude
        na = float(jnp.linalg.norm(a.astype(jnp.float32)))
        nb = float(jnp.linalg.norm(b_.astype(jnp.float32)))
        assert abs(na - nb) / max(nb, 1e-6) < 0.05


def test_flash_kernel_gemma_features(chip):
    """GQA + sliding window + soft-capping (the gemma2/3 decode-path
    feature set) compile and match XLA on-chip."""
    from torchacc_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(1)
    b, s, hq, hk, d = 2, 512, 8, 2, 128
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.bfloat16)
    win = (256, -1)  # keep window < seq: the sliding mask must
    # actually cut keys, or the feature is untested
    kw = dict(causal=True, window=win, logit_softcap=50.0)
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, **kw))(q, k, v)
    ref = _xla_attention(q, k, v, causal=True, window=win,
                         logit_softcap=50.0)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_fused_ce_tp_lowers_and_matches(chip):
    """The vocab-parallel fused CE's hand-written manual collectives
    (pmax/psum inside shard_map) lower and execute on the real backend;
    value matches a plain log_softmax CE."""
    from torchacc_tpu.ops.fused import fused_linear_cross_entropy_tp

    rng = np.random.default_rng(2)
    b, s, h, v = 2, 128, 64, 512
    hidden = jnp.asarray(rng.standard_normal((b, s, h)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((h, v)) * 0.02, jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, size=(b, s)), jnp.int32)
    labels = labels.at[0, :4].set(-100)  # ignored rows exercise masking

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tp",))
    with jax.sharding.set_mesh(mesh):
        loss, count = jax.jit(
            lambda x, w, y: fused_linear_cross_entropy_tp(x, w, y)
        )(hidden, w, labels)

    logits = hidden.reshape(-1, h) @ w
    y = labels.reshape(-1)
    valid = y != -100
    ref = -jax.nn.log_softmax(logits)[jnp.arange(y.size),
                                      jnp.clip(y, 0, v - 1)]
    ref = float(jnp.sum(jnp.where(valid, ref, 0.0)))
    assert float(count) == float(jnp.sum(valid))
    np.testing.assert_allclose(float(loss), ref, rtol=1e-5)


def test_offload_placement_executes(chip):
    """pinned_host offload EXECUTES (XLA:CPU cannot run memory-space
    placement, so tests/ only see it lower).  The compiled module must
    place the annotated residuals in host memory, and grads must match
    the plain 'dots' policy bit-for-bit (offload changes residency, not
    math)."""
    from jax.ad_checkpoint import checkpoint_name

    from torchacc_tpu.utils.remat import _host_memory_available, remat_policy

    assert _host_memory_available(), (
        "the chip exposes no pinned_host memory space")

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((64, 256)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((256, 1024)) * 0.02, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((1024, 256)) * 0.02, jnp.float32)

    def mlp(x, w1, w2):
        h = checkpoint_name(x @ w1, "attn_out")
        h = jax.nn.gelu(h)
        o = checkpoint_name(h @ w2, "mlp_out")
        return jnp.sum(o ** 2)

    def run(policy):
        f = jax.checkpoint(mlp, policy=remat_policy(policy))
        g = jax.jit(jax.grad(f, argnums=(1, 2)))
        lowered = g.lower(x, w1, w2)
        return lowered.compile(), g(x, w1, w2)

    compiled_off, g_off = run("offload_dots")
    _, g_dots = run("dots")
    txt = compiled_off.as_text()
    assert "pinned_host" in txt or "S(5)" in txt, (
        "offload policy compiled without a host memory-space placement")
    for a, b in zip(g_off, g_dots):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shadow", [False, True],
                         ids=["default", "bf16_shadow"])
def test_train_step_and_decode(chip, shadow):
    """One real optimizer step on the chip (finite loss, loss drops over
    a few repeats of the same batch) and a cached greedy decode — in the
    default precision mode and in the headline bench's
    compute.bf16_compute_params mode (bf16 shadow leaves in opt state,
    serving-cast decode against the f32 masters)."""
    import optax

    import torchacc_tpu as ta
    from torchacc_tpu.models import get_preset
    from torchacc_tpu.models.generate import generate
    from torchacc_tpu.train import accelerate

    mc = get_preset("llama-tiny", hidden_size=256, num_layers=2,
                    num_heads=4, num_kv_heads=4, intermediate_size=512,
                    vocab_size=1024, max_seq_len=256)
    cfg = ta.Config(compute=ta.ComputeConfig(bf16_compute_params=shadow))
    trainer, _ = accelerate(mc, None, cfg, optimizer=optax.adamw(1e-3))
    trainer.init()
    rng = np.random.default_rng(4)
    batch = {"input_ids": jnp.asarray(
        rng.integers(0, 1024, size=(2, 128)), jnp.int32)}
    losses = [float(trainer.step(batch)["loss"]) for _ in range(8)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    if shadow:
        from torchacc_tpu.train.amp import shadow_params
        sh = jax.tree.leaves(shadow_params(trainer.state.opt_state))
        assert all(x.dtype == jnp.bfloat16 for x in sh)

    prompts = jnp.asarray(rng.integers(0, 1024, size=(2, 16)), jnp.int32)
    decode_kwargs = {"param_dtype": jnp.bfloat16} if shadow else {}
    with jax.sharding.set_mesh(trainer.mesh):
        toks = generate(trainer.model, trainer.state.params, prompts,
                        max_new_tokens=8, **decode_kwargs)
    assert toks.shape == (2, 16 + 8)
    assert bool(jnp.all(toks[:, :16] == prompts))
