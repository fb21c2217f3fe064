"""Quantized-matmul tests (compute.quant; ops/quantized_matmul.py) and
the FSDP overlap path (perf.overlap_fsdp).

Contracts under test (docs/performance.md "Quantized matmuls" /
"FSDP overlap"):

- int8: the fused Pallas kernel (interpret mode on CPU) and the XLA
  dot agree BITWISE (both accumulate exact int32); both track the f32
  dequantize-then-matmul reference within the documented tolerance.
- Delayed scaling: scales come from the amax HISTORY (previous steps),
  falling back to just-in-time on an empty history; the history state
  rides TrainState.quant, persists through checkpoints, and a resumed
  run continues bitwise-identically to an uninterrupted one.
- ``quant='none'`` (default) changes nothing: no quant state exists
  and the param layout is identical to the pre-quant model.
- A short int8 train run loss-tracks the bf16 run within 2%.
- ``perf.dispatch_depth`` stays trajectory-invariant with quant on.
- ``overlap_fsdp``: forward (and first-step loss) bitwise-identical to
  the non-overlapped unrolled path; multi-step trajectories agree to
  reduction-order tolerance on an fsdp mesh and bitwise without one.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchacc_tpu as ta
from torchacc_tpu.models import get_preset
from torchacc_tpu.train import accelerate

pytestmark = pytest.mark.quant

CHAOS_SEED = int(os.environ.get("CHAOS_SEED", "0"))


def _model(**kw):
    base = dict(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                num_kv_heads=2, intermediate_size=64, max_seq_len=64)
    base.update(kw)
    return get_preset("llama-tiny", **base)


def _batches(n, seed=None, rows=8, vocab=128):
    rng = np.random.default_rng(CHAOS_SEED if seed is None else seed)
    return [{"input_ids": rng.integers(0, vocab,
                                       size=(rows, 16)).astype(np.int32)}
            for _ in range(n)]


def _trainer(quant="none", model=None, depth=1, overlap=False,
             dp=None, fsdp=None, lr=1e-2, grad_accum=1, **ckw):
    import optax
    cfg = ta.Config()
    cfg.compute.quant = quant
    for k, v in ckw.items():
        setattr(cfg.compute, k, v)
    cfg.grad_accum = grad_accum
    cfg.perf.dispatch_depth = depth
    cfg.perf.overlap_fsdp = overlap
    if dp or fsdp:
        cfg.dist.dp.size = dp or 1
        cfg.dist.fsdp.size = fsdp or 1
        cfg.dist.fsdp.min_weight_size = 1
        cfg.get_mesh(jax.devices()[: (dp or 1) * (fsdp or 1)])
    tr, _ = accelerate(model or _model(), None, cfg,
                       optimizer=optax.adam(lr))
    return tr


def _run(tr, batches):
    losses = []
    for b in batches:
        losses.append(tr.step(b)["loss"])
    tr.drain()
    jax.block_until_ready(tr.state.params)
    return [float(l) for l in losses]


def _tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# -- config -------------------------------------------------------------------

def test_quant_config_validation():
    with pytest.raises(ta.ConfigError):
        ta.Config.from_dict({"compute": {"quant": "int4"}})
    with pytest.raises(ta.ConfigError):
        ta.Config.from_dict({"compute": {"quant": "int8",
                                         "quant_sites": ["attn", "conv"]}})
    with pytest.raises(ta.ConfigError):
        ta.Config.from_dict({"compute": {"quant_amax_history_len": 0}})
    # quant x pp rejected up front (the pipeline regions don't thread
    # the delayed-scaling state)
    with pytest.raises(ta.ConfigError):
        ta.Config.from_dict({"compute": {"quant": "int8"},
                             "dist": {"pp": {"size": 2,
                                             "num_micro_batches": 2}}})
    ta.Config.from_dict({"compute": {"quant": "fp8",
                                     "quant_sites": ["mlp", "head"]}})


# -- op-level numerics --------------------------------------------------------

def test_quantize_dequantize_roundtrip():
    from torchacc_tpu.ops.quantized_matmul import (
        compute_scale, dequantize, quantize,
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 32)) * 3.0, jnp.float32)
    amax = float(jnp.max(jnp.abs(x)))
    for fmt in ("int8", "fp8"):
        s = compute_scale(jnp.max(jnp.abs(x)), fmt)
        xd = dequantize(quantize(x, s, fmt), s)
        err = float(jnp.max(jnp.abs(xd - x)))
        if fmt == "int8":
            # uniform grid: error <= half a quantization step
            assert err <= float(s) * 0.5 + 1e-6
        else:
            # e4m3 is a FLOAT format: error is relative (3 mantissa
            # bits -> <= 2^-4 of the value's magnitude)
            assert err <= amax * 2.0 ** -4 + 1e-6


def test_scale_guard_zero_amax():
    from torchacc_tpu.ops.quantized_matmul import compute_scale
    assert float(compute_scale(jnp.zeros(()), "int8")) == 1.0


def test_kernel_vs_xla_bitwise_and_f32_reference():
    from torchacc_tpu.ops.quantized_matmul import (
        quantized_dot, quantized_matmul_reference,
    )
    rng = np.random.default_rng(CHAOS_SEED)
    x = jnp.asarray(rng.normal(size=(4, 33, 48)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(48, 40)) * 0.05, jnp.float32)
    for fmt in ("int8", "fp8"):
        y_xla = quantized_dot(x, w, 1, fmt=fmt, impl="xla")
        y_pal = quantized_dot(x, w, 1, fmt=fmt, impl="pallas")
        y_ref = quantized_matmul_reference(x, w, 1, fmt=fmt)
        if fmt == "int8":
            # exact int32 accumulation on both paths: kernel (interpret
            # mode) and XLA dot agree bitwise
            np.testing.assert_array_equal(np.asarray(y_xla),
                                          np.asarray(y_pal), err_msg=fmt)
        else:
            # fp8 accumulates in f32 on both paths, in different orders:
            # measured on the jax 0.9.0 CPU backend, <= 10 of 5280
            # elements differ by one f32 ulp (6e-8, 4.5e-8 of the output
            # scale); two ulps of the scale is the bound
            diff = float(jnp.max(jnp.abs(y_xla - y_pal)))
            assert diff <= 2.0 ** -22 * float(jnp.max(jnp.abs(y_ref))), diff
        # reference differs only by accumulation order (f32 sums);
        # documented tolerance relative to the output scale
        scale = float(jnp.max(jnp.abs(y_ref))) + 1e-9
        rel = float(jnp.max(jnp.abs(y_xla - y_ref))) / scale
        assert rel < 5e-3, (fmt, rel)


def test_quantized_dot_contract_two_dims():
    from torchacc_tpu.ops.quantized_matmul import (
        quantized_dot, quantized_matmul_reference,
    )
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 5, 2, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 16, 24)) * 0.1, jnp.float32)
    y = quantized_dot(x, w, 2, fmt="int8", impl="xla")
    r = quantized_matmul_reference(x, w, 2, fmt="int8")
    assert y.shape == (2, 5, 24)
    assert float(jnp.max(jnp.abs(y - r))) < 5e-3 * float(
        jnp.max(jnp.abs(r)) + 1e-9)


def test_quantized_dot_grads_flow():
    from torchacc_tpu.ops.quantized_matmul import quantized_dot
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 8)) * 0.1, jnp.float32)

    def loss(w, x):
        return jnp.sum(quantized_dot(x, w, 1, fmt="int8", impl="xla") ** 2)
    gw, gx = jax.grad(loss, argnums=(0, 1))(w, x)
    # straight-through backward: compute-dtype matmuls on the saved
    # unquantized operands — finite, nonzero
    assert bool(jnp.all(jnp.isfinite(gw))) and float(
        jnp.max(jnp.abs(gw))) > 0
    assert bool(jnp.all(jnp.isfinite(gx)))


def test_delayed_scaling_history_semantics():
    from torchacc_tpu.ops.quantized_matmul import (
        amax_history_init, compute_scale, delayed_scale,
        update_amax_history,
    )
    h = amax_history_init(4)
    # empty history -> just-in-time fallback on the current amax
    s0 = delayed_scale(h, jnp.asarray(2.0), "int8")
    assert float(s0) == float(compute_scale(jnp.asarray(2.0), "int8"))
    h = update_amax_history(h, jnp.asarray(2.0))
    assert np.asarray(h).tolist() == [2.0, 0.0, 0.0, 0.0]
    # the delayed scale reads the HISTORY max, not the current amax
    s1 = delayed_scale(h, jnp.asarray(100.0), "int8")
    assert float(s1) == float(compute_scale(jnp.asarray(2.0), "int8"))
    # the window rolls: 4 more updates evict the 2.0
    for a in (1.0, 1.0, 1.0, 1.0):
        h = update_amax_history(h, jnp.asarray(a))
    assert float(jnp.max(h)) == 1.0


# -- trainer integration ------------------------------------------------------

def test_quant_none_is_legacy_layout():
    tr = _trainer("none")
    tr.init()
    assert tr.state.quant is None
    trq = _trainer("int8")
    trq.init()
    assert trq.state.quant is not None
    # identical param trees (same names, shapes, init stream)
    assert jax.tree.structure(tr.state.params) == \
        jax.tree.structure(trq.state.params)
    assert _tree_equal(tr.state.params, trq.state.params)


def test_quant_histories_advance_and_eval_reads_only(tmp_path):
    tr = _trainer("int8")
    batches = _batches(3)
    _run(tr, batches)
    h0 = jax.device_get(tr.state.quant)
    leaves = jax.tree.leaves(h0)
    assert leaves and all(np.asarray(l).shape[-1] == 16 for l in leaves)
    # 3 steps recorded 3 amax observations
    assert all((np.asarray(l) > 0).sum(axis=-1).max() == 3
               for l in leaves)
    # eval does not mutate the histories
    tr.eval_step(batches[0])
    assert _tree_equal(h0, jax.device_get(tr.state.quant))


def test_int8_loss_tracks_bf16_within_2pct():
    steps = 50
    batches = _batches(steps, seed=7)
    l_bf16 = _run(_trainer("none", lr=5e-3), batches)
    l_int8 = _run(_trainer("int8", lr=5e-3), batches)
    final_ref = np.mean(l_bf16[-5:])
    final_q = np.mean(l_int8[-5:])
    assert abs(final_q - final_ref) / final_ref < 0.02, (final_q, final_ref)


def test_dispatch_depth_invariant_with_quant():
    runs = {}
    for depth in (1, 3):
        tr = _trainer("int8", depth=depth)
        losses = _run(tr, _batches(5, seed=3))
        runs[depth] = (losses, jax.device_get(tr.state.params),
                       jax.device_get(tr.state.quant))
    assert runs[1][0] == runs[3][0]
    assert _tree_equal(runs[1][1], runs[3][1])
    assert _tree_equal(runs[1][2], runs[3][2])


def test_quant_with_grad_accum_threads_history():
    # single-device mesh: grad-accum on the 8-device emulated dp mesh
    # NaNs on the PRE-PR tree too (the known amp/accum env drift —
    # test_bf16_compute_params_matches_baseline sits in the same
    # pre-existing failure set); the quant threading under test is
    # mesh-independent
    tr = _trainer("int8", grad_accum=2, dp=1)
    losses = _run(tr, _batches(2, rows=16))
    assert all(np.isfinite(losses))
    # 2 optimizer steps x 2 micro-steps = 4 observations per site
    leaves = jax.tree.leaves(jax.device_get(tr.state.quant))
    assert all((np.asarray(l) > 0).sum(axis=-1).max() == 4
               for l in leaves)


def test_quant_state_resume_bitwise(tmp_path):
    batches = _batches(8, seed=11)

    def fit(tr, ckdir, max_steps, resume=None):
        return tr.fit(list(batches), max_steps=max_steps,
                      checkpoint_dir=str(ckdir), checkpoint_every=2,
                      log_every=1, resume=resume)

    # uninterrupted 8 steps
    t_full = _trainer("int8")
    h_full = fit(t_full, tmp_path / "full", 8)
    # interrupted at 4, resumed to 8 in a FRESH trainer
    t_a = _trainer("int8")
    fit(t_a, tmp_path / "split", 4)
    t_b = _trainer("int8")
    h_b = fit(t_b, tmp_path / "split", 8, resume="auto")
    proj = lambda h: [(r["step"], r["loss"]) for r in h]  # noqa: E731
    assert proj(h_b) == proj(h_full)[4:]
    assert _tree_equal(jax.device_get(t_full.state.params),
                       jax.device_get(t_b.state.params))
    # the delayed-scaling histories came back bit-exact too — elastic
    # resume stays exact with quant on
    assert _tree_equal(jax.device_get(t_full.state.quant),
                       jax.device_get(t_b.state.quant))


def test_save_blocked_ms_in_records(tmp_path):
    tr = _trainer("none", depth=2)
    hist = tr.fit(list(_batches(4)), max_steps=4,
                  checkpoint_dir=str(tmp_path), checkpoint_every=2,
                  log_every=1)
    assert all("save_blocked_ms" in r for r in hist)
    # a writing step paid a nonzero save path; the checkpoint is valid
    assert any(r["save_blocked_ms"] > 0 for r in hist)
    from torchacc_tpu.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path))
    try:
        assert 4 in mgr.valid_steps()
    finally:
        mgr.close()


def test_generate_strips_quant():
    from torchacc_tpu.models.generate import generate
    tr = _trainer("int8")
    _run(tr, _batches(1))
    prompts = jnp.asarray(_batches(1, seed=5)[0]["input_ids"][:2, :8])
    out = generate(tr.model, tr.state.params, prompts, max_new_tokens=4)
    assert out.shape == (2, 12)


def test_head_only_quant_sites_on_unrolled_path():
    # quant_sites=('head',) leaves the BLOCKS plain — the unrolled /
    # overlap loops must not look for per-layer quant state that was
    # never created (regression: KeyError 'layers')
    import dataclasses
    model = dataclasses.replace(_model(), scan_layers=False)
    tr = _trainer("int8", model=model, quant_sites=("head",),
                  fused_kernels=False)
    losses = _run(tr, _batches(2))
    assert all(np.isfinite(losses))
    leaves = jax.tree_util.tree_flatten_with_path(
        jax.device_get(tr.state.quant))[0]
    paths = [jax.tree_util.keystr(p) for p, _ in leaves]
    assert paths == ["['lm_head']['amax_history']"], paths
    assert (np.asarray(leaves[0][1]) > 0).sum() == 2


def test_head_site_with_fused_ce_rejected():
    # the fused-CE loss never reaches the lm_head module — a 'head'
    # quant site would be silently inert; the Trainer rejects it
    from torchacc_tpu.errors import TrainerStateError
    with pytest.raises(TrainerStateError):
        _trainer("int8", quant_sites=("attn", "mlp", "head"))
    # with the materialised head it is accepted
    _trainer("int8", quant_sites=("head",), fused_kernels=False)


def test_head_site_with_tied_embeddings_rejected():
    # the tied head projects through emb.attend — no lm_head dense
    # exists to quantize; a silent no-op would lie to the user
    import dataclasses
    from torchacc_tpu.models.transformer import TransformerLM
    mc = dataclasses.replace(_model(), quant="int8",
                             quant_sites=("head",), tie_embeddings=True)
    with pytest.raises(ValueError, match="tie_embeddings"):
        TransformerLM(mc).init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))


def test_overlap_fsdp_layer_pattern_rejected():
    import dataclasses
    from torchacc_tpu.models.transformer import TransformerLM
    mc = dataclasses.replace(
        _model(), overlap_fsdp=True,
        layer_pattern=("sliding", "global"), window=(4, 0))
    m = TransformerLM(mc)
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(NotImplementedError):
        m.apply(v, jnp.zeros((1, 8), jnp.int32))


def test_fsdp_gather_specs_keep_tp_drop_fsdp():
    from jax.sharding import PartitionSpec as P
    from torchacc_tpu.parallel.sharding import (
        DEFAULT_RULES, fsdp_gather_specs,
    )
    tree = {"block": {"attn": {"q_proj": {
        "kernel": jnp.zeros((32, 2, 16))}},
        "mlp": {"up_proj": {"kernel": jnp.zeros((32, 64))}}}}
    specs = fsdp_gather_specs(tree, DEFAULT_RULES)
    # q_proj kernel: ('embed','heads','kv') -> fsdp dropped, tp kept
    assert specs["block"]["attn"]["q_proj"]["kernel"] == P(None, "tp", None)
    # up_proj kernel: ('embed','mlp') -> fsdp dropped, tp kept
    assert specs["block"]["mlp"]["up_proj"]["kernel"] == P(None, "tp")


def test_quant_unsupported_compositions_raise():
    import dataclasses
    from torchacc_tpu.models.transformer import TransformerLM
    mc = _model()
    # layer_pattern x quant
    mcq = dataclasses.replace(
        mc, quant="int8", layer_pattern=("sliding", "global"),
        window=(4, 0))
    m = TransformerLM(mcq)
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(NotImplementedError):
        m.apply(v, jnp.zeros((1, 8), jnp.int32), mutable=["quant"])


# -- overlap_fsdp -------------------------------------------------------------

def _overlap_pair(devices, quant="none", scan=False, steps=3):
    import dataclasses
    batches = _batches(steps, seed=21)
    out = {}
    for overlap in (False, True):
        model = dataclasses.replace(_model(), scan_layers=scan)
        tr = _trainer(quant, model=model, overlap=overlap, dp=2, fsdp=4)
        out[overlap] = (_run(tr, batches),
                        jax.device_get(tr.state.params))
    return out


def test_overlap_fsdp_first_step_bitwise_and_close(devices):
    out = _overlap_pair(devices)
    l_off, l_on = out[False][0], out[True][0]
    # forward is bitwise-identical: the very first loss (computed before
    # any backward-perturbed params) matches exactly
    assert l_off[0] == l_on[0]
    # later steps agree to reduction-order tolerance (backward weight
    # grads all-reduce vs reduce-scatter in a different order)
    np.testing.assert_allclose(l_off, l_on, rtol=2e-2)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        rtol=0.1, atol=5e-2), out[False][1], out[True][1])


def test_overlap_fsdp_no_fsdp_axis_fully_bitwise():
    # without a live fsdp extent the gather constraint is a no-op and
    # the overlap loop must be bitwise-identical to the unrolled path
    import dataclasses
    batches = _batches(3, seed=23)
    out = {}
    for overlap in (False, True):
        model = dataclasses.replace(_model(), scan_layers=False)
        tr = _trainer("none", model=model, overlap=overlap)
        out[overlap] = (_run(tr, batches),
                        jax.device_get(tr.state.params))
    assert out[False][0] == out[True][0]
    assert _tree_equal(out[False][1], out[True][1])


def test_overlap_fsdp_with_remat_first_step_bitwise(devices):
    # the gather sits INSIDE the remat region (residuals stay
    # fsdp-sharded; backward re-gathers) — values must still match the
    # non-overlapped remat path.  The remat+unrolled base path itself
    # is broken on this flax/jax combo (JaxTransformError — the same
    # PRE-EXISTING env drift that fails test_gc_cnt_nonscan_path, with
    # or without overlap), so skip when the BASELINE cannot run.
    import dataclasses
    import flax.errors
    import optax
    batches = _batches(2, seed=29)
    out = {}
    for overlap in (False, True):
        cfg = ta.Config()
        cfg.memory.gc = True
        cfg.memory.gc_policy = "dots"
        cfg.perf.overlap_fsdp = overlap
        cfg.dist.dp.size = 2
        cfg.dist.fsdp.size = 4
        cfg.dist.fsdp.min_weight_size = 1
        cfg.get_mesh(jax.devices()[:8])
        model = dataclasses.replace(_model(), scan_layers=False)
        tr, _ = accelerate(model, None, cfg, optimizer=optax.adam(1e-2))
        try:
            out[overlap] = _run(tr, batches)
        except flax.errors.JaxTransformError:
            assert not overlap, \
                "overlap broke a remat path the baseline can run"
            pytest.skip("remat + unrolled layers unrunnable on this "
                        "flax/jax (pre-existing env drift — see "
                        "test_gc_cnt_nonscan_path)")
    assert out[False][0] == out[True][0]
    np.testing.assert_allclose(out[False], out[True], rtol=2e-2)


def test_overlap_fsdp_composes_with_quant(devices):
    out = _overlap_pair(devices, quant="int8", steps=2)
    assert out[False][0][0] == out[True][0][0]
    np.testing.assert_allclose(out[False][0], out[True][0], rtol=2e-2)


# -- shard-local digest subsample ---------------------------------------------

def test_subsample_strides_prefer_unsharded_dims():
    from torchacc_tpu.resilience.sdc import _subsample_strides
    # dim1 sharded: the whole bound lands on dim0
    s = _subsample_strides((1024, 64), 256, [False, True])
    assert s[1] == 1 and s[0] >= 256
    kept = -(-1024 // s[0]) * 64
    assert kept <= 256 * 2  # ~bound (per-dim ceil slack)
    # no sharding info: largest dim strided first
    s2 = _subsample_strides((8, 4096), 128, [False, False])
    assert s2[1] > 1


def test_leaf_digest_spec_steered_subsample_properties():
    from jax.sharding import PartitionSpec as P
    from torchacc_tpu.resilience.sdc import _leaf_digest
    x = jnp.asarray(np.random.default_rng(CHAOS_SEED).normal(
        size=(64, 64)), jnp.float32)
    hit_no, hit_yes = jnp.zeros((), bool), jnp.ones((), bool)
    mask = jnp.asarray(0x00010000, jnp.uint32)
    spec = P(None, "fsdp")
    a = _leaf_digest(x, hit_no, mask, max_elems=128, spec=spec)
    b = _leaf_digest(x, hit_no, mask, max_elems=128, spec=spec)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # bound below the leaf size changes what is folded
    full = _leaf_digest(x, hit_no, mask)
    assert not np.array_equal(np.asarray(a), np.asarray(full))
    # element 0 (the chaos flip site) stays covered under the
    # spec-steered per-dim strides
    f = _leaf_digest(x, hit_yes, mask, max_elems=128, spec=spec)
    assert not np.array_equal(np.asarray(a)[:2], np.asarray(f)[:2])


def test_sdc_check_with_bounded_digests_and_quant(devices):
    # per-step SDC digests with the bounded (per-dim-stride) fold +
    # quant: clean run never flags, losses finite.  dp-only mesh: the
    # digest shard_map on a live-fsdp CPU mesh trips a PRE-EXISTING
    # jax-0.4.37 SPMD PartitionId limitation unrelated to the bound
    # (verified identical on the pre-PR tree); the shard-local stride
    # property itself is unit-tested above.
    import optax
    cfg = ta.Config()
    cfg.compute.quant = "int8"
    cfg.dist.dp.size = 2
    cfg.resilience.sdc_check_interval_steps = 1
    cfg.resilience.sdc_digest_max_elems = 64
    cfg.get_mesh(jax.devices()[:2])
    tr, _ = accelerate(_model(), None, cfg, optimizer=optax.adam(1e-3))
    losses = _run(tr, _batches(3))
    assert all(np.isfinite(losses))
