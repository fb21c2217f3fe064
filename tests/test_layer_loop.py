"""Who picks the layer loop (ISSUE 38): ``ModelConfig.scan_layers`` is
``None`` unless a caller sets it, ``apply_config_to_model`` resolves it
from the mesh's axis sizes — unrolled where every device holds the layer
parameters whole, the scan otherwise — and a bare ``TransformerLM``
keeps the scan.  The two loops are the same mathematics."""

import dataclasses
import logging

import chex
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import torchacc_tpu as ta
from torchacc_tpu.models import TransformerLM, get_preset
from torchacc_tpu.models.transformer import (
    ModelConfig,
    layer_loop,
    scans_layers,
)
from torchacc_tpu.obs import tracing
from torchacc_tpu.train.accelerate import accelerate, apply_config_to_model


def _model(**kw):
    return get_preset("llama-tiny", vocab_size=128, hidden_size=64,
                      num_layers=2, num_heads=4, num_kv_heads=2,
                      intermediate_size=128, **kw)


def _dist(**axes):
    """``DistConfig`` with the named axes' sizes set."""
    kinds = dict(dp=ta.DPConfig, fsdp=ta.FSDPConfig, tp=ta.TPConfig,
                 sp=ta.SPConfig, ep=ta.EPConfig)
    kw = {a: kinds[a](size=n) for a, n in axes.items() if a != "pp"}
    if "pp" in axes:
        kw["pp"] = ta.PPConfig(size=axes["pp"],
                               num_micro_batches=2 * axes["pp"])
    return ta.DistConfig(**kw)


@pytest.mark.parametrize("axes,given,want", [
    # nobody chose: from the mesh
    (dict(dp=1), None, "unrolled"),              # one device
    (dict(), None, "unrolled"),                  # dp = whatever is there
    (dict(dp=8), None, "unrolled"),
    (dict(sp=2), None, "unrolled"),              # activations split, not params
    (dict(fsdp=4), None, "scan"),
    (dict(fsdp=4, dp=2), None, "scan"),
    (dict(pp=2), None, "scan"),
    (dict(tp=2), None, "scan"),
    (dict(ep=2), None, "scan"),
    # somebody chose: honoured on both kinds of mesh
    (dict(dp=8), True, "scan"),
    (dict(dp=8), False, "unrolled"),
    (dict(fsdp=4), True, "scan"),
    (dict(fsdp=4), False, "unrolled"),
])
def test_apply_config_picks_the_layer_loop_from_the_mesh(axes, given, want):
    mc = _model() if given is None else _model(scan_layers=given)
    assert mc.scan_layers is given
    out = apply_config_to_model(mc, ta.Config(dist=_dist(**axes)))
    assert out.scan_layers is (want == "scan")
    assert layer_loop(out) == want


def test_overlap_fsdp_still_forces_its_own_loop():
    cfg = ta.Config(dist=_dist(fsdp=4),
                    perf=ta.PerfConfig(overlap_fsdp=True))
    out = apply_config_to_model(_model(), cfg)
    assert out.scan_layers is True and layer_loop(out) == "unrolled"


def test_a_bare_model_config_scans():
    """Outside ``accelerate()`` nobody resolves the field: ``None`` has
    to read as the scan in the model (plain truth would unroll)."""
    assert ModelConfig().scan_layers is None
    assert scans_layers(ModelConfig()) and layer_loop(ModelConfig()) == "scan"
    cfg = _model(dtype=jnp.float32)
    assert cfg.scan_layers is None
    model = TransformerLM(cfg)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    text = str(jax.make_jaxpr(
        lambda p: model.apply({"params": p}, ids))(params))
    assert "scan[" in text
    loop = TransformerLM(dataclasses.replace(cfg, scan_layers=False))
    text = str(jax.make_jaxpr(
        lambda p: loop.apply({"params": p}, ids))(params))
    assert "scan[" not in text


def _one_step(mc, batch):
    cfg = ta.Config(memory=ta.MemoryConfig(gc=True,
                                           gc_policy="save_attn_mlp"))
    # plain SGD: the updated parameters then differ by the gradients'
    # own difference (Adam's first step is lr * sign(g): a gradient that
    # rounds to the other side of zero moves a weight by 2 * lr)
    tr, _ = accelerate(mc, None, cfg, optimizer=optax.sgd(0.1))
    tr.init()
    m = tr.step(batch)
    return tr, float(m["loss"]), float(m["grad_norm"])


def test_default_step_is_unrolled_and_equals_the_scan_step(devices):
    """Through ``accelerate()`` on the emulated dp-only mesh: the default
    (unrolled) step and the explicit-scan step give the same loss,
    gradient norm and updated parameters, to the tolerance of
    ``test_models.py::test_scan_vs_loop_equivalence``."""
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 128, size=(8, 32)).astype(np.int32)}
    t_def, loss_def, gn_def = _one_step(_model(dtype=jnp.float32), batch)
    t_scan, loss_scan, gn_scan = _one_step(
        _model(dtype=jnp.float32, scan_layers=True), batch)
    assert t_def.layer_loop == "unrolled" and t_scan.layer_loop == "scan"
    assert t_def.model.cfg.scan_layers is False
    np.testing.assert_allclose(loss_def, loss_scan, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(gn_def, gn_scan, rtol=2e-4, atol=2e-4)
    chex.assert_trees_all_close(
        jax.device_get(t_def.state.params),
        jax.device_get(t_scan.state.params), atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("axes,given,want", [
    (dict(), None, "unrolled"),
    (dict(fsdp=4), None, "scan"),
    (dict(), True, "scan"),
])
def test_the_run_says_which_loop_it_took(devices, axes, given, want):
    """The start-up line and every ``train/dispatch`` span name the
    path."""
    from torchacc_tpu.utils.logger import logger as ta_logger

    mc = _model() if given is None else _model(scan_layers=given)
    tr, _ = accelerate(mc, None, ta.Config(dist=_dist(**axes)),
                       optimizer=optax.adam(1e-3))
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    level = ta_logger.level
    ta_logger.addHandler(handler)      # the logger has propagate=False
    ta_logger.setLevel(logging.INFO)
    try:
        tr.init()
    finally:
        ta_logger.setLevel(level)
        ta_logger.removeHandler(handler)
    lines = [r.getMessage() for r in records
             if r.getMessage().startswith("initialised ")]
    assert len(lines) == 1 and lines[0].endswith(f"layers={want}")

    batch = {"input_ids": np.zeros((8, 32), np.int32)}
    tracing.configure(enabled=True)
    tracing.clear()
    try:
        tr.step(batch)
        spans = [s for s in tracing.snapshot()
                 if s["name"] == "train/dispatch"]
    finally:
        tracing.clear()
        tracing.configure(enabled=False)
    assert spans and all(s["attrs"]["layers"] == want for s in spans)
