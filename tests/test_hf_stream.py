"""Streamed safetensors ingestion (models/hf_stream.py): bounded host
memory, shard-by-shard conversion, direct placement into target
shardings.  Reference capability: LOW_CPU_MEM_USAGE deferred init
(reference accelerate.py:13-17,114-119 via torchdistx fake tensors) —
here the TPU-native answer is streaming straight to sharded device
arrays, no full-model materialisation ever."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

import transformers

from torchacc_tpu.models import TransformerLM
from torchacc_tpu.models.hf import config_from_hf, params_from_hf_state_dict
from torchacc_tpu.models.hf_stream import (
    ingestion_plan, load_hf_model_streamed, resolve_checkpoint_files,
    stream_params, validate_checkpoint_header)


def _tiny_llama_cfg(**kw):
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=False, attn_implementation="eager")
    base.update(kw)
    return transformers.LlamaConfig(**base)


def _save_sharded(hf_model, path, n_shards=3):
    """Write an HF-style multi-shard safetensors checkpoint (index json
    + shards), the exact on-disk layout real releases ship."""
    from safetensors.torch import save_file

    sd = {k: v.contiguous() for k, v in hf_model.state_dict().items()}
    os.makedirs(path, exist_ok=True)
    hf_model.config.save_pretrained(path)
    names = sorted(sd)
    weight_map = {}
    for s in range(n_shards):
        part = {n: sd[n] for n in names[s::n_shards]}
        fname = f"model-{s + 1:05d}-of-{n_shards:05d}.safetensors"
        save_file(part, os.path.join(path, fname))
        for n in part:
            weight_map[n] = fname
    with open(os.path.join(path, "model.safetensors.index.json"), "w") as f:
        json.dump({"metadata": {}, "weight_map": weight_map}, f)


def test_streamed_matches_materialised(tmp_path):
    """Tensor-for-tensor: streaming the shards reproduces exactly what
    the materialising converter builds from the same checkpoint."""
    torch.manual_seed(0)
    hf_model = transformers.LlamaForCausalLM(_tiny_llama_cfg()).eval()
    path = str(tmp_path / "ckpt")
    _save_sharded(hf_model, path, n_shards=3)

    cfg = config_from_hf(hf_model.config, dtype=jnp.float32,
                         param_dtype=jnp.float32)
    ref = params_from_hf_state_dict(hf_model.state_dict(), cfg)

    files = resolve_checkpoint_files(path)
    assert files is not None and len(files) == 3
    got = stream_params(files, cfg, param_dtype=jnp.float32)

    ref_flat = jax.tree_util.tree_flatten_with_path(ref)[0]
    got_flat = jax.tree_util.tree_flatten_with_path(got)[0]
    assert [k for k, _ in ref_flat] == [k for k, _ in got_flat]
    for (k, a), (_, b) in zip(ref_flat, got_flat):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(k))


def test_streamed_single_file_and_tied(tmp_path):
    """Single-file checkpoints and tied embeddings (no lm_head tensor on
    disk) both stream."""
    torch.manual_seed(1)
    hf_model = transformers.LlamaForCausalLM(
        _tiny_llama_cfg(tie_word_embeddings=True)).eval()
    from safetensors.torch import save_file
    path = str(tmp_path / "ckpt")
    os.makedirs(path)
    hf_model.config.save_pretrained(path)
    sd = {k: v.contiguous() for k, v in hf_model.state_dict().items()
          if k != "lm_head.weight"}
    save_file(sd, os.path.join(path, "model.safetensors"))

    cfg, params = load_hf_model_streamed(path, dtype=jnp.float32,
                                         param_dtype=jnp.float32)
    assert cfg.tie_embeddings and "lm_head" not in params

    ids = np.random.default_rng(0).integers(0, 128, size=(2, 16))
    ours = TransformerLM(cfg).apply({"params": params},
                                    jnp.asarray(ids, jnp.int32))
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(ids)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4)


def test_streamed_tied_with_dealiased_head(tmp_path):
    """Some exporters write a DE-ALIASED lm_head copy even for tied
    models (safetensors refuses aliased tensors): it must stream as a
    discard, exactly like the materialising path ignores it."""
    torch.manual_seed(4)
    hf_model = transformers.LlamaForCausalLM(
        _tiny_llama_cfg(tie_word_embeddings=True)).eval()
    path = str(tmp_path / "ckpt")
    os.makedirs(path)
    hf_model.config.save_pretrained(path)
    from safetensors.torch import save_file
    sd = {k: v.contiguous() for k, v in hf_model.state_dict().items()}
    sd["lm_head.weight"] = hf_model.model.embed_tokens.weight.detach().clone()
    save_file(sd, os.path.join(path, "model.safetensors"))

    cfg, params = load_hf_model_streamed(path, dtype=jnp.float32,
                                         param_dtype=jnp.float32)
    assert cfg.tie_embeddings and "lm_head" not in params
    # header validation accepts the same checkpoint abstractly
    validate_checkpoint_header({k: tuple(v.shape) for k, v in sd.items()},
                               cfg)


def test_streamed_bf16_checkpoint(tmp_path):
    """bf16 shards (what real llama3 releases ship) stream without the
    f32 upcast round-trip: values land bit-identical to the checkpoint."""
    torch.manual_seed(2)
    hf_model = transformers.LlamaForCausalLM(_tiny_llama_cfg()).to(
        torch.bfloat16)
    path = str(tmp_path / "ckpt")
    _save_sharded(hf_model, path, n_shards=2)

    cfg = config_from_hf(hf_model.config, param_dtype=jnp.bfloat16)
    got = stream_params(resolve_checkpoint_files(path), cfg,
                        param_dtype=jnp.bfloat16)
    want = hf_model.model.embed_tokens.weight.detach().view(
        torch.uint16).numpy()
    np.testing.assert_array_equal(
        np.asarray(got["embed_tokens"]["embedding"]).view(np.uint16), want)


def test_streamed_into_fsdp_shardings(tmp_path, devices):
    """accelerate(checkpoint_path) streams into the live FSDP shardings:
    params come back already sharded over the mesh and the model trains."""
    import optax

    import torchacc_tpu as ta
    from torchacc_tpu.train import accelerate

    torch.manual_seed(3)
    hf_model = transformers.LlamaForCausalLM(_tiny_llama_cfg()).eval()
    path = str(tmp_path / "ckpt")
    _save_sharded(hf_model, path, n_shards=2)

    cfg = ta.Config(dist=ta.DistConfig(
        fsdp=ta.FSDPConfig(size=8, min_weight_size=0)))
    cfg.compute.dtype = "float32"
    cfg.compute.param_dtype = "float32"
    trainer, _ = accelerate(path, None, cfg, optimizer=optax.adam(1e-3))

    # weights must match the checkpoint (spot-check embed) AND be sharded
    emb = trainer.state.params["embed_tokens"]["embedding"]
    np.testing.assert_allclose(
        np.asarray(emb),
        hf_model.model.embed_tokens.weight.detach().float().numpy(),
        atol=1e-6)
    sharded = [x for x in jax.tree.leaves(trainer.state.params)
               if "fsdp" in str(x.sharding.spec)]
    assert sharded, "no parameter landed sharded over fsdp"

    batch = {"input_ids": jnp.asarray(
        np.random.default_rng(0).integers(0, 128, size=(8, 32)), jnp.int32)}
    assert np.isfinite(float(trainer.step(batch)["loss"]))


def test_header_validation_catches_mismatch():
    cfg = config_from_hf(_tiny_llama_cfg())
    plan = ingestion_plan(cfg)
    shapes = {n: e[0].hf_shape for n, e in plan.items()}
    validate_checkpoint_header(shapes, cfg)  # clean header passes

    bad = dict(shapes)
    bad["layers.0.self_attn.q_proj.weight"] = (7, 7)
    with pytest.raises(ValueError, match="shape"):
        validate_checkpoint_header(bad, cfg)
    with pytest.raises(KeyError, match="unmappable"):
        validate_checkpoint_header({**shapes, "visual.patch_embed": (3, 3)},
                                   cfg)
    del shapes["layers.1.mlp.up_proj.weight"]
    with pytest.raises(ValueError, match="missing"):
        validate_checkpoint_header(shapes, cfg)


@pytest.mark.slow
def test_streamed_peak_rss_bounded(tmp_path):
    """THE point of streaming: peak host RSS while ingesting stays at
    resident-params + a transient bounded by a couple of stacked leaves
    — NOT the 2-3x full-model overhead of the materialising path (torch
    module + stacked numpy copies).  ~360 MB synthetic checkpoint keeps
    the signal far above allocator noise; measured in a subprocess so
    ru_maxrss is this load's peak and nothing else's."""
    from safetensors.numpy import save_file

    hf_cfg = _tiny_llama_cfg(
        vocab_size=4096, hidden_size=1024, intermediate_size=3072,
        num_hidden_layers=6, num_attention_heads=8, num_key_value_heads=8)
    mc = config_from_hf(hf_cfg, param_dtype=jnp.float32)
    plan = ingestion_plan(mc)
    path = str(tmp_path / "big")
    os.makedirs(path)
    hf_cfg.save_pretrained(path)
    rng = np.random.default_rng(0)
    names = sorted(plan)
    n_shards, weight_map = 3, {}
    for s in range(n_shards):
        part = {f"model.{n}": rng.standard_normal(
                    plan[n][0].hf_shape).astype(np.float32) * 0.02
                for n in names[s::n_shards]}
        fname = f"model-{s + 1:05d}-of-{n_shards:05d}.safetensors"
        save_file(part, os.path.join(path, fname))
        for n in part:
            weight_map[n] = fname
    with open(os.path.join(path, "model.safetensors.index.json"), "w") as f:
        json.dump({"metadata": {}, "weight_map": weight_map}, f)

    child = textwrap.dedent(f"""
        import ctypes, json, os, sys
        # fix glibc's dynamic mmap threshold at 1 MB so every large
        # buffer is mmap'd and returned to the OS on free — otherwise
        # arena retention adds a nondeterministic hundreds-of-MB floor
        # that has nothing to do with what the loader keeps alive
        try:
            ctypes.CDLL("libc.so.6").mallopt(-3, 1 << 20)
        except Exception:
            pass
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from torchacc_tpu.models.hf import config_from_hf
        from torchacc_tpu.models.hf_stream import (
            resolve_checkpoint_files, stream_params)
        import transformers
        def _status(key):
            with open("/proc/self/status") as f:
                for line in f:
                    if line.startswith(key):
                        return int(line.split()[1]) * 1024
        rss = lambda: _status("VmRSS")
        # NOT getrusage ru_maxrss: on linux it survives execve, so a
        # subprocess inherits the pytest parent's high-water mark.
        # VmHWM belongs to this process's own mm and resets on exec.
        hwm = lambda: _status("VmHWM")
        jnp.ones((8, 8)).sum().item()  # backend warm before baseline
        hf_cfg = transformers.AutoConfig.from_pretrained({path!r})
        cfg = config_from_hf(hf_cfg, param_dtype=jnp.float32)
        baseline = rss()
        params = stream_params(resolve_checkpoint_files({path!r}), cfg,
                               param_dtype=jnp.float32)
        jax.block_until_ready(params)
        final = rss()
        peak = hwm()
        pbytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(params))
        print(json.dumps({{"baseline": baseline, "final": final,
                           "peak": peak, "params_bytes": pbytes}}))
    """)
    r = subprocess.run([sys.executable, "-c", child], capture_output=True,
                       text=True, timeout=420,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
    m = json.loads(r.stdout.strip().splitlines()[-1])
    pb = m["params_bytes"]
    assert pb > 250e6  # the checkpoint is big enough to measure
    load_overhead = m["peak"] - m["baseline"]
    transient = m["peak"] - m["final"]
    # materialising path: torch state dict + stacked numpy copies =
    # >= 2x params on top of the resident arrays.  Streaming: resident
    # params + a transient bounded by ~2 stacked leaves + jit machinery.
    assert load_overhead < 1.5 * pb, (load_overhead, pb, m)
    assert transient < 0.6 * pb, (transient, pb, m)


def test_llama3_70b_abstract_ingestion_dryrun(devices):
    """The 70B-scale leg (BASELINE.json config 3) WITHOUT 140 GB of
    weights: HF's own meta-device module provides the checkpoint header
    (independent source of truth for every tensor name+shape), the plan
    validates it, and the FSDP+TP trainer's resolved shardings cover
    every stacked leaf at the real [80, ...] geometry."""
    from accelerate import init_empty_weights

    import torchacc_tpu as ta
    from torchacc_tpu.models.hf_stream import _tree_get
    from torchacc_tpu.train import accelerate as ta_accelerate
    from torchacc_tpu.train.accelerate import apply_config_to_model
    from torchacc_tpu.train.trainer import Trainer

    hf_cfg = transformers.LlamaConfig(
        vocab_size=128256, hidden_size=8192, intermediate_size=28672,
        num_hidden_layers=80, num_attention_heads=64,
        num_key_value_heads=8, max_position_embeddings=8192,
        rope_theta=500000.0, rms_norm_eps=1e-5, tie_word_embeddings=False)
    with init_empty_weights():
        meta = transformers.AutoModelForCausalLM.from_config(hf_cfg)
    shapes = {k: tuple(v.shape) for k, v in meta.state_dict().items()}

    mc = config_from_hf(hf_cfg, dtype=jnp.bfloat16,
                        param_dtype=jnp.bfloat16)
    validate_checkpoint_header(shapes, mc)

    cfg = ta.Config(dist=ta.DistConfig(
        fsdp=ta.FSDPConfig(size=4, min_weight_size=0),
        tp=ta.TPConfig(size=2)))
    model = TransformerLM(apply_config_to_model(mc, cfg))
    import optax
    trainer = Trainer(model, cfg, optimizer=optax.adamw(1e-4))
    trainer.resolve_shardings()  # abstract only: nothing materialises
    sh = trainer.state_shardings.params

    plan = ingestion_plan(mc)
    total = 0
    for name, ents in plan.items():
        for ent in ents:  # every plan path must resolve
            assert _tree_get(sh, ent.path) is not None, name
        total += int(np.prod(ents[0].hf_shape))
    assert total == 70_553_706_496  # llama-3-70b exact param count


def _tiny_mixtral_cfg(**kw):
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, sliding_window=None,
        tie_word_embeddings=False, attn_implementation="eager")
    base.update(kw)
    return transformers.MixtralConfig(**base)


def test_streamed_mixtral_matches_materialised(tmp_path):
    """Mixtral MoE leaves ([L, E, ...] stacked experts, two-level index)
    stream tensor-for-tensor identical to the materialising converter."""
    torch.manual_seed(5)
    hf_model = transformers.MixtralForCausalLM(_tiny_mixtral_cfg()).eval()
    path = str(tmp_path / "ckpt")
    _save_sharded(hf_model, path, n_shards=3)

    cfg = config_from_hf(hf_model.config, dtype=jnp.float32,
                         param_dtype=jnp.float32)
    ref = params_from_hf_state_dict(hf_model.state_dict(), cfg)
    got = stream_params(resolve_checkpoint_files(path), cfg,
                        param_dtype=jnp.float32)

    ref_flat = jax.tree_util.tree_flatten_with_path(ref)[0]
    got_flat = jax.tree_util.tree_flatten_with_path(got)[0]
    assert [k for k, _ in ref_flat] == [k for k, _ in got_flat]
    for (k, a), (_, b) in zip(ref_flat, got_flat):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(k))


def test_mixtral_8x7b_abstract_ingestion_dryrun(devices):
    """BASELINE config 5 (Mixtral-8x7B) abstractly: HF's meta-device
    module provides the header, the plan validates it, and an
    EP x PP x FSDP trainer's resolved shardings cover every leaf —
    including the [32, 8, ...] stacked-expert ones — without a byte of
    weight data."""
    from accelerate import init_empty_weights

    import optax

    import torchacc_tpu as ta
    from torchacc_tpu.models.hf_stream import _tree_get
    from torchacc_tpu.train.accelerate import apply_config_to_model
    from torchacc_tpu.train.trainer import Trainer

    hf_cfg = transformers.MixtralConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=32, num_attention_heads=32,
        num_key_value_heads=8, num_local_experts=8, num_experts_per_tok=2,
        max_position_embeddings=32768, rope_theta=1e6,
        tie_word_embeddings=False)
    with init_empty_weights():
        meta = transformers.AutoModelForCausalLM.from_config(hf_cfg)
    shapes = {k: tuple(v.shape) for k, v in meta.state_dict().items()}

    mc = config_from_hf(hf_cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    validate_checkpoint_header(shapes, mc)

    cfg = ta.Config(dist=ta.DistConfig(
        pp=ta.PPConfig(size=2, num_micro_batches=2),
        ep=ta.EPConfig(size=2),
        fsdp=ta.FSDPConfig(size=2, min_weight_size=0)))
    model = TransformerLM(apply_config_to_model(mc, cfg))
    trainer = Trainer(model, cfg, optimizer=optax.adamw(1e-4))
    trainer.resolve_shardings()  # abstract only
    sh = trainer.state_shardings.params

    plan = ingestion_plan(mc)
    total = 0
    for name, ents in plan.items():
        for ent in ents:
            assert _tree_get(sh, ent.path) is not None, name
        total += int(np.prod(ents[0].hf_shape))
    assert total == 46_702_792_704  # mixtral-8x7b exact param count


def test_streamed_into_pp_shardings(tmp_path, devices):
    """Streaming into a PP x FSDP layout: the stacked LAYER dim is
    itself sharded over 'pp', so each arriving layer's piece transfer
    drops that leading spec entry and the donated set writes into a
    pp-sharded buffer.  Weights must land exactly and the pipeline must
    train from them."""
    import optax

    import torchacc_tpu as ta
    from torchacc_tpu.train import accelerate

    torch.manual_seed(6)
    hf_model = transformers.LlamaForCausalLM(
        _tiny_llama_cfg(num_hidden_layers=4)).eval()
    path = str(tmp_path / "ckpt")
    _save_sharded(hf_model, path, n_shards=2)

    cfg = ta.Config(dist=ta.DistConfig(
        pp=ta.PPConfig(size=2, num_micro_batches=2),
        fsdp=ta.FSDPConfig(size=2, min_weight_size=0),
        dp=ta.DPConfig(size=2)))
    cfg.compute.dtype = "float32"
    cfg.compute.param_dtype = "float32"
    trainer, _ = accelerate(path, None, cfg, optimizer=optax.adam(1e-3))

    k = trainer.state.params["layers"]["block"]["attn"]["q_proj"]["kernel"]
    assert "pp" in str(k.sharding.spec), k.sharding.spec
    # exact landing: compare the full stacked q kernel against the
    # materialising conversion
    from torchacc_tpu.models.hf import config_from_hf, params_from_hf_state_dict
    mc = config_from_hf(hf_model.config, dtype=jnp.float32,
                        param_dtype=jnp.float32)
    want = params_from_hf_state_dict(hf_model.state_dict(), mc)
    np.testing.assert_array_equal(
        np.asarray(k),
        np.asarray(want["layers"]["block"]["attn"]["q_proj"]["kernel"]))

    ids = np.random.default_rng(0).integers(0, 128, size=(8, 32))
    loss = float(trainer.step({"input_ids": jnp.asarray(ids, jnp.int32)})
                 ["loss"])
    assert np.isfinite(loss)


def test_streamed_qwen3(tmp_path):
    """Qwen3 (qk-norm family) streams: the q_norm/k_norm per-layer
    tensors are covered by the generic qk_norm plan entries."""
    hf_cfg = transformers.Qwen3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, max_position_embeddings=64, rms_norm_eps=1e-6,
        tie_word_embeddings=False, attn_implementation="eager")
    torch.manual_seed(7)
    hf_model = transformers.Qwen3ForCausalLM(hf_cfg).eval()
    path = str(tmp_path / "ckpt")
    _save_sharded(hf_model, path, n_shards=2)

    cfg, params = load_hf_model_streamed(path, dtype=jnp.float32,
                                         param_dtype=jnp.float32)
    assert cfg.qk_norm
    ids = np.random.default_rng(7).integers(0, 128, size=(2, 16))
    ours = TransformerLM(cfg).apply({"params": params},
                                    jnp.asarray(ids, jnp.int32))
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(ids)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4)


def test_streamed_olmo2(tmp_path):
    """OLMo2 streams: post-norm ln1/ln2 mapping + flat-projection
    qk-norm shapes in the plan."""
    hf_cfg = transformers.Olmo2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False, attn_implementation="eager")
    torch.manual_seed(8)
    hf_model = transformers.Olmo2ForCausalLM(hf_cfg).eval()
    path = str(tmp_path / "ckpt")
    _save_sharded(hf_model, path, n_shards=2)

    cfg, params = load_hf_model_streamed(path, dtype=jnp.float32,
                                         param_dtype=jnp.float32)
    assert cfg.norm_placement == "post"
    ids = np.random.default_rng(8).integers(0, 128, size=(2, 16))
    ours = TransformerLM(cfg).apply({"params": params},
                                    jnp.asarray(ids, jnp.int32))
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(ids)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4)


def test_streamed_phi3_packed(tmp_path):
    """Phi-3's packed qkv_proj / gate_up_proj: one checkpoint tensor
    feeds several leaves (multi-entry plan), detected from the header."""
    hf_cfg = transformers.Phi3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, pad_token_id=0,
        tie_word_embeddings=False, attn_implementation="eager")
    torch.manual_seed(9)
    hf_model = transformers.Phi3ForCausalLM(hf_cfg).eval()
    path = str(tmp_path / "ckpt")
    _save_sharded(hf_model, path, n_shards=2)

    cfg, params = load_hf_model_streamed(path, dtype=jnp.float32,
                                         param_dtype=jnp.float32)
    ids = np.random.default_rng(9).integers(0, 128, size=(2, 16))
    ours = TransformerLM(cfg).apply({"params": params},
                                    jnp.asarray(ids, jnp.int32))
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(ids)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4)
    # abstract header validation sees the packed layout too
    validate_checkpoint_header(
        {k: tuple(v.shape) for k, v in hf_model.state_dict().items()}, cfg)


def test_streamed_qwen3_moe(tmp_path):
    """Qwen3-MoE streams: the qwen expert naming (mlp.experts.N.*)
    detected from the header feeds the [L, E, ...] stacked leaves."""
    hf_cfg = transformers.Qwen3MoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=96, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=True,
        max_position_embeddings=64, rms_norm_eps=1e-6,
        tie_word_embeddings=False, attn_implementation="eager")
    torch.manual_seed(10)
    hf_model = transformers.Qwen3MoeForCausalLM(hf_cfg).eval()
    path = str(tmp_path / "ckpt")
    _save_sharded(hf_model, path, n_shards=2)

    cfg, params = load_hf_model_streamed(path, dtype=jnp.float32,
                                         param_dtype=jnp.float32)
    ids = np.random.default_rng(10).integers(0, 128, size=(2, 16))
    ours = TransformerLM(cfg).apply({"params": params},
                                    jnp.asarray(ids, jnp.int32))
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(ids)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4)


@pytest.mark.slow
def test_streamed_full_lifecycle(tmp_path, devices):
    """The complete big-model user journey in miniature: safetensors
    checkpoint -> STREAMED ingestion into FSDP shardings -> train ->
    orbax save -> restore into a DIFFERENT layout -> identical
    continuation.  Closes the loop between the two checkpoint systems
    (HF safetensors in, orbax out)."""
    import optax

    import torchacc_tpu as ta
    from torchacc_tpu.train import accelerate

    torch.manual_seed(11)
    hf_model = transformers.LlamaForCausalLM(
        _tiny_llama_cfg(num_hidden_layers=4)).eval()
    path = str(tmp_path / "hf_ckpt")
    _save_sharded(hf_model, path, n_shards=2)

    rng = np.random.default_rng(0)
    batches = [{"input_ids": jnp.asarray(
        rng.integers(0, 128, size=(8, 32)), jnp.int32)} for _ in range(4)]

    cfg = ta.Config(dist=ta.DistConfig(
        fsdp=ta.FSDPConfig(size=8, min_weight_size=0)))
    cfg.compute.dtype = "float32"
    cfg.compute.param_dtype = "float32"
    t, _ = accelerate(path, None, cfg, optimizer=optax.adam(1e-3))
    for b in batches[:2]:
        t.step(b)
    ck = str(tmp_path / "orbax")
    t.save(ck)
    cont = [float(t.step(b)["loss"]) for b in batches[2:]]

    # resume does NOT need the HF checkpoint again: the orbax save is
    # self-sufficient — build the trainer from the config and restore
    # into a DIFFERENT layout
    mc = config_from_hf(hf_model.config, dtype=jnp.float32,
                        param_dtype=jnp.float32)
    cfg2 = ta.Config(dist=ta.DistConfig(
        dp=ta.DPConfig(size=2),
        fsdp=ta.FSDPConfig(size=4, min_weight_size=0)))
    cfg2.compute.dtype = "float32"
    cfg2.compute.param_dtype = "float32"
    t2, _ = accelerate(mc, None, cfg2, optimizer=optax.adam(1e-3))
    t2.init()
    t2.restore(ck)
    assert int(t2.state.step) == 2
    resumed = [float(t2.step(b)["loss"]) for b in batches[2:]]
    np.testing.assert_allclose(cont, resumed, rtol=1e-6)


def test_streamed_llama_with_biases(tmp_path):
    """attention_bias + mlp_bias checkpoints stream (o_proj and mlp
    bias plan entries)."""
    hf_cfg = _tiny_llama_cfg(attention_bias=True, mlp_bias=True)
    torch.manual_seed(12)
    hf_model = transformers.LlamaForCausalLM(hf_cfg).eval()
    path = str(tmp_path / "ckpt")
    _save_sharded(hf_model, path, n_shards=2)

    cfg, params = load_hf_model_streamed(path, dtype=jnp.float32,
                                         param_dtype=jnp.float32)
    ids = np.random.default_rng(12).integers(0, 128, size=(2, 16))
    ours = TransformerLM(cfg).apply({"params": params},
                                    jnp.asarray(ids, jnp.int32))
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(ids)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4)


def test_streamed_starcoder2(tmp_path):
    """StarCoder2 streams: non-gated c_fc/c_proj MLP entries, biased
    LayerNorm entries (ln1/ln2/final_norm .bias leaves), biases on every
    projection, tied embeddings."""
    hf_cfg = transformers.Starcoder2Config(
        vocab_size=128, hidden_size=64, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, norm_epsilon=1e-5,
        tie_word_embeddings=True, attn_implementation="eager",
        residual_dropout=0.0, embedding_dropout=0.0)
    torch.manual_seed(9)
    hf_model = transformers.Starcoder2ForCausalLM(hf_cfg).eval()
    path = str(tmp_path / "ckpt")
    _save_sharded(hf_model, path, n_shards=2)

    cfg, params = load_hf_model_streamed(path, dtype=jnp.float32,
                                         param_dtype=jnp.float32)
    assert cfg.norm == "layernorm" and cfg.activation == "gelu"
    assert "bias" in params["final_norm"]
    assert "gate_proj" not in params["layers"]["block"]["mlp"]
    ids = np.random.default_rng(9).integers(0, 128, size=(2, 16))
    ours = TransformerLM(cfg).apply({"params": params},
                                    jnp.asarray(ids, jnp.int32))
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(ids)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4)


def test_streamed_cohere(tmp_path):
    """Cohere streams: parallel-block plan (ln1 only, no ln2 entries),
    biasless LayerNorm, tied embeddings, logit_scale binding."""
    hf_cfg = transformers.CohereConfig(
        vocab_size=128, hidden_size=64, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, logit_scale=0.0625,
        tie_word_embeddings=True, attn_implementation="eager")
    torch.manual_seed(14)
    hf_model = transformers.CohereForCausalLM(hf_cfg).eval()
    path = str(tmp_path / "ckpt")
    _save_sharded(hf_model, path, n_shards=2)

    cfg, params = load_hf_model_streamed(path, dtype=jnp.float32,
                                         param_dtype=jnp.float32)
    assert cfg.parallel_block and not cfg.norm_bias
    blk = params["layers"]["block"]
    assert "ln2" not in blk and "bias" not in blk["ln1"]
    ids = np.random.default_rng(14).integers(0, 128, size=(2, 16))
    ours = TransformerLM(cfg).apply({"params": params},
                                    jnp.asarray(ids, jnp.int32))
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(ids)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4)


def test_streamed_nemotron(tmp_path):
    """Nemotron streams: gate-free up/down plan entries + layernorm1p
    bias entries."""
    hf_cfg = transformers.NemotronConfig(
        vocab_size=128, hidden_size=64, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, partial_rotary_factor=0.5,
        tie_word_embeddings=False, attn_implementation="eager")
    torch.manual_seed(16)
    hf_model = transformers.NemotronForCausalLM(hf_cfg).eval()
    path = str(tmp_path / "ckpt")
    _save_sharded(hf_model, path, n_shards=2)

    cfg, params = load_hf_model_streamed(path, dtype=jnp.float32,
                                         param_dtype=jnp.float32)
    blk = params["layers"]["block"]
    assert "gate_proj" not in blk["mlp"] and "bias" in blk["ln1"]
    ids = np.random.default_rng(16).integers(0, 128, size=(2, 16))
    ours = TransformerLM(cfg).apply({"params": params},
                                    jnp.asarray(ids, jnp.int32))
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(ids)).logits.float().numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4)
