"""`accelerate()` — the one-call entry point.

Reference: ``torchacc.accelerate(model, dataloader, config)``
(accelerate.py:49-149) validates config, initialises the distributed
backend, wraps the dataloader in an AsyncLoader, applies kernel patches,
and composes the parallel strategies.  TPU-native: validate → build mesh
→ build Trainer (sharded init + jitted step; the shardings *are* the
strategy composition) → wrap the loader.  No patches: kernel selection
is the model's ``attention_impl`` and the ops dispatch layer.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Iterable, Optional, Tuple

import jax.numpy as jnp
import optax

from torchacc_tpu.config import Config
from torchacc_tpu.data.async_loader import AsyncLoader
from torchacc_tpu.parallel.sharding import make_rules
from torchacc_tpu.models.transformer import ModelConfig, TransformerLM
from torchacc_tpu.train.trainer import Trainer

_DTYPES = {"bfloat16": jnp.bfloat16, "float16": jnp.float16,
           "float32": jnp.float32}


def apply_config_to_model(mc: ModelConfig, config: Config) -> ModelConfig:
    """Fold framework-level compute/memory settings into the model config
    (the reference does this via patches + wrapper kwargs; here it is a
    dataclass transform)."""
    updates = dict(
        dtype=_DTYPES[config.compute.dtype],
        param_dtype=_DTYPES[config.compute.param_dtype],
        attention_impl=(config.compute.attention_impl
                        if config.compute.flash_attention else "xla"),
        # offload_activations forces the host-offload remat policy
        # (reference utils/cpu_offload.py analogue); gc_cls/gc_cnt select
        # which submodules / how many layers remat (utils/checkpoint.py:67-81)
        remat=config.memory.gc or config.memory.offload_activations,
        remat_policy=("offload_dots" if config.memory.offload_activations
                      else config.memory.gc_policy),
        remat_cls=(tuple(config.memory.gc_cls)
                   if config.memory.gc_cls else None),
        remat_cnt=config.memory.gc_cnt,
        quant=config.compute.quant,
        quant_sites=tuple(config.compute.quant_sites),
        quant_amax_history_len=config.compute.quant_amax_history_len,
        quant_impl=config.compute.quant_impl,
        overlap_fsdp=config.perf.overlap_fsdp,
        context_parallel=config.dist.sp.size > 1,
        pp_size=config.dist.pp.size,
        pp_num_micro=config.dist.pp.num_micro_batches,
        pp_virtual=config.dist.pp.virtual_stages,
        logical_axis_rules=tuple(make_rules(config)),
    )
    # the layer loop, where the model config leaves it open: unrolled
    # when every device holds the layer parameters whole (one chip, or
    # plain data parallelism; sp splits activations, not parameters) —
    # no [L, ...] stacking of saved residuals and weight gradients, and
    # less live memory; the scan otherwise — under ZeRO-3 the unrolled
    # step gathers many layers' weights at once and does not fit
    # (PERF.md sections 4 and 7, PR 38); pp, tp and ep keep the scan
    # until a cell reads them
    if mc.scan_layers is None:
        dist = config.dist
        updates["scan_layers"] = any(
            axis.size > 1 for axis in (dist.fsdp, dist.pp, dist.tp, dist.ep))
    # expert capacity: the dist-level knob feeds the model's dispatcher;
    # an explicit model-config value wins
    if (config.dist.ep.capacity_factor is not None
            and mc.num_experts > 0 and mc.moe_capacity_factor is None):
        updates["moe_capacity_factor"] = config.dist.ep.capacity_factor
    return dataclasses.replace(mc, **updates)


def accelerate(
    model: Any,
    dataloader: Optional[Iterable] = None,
    config: Optional[Config] = None,
    optimizer: Optional[optax.GradientTransformation] = None,
    **trainer_kwargs,
) -> Tuple[Trainer, Optional[AsyncLoader]]:
    """Returns ``(trainer, async_loader)``.

    ``model`` may be a :class:`ModelConfig` (zoo model is built for
    you), any flax Module following the ``(input_ids, positions,
    segment_ids)`` call convention, or an HF torch model / checkpoint
    path (reference: ``ta.accelerate(hf_model, config)``
    accelerate.py:49-149) — the weights convert through ``models/hf.py``
    and the trainer comes back already initialised from them.
    """
    config = config or Config()
    config.validate()
    import jax
    # set unconditionally ('default' -> None) so one accelerate() call's
    # precision choice cannot leak into the next
    jax.config.update(
        "jax_default_matmul_precision",
        None if config.compute.matmul_precision == "default"
        else config.compute.matmul_precision)
    hf_params = None
    stream_files = None
    if isinstance(model, str):
        # safetensors checkpoints stream tensor-by-tensor into the
        # target shardings (bounded host memory — the 70B-scale path;
        # reference capability: LOW_CPU_MEM_USAGE deferred init,
        # accelerate.py:13-17,114-119).  Only the config is read here;
        # weights stream AFTER the trainer resolves shardings.
        from torchacc_tpu.models.hf_stream import resolve_checkpoint_files
        stream_files = resolve_checkpoint_files(model)
        if stream_files is None and not os.path.isdir(model):
            from torchacc_tpu.utils.logger import logger
            logger.warning(
                f"{model!r} is not a local directory — falling back to "
                f"the materialising from_pretrained load (full model in "
                f"host RAM).  For bounded-memory streamed ingestion, "
                f"download the snapshot and pass its local path.")
        if stream_files is not None:
            from torchacc_tpu.models.hf_stream import (
                checkpoint_tensor_names,
                streamable_names,
            )
            stream_names = checkpoint_tensor_names(model)
            if stream_names is not None \
                    and not streamable_names(stream_names):
                # e.g. GPT-2's Conv1D layout — the stream plan does not
                # map it; the materialising converter below does
                stream_files = None
        if stream_files is not None:
            import transformers

            from torchacc_tpu.models.hf import config_from_hf
            mc = config_from_hf(
                transformers.AutoConfig.from_pretrained(model),
                dtype=_DTYPES[config.compute.dtype],
                param_dtype=_DTYPES[config.compute.param_dtype])
            model = mc
    if isinstance(model, str) or hasattr(model, "state_dict"):
        # HF torch model (or a .bin-only checkpoint path): materialising
        # conversion, then fold the framework config in like the zoo path
        from torchacc_tpu.models.hf import load_hf_model
        mc, hf_params = load_hf_model(
            model, dtype=_DTYPES[config.compute.dtype],
            param_dtype=_DTYPES[config.compute.param_dtype])
        model = mc
    if isinstance(model, ModelConfig):
        mc = model
        model = TransformerLM(apply_config_to_model(model, config))
    trainer = Trainer(model, config, optimizer=optimizer, **trainer_kwargs)
    if stream_files is not None:
        from torchacc_tpu.models.hf_stream import stream_params
        trainer.resolve_shardings()
        with jax.sharding.set_mesh(trainer.mesh):
            params = stream_params(
                stream_files, mc,
                shardings=trainer.state_shardings.params,
                param_dtype=_DTYPES[config.compute.param_dtype],
                tensor_names=stream_names)
        trainer.init_from_params(params)
    elif hf_params is not None:
        trainer.init_from_params(hf_params)
    loader = None
    if dataloader is not None:
        loader = AsyncLoader(dataloader, config, mesh=trainer.mesh)
    return trainer, loader
