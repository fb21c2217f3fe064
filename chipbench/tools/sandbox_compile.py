"""Compile a cell's programs for a DESCRIBED v5e:2x2 in the sandbox
(no chip): do they fit, how many bytes a device, how many kernels.

    JAX_PLATFORMS=cpu python3 -m chipbench.tools.sandbox_compile \
        --workload <cell> [--depth N] [--batch N]

A train cell's step; a serve cell's decode program and its two
single-sequence prefill programs (final and non-final chunk) at the
cell's slots, pool and chunk.  A compile that passes is a compile,
never a chip run.  This is how the depths written in
``chipbench/configs/*.json`` were fixed (PERF.md section 4).  A tool,
not part of a run: it reaches into the program's privates
(``Trainer._build_train_step``, ``PagedDecoder._decode/_prefill``).
"""

from __future__ import annotations

import argparse
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--depth", type=int)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--scan", type=int, choices=(0, 1))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    import torchacc_tpu.ops.attn as attn
    import torchacc_tpu.ops.flash_attention as fa
    from chipbench import program, spec
    from torchacc_tpu.models.transformer import TransformerLM
    from torchacc_tpu.train.accelerate import apply_config_to_model
    from torchacc_tpu.train.trainer import Trainer

    jax.config.update("jax_enable_compilation_cache", False)
    fa._interpret = lambda: False
    attn._on_tpu = lambda: True

    cell = spec.Cell(args.workload)
    traffic = cell.traffic
    depth = args.depth or cell.depth
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if cell.mode == "serve":
        return _serve(cell, depth, topo)
    batch, seq = args.batch or traffic["batch"], traffic["seq"]
    overrides = dict(traffic.get("model_overrides", {}))
    if args.scan is not None:
        overrides["scan_layers"] = bool(args.scan)
    mc = program.model_config(cell.published, depth, max_seq_len=seq,
                              **overrides)
    cfg = program.framework_config(traffic["settings"], 0)
    devices = topo.devices[:cell.chips]
    names = tuple(cfg.dist.topology)
    sizes = cfg.dist.axis_sizes(len(devices))
    mesh = Mesh(np.array(devices).reshape([sizes[a] for a in names]), names)
    model = TransformerLM(apply_config_to_model(mc, cfg))
    trainer = Trainer(model, cfg, optimizer=program.optimizer(
        traffic["optimizer"]), mesh=mesh)
    state = trainer.abstract_state()
    b = {"input_ids": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    sh = trainer._batch_shardings(b)
    b = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh[k])
         for k, v in b.items()}
    t0 = time.perf_counter()
    with jax.sharding.set_mesh(mesh):
        compiled = trainer._build_train_step(b).lower(state, b).compile()
    dt = time.perf_counter() - t0
    m = compiled.memory_analysis()
    text = compiled.as_text()
    n_params = sum(int(np.prod(x.shape)) for x in
                   jax.tree.leaves(state.params))
    print(f"COMPILED (not run) {cell.name} depth={depth} batch={batch} "
          f"seq={seq} scan_layers={mc.scan_layers} chips={cell.chips} "
          f"params={n_params / 1e6:.1f}M compile_s={dt:.1f} "
          f"{_memory(m)} "
          f"tpu_custom_call={text.count('tpu_custom_call')} "
          f"all-gather={text.count(' all-gather')} "
          f"reduce-scatter={text.count(' reduce-scatter')} "
          f"all-reduce={text.count(' all-reduce')}")
    return 0


def _memory(m) -> str:
    return (f"arguments={m.argument_size_in_bytes / 2**30:.2f}GiB "
            f"temporaries={m.temp_size_in_bytes / 2**30:.2f}GiB "
            f"outputs={m.output_size_in_bytes / 2**30:.2f}GiB "
            f"aliased={m.alias_size_in_bytes / 2**30:.2f}GiB")


def _serve(cell, depth, topo) -> int:
    """The decode program and the single-sequence prefill programs of a
    serve cell, as the scheduler calls them (``Scheduler._decode_once``,
    ``_prefill_one``), on abstract arguments placed on one described
    chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import torchacc_tpu.ops.paged_attention as pa
    from chipbench import program
    from torchacc_tpu.serve.kv_cache import blocks_needed
    from torchacc_tpu.serve.scheduler import PagedDecoder

    pa._interpret = lambda: False
    traffic = cell.traffic
    mc = program.model_config(cell.published, depth,
                              max_seq_len=traffic["max_seq_len"],
                              param_dtype=traffic["param_dtype"])
    sc = program.framework_config(traffic["settings"], 0).serve
    decoder = PagedDecoder(mc, sc, traffic["require_impl"])
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one)

    dtype = jnp.dtype(traffic["param_dtype"])
    weights, layout = cell.weights(), cell.layout()
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(
            lambda k: layout.to_program_params(
                weights.make(k, cell.published, depth, dtype), mc),
            weights.base_key(0)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    pool = sds((depth, sc.num_blocks, mc.kv_heads, sc.block_size,
                mc.head_size), mc.dtype)
    s = sc.max_slots
    mb = min(sc.num_blocks - 1, blocks_needed(
        mc.max_seq_len + sc.decode_depth, sc.block_size))
    carry = {"tok": sds((s,), "int32"), "key": sds((s, 2), "uint32")}
    i32 = sds((), "int32")
    programs = {
        "decode": lambda: decoder._decode.lower(
            params, (pool, pool), carry, sds((s, mb), "int32"),
            sds((s,), "int32"), sds((s,), "bool"), sds((s,), "float32"),
            sds((s,), "int32"), sds((s,), "float32"), True),
        "prefill_chunk": lambda: decoder._prefill.lower(
            params, (pool, pool), sds((mb,), "int32"), i32,
            sds((sc.prefill_chunk,), "int32"), i32, False),
        "prefill_final_chunk": lambda: decoder._prefill.lower(
            params, (pool, pool), sds((mb,), "int32"), i32,
            sds((sc.prefill_chunk,), "int32"), i32, True)}
    pool_gib = 2 * pool.size * jnp.dtype(mc.dtype).itemsize / 2**30
    for name, lower in programs.items():
        t0 = time.perf_counter()
        compiled = lower().compile()
        dt = time.perf_counter() - t0
        text = compiled.as_text()
        print(f"COMPILED (not run) {cell.name} {name} depth={depth} "
              f"slots={s} num_blocks={sc.num_blocks} "
              f"block_size={sc.block_size} chunk={sc.prefill_chunk} "
              f"table_blocks={mb} params={n_params / 1e6:.1f}M "
              f"pools={pool_gib:.2f}GiB compile_s={dt:.1f} "
              f"{_memory(compiled.memory_analysis())} "
              f"tpu_custom_call={text.count('tpu_custom_call')}",
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
