"""LM training benchmark CLI.

Reference equivalent: ``benchmarks/transformer.py`` (GPT-2/HF CLM loop
with --dp/--fsdp/--pp/--gc/--fp16/--bf16/--profile flags,
transformer.py:33-220).  Trains a zoo preset on synthetic data and
reports tokens/s, step time, and MFU.

Examples:
  python benchmarks/train_lm.py --model llama-tiny --steps 20
  python benchmarks/train_lm.py --model gpt2 --fsdp 8 --gc
  python benchmarks/train_lm.py --model llama3-8b --fsdp 16 --tp 4 \
      --seq 4096 --batch 16 --profile /tmp/trace
  python benchmarks/train_lm.py --config my_config.json --json
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

# repo root on sys.path so `bench` (peak_flops table) resolves when this
# script is run directly (sys.path[0] is benchmarks/ in that case)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="torchacc_tpu LM benchmark")
    p.add_argument("--model", default="llama-tiny")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=512)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--lr", type=float, default=1e-4)
    # parallelism (reference: --dp/--fsdp/--tp/--pp flags)
    p.add_argument("--dp", type=int, default=-1)
    p.add_argument("--fsdp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--pp_microbatches", type=int, default=None)
    p.add_argument("--pp_schedule", default="gpipe",
                   choices=["gpipe", "1f1b"])
    p.add_argument("--pp_virtual", type=int, default=1,
                   help="interleaved virtual stages per device")
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--sp_mode", default="ulysses",
                   choices=["ulysses", "ring", "2d"])
    p.add_argument("--sp_intra", type=int, default=None)
    p.add_argument("--ep", type=int, default=1)
    # memory / numerics (reference: --gc/--fp16/--bf16)
    p.add_argument("--gc", action="store_true")
    p.add_argument("--gc_policy", default="nothing")
    p.add_argument("--gc_cnt", type=int, default=None,
                   help="remat only the first N layers")
    p.add_argument("--offload_activations", action="store_true")
    p.add_argument("--attn_dropout", type=float, default=0.0)
    p.add_argument("--fp16", action="store_true")
    p.add_argument("--fp32", action="store_true")
    p.add_argument("--bf16_shadow", action="store_true",
                   help="compute.bf16_compute_params: bf16 param shadow "
                        "in opt state (main-params AMP, docs/PERF.md)")
    p.add_argument("--no_flash", action="store_true")
    p.add_argument("--grad_accum", type=int, default=1)
    p.add_argument("--profile", default=None, metavar="LOGDIR")
    p.add_argument("--json", action="store_true", help="one JSON line out")
    p.add_argument("--config", default=None, metavar="JSON_FILE",
                   help="full ta.Config as JSON (overrides parallelism/"
                        "memory/numerics flags)")
    return p.parse_args(argv)


def _config_from_flags(args, dtype):
    import torchacc_tpu as ta
    return ta.Config(
        compute=ta.ComputeConfig(dtype=dtype,
                                 flash_attention=not args.no_flash,
                                 bf16_compute_params=args.bf16_shadow),
        memory=ta.MemoryConfig(gc=args.gc, gc_policy=args.gc_policy,
                               gc_cnt=args.gc_cnt,
                               offload_activations=args.offload_activations),
        dist=ta.DistConfig(
            dp=ta.DPConfig(size=args.dp),
            fsdp=ta.FSDPConfig(size=args.fsdp),
            tp=ta.TPConfig(size=args.tp),
            pp=ta.PPConfig(size=args.pp,
                           num_micro_batches=(args.pp_microbatches
                                              or max(1, 2 * args.pp)),
                           schedule=args.pp_schedule,
                           virtual_stages=args.pp_virtual),
            sp=ta.SPConfig(size=args.sp, mode=args.sp_mode,
                           intra_size=args.sp_intra),
            ep=ta.EPConfig(size=args.ep),
        ),
        grad_accum=args.grad_accum,
    )


def main(argv=None) -> int:
    args = parse_args(argv)

    import jax
    import numpy as np
    import optax

    import torchacc_tpu as ta
    from torchacc_tpu.models import get_preset
    from torchacc_tpu.train import accelerate

    if args.config:
        with open(args.config) as f:
            cfg = ta.Config.from_dict(json.load(f))
        dtype = cfg.compute.dtype
    else:
        dtype = ("float16" if args.fp16
                 else ("float32" if args.fp32 else "bfloat16"))
        cfg = _config_from_flags(args, dtype)

    mc = get_preset(args.model, max_seq_len=max(args.seq, 8),
                    attn_dropout=args.attn_dropout)
    trainer, _ = accelerate(mc, None, cfg, optimizer=optax.adamw(args.lr))
    trainer.init()

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, mc.vocab_size, size=(args.batch, args.seq)).astype(np.int32)}

    m = None
    for _ in range(args.warmup):
        m = trainer.step(batch)
    if m is not None:
        float(m["loss"])  # drain warmup before timing

    if args.profile:
        from torchacc_tpu.utils.profiling import trace
        ctx = trace(args.profile)
    else:
        ctx = contextlib.nullcontext()
    # steps dispatch asynchronously, so wall time over the whole loop with
    # one final sync is the only honest per-step measure
    with ctx:
        t0 = time.perf_counter()
        for _ in range(args.steps):
            m = trainer.step(batch)
        loss = float(m["loss"])  # sync
        total = time.perf_counter() - t0
    dt = total / max(args.steps, 1)

    n_chips = len(jax.devices())
    tokens_per_sec = args.batch * args.seq / dt
    flops_per_token = (6.0 * mc.num_params()
                       + 6.0 * mc.num_layers * mc.hidden_size * args.seq)
    from bench import peak_flops  # repo-root bench helpers
    dev = jax.devices()[0]
    # a CPU run is a functional one: it has no MFU
    mfu = (flops_per_token * tokens_per_sec / (peak_flops(dev) * n_chips)
           if dev.platform != "cpu" else None)

    result = {
        "model": args.model,
        "loss": round(loss, 4),
        "step_time_s": round(dt, 4),
        "tokens_per_sec": round(tokens_per_sec, 1),
        "tokens_per_sec_per_chip": round(tokens_per_sec / n_chips, 1),
        "mfu": None if mfu is None else round(mfu, 4),
        "params_m": round(mc.num_params() / 1e6, 1),
        "mesh": dict(trainer.mesh.shape),
        "dtype": dtype,
    }
    if args.json:
        print(json.dumps(result))
    else:
        for k, v in result.items():
            print(f"{k:>24}: {v}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
