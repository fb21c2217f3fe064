#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls,
at the published widths of Mistral-7B-v0.3 with the depth cut to what
one 16 GB TPU v5e chip holds and weights drawn from ``--seed``:

- **train**: ``ta.accelerate(ModelConfig, loader, Config)`` ->
  ``Trainer.step`` / ``Trainer.fit`` for a few steps on one repeated
  batch; the loss must be finite and fall, and the compiled step must
  hold the flash-attention kernels.
- **serve**: ``ServeEngine`` -> scheduler -> paged KV pool, two waves of
  mixed-length greedy requests; every request must complete, the decode
  and prefill programs must hold the paged-attention kernel, the kernel
  must agree with the jnp gather path on the live pool, and the tokens
  must agree with an ``attention_impl="xla"`` engine on the same weights.

``--chips 4`` runs instead the sharded-training phase (``fsdp=4`` and
``fsdp=2 x tp=2``) against the one-chip loss sequence from the same
seed, and nothing else.

One process; it is the only one that touches JAX.  Without a TPU it
exits non-zero before any phase.  A phase that raises ends the run with
its traceback.  The lines it prints are smoke lines, not metrics.  The
last line of standard output is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import types

# mistralai/Mistral-7B-v0.3 config.json.  Widths are never cut.
MISTRAL_7B_V03 = dict(
    model_type="mistral", hidden_size=4096, intermediate_size=14336,
    num_attention_heads=32, num_key_value_heads=8, head_dim=None,
    num_hidden_layers=32, vocab_size=32768, max_position_embeddings=32768,
    rms_norm_eps=1e-5, rope_theta=1e6, sliding_window=None,
    tie_word_embeddings=False, hidden_act="silu",
)

SEQ = 4096                   # train sequence length and serve max_seq_len
# greedy tokens of the kernel and the xla engine must agree wherever the
# reference's top-2 logit margin exceeds this (seeded random weights give
# near-ties; bf16 attention differences move logits by about a hundredth)
MARGIN_TOL = 0.1
# paged kernel vs the jnp gather path on the live bf16 pool: largest
# absolute difference over the largest output, i.e. two bf16 ulps
KERNEL_RTOL = 2.0 ** -6
# sharded vs one-chip loss, per step: relative above a loss of 1, absolute
# below it (one repeated batch is memorised within a few steps)
LOSS_RTOL = 1e-2


def mistral_config(depth: int, **overrides):
    """Mistral-7B-v0.3 through the repo's own HF ingest, ``depth`` layers."""
    from torchacc_tpu.models.hf import config_from_hf
    return config_from_hf(types.SimpleNamespace(**MISTRAL_7B_V03),
                          num_layers=depth, **overrides)


def _per_layer_params(mc) -> int:
    attn = mc.hidden_size * mc.head_size * 2 * (mc.num_heads + mc.kv_heads)
    return attn + 3 * mc.hidden_size * mc.intermediate_size


def train_depth(mc, hbm_bytes: int, batch: int, seq: int):
    """Layers one chip trains: f32 params + two f32 Adam moments + the
    bf16 compute copy are 14 bytes a parameter of state; the step adds
    bf16 grads (2 bytes a parameter) and, by the compiler's count for
    this model, 0.9 GB of logits and saved activations a sequence of
    4096 — all within 92% of the chip's memory."""
    fixed = 2 * mc.vocab_size * mc.hidden_size * 16
    temps = int(0.9e9 * batch * seq / 4096)
    layer = _per_layer_params(mc) * 16
    depth = int((0.92 * hbm_bytes - fixed - temps) // layer)
    why = (f"16 B/param (f32 params + Adam m,v + bf16 copy + bf16 grads): "
           f"embedding+head {fixed / 1e9:.1f} GB, {layer / 1e9:.1f} GB a "
           f"layer, ~{temps / 1e9:.1f} GB of logits and saved activations, "
           f"within 92% of {hbm_bytes / 1e9:.1f} GB")
    return max(1, min(depth, mc.num_layers)), why


def serve_depth(mc, hbm_bytes: int, pool_tokens: int):
    """Layers one chip serves: bf16 weights, the KV pool (one buffer
    for keys and one for values, updated in place), and ~3 GB for the
    widest step's temporaries."""
    fixed = 2 * mc.vocab_size * mc.hidden_size * 2 + int(3e9)
    layer = (_per_layer_params(mc) * 2
             + 2 * pool_tokens * mc.kv_heads * mc.head_size * 2)
    depth = int((0.7 * hbm_bytes - fixed) // layer)
    why = (f"bf16 weights {_per_layer_params(mc) * 2 / 1e9:.2f} GB a layer "
           f"+ a {pool_tokens}-token KV pool, 3 GB of step "
           f"temporaries, within 70% of {hbm_bytes / 1e9:.1f} GB")
    return max(1, min(depth, mc.num_layers)), why


def _param_count(tree) -> int:
    import jax
    return sum(int(x.size) for x in jax.tree.leaves(tree))


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _device_share(tree):
    """Largest fraction of ``tree``'s bytes that any one device holds."""
    import jax
    held, total = {}, 0
    for leaf in jax.tree.leaves(tree):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            held[sh.device] = held.get(sh.device, 0) + sh.data.nbytes
    return max(held.values()) / total, len(held)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(mc, *, batch: int, seq: int, steps: int, seed: int,
                devices=None, fsdp: int = 1, tp: int = 1,
                require_kernels: bool = True, label: str = "train"):
    """``accelerate()`` -> ``Trainer`` for ``steps`` steps on one repeated
    seeded batch.  Returns the loss sequence and what was observed."""
    import jax
    import numpy as np
    import optax

    import torchacc_tpu as ta
    from torchacc_tpu.parallel.mesh import build_mesh

    cfg = ta.Config()
    cfg.seed = seed
    cfg.memory.gc = True
    cfg.memory.gc_policy = "save_attn_mlp"
    cfg.compute.bf16_compute_params = True
    cfg.dist.fsdp.size = fsdp
    cfg.dist.tp.size = tp
    cfg.validate()
    devices = list(devices if devices is not None else jax.devices())
    mesh = build_mesh(cfg.dist, devices=devices[:fsdp * tp])

    rng = np.random.default_rng(seed)
    one_batch = {"input_ids": rng.integers(
        0, mc.vocab_size, size=(batch, seq)).astype(np.int32)}
    trainer, loader = ta.accelerate(
        mc, [one_batch] * steps, cfg, optimizer=optax.adamw(1e-4), mesh=mesh)
    trainer.init()
    n_params = _param_count(trainer.state.params)
    share, n_holders = _device_share(
        (trainer.state.params, trainer.state.opt_state))

    batches = iter(loader)
    first = next(batches)
    trainer._ensure_compiled(first)
    t0 = time.perf_counter()
    with jax.sharding.set_mesh(trainer.mesh):
        compiled = trainer._train_step.lower(trainer.state, first).compile()
    compile_s = time.perf_counter() - t0
    kernels = compiled.as_text().count("tpu_custom_call")
    if require_kernels and kernels < 3:
        raise AssertionError(
            f"{label}: the compiled step holds {kernels} tpu_custom_call "
            f"(flash fwd, dq, dkv expected) — attention took the XLA path")

    losses = [float(trainer.step(first)["loss"])]
    t0 = time.perf_counter()
    history = trainer.fit(batches, max_steps=steps - 1, log_every=1)
    jax.block_until_ready(trainer.state)
    step_s = (time.perf_counter() - t0) / max(1, steps - 1)
    losses += [float(r["loss"]) for r in history]

    if len(losses) != steps or not all(np.isfinite(losses)):
        raise AssertionError(f"{label}: losses {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{label}: loss did not fall: {losses}")
    out = dict(losses=losses, kernels=kernels, n_params=n_params,
               compile_s=compile_s, step_s=step_s, device_share=share,
               holders=n_holders, peak_bytes=_peak_bytes(devices[0]))
    print(f"[{label}] depth={mc.num_layers} params={n_params / 1e6:.1f}M "
          f"mesh={dict(trainer.mesh.shape)} batch={batch} seq={seq} "
          f"kernels_in_step={kernels} compile_s={compile_s:.1f} "
          f"step_s={step_s:.3f} peak_bytes_in_use={out['peak_bytes']} "
          f"max_device_share={share:.3f}", flush=True)
    print(f"[{label}] losses " + " ".join(f"{x:.4f}" for x in losses),
          flush=True)
    return out


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _run_engine(engine, prompts, max_new, wave_steps, between=None):
    """Submit the first half, step until they are mid-decode, submit the
    rest (``between`` runs at that moment), run to completion.  Returns
    the Results in submit order and how many slots were mid-decode."""
    from torchacc_tpu.serve import Request
    half = len(prompts) // 2
    ids = [engine.submit(Request(prompt_ids=p, max_new_tokens=max_new))
           for p in prompts[:half]]
    for _ in range(wave_steps):
        engine.step()
    mid_decode = int(engine.scheduler.active.sum())
    if mid_decode == 0:
        raise AssertionError("serve: no sequence is mid-decode when the "
                             "second wave arrives")
    ids += [engine.submit(Request(prompt_ids=p, max_new_tokens=max_new))
            for p in prompts[half:]]
    seen = between(engine) if between is not None else None
    engine.run()
    return [engine.result(i) for i in ids], mid_decode, seen


def _live_pool_check(engine):
    """Kernel vs the jnp gather path on the engine's live pool (the
    whole [L, NB, BS, KH*D] stack, read at its last layer), block tables
    and context lengths: a decode-shaped query for every active slot
    and a prefill-chunk-shaped one for those that hold a chunk.
    Returns the largest absolute difference over the largest output."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchacc_tpu.ops.paged_attention import paged_attention
    sched = engine.scheduler
    cfg = engine.cfg
    chunk = sched.serve_cfg.prefill_chunk
    kp, vp = sched.pools["k"], sched.pools["v"]
    layer = cfg.num_layers - 1
    active = np.flatnonzero(sched.active)
    worst = 0.0
    for t, slots in ((1, active),
                     (chunk, active[sched.seq_lens[active] >= chunk])):
        if slots.size == 0:
            raise AssertionError(
                f"serve: no active slot holds {t} tokens for the live-pool "
                f"comparison (context lengths {sched.seq_lens[active]})")
        ctx = jnp.array(sched.seq_lens[slots])
        q = jax.random.normal(
            jax.random.PRNGKey(t),
            (slots.size, t, cfg.num_heads, cfg.head_size), kp.dtype)
        args = (q, kp, vp, jnp.array(sched.tables[slots]), ctx, ctx - t)
        got = paged_attention(*args, layer=layer, impl=sched.decoder.impl)
        ref = paged_attention(*args, layer=layer, impl="xla")
        got, ref = got.astype(jnp.float32), ref.astype(jnp.float32)
        worst = max(worst, float(jnp.max(jnp.abs(got - ref))
                                 / jnp.max(jnp.abs(ref))))
    return worst


def _program_kernels(engine) -> dict:
    """tpu_custom_call count of the compiled decode and prefill programs,
    lowered with the engine's own live arguments."""
    import jax.numpy as jnp
    sched = engine.scheduler
    dec = sched.decoder
    pools = sched.pools
    tables, active, temp, top_k, top_p = sched._dev_stable_arrays()
    decode = dec._decode.lower(
        sched.params, pools, sched.carry, {"blocks": tables},
        jnp.array(sched.seq_lens), active, temp, top_k, top_p,
        True).compile().as_text()
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    chunk = sched.serve_cfg.prefill_chunk
    prefill = dec._prefill.lower(
        sched.params, pools, {"blocks": tables[0]}, i32(0),
        jnp.zeros((chunk,), jnp.int32), i32(1), True).compile().as_text()
    return {"decode": decode.count("tpu_custom_call"),
            "prefill": prefill.count("tpu_custom_call")}


def serve_phase(mc, *, prompt_lens, max_new: int, seed: int,
                block_size: int, num_blocks: int, max_slots: int,
                prefill_chunk: int, wave_steps: int,
                require_kernels: bool = True):
    """``ServeEngine`` with the default attention path against an
    ``attention_impl="xla"`` engine on the same weights."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    import torchacc_tpu as ta
    from torchacc_tpu.models import TransformerLM
    from torchacc_tpu.serve import ServeEngine

    model = TransformerLM(mc)
    params = jax.jit(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"])(jax.random.PRNGKey(seed))
    n_params = _param_count(params)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, mc.vocab_size, size=n).tolist()
               for n in prompt_lens]

    def config():
        cfg = ta.Config()
        cfg.serve.block_size = block_size
        cfg.serve.num_blocks = num_blocks
        cfg.serve.max_slots = max_slots
        cfg.serve.prefill_chunk = prefill_chunk
        return cfg

    t0 = time.perf_counter()
    engine = ServeEngine(model, params, config())
    impl = engine.scheduler.decoder.impl
    results, mid_decode, kernel_err = _run_engine(
        engine, prompts, max_new, wave_steps, between=_live_pool_check)
    wall_s = time.perf_counter() - t0
    if any(r.finish_reason != "length" or len(r.tokens) != max_new
           for r in results):
        raise AssertionError("serve: a request did not complete: " + str(
            [(r.finish_reason, len(r.tokens)) for r in results]))
    if kernel_err > KERNEL_RTOL:
        raise AssertionError(
            f"serve: paged attention ({impl}) differs from the jnp gather "
            f"path on the live pool by {kernel_err:.4f} of the largest "
            f"output > {KERNEL_RTOL}")
    t0 = time.perf_counter()
    kernels = _program_kernels(engine)
    lower_s = time.perf_counter() - t0
    if require_kernels and (impl != "pallas" or not all(kernels.values())):
        raise AssertionError(
            f"serve: attention resolved to {impl!r}; tpu_custom_call in "
            f"decode/prefill programs: {kernels}")
    gaps = [g for r in results for g in r.token_latencies_s]
    engine.close()
    del engine

    ref_model = TransformerLM(dataclasses.replace(mc, attention_impl="xla"))
    ref_engine = ServeEngine(ref_model, params, config())
    ref_results, _, _ = _run_engine(ref_engine, prompts, max_new, wave_steps)
    ref_engine.close()
    del ref_engine

    # where the two engines first part ways, the reference's own top-2
    # margin at that position (teacher-forced on the shared prefix) must
    # be inside the tolerance; later tokens follow different prefixes
    agree = compared = 0
    for prompt, got, ref in zip(prompts, results, ref_results):
        same = next((i for i, (a, b) in enumerate(zip(got.tokens, ref.tokens))
                     if a != b), max_new)
        agree += same
        compared += min(same + 1, max_new)
        if same == max_new:
            continue
        ids = jnp.asarray([prompt + ref.tokens[:same]], jnp.int32)
        logits = ref_model.apply({"params": params}, ids)[0, -1]
        top2 = jax.lax.top_k(logits.astype(jnp.float32), 2)[0]
        margin = float(top2[0] - top2[1])
        print(f"[serve] request of {len(prompt)} prompt tokens parts from "
              f"the xla engine at token {same}: top-2 margin {margin:.4f}",
              flush=True)
        if margin > MARGIN_TOL:
            raise AssertionError(
                f"serve: tokens differ from the xla engine at a top-2 "
                f"margin of {margin:.4f} > {MARGIN_TOL}")
    out = dict(impl=impl, kernels=kernels, kernel_err=kernel_err,
               agree=agree, compared=compared, mid_decode=mid_decode,
               n_params=n_params, tokens=[r.tokens for r in results])
    print(f"[serve] depth={mc.num_layers} params={n_params / 1e6:.1f}M "
          f"impl={impl} block_size={block_size} num_blocks={num_blocks} "
          f"max_slots={max_slots} prefill_chunk={prefill_chunk} "
          f"max_seq_len={mc.max_seq_len} requests={len(prompts)} "
          f"prompt_lens={list(prompt_lens)} max_new={max_new} "
          f"mid_decode_at_wave2={mid_decode}", flush=True)
    print(f"[serve] kernels_in_decode={kernels['decode']} "
          f"kernels_in_prefill={kernels['prefill']} "
          f"kernel_vs_gather_rel_to_max={kernel_err:.5f} "
          f"(tol {KERNEL_RTOL}) "
          f"tokens_agreeing_with_xla_engine={agree}/{compared} "
          f"(margin tol {MARGIN_TOL})", flush=True)
    print(f"[serve] wall_s_first_engine={wall_s:.1f} (compiles included) "
          f"relower_s={lower_s:.1f} "
          f"per_token_s_median={float(np.median(gaps)):.4f} "
          f"peak_bytes_in_use={_peak_bytes(jax.devices()[0])}", flush=True)
    return out


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def multichip_phase(mc, *, layouts, batch: int, seq: int, steps: int,
                    seed: int, devices=None, require_kernels: bool = True):
    """The same cut model, seed and batch on one chip and under each
    ``(fsdp, tp)`` layout: loss sequences within ``LOSS_RTOL``, state
    really sharded."""
    import jax
    devices = list(devices if devices is not None else jax.devices())
    kw = dict(batch=batch, seq=seq, steps=steps, seed=seed, devices=devices,
              require_kernels=require_kernels)
    ref = train_phase(mc, label="one-chip", **kw)
    out = {"one-chip": ref}
    for fsdp, tp in layouts:
        label = f"fsdp={fsdp} x tp={tp}"
        got = train_phase(mc, fsdp=fsdp, tp=tp, label=label, **kw)
        worst = max(abs(a - b) / max(abs(b), 1.0)
                    for a, b in zip(got["losses"], ref["losses"]))
        n = fsdp * tp
        print(f"[{label}] loss vs one chip: max diff {worst:.2e} of "
              f"max(loss, 1) (tol {LOSS_RTOL}); largest share of "
              f"params+optimizer bytes on one device "
              f"{got['device_share']:.3f} over {got['holders']} devices",
              flush=True)
        if worst > LOSS_RTOL:
            raise AssertionError(f"{label}: losses {got['losses']} vs one "
                                 f"chip {ref['losses']}")
        if got["holders"] != n or got["device_share"] > 1.2 / n:
            raise AssertionError(
                f"{label}: state is not sharded {n} ways: one device holds "
                f"{got['device_share']:.3f} of the bytes")
        out[label] = got
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0] is {dev.platform!r}",
              file=sys.stderr)
        return 1
    if n_dev != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {n_dev} "
              f"devices", file=sys.stderr)
        return 1

    import jax.numpy as jnp

    from torchacc_tpu.utils.compile_cache import (
        compile_cache_stats,
        enable_compile_cache,
    )
    cache_dir = enable_compile_cache()
    hbm = int((dev.memory_stats() or {}).get("bytes_limit", 16 * 2**30))
    print(f"[device] {dev.platform} {dev.device_kind} x{n_dev} "
          f"hbm_bytes={hbm} jax={jax.__version__} "
          f"compile_cache_dir={cache_dir}", flush=True)
    full = mistral_config(MISTRAL_7B_V03["num_hidden_layers"])
    batch, steps = 4, 6

    if args.chips == 4:
        depth, why = train_depth(full, hbm, batch, SEQ)
        print(f"[four-chip] depth {depth} of {full.num_layers}, the one-chip "
              f"depth, so that both sides train the same model: {why}",
              flush=True)
        multichip_phase(
            mistral_config(depth, max_seq_len=SEQ, scan_layers=False),
            layouts=((4, 1), (2, 2)), batch=batch, seq=SEQ, steps=steps,
            seed=args.seed)
    else:
        depth, why = train_depth(full, hbm, batch, SEQ)
        print(f"[train] depth {depth} of {full.num_layers}: {why}",
              flush=True)
        train_phase(mistral_config(depth, max_seq_len=SEQ, scan_layers=False),
                    batch=batch, seq=SEQ, steps=steps, seed=args.seed)

        slots, block = 8, 128
        blocks = slots * SEQ // block            # every slot at max_seq_len
        depth, why = serve_depth(full, hbm, blocks * block)
        print(f"[serve] depth {depth} of {full.num_layers}: {why}",
              flush=True)
        serve_phase(
            mistral_config(depth, max_seq_len=SEQ, param_dtype=jnp.bfloat16),
            prompt_lens=(37, 1500, 300, 700, 64, 150), max_new=24,
            seed=args.seed, block_size=block, num_blocks=blocks,
            max_slots=slots, prefill_chunk=256, wave_steps=10)

    stats = compile_cache_stats()
    print(f"[compile-cache] dir={cache_dir} hits={stats['hits']} "
          f"misses={stats['misses']}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
