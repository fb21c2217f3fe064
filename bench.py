"""Headline benchmark: decoder-LM training throughput + MFU on real TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Metric: model flops utilisation (MFU) of a bf16 Llama-style causal-LM
train step on the available TPU chip(s).  vs_baseline is measured MFU
against the driver's north star of 50% MFU (BASELINE.md: Llama-3-8B FSDP
>= 50% MFU target; the reference's own headline is 4044.8 tokens/s/GPU
on 8xA100 == ~62% MFU equivalent).

One process, which is the only one that opens the chip.  Progress goes
to stderr one line a stage; a failure is a traceback and a non-zero
exit.  The persistent compile cache is the repo's one
(torchacc_tpu/utils/compile_cache.py).  --fast: a small shape that
compiles in well under a minute.
"""

import argparse
import json
import os
import sys
import time

# bf16 peak FLOPs/s per chip by TPU generation
_PEAK = {
    "v5 lite": 197e12,  # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v6e": 918e12,
    "v6 lite": 918e12,
}

_METRIC = "llama350m_train_mfu"
_T0 = time.monotonic()

def _emit(result: dict) -> None:
    """The one stdout JSON line the driver records."""
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()


def _stage(name: str) -> None:
    """One stderr line a stage, so a log tail says where a run is."""
    print(f"[bench] stage={name} elapsed={time.monotonic() - _T0:.0f}s",
          file=sys.stderr, flush=True)


def peak_flops(device) -> float:
    """bf16 peak for a jax Device or a device_kind string.  A device
    that is not in the table is an error, not a default."""
    kind = (device if isinstance(device, str)
            else getattr(device, "device_kind", "")).lower()
    for key, val in _PEAK.items():
        if key in kind:
            return val
    raise KeyError(f"no bf16 peak known for device kind {kind!r}; add it "
                   f"to bench._PEAK with its source")


def _discover_devices(platform: str | None):
    """The devices of this process's backend; ``platform`` forces one
    (``--platform cpu`` for the functional gates)."""
    _stage("device_init")
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    devs = jax.devices()
    if platform and devs[0].platform != platform:
        raise RuntimeError(
            f"requested platform {platform!r} but got {devs[0].platform!r}")
    if not platform and devs[0].platform == "cpu":
        # never report a CPU run as a TPU MFU number
        raise RuntimeError(
            "backend resolved to CPU without --platform cpu — refusing to "
            "report a CPU run against the TPU baseline")
    return devs


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="small shape (sub-minute compile) for smoke runs")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--compile-budget", type=float, default=900.0,
                    help="seconds allowed for jit compile + first step")
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu) for debugging")
    ap.add_argument("--profile", default=None,
                    help="directory to write a jax.profiler trace of the "
                         "timed iterations")
    ap.add_argument("--no-decode", action="store_true",
                    help="skip the greedy-decode throughput row")
    ap.add_argument("--quant", default="none",
                    choices=("none", "int8", "fp8"),
                    help="run the train-step bench with quantized "
                         "forward matmuls (compute.quant; ops/"
                         "quantized_matmul.py).  'auto' impl = fused "
                         "Pallas kernel on TPU, XLA dot on CPU — the "
                         "CPU leg is the numerics/plumbing gate, the "
                         "TPU leg the MFU number")
    ap.add_argument("--dispatch-depth", type=int, default=2,
                    help="perf.dispatch_depth: train steps the host may "
                         "keep in flight (lagged readback; 1 = resolve "
                         "every step immediately)")
    ap.add_argument("--guards", action="store_true",
                    help="enable StepGuard (nan+spike) and per-step SDC "
                         "digest checks to measure the resilience "
                         "layer's hot-loop cost; read it off the "
                         "host_blocked_ms_per_step detail row at "
                         "--dispatch-depth 1 vs >1")
    ap.add_argument("--handoff", action="store_true",
                    help="benchmark the in-memory train->serve weight "
                         "handoff (Trainer.serving_params -> "
                         "ServeEngine.load_params, parallel/transfer.py)"
                         ": time a fit->serve->fit round trip, report "
                         "handoff_ms / transfer_compile_ms / "
                         "transfer_cache_hits / bytes moved vs the "
                         "checkpoint round-trip, and FAIL unless the "
                         "served tokens are identical to serving the "
                         "checkpoint-restored weights AND the second "
                         "handoff is a pure cache hit (`make "
                         "handoff-smoke` runs this on CPU as the gate)")
    ap.add_argument("--checkpoint", action="store_true",
                    help="benchmark the tiered zero-stall checkpoint "
                         "pipeline (checkpoint/tiered.py): drive the "
                         "SAME fit loop with blocking orbax saves vs "
                         "tiered in-gap snapshots at two cadences, "
                         "report save_blocked_ms per save step, and "
                         "FAIL unless the tiered stall is >= 10x lower "
                         "AND resume from every tier (host RAM, local "
                         "disk, mirror) is bitwise identical to the "
                         "blocking path (`make ckpt-smoke` runs this "
                         "on CPU as the gate)")
    ap.add_argument("--obs", action="store_true",
                    help="run the unified-telemetry-plane gate "
                         "(torchacc_tpu/obs, docs/observability.md): "
                         "measure telemetry_overhead_ms_per_step (obs "
                         "off vs on at dispatch_depth=2, FAIL over "
                         "--obs-budget-ms), scrape /metrics + /healthz "
                         "live during a fit (healthz must flip to "
                         "degraded under an injected watchdog stall), "
                         "verify trainer+checkpoint+serve spans export "
                         "as one Chrome-trace JSON, and verify an "
                         "injected SDC abort writes a flight-recorder "
                         "bundle naming the flagged step (`make "
                         "obs-smoke` runs this on CPU as the gate)")
    ap.add_argument("--obs-budget-ms", type=float, default=10.0,
                    help="telemetry_overhead_ms_per_step budget for "
                         "--obs (generous on CPU --fast shapes: the "
                         "measured overhead is microseconds; the gate "
                         "exists to catch a regression that puts real "
                         "work on the hot loop)")
    ap.add_argument("--serve", action="store_true",
                    help="benchmark the continuous-batching serving "
                         "engine (torchacc_tpu/serve) on a mixed-length "
                         "staggered workload instead of the train step; "
                         "reports tokens/s + TTFT and per-token latency "
                         "percentiles, and verifies greedy outputs are "
                         "token-identical to batch-synchronous "
                         "generate().  Includes the shared-prefix leg: "
                         "N requests over K system prompts through a "
                         "prefix-cache + batched-prefill + priority "
                         "engine (one request streamed), FAILING unless "
                         "token-identical AND prefix_hit_rate > 0 with "
                         "prefill_tokens_saved > 0; emits hit rate, "
                         "tokens saved, cow/eviction counts and warm-vs-"
                         "cold TTFT p50/p95 (`make serve-smoke` runs "
                         "this on CPU as the PR gate)")
    ap.add_argument("--data", action="store_true",
                    help="benchmark the streaming data plane (torchacc_"
                         "tpu/data/store.py + stream.py, docs/data.md): "
                         "host-side ingestion tokens/s over a 2-source "
                         "ChaosStore mixture (transient errors, 429 "
                         "throttles, torn reads, latency spikes), then "
                         "a short fit over the same stream reporting "
                         "data_wait ms/step from the goodput ledger "
                         "plus the retry/quarantine counters.  FAILS "
                         "unless the chaos-run batch stream is bitwise "
                         "identical to a fault-free run and every "
                         "injected stall lands in data_wait (`make "
                         "data-chaos` runs the pytest gate)")
    args = ap.parse_args()

    return _bench(args)


def _bench(args) -> int:
    _stage("import_jax")
    import jax

    import jax.numpy as jnp
    import numpy as np

    devs = _discover_devices(args.platform)
    dev, n_chips = devs[0], len(devs)
    print(f"[bench] devices: {n_chips}x {getattr(dev, 'device_kind', dev)}",
          file=sys.stderr)

    # one persistent compile cache for every leg (JAX_COMPILATION_CACHE_DIR
    # where set, else <checkout>/.cache/jax): a second run skips compiles
    from torchacc_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.data:
        return _bench_data(args, devs)
    if args.handoff:
        return _bench_handoff(args, devs)
    if args.obs:
        return _bench_obs(args, devs)
    if args.serve:
        return _bench_serve(args, devs)
    if args.checkpoint:
        return _bench_checkpoint(args, devs)

    _stage("build_model")
    import optax

    import torchacc_tpu as ta
    from torchacc_tpu.models import get_preset
    from torchacc_tpu.train import accelerate

    if args.fast:
        seq, batch, iters = 512, 2, args.iters or 5
        mc = get_preset(
            "llama-tiny",
            hidden_size=512, num_layers=4, num_heads=4, num_kv_heads=4,
            intermediate_size=2048, vocab_size=32000, max_seq_len=seq,
        )
    else:
        # ~470M-param Llama-architecture model: big enough for meaningful
        # MXU utilisation, small enough for one v5e chip with Adam in f32.
        # head_dim 128 (Llama-3 standard): d=64 wastes half the MXU lanes
        # and costs ~16 MFU points on v5e.  scan_layers=False: unrolling
        # the 24 layers removes the scan's saved-residual stacking
        # (dynamic-update-slice fusions, ~21% of the scan step) — 56.2%
        # -> 63.4% MFU measured; costs ~2 min first compile, amortised
        # by the persistent cache (docs/PERF.md).  Since round 3 the
        # unrolled path shares the stacked param layout and composes
        # with PP (per-stage static unroll), so this IS the config
        # users run, not a bench-only special case.
        seq, batch, iters = 2048, 4, args.iters or 10
        mc = get_preset(
            "llama-tiny",
            hidden_size=1024, num_layers=24, num_heads=8, num_kv_heads=8,
            intermediate_size=4096, vocab_size=32000, max_seq_len=seq,
            scan_layers=False,
        )
    cfg = ta.Config()
    cfg.memory.gc = True
    # best measured policy on v5e (docs/PERF.md): saves q/k/v + flash
    # residuals + ffn projections, recompute is elementwise-only
    cfg.memory.gc_policy = "save_attn_mlp"
    # Megatron-style main-params AMP: bf16 shadow in opt_state kills the
    # ~2.8 GB/step f32->bf16 param-cast traffic (docs/PERF.md)
    cfg.compute.bf16_compute_params = True
    cfg.perf.dispatch_depth = max(1, args.dispatch_depth)
    cfg.compute.quant = args.quant
    if args.guards:
        cfg.resilience.nan_guard = True
        cfg.resilience.spike_guard = True
        cfg.resilience.sdc_check_interval_steps = 1

    trainer, _ = accelerate(mc, None, cfg, optimizer=optax.adamw(1e-4))
    trainer.init()

    rng = np.random.default_rng(0)
    batch_data = {
        "input_ids": jnp.asarray(
            rng.integers(0, mc.vocab_size, size=(batch, seq)), jnp.int32)
    }

    # warmup (compile)
    _stage("compile_and_warmup")
    for _ in range(3):
        m = trainer.step(batch_data)
    jax.block_until_ready(m["loss"])

    _stage("timed_iters")
    import contextlib
    with contextlib.ExitStack() as stack:
        if args.profile:
            stack.enter_context(jax.profiler.trace(args.profile))
        trainer.blocked.take_ms()  # zero the host-blocked meter
        t0 = time.perf_counter()
        for _ in range(iters):
            m = trainer.step(batch_data)
        jax.block_until_ready(m["loss"])
        dt = (time.perf_counter() - t0) / iters
        # host time spent blocked on the device per step (guard verdict
        # fetches, SDC digest pulls) — the dispatch-pipelining win shows
        # as this dropping when --dispatch-depth > 1 under --guards
        host_blocked_ms = trainer.blocked.take_ms() / iters
        trainer.drain()  # resolve any still-in-flight verdicts

    decode_tps = None
    if not args.no_decode:
        # Decode throughput row (VERDICT r4 next-8): generate() is a
        # product surface (incl. pp stage-ring and cp sharded-cache
        # paths) with correctness tests but, until now, no perf number.
        # Greedy KV-cache decode on the SAME trained model: batch 8,
        # prompt 128, 128 new tokens.  _generate_cached is jitted with
        # static model args, so call 1 compiles and call 2 times the
        # steady-state prefill + decode scan.  param_dtype=bf16 is the
        # serving-precision cast: without it every decode step re-reads
        # the f32 master weights (1.87 GB at this size) from HBM; bf16
        # storage halves the traffic of the memory-bound decode loop.
        from torchacc_tpu.models.generate import generate
        dbatch, dprompt, dnew = 8, 128, 128
        prompts = jnp.asarray(
            rng.integers(0, mc.vocab_size, size=(dbatch, dprompt)),
            jnp.int32)
        _stage("decode_compile")
        # pre-cast ONCE (what a serving loop would do) so the timed
        # call measures steady state, not the tree cast; the
        # generate(param_dtype=...) convenience is equivalent
        # (tests/test_models.py::test_generate_param_dtype_cast) but
        # re-casts eagerly per call
        serve_params = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            trainer.state.params)
        with jax.sharding.set_mesh(trainer.mesh):
            out = generate(trainer.model, serve_params,
                           prompts, max_new_tokens=dnew)
            jax.block_until_ready(out)
            _stage("decode_timed")
            t0 = time.perf_counter()
            out = generate(trainer.model, serve_params,
                           prompts, max_new_tokens=dnew)
            jax.block_until_ready(out)
            ddt = time.perf_counter() - t0
        decode_tps = dbatch * dnew / ddt / n_chips

    _stage("report")
    n_params = mc.num_params()
    tokens = batch * seq
    tokens_per_sec = tokens / dt
    # PaLM-style MFU flops: 6N per token + causal attention 6*L*hidden*seq
    # (12*L*hidden*seq halved for causality), fwd+bwd included in the 6x.
    flops_per_token = 6.0 * n_params + 6.0 * mc.num_layers * mc.hidden_size * seq
    # a forced --platform cpu run is a functional gate: it has no MFU
    mfu = (flops_per_token * tokens / dt / (peak_flops(dev) * n_chips)
           if dev.platform != "cpu" else None)

    result = {
        "metric": _METRIC,
        "value": None if mfu is None else round(float(mfu), 4),
        "unit": "mfu_fraction",
        "vs_baseline": None if mfu is None else round(float(mfu) / 0.50, 4),
        "detail": {
            "tokens_per_sec_per_chip": round(tokens_per_sec / n_chips, 1),
            "step_time_s": round(dt, 4),
            "params_m": round(n_params / 1e6, 1),
            "seq": seq,
            "batch": batch,
            "chip": getattr(dev, "device_kind", str(dev)),
            "n_chips": n_chips,
            "decode_tokens_per_sec_per_chip": (
                round(decode_tps, 1) if decode_tps else None),
            "dispatch_depth": max(1, args.dispatch_depth),
            "host_blocked_ms_per_step": round(host_blocked_ms, 3),
            "quant": args.quant,
            "guards": bool(args.guards),
            "fast": bool(args.fast),
            "profile": args.profile,
            "wall_s": round(time.monotonic() - _T0, 1),
        },
    }
    _emit(result)
    return 0


def _ragged_batch(prompts):
    """Left-padded (ids, mask, p_max) for ONE batch-synchronous
    generate() call over ragged prompts — the ONE home for the padding
    recipe both serve legs' identity gates compare against."""
    import numpy as np
    p_max = max(len(p) for p in prompts)
    ids = np.zeros((len(prompts), p_max), np.int32)
    mask = np.zeros((len(prompts), p_max), np.int32)
    for i, p in enumerate(prompts):
        ids[i, p_max - len(p):] = p
        mask[i, p_max - len(p):] = 1
    return ids, mask, p_max


def _bench_serve(args, devs) -> int:
    """Continuous-batching serving benchmark (docs/serving.md).

    Workload: greedy requests with prompt lengths spanning 8x, the
    second half submitted MID-DECODE of the first (staggered arrivals —
    the continuous-batching case batch-synchronous generate() cannot
    serve without head-of-line blocking).  The run is a correctness
    gate too: outputs must be token-identical to generate() on the
    same prompts, or the bench reports value 0.0 + an error field.

    ``vs_baseline`` here is serve-tokens/s over batch-synchronous
    generate()-tokens/s on the SAME workload (one ragged left-padded
    batch, every request padded to the longest) — >1.0 means
    continuous batching beats the static batch on wall clock.  On CPU
    --fast shapes expect << 1.0: the engine pays one host dispatch per
    engine iteration while generate() runs its whole decode inside one
    lax.scan, and at tiny model sizes that overhead dominates.  The
    CPU gate is about CORRECTNESS (token identity) + the SLO metric
    plumbing; throughput judgments belong on real TPU shapes where
    per-token compute amortises the dispatch.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import torchacc_tpu as ta
    from torchacc_tpu.models import TransformerLM, get_preset
    from torchacc_tpu.models.generate import generate
    from torchacc_tpu.serve import Request, ServeEngine

    n_chips = len(devs)
    metric = "serve_mixed_tokens_per_sec"

    def fail(error: str, stage: str) -> int:
        _emit({"metric": metric, "value": 0.0, "unit": "tokens_per_sec",
               "vs_baseline": 0.0, "error": error, "stage": stage,
               "elapsed_s": round(time.monotonic() - _T0, 1)})
        return 1

    _stage("serve_build_model")
    if args.fast:
        mc = get_preset(
            "llama-tiny", dtype=jnp.float32, hidden_size=256,
            num_layers=2, num_heads=4, num_kv_heads=4,
            intermediate_size=1024, vocab_size=32000, max_seq_len=512)
        lens = [6, 12, 24, 48, 8, 16, 40, 32]      # 48/6 = 8x span
        max_new, max_slots, chunk = 16, 4, 16
    else:
        mc = get_preset(
            "llama-tiny",
            hidden_size=1024, num_layers=24, num_heads=8, num_kv_heads=8,
            intermediate_size=4096, vocab_size=32000, max_seq_len=2048)
        lens = [16, 640, 128, 1024, 64, 256, 32, 512, 96, 384, 48, 768]
        max_new, max_slots, chunk = 64, 8, 128
    cfg = ta.Config()
    cfg.serve.block_size = 16
    cfg.serve.max_slots = max_slots
    cfg.serve.prefill_chunk = chunk
    from torchacc_tpu.serve import blocks_needed
    cfg.serve.num_blocks = 2 + sum(
        blocks_needed(n + max_new + cfg.serve.decode_depth,
                      cfg.serve.block_size) for n in lens)
    model = TransformerLM(mc)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, mc.vocab_size, size=n).tolist()
               for n in lens]

    engine = ServeEngine(model, params, cfg)

    # warmup: compile prefill/decode/sample programs off the clock.
    # The prompt spans chunk + 3 tokens so BOTH prefill traces compile
    # (the non-final chunk skips the vocab head — a distinct program;
    # the serve path runs cache-less, so anything not warmed here
    # would compile inside the timed window)
    _stage("serve_compile_warmup")
    warm = engine.generate([Request(prompt_ids=[1] * (chunk + 3),
                                    max_new_tokens=2)])
    n_warm_tokens = len(warm[0].tokens)
    # fresh SLO window: warmup compile waits / warmup tokens must not
    # pollute the reported percentiles or host_blocked_ms
    engine.discard(warm[0].request_id)
    engine.reset_stats()

    _stage("serve_timed")
    t0 = time.perf_counter()
    ids = [engine.submit(Request(prompt_ids=p, max_new_tokens=max_new))
           for p in prompts[: len(prompts) // 2]]
    for _ in range(4):                       # second wave lands mid-decode
        engine.step()
    ids += [engine.submit(Request(prompt_ids=p, max_new_tokens=max_new))
            for p in prompts[len(prompts) // 2:]]
    engine.run()
    dt = time.perf_counter() - t0
    # SLO aggregation comes from engine.stats() — the same payload a
    # production driver reads (warmup excluded by the reset above)
    stats = engine.stats()
    results = [engine.result(i) for i in ids]
    engine.close()

    # batch-synchronous baseline: ONE ragged left-padded generate()
    # batch over the same prompts (what the pre-serving inference path
    # would do: everyone padded to the longest prompt, nobody returns
    # before the slowest request)
    _stage("serve_reference")
    ids_np, mask, p_max = _ragged_batch(prompts)
    out = generate(model, params, jnp.asarray(ids_np),
                   max_new_tokens=max_new, prompt_mask=jnp.asarray(mask))
    jax.block_until_ready(out)               # compiled; now time it
    t0 = time.perf_counter()
    out = generate(model, params, jnp.asarray(ids_np),
                   max_new_tokens=max_new, prompt_mask=jnp.asarray(mask))
    jax.block_until_ready(out)
    ref_dt = time.perf_counter() - t0
    refs = [np.asarray(out)[i, p_max:].tolist()
            for i in range(len(prompts))]

    _stage("report")
    mismatched = [i for i, (r, ref) in enumerate(zip(results, refs))
                  if r.tokens != ref]
    if mismatched:
        return fail(f"continuous-batching outputs diverge from "
                    f"generate() on requests {mismatched}", "verify")

    # ---- shared-prefix leg (docs/serving.md "Prefix cache"): N
    # requests over K system prompts through a prefix-cache + batched-
    # prefill + priority-policy engine, one of them streamed.  Gates:
    # (a) token identity to generate() for every request — prefix-hit,
    # partial-hit, COW-dup, batched-prefill, priority and streamed
    # mixes all ride this wave; (b) prefix_hit_rate > 0 AND
    # prefill_tokens_saved > 0 (the cache must actually fire).  The
    # no-prefix control engine serves the SAME wave for the TTFT /
    # tokens-per-sec comparison (and is itself identity-gated).
    if args.fast:
        k_sys, n_per, sys_len, suf_len, p_new = 3, 2, 48, 7, 8
    else:
        k_sys, n_per, sys_len, suf_len, p_new = 4, 3, 256, 32, 32
    rng_p = np.random.default_rng(7)
    sys_prompts = [rng_p.integers(1, mc.vocab_size, size=sys_len).tolist()
                   for _ in range(k_sys)]
    # per system prompt: n_per suffixed requests (partial hits) + one
    # exact duplicate (fully-cached prompt -> copy-on-write)
    p_prompts = []
    for sp in sys_prompts:
        for _ in range(n_per):
            p_prompts.append(
                sp + rng_p.integers(1, mc.vocab_size, size=suf_len).tolist())
        p_prompts.append(list(sp))
    pn = len(p_prompts)

    def serve_prefix_wave(prefix_on: bool):
        c2 = ta.Config()
        c2.serve.block_size = 16
        c2.serve.max_slots = max_slots
        c2.serve.prefill_chunk = chunk
        c2.serve.num_blocks = 2 + sum(
            blocks_needed(len(p) + p_new + c2.serve.decode_depth, 16)
            for p in p_prompts + sys_prompts)
        # the control differs ONLY in prefix_cache, so the noprefix
        # TTFT/throughput deltas isolate the cache (batched prefill +
        # priority policy run on BOTH engines)
        c2.serve.prefix_cache = prefix_on
        c2.serve.prefill_batch = min(4, max_slots)
        c2.serve.policy = "priority"
        eng2 = ServeEngine(model, params, c2)
        # warmers, two phases: the bare system prompts register the
        # prefix chains and compile the batched-prefill/decode/sample
        # programs off the measured window; THEN one duplicate — only
        # after the first phase completed, so its prompt actually hits
        # the (now-registered) cache and compiles the copy-on-write +
        # single-sequence-prefill programs too (submitted together, it
        # would admit cold in the same first admission pass and leave
        # those compiles inside the measured wave)
        warm_ids = [eng2.submit(Request(prompt_ids=sp, max_new_tokens=2))
                    for sp in sys_prompts]
        eng2.run()
        warm_ids.append(eng2.submit(
            Request(prompt_ids=list(sys_prompts[0]), max_new_tokens=2)))
        eng2.run()
        for wi in warm_ids:
            eng2.discard(wi)
        eng2.reset_stats()
        streamed: list = []
        t0 = time.perf_counter()
        ids2 = []
        for i, p in enumerate(p_prompts):
            ids2.append(eng2.submit(
                Request(prompt_ids=p, max_new_tokens=p_new,
                        priority=i % 3, deadline_s=120.0),
                on_token=((lambda t, ts: streamed.append(t))
                          if i == 0 else None)))
        eng2.run()
        dt2 = time.perf_counter() - t0
        st2 = eng2.stats()
        res2 = [eng2.result(i) for i in ids2]
        eng2.close()
        return res2, st2, dt2, streamed

    _stage("serve_prefix_leg")
    p_res, p_stats, p_dt, p_streamed = serve_prefix_wave(True)
    c_res, c_stats, c_dt, _ = serve_prefix_wave(False)
    ids2_np, mask2, p_max2 = _ragged_batch(p_prompts)
    out2 = generate(model, params, jnp.asarray(ids2_np),
                    max_new_tokens=p_new, prompt_mask=jnp.asarray(mask2))
    p_refs = [np.asarray(out2)[i, p_max2:].tolist() for i in range(pn)]
    bad = [i for i in range(pn) if p_res[i].tokens != p_refs[i]]
    if bad:
        return fail(f"shared-prefix serving diverges from generate() "
                    f"on requests {bad}", "prefix_verify")
    bad = [i for i in range(pn) if c_res[i].tokens != p_refs[i]]
    if bad:
        return fail(f"no-prefix control diverges from generate() on "
                    f"requests {bad}", "prefix_control_verify")
    if p_streamed != p_res[0].tokens:
        return fail("streamed tokens diverge from the request's result",
                    "prefix_stream_verify")
    if not (p_stats.get("prefix_hit_rate", 0) > 0
            and p_stats.get("prefill_tokens_saved", 0) > 0):
        return fail(
            f"prefix cache never fired: hit_rate="
            f"{p_stats.get('prefix_hit_rate')} tokens_saved="
            f"{p_stats.get('prefill_tokens_saved')}", "prefix_hit_gate")
    prefix_detail = {
        "requests": pn,
        "system_prompts": k_sys,
        "prefix_hit_rate": round(float(p_stats["prefix_hit_rate"]), 3),
        "prefill_tokens_saved": int(p_stats["prefill_tokens_saved"]),
        "prefix_blocks_reused": int(p_stats["prefix_blocks_reused"]),
        "cow_copies": int(p_stats["cow_copies"]),
        "prefix_evictions": int(p_stats["prefix_evictions"]),
        "deadline_misses": int(p_stats["deadline_misses"]),
        "tokens_per_sec": round(pn * p_new / p_dt, 1),
        "tokens_per_sec_noprefix": round(pn * p_new / c_dt, 1),
        "ttft_s_p50": round(float(p_stats["ttft_s_p50"]), 4),
        "ttft_s_p95": round(float(p_stats["ttft_s_p95"]), 4),
        "ttft_s_p50_noprefix": round(float(c_stats["ttft_s_p50"]), 4),
        "ttft_s_p95_noprefix": round(float(c_stats["ttft_s_p95"]), 4),
        "prefill_batch": min(4, max_slots),
        "policy": "priority",
        "streamed_ok": True,
        "token_identical_to_generate": True,
    }

    n_tokens = sum(len(r.tokens) for r in results)
    tps = n_tokens / dt
    ref_tps = n_tokens / ref_dt
    r4 = lambda k: round(float(stats.get(k, 0.0)), 4)  # noqa: E731
    result = {
        "metric": metric,
        "value": round(tps, 1),
        "unit": "tokens_per_sec",
        "vs_baseline": round(tps / ref_tps, 3) if ref_tps else 0.0,
        "detail": {
            "requests": len(results),
            "tokens": n_tokens,
            "tokens_per_sec": round(tps, 1),
            "generate_tokens_per_sec": round(ref_tps, 1),
            "ttft_s_p50": r4("ttft_s_p50"),
            "ttft_s_p95": r4("ttft_s_p95"),
            "per_token_s_p50": r4("per_token_s_p50"),
            "per_token_s_p95": r4("per_token_s_p95"),
            "queue_wait_s_p50": r4("queue_wait_s_p50"),
            "host_blocked_ms": r4("host_blocked_ms"),
            "token_identical_to_generate": True,
            "prefix": prefix_detail,
            "warmup_tokens": n_warm_tokens,
            "prompt_lens": lens,
            "max_new_tokens": max_new,
            "max_slots": max_slots,
            "prefill_chunk": chunk,
            "n_chips": n_chips,
            "fast": bool(args.fast),
            "wall_s": round(time.monotonic() - _T0, 1),
        },
    }
    _emit(result)
    return 0


def _bench_obs(args, devs) -> int:
    """Unified-telemetry-plane gate + overhead bench
    (docs/observability.md; ``make obs-smoke`` runs this on CPU).

    Four legs, all FAILING the run on violation:

    1. **Overhead**: the same short fit at ``dispatch_depth=2`` with
       obs off vs on (spans + histograms + flight ring, no HTTP
       server); the median per-step delta is emitted as
       ``telemetry_overhead_ms_per_step`` and must stay under the
       budget — the tracer's hot-loop cost is measured, not assumed.
    2. **Live endpoint**: a fit with tiered checkpointing + the
       telemetry server on an ephemeral port while a poller thread
       scrapes it: ``/metrics`` must parse as Prometheus text with
       non-zero step series and the trainer gauges, and ``/healthz``
       must flip to ``degraded`` during an injected
       ``ChaosPlan.hang`` watchdog stall (and answer ``ok`` after).
    3. **Serve wave**: a small engine under the same obs config; the
       scrape must show non-zero serve series (TTFT histogram,
       KV-pool gauges) and the Chrome-trace export must now hold
       trainer + tiered-checkpoint + serving spans in ONE valid JSON
       timeline.
    4. **Flight recorder**: an injected ``flip_bits`` SDC abort must
       write ``flight_<step>.json`` naming exactly the flagged step.
    """
    import json as _json
    import shutil
    import tempfile
    import threading
    import urllib.request

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import torchacc_tpu as ta
    from torchacc_tpu.errors import SDCError
    from torchacc_tpu.models import TransformerLM, get_preset
    from torchacc_tpu.obs import flight, hist, server, tracing
    from torchacc_tpu.obs.runtime import shutdown_all
    from torchacc_tpu.resilience import ChaosPlan
    from torchacc_tpu.serve import Request, ServeEngine
    from torchacc_tpu.train import accelerate
    from torchacc_tpu.utils.metrics import counters

    metric = "telemetry_overhead_ms_per_step"
    budget_ms = args.obs_budget_ms

    def fail(error: str, stage: str) -> int:
        _emit({"metric": metric, "value": 0.0, "unit": "ms",
               "vs_baseline": 0.0, "error": error, "stage": stage,
               "elapsed_s": round(time.monotonic() - _T0, 1)})
        return 1

    def parse_prometheus(text: str) -> dict:
        """Minimal strict parser: every sample line must be
        ``name[{labels}] value`` — a malformed line raises."""
        out: dict = {}
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            name_labels, value = line.rsplit(" ", 1)
            if "{" in name_labels:
                name, rest = name_labels.split("{", 1)
                if not rest.endswith("}"):
                    raise ValueError(f"malformed sample line: {line!r}")
                labels = rest[:-1]
            else:
                name, labels = name_labels, ""
            out.setdefault(name, {})[labels] = float(value)
        return out

    _stage("obs_build_model")
    mc = get_preset(
        "llama-tiny", dtype=jnp.float32, hidden_size=64,
        num_layers=2, num_heads=4, num_kv_heads=4,
        intermediate_size=256, vocab_size=512, max_seq_len=128)
    seq, batch = 32, 4
    overhead_steps = 16 if args.fast else 48
    rng = np.random.default_rng(0)

    def batches(n, seed=0):
        r = np.random.default_rng(seed)
        return [{"input_ids": r.integers(
            0, mc.vocab_size, size=(batch, seq)).astype(np.int32)}
            for _ in range(n)]

    def trainer(obs_cfg=None, **res_kwargs):
        cfg = ta.Config(
            resilience=ta.ResilienceConfig(**res_kwargs),
            perf=ta.PerfConfig(dispatch_depth=2),
            obs=obs_cfg or ta.ObsConfig())
        tr, _ = accelerate(get_preset("llama-tiny", **{
            f: getattr(mc, f) for f in (
                "hidden_size", "num_layers", "num_heads", "num_kv_heads",
                "intermediate_size", "vocab_size", "max_seq_len")},
            dtype=jnp.float32), None, cfg, optimizer=optax.adam(1e-3))
        return tr

    base = tempfile.mkdtemp(prefix="bench_obs_")
    try:
        # ---- leg 1: telemetry overhead, obs off vs on -------------------
        _stage("obs_overhead")

        def timed_fit(obs_on: bool):
            counters.reset()
            tr = trainer(ta.ObsConfig(enabled=obs_on,
                                      flight_dir=os.path.join(base, "fo")))
            bs = batches(overhead_steps + 3)
            # compile + pipeline fill off the clock
            for b in bs[:3]:
                tr.step(b)
            tr.drain()
            times = []
            import time as _t

            class Timed:
                def __iter__(self):
                    for b in bs[3:]:
                        t0 = _t.perf_counter()
                        yield b
                        times.append(_t.perf_counter() - t0)
            tr.fit(Timed(), max_steps=None, log_every=1)
            # the per-yield timing brackets one full loop body
            # (dispatch + lagged resolve + record); median over steps
            return float(np.median(times) * 1e3), tr

        off_ms, _ = timed_fit(False)
        on_ms, _ = timed_fit(True)
        overhead_ms = max(0.0, on_ms - off_ms)
        shutdown_all()
        if overhead_ms > budget_ms:
            return fail(
                f"telemetry overhead {overhead_ms:.3f} ms/step exceeds "
                f"the {budget_ms:.1f} ms budget at dispatch_depth=2 "
                f"(obs off {off_ms:.3f} -> on {on_ms:.3f})", "overhead")

        # ---- leg 2: live endpoint + degraded-under-stall ----------------
        _stage("obs_endpoint")
        counters.reset()
        tracing.clear()
        hist.reset()
        flight.recorder.clear()
        ck = os.path.join(base, "ck")
        obs_cfg = ta.ObsConfig(enabled=True, http_port=0,
                               health_degraded_heartbeat_s=0.3,
                               health_unhealthy_heartbeat_s=600.0)
        tr = trainer(obs_cfg, tiered_checkpointing=True,
                     step_deadline_s=0.25)
        # enough post-stall steps that the poller reliably samples the
        # recovered-ok state WHILE the fit still runs (the recovery
        # assertion below requires live trainer providers)
        bs = batches(26, seed=1)
        for b in bs[:2]:                 # compile off the watched window
            tr.step(b)
        tr.drain()
        # (status, fit_live) samples: fit_live = the trainer gauges were
        # registered at scrape time, i.e. the sample was taken WHILE the
        # fit ran — the recovery assertion below must not be satisfied
        # by the trivially-ok post-run endpoint (providers deregister at
        # fit exit)
        samples: list = []
        stop = threading.Event()

        def poll():
            while not stop.wait(0.03):
                try:
                    srv = server.get()
                    if srv is None:
                        continue
                    with urllib.request.urlopen(
                            srv.url + "/healthz", timeout=2) as r:
                        status = _json.loads(r.read())["status"]
                    with urllib.request.urlopen(
                            srv.url + "/metrics", timeout=2) as r:
                        mtext = r.read().decode()
                    samples.append(
                        (status,
                         "torchacc_train_inflight_depth" in mtext))
                except Exception:  # noqa: BLE001 - poller must survive
                    pass

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        with ChaosPlan(seed=0).hang("trainer.step", seconds=1.0,
                                    times=1):
            tr.fit(bs[2:], max_steps=None, log_every=1,
                   checkpoint_dir=ck, checkpoint_every=3)
        srv = server.get()
        if srv is None:
            stop.set()
            return fail("telemetry server never started", "endpoint")
        with urllib.request.urlopen(srv.url + "/metrics",
                                    timeout=5) as r:
            final_metrics = r.read().decode()
        with urllib.request.urlopen(srv.url + "/healthz",
                                    timeout=5) as r:
            final_health = _json.loads(r.read())
        stop.set()
        poller.join(timeout=5)
        statuses = [s for s, _ in samples]
        try:
            m = parse_prometheus(final_metrics)
        except ValueError as e:
            return fail(f"/metrics is not valid Prometheus text: {e}",
                        "endpoint")
        if m.get("torchacc_step_time_ms_count", {}).get("", 0) <= 0:
            return fail("no non-zero step_time_ms series in /metrics",
                        "endpoint")
        if not any(live for _, live in samples):
            return fail("trainer gauges never appeared in /metrics "
                        "during the run", "endpoint")
        deg = [i for i, (s, _) in enumerate(samples) if s == "degraded"]
        if not deg:
            return fail(
                f"/healthz never reported degraded during the injected "
                f"watchdog stall (saw {sorted(set(statuses))})",
                "healthz")
        # recovery must be observed while the fit is STILL RUNNING
        # (providers registered — fit_live): after fit the providers
        # deregister and /healthz is trivially ok
        if not any(s == "ok" and live
                   for s, live in samples[deg[-1] + 1:]):
            return fail(
                "/healthz never recovered to ok (with live trainer "
                "providers) after the injected stall cleared",
                "healthz")
        if final_health["status"] != "ok":
            return fail(f"/healthz did not answer ok after fit "
                        f"({final_health})", "healthz")
        # goodput breakdown for the leg-2 fit (obs/goodput.py): the
        # buckets must sum to the fit wall clock — the same invariant
        # `make fleet-smoke` gates pod-wide, checked here per-process
        # on every PR (generous tolerance: this fit hosts an injected
        # 1s hang whose tail is unlapped)
        from torchacc_tpu.obs.goodput import (
            check_sum as _gp_check,
            summary_from_counters as _gp_summary,
        )
        goodput = _gp_summary(counters.snapshot())
        gp_ok, gp_gap = _gp_check(goodput, tolerance=0.10)
        if goodput["wall_ms"] <= 0 or not gp_ok:
            return fail(
                f"goodput buckets diverge from wall clock "
                f"(wall {goodput['wall_ms']:.0f}ms, attributed "
                f"{goodput['attributed_ms']:.0f}ms, gap {gp_gap:.1%})",
                "goodput")

        # ---- leg 3: serve wave + one-timeline trace export --------------
        _stage("obs_serve")
        smodel = TransformerLM(mc)
        sparams = smodel.init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 8), jnp.int32))["params"]
        scfg = ta.Config(obs=obs_cfg)
        scfg.serve.block_size = 8
        scfg.serve.num_blocks = 128
        scfg.serve.max_slots = 4
        scfg.serve.prefill_chunk = 8
        engine = ServeEngine(smodel, sparams, scfg)
        prompts = [rng.integers(1, mc.vocab_size, size=n).tolist()
                   for n in (6, 12, 20, 9)]
        serve_results = engine.generate(
            [Request(prompt_ids=p, max_new_tokens=8) for p in prompts])
        with urllib.request.urlopen(srv.url + "/metrics",
                                    timeout=5) as r:
            serve_metrics = parse_prometheus(r.read().decode())
        engine.close()
        if serve_metrics.get("torchacc_serve_ttft_ms_count",
                             {}).get("", 0) <= 0:
            return fail("no non-zero serve TTFT series in /metrics",
                        "serve")
        if "torchacc_kv_pool_free_blocks" not in serve_metrics:
            return fail("KV-pool gauges missing from /metrics while "
                        "the engine was live", "serve")
        trace_path = os.path.join(base, "obs_trace.json")
        tracing.export_chrome_trace(trace_path)
        doc = _json.load(open(trace_path))   # must be valid JSON
        cats = {e.get("cat") for e in doc["traceEvents"]
                if e.get("ph") == "X"}
        if not {"train", "ckpt", "serve"} <= cats:
            return fail(
                f"Chrome-trace export is missing subsystem spans "
                f"(have {sorted(c for c in cats if c)}, need "
                f"train+ckpt+serve)", "trace")
        span_counts = {c: sum(1 for e in doc["traceEvents"]
                              if e.get("ph") == "X" and e.get("cat") == c)
                       for c in sorted(c for c in cats if c)}
        # per-request trace ids (docs/observability.md "Per-request
        # serve traces"): every served request's id must be findable in
        # the exported timeline
        for rr in serve_results:
            if not rr.trace_id:
                return fail("RequestResult carries no trace id", "trace")
            if not any(
                    e.get("args", {}).get("trace") == rr.trace_id
                    or (e.get("args", {}).get("traces")
                        and rr.trace_id in e["args"]["traces"])
                    for e in doc["traceEvents"]):
                return fail(
                    f"trace id {rr.trace_id} of request "
                    f"{rr.request_id} missing from the exported "
                    f"timeline", "trace")

        # ---- leg 4: SDC abort -> flight bundle --------------------------
        _stage("obs_flight")
        counters.reset()
        flight.recorder.clear()
        fdir = os.path.join(base, "flight")
        flip_at = 2
        tr2 = trainer(ta.ObsConfig(enabled=True, flight_dir=fdir),
                      sdc_recompute_interval_steps=1)
        hit = False
        try:
            with ChaosPlan(seed=0).flip_bits(host=0, at=flip_at,
                                             where="recompute"):
                tr2.fit(batches(6, seed=2), max_steps=6, log_every=1)
        except SDCError:
            hit = True
        if not hit:
            return fail("injected flip_bits SDC abort never raised",
                        "flight")
        bundle_path = flight.recorder.last_dump_path
        if not bundle_path or not os.path.exists(bundle_path):
            return fail("SDC abort wrote no flight-recorder bundle",
                        "flight")
        bundle = _json.load(open(bundle_path))
        if bundle.get("step") != flip_at \
                or bundle.get("error", {}).get("type") != "SDCError":
            return fail(
                f"flight bundle does not name the flagged step "
                f"(step={bundle.get('step')}, want {flip_at})", "flight")

        _stage("report")
        result = {
            "metric": metric,
            "value": round(overhead_ms, 3),
            "unit": "ms_per_step",
            # headroom multiple under the budget (>1 = within budget)
            "vs_baseline": round(budget_ms / max(overhead_ms, 1e-3), 2),
            "detail": {
                "step_ms_obs_off": round(off_ms, 3),
                "step_ms_obs_on": round(on_ms, 3),
                "overhead_budget_ms": budget_ms,
                "dispatch_depth": 2,
                "overhead_steps": overhead_steps,
                "healthz_statuses_seen": sorted(set(statuses)),
                "healthz_final": final_health["status"],
                "metrics_parse_ok": True,
                "goodput_fraction": round(goodput["goodput_fraction"], 4),
                "goodput_wall_ms": round(goodput["wall_ms"], 1),
                "goodput_buckets_ms": {k: round(v, 1) for k, v in
                                       goodput["buckets"].items()},
                "goodput_sum_gap": round(gp_gap, 4),
                "trace_span_counts": span_counts,
                "flight_bundle": os.path.basename(bundle_path),
                "flight_step": bundle["step"],
                "n_chips": len(devs),
                "fast": bool(args.fast),
                "wall_s": round(time.monotonic() - _T0, 1),
            },
        }
        _emit(result)
        return 0
    finally:
        shutdown_all()
        tracing.clear()
        hist.reset()
        flight.recorder.clear()
        counters.reset()
        shutil.rmtree(base, ignore_errors=True)


def _bench_data(args, devs) -> int:
    """Streaming-data-plane benchmark + gate (docs/data.md).

    Leg 1 (host-side): stream one epoch of a 2-source weighted mixture
    through ChaosStore-wrapped local stores (transient errors, 429
    throttles, torn reads, latency spikes) and report ingestion
    tokens/s plus the retry/quarantine counters; FAILS unless the
    delivered batch stream is bitwise identical to a fault-free run.

    Leg 2 (fit): a short ``accelerate`` fit over the same stream via
    AsyncLoader with the goodput ledger on, reporting ``data_wait``
    ms/step — the data-plane SLO — with the injected store latency
    visibly accounted there (FAILS if data_wait misses the injected
    stall time).
    """
    import shutil
    import tempfile

    import jax.numpy as jnp
    import numpy as np
    import optax

    import torchacc_tpu as ta
    from torchacc_tpu.data import AsyncLoader
    from torchacc_tpu.data.store import (ChaosStore, LocalShardStore,
                                         write_store)
    from torchacc_tpu.data.stream import StreamingDataset, StreamingSource
    from torchacc_tpu.models import TransformerLM, get_preset
    from torchacc_tpu.train import Trainer
    from torchacc_tpu.utils.metrics import counters

    n_chips = len(devs)
    metric = "data_plane_ingest_tokens_per_s"

    def fail(error: str, stage: str) -> int:
        _emit({"metric": metric, "value": 0.0, "unit": "tokens_per_sec",
               "vs_baseline": 0.0, "error": error, "stage": stage,
               "elapsed_s": round(time.monotonic() - _T0, 1)})
        return 1

    _stage("data_build_stores")
    seq, rows, vocab = (128, 8, 256) if args.fast else (512, 8, 1024)
    n_docs = 600 if args.fast else 4000
    rng = np.random.default_rng(7)
    base = tempfile.mkdtemp(prefix="bench_data_")

    def mk_store(tag, n):
        root = os.path.join(base, tag)
        docs = [rng.integers(1, vocab, size=int(rng.integers(
            seq // 4, seq))).astype(np.int32) for _ in range(n)]
        write_store(root, docs, source=tag, shard_docs=48)
        return root

    ra = mk_store("web", n_docs)
    rb = mk_store("code", n_docs // 2)
    latency_s = 0.05

    def mk_ds(chaos: bool):
        def store(root, seed):
            if not chaos:
                return LocalShardStore(root)
            return ChaosStore(
                LocalShardStore(root), seed=seed, transient_rate=0.15,
                throttle_rate=0.1, torn_rate=0.1, latency_s=latency_s,
                latency_rate=0.15)
        stores = [store(ra, 1), store(rb, 2)]
        ds = StreamingDataset(
            [StreamingSource("web", stores[0], weight=2.0),
             StreamingSource("code", stores[1], weight=1.0)],
            seq, rows, buffer_docs=96, shuffle_seed=11)
        return ds, stores

    try:
        # -- leg 1: host-side ingestion under chaos, bitwise gate ----------
        _stage("data_ingest")
        counters.reset()
        ref_ds, _ = mk_ds(chaos=False)
        ref = [b["input_ids"].copy() for b in ref_ds]
        ds, stores = mk_ds(chaos=True)
        t0 = time.perf_counter()
        got = [b["input_ids"].copy() for b in ds]
        ingest_wall = time.perf_counter() - t0
        if len(got) != len(ref) or not all(
                np.array_equal(a, b) for a, b in zip(got, ref)):
            return fail("chaos-run batch stream is not bitwise identical "
                        "to the fault-free run", "ingest")
        tokens = len(got) * rows * seq
        tokens_per_s = tokens / ingest_wall
        injected_s = sum(getattr(s, "slept_s", 0.0) for s in stores)
        injected = {}
        for s in stores:
            for k, v in getattr(s, "injected", {}).items():
                injected[k] = injected.get(k, 0) + v
        ingest_counters = {
            k: counters.get(k) for k in
            ("store_gets", "shard_fetch_retries", "shards_quarantined",
             "data_sources_shed")}
        if ingest_counters["shard_fetch_retries"] <= 0:
            return fail("chaos injected faults but shard_fetch_retries "
                        "stayed 0 — the retry path was bypassed",
                        "ingest")

        # -- leg 2: fit over the stream; data_wait is the SLO --------------
        _stage("data_fit")
        counters.reset()
        steps = 8 if args.fast else 16
        mc = get_preset(
            "llama-tiny", dtype=jnp.float32, vocab_size=vocab,
            hidden_size=64, num_layers=1, num_heads=2, num_kv_heads=2,
            intermediate_size=128, max_seq_len=seq)
        cfg = ta.Config(
            obs=ta.ObsConfig(enabled=True, goodput=True),
            resilience=ta.ResilienceConfig(retry_base_delay_s=0.01,
                                           retry_max_delay_s=0.05))
        cfg.dist.dp.size = n_chips
        tr = Trainer(TransformerLM(mc), cfg, optimizer=optax.adamw(1e-3))
        fit_ds, fit_stores = mk_ds(chaos=True)
        loader = AsyncLoader(fit_ds, cfg)
        t0 = time.perf_counter()
        tr.fit(loader, max_steps=steps,
               metrics_dir=os.path.join(base, "metrics"))
        fit_wall = time.perf_counter() - t0
        data_wait_ms = counters.get("goodput_data_wait_ms")
        fit_injected_s = sum(getattr(s, "slept_s", 0.0)
                             for s in fit_stores)
        _stage("report")
        result = {
            "metric": metric,
            "value": round(tokens_per_s, 1),
            "unit": "tokens_per_sec",
            "vs_baseline": 1.0,
            "detail": {
                "ingest": {
                    "tokens": tokens,
                    "batches": len(got),
                    "wall_s": round(ingest_wall, 3),
                    "injected_faults": injected,
                    "injected_latency_s": round(injected_s, 3),
                    "counters": ingest_counters,
                    "bitwise_vs_fault_free": True,
                },
                "fit": {
                    "steps": steps,
                    "wall_s": round(fit_wall, 3),
                    "data_wait_ms_per_step": round(
                        data_wait_ms / max(steps, 1), 2),
                    "data_wait_ms_total": data_wait_ms,
                    "injected_latency_s": round(fit_injected_s, 3),
                    "loader_retries": counters.get("loader_retries"),
                    "shard_fetch_retries": counters.get(
                        "shard_fetch_retries"),
                    "stalls_deferred": counters.get(
                        "loader_stalls_deferred"),
                },
                "seq_len": seq,
                "batch_rows": rows,
                "n_chips": n_chips,
                "fast": bool(args.fast),
                "wall_s": round(time.monotonic() - _T0, 1),
            },
        }
        _emit(result)
        return 0
    finally:
        shutil.rmtree(base, ignore_errors=True)


def _bench_checkpoint(args, devs) -> int:
    """Tiered zero-stall checkpointing benchmark + gate
    (docs/resilience.md "Tiered checkpointing").

    Drives the SAME fit loop four ways — blocking orbax saves vs tiered
    in-gap snapshots, at two checkpoint cadences — and reports the
    save-step stall (``save_blocked_ms`` summed over the run / number
    of saves).  FAILS unless (a) the tiered stall at the main cadence
    is >= 10x below the blocking path's, and (b) resume from every tier
    — the trainer's host-RAM tier-0 snapshot, the tier-1 local dir, and
    the tier-2 mirror — is bitwise identical to restoring the blocking
    run's checkpoint of the same step.  ``make ckpt-smoke`` runs this
    on 8 emulated CPU devices as the per-PR gate.
    """
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import torchacc_tpu as ta
    from torchacc_tpu.models import TransformerLM, get_preset
    from torchacc_tpu.train import Trainer
    from torchacc_tpu.utils.metrics import counters

    n_chips = len(devs)
    metric = "ckpt_save_stall_ms"

    def fail(error: str, stage: str) -> int:
        _emit({"metric": metric, "value": 0.0, "unit": "ms",
               "vs_baseline": 0.0, "error": error, "stage": stage,
               "elapsed_s": round(time.monotonic() - _T0, 1)})
        return 1

    _stage("ckpt_build_model")
    if args.fast:
        mc = get_preset(
            "llama-tiny", dtype=jnp.float32, hidden_size=256,
            num_layers=2, num_heads=4, num_kv_heads=4,
            intermediate_size=1024, vocab_size=8192, max_seq_len=256)
        seq, batch, steps = 128, 8, 9
    else:
        mc = get_preset(
            "llama-tiny", hidden_size=1024, num_layers=8, num_heads=8,
            num_kv_heads=8, intermediate_size=4096, vocab_size=32000,
            max_seq_len=2048)
        seq, batch, steps = 512, 8, 13
    cadences = (2, 4)
    rng = np.random.default_rng(0)
    batches = [{"input_ids": rng.integers(
        0, mc.vocab_size, size=(batch, seq)).astype(np.int32)}
        for _ in range(steps)]

    base = tempfile.mkdtemp(prefix="bench_ckpt_")
    trainers = {}

    def run(tag: str, tiered: bool, every: int, mirror=None):
        counters.reset()
        cfg = ta.Config(
            resilience=ta.ResilienceConfig(
                tiered_checkpointing=tiered, tiered_mirror_dir=mirror),
            perf=ta.PerfConfig(dispatch_depth=args.dispatch_depth))
        cfg.dist.dp.size = n_chips
        tr = Trainer(TransformerLM(mc), cfg, optimizer=optax.adamw(1e-3))
        t0 = time.perf_counter()
        hist = tr.fit(list(batches), max_steps=steps, log_every=1,
                      checkpoint_dir=os.path.join(base, tag),
                      checkpoint_every=every)
        wall = time.perf_counter() - t0
        n_saves = sum(1 for s in range(1, steps + 1) if s % every == 0)
        stall = sum(r.get("save_blocked_ms", 0.0) for r in hist)
        trainers[tag] = tr
        out = {"save_stall_ms_per_save": round(stall / max(n_saves, 1), 3),
               "save_stall_ms_total": round(stall, 2),
               "n_saves": n_saves,
               "steps_per_sec": round(steps / wall, 3),
               "tiered_saves": counters.get("tiered_saves"),
               "wall_s": round(wall, 2)}
        # tier-2 object-store leg: upload time/volume through the ONE
        # shared PUT path (store/client.py), off the step critical path
        cli = (tr._tiered_cache[1]._mirror_cli
               if tr._tiered_cache is not None else None)
        if cli is not None:
            out.update({
                "tier2_upload_ms": round(cli.put_ms, 2),
                "tier2_upload_bytes": int(cli.put_bytes),
                "tier2_uploads": int(cli.puts),
                "tier2_put_retries": counters.get("store_put_retries"),
            })
        return out

    try:
        rows = {}
        mirror_dir = os.path.join(base, "mirror")
        for every in cadences:
            _stage(f"ckpt_blocking_c{every}")
            rows[f"blocking_c{every}"] = run(
                f"blocking_c{every}", False, every)
            _stage(f"ckpt_tiered_c{every}")
            rows[f"tiered_c{every}"] = run(
                f"tiered_c{every}", True, every,
                mirror=mirror_dir if every == cadences[0] else None)

        main = cadences[0]
        blocking = rows[f"blocking_c{main}"]["save_stall_ms_per_save"]
        tiered = rows[f"tiered_c{main}"]["save_stall_ms_per_save"]
        speedup = blocking / max(tiered, 1e-6)

        # bitwise gate: every tier of the tiered run must restore the
        # exact bits the blocking run committed for the same step
        _stage("ckpt_verify_bitwise")
        from torchacc_tpu.checkpoint import CheckpointManager
        ref_tr = trainers[f"blocking_c{main}"]
        abstract = ref_tr.abstract_state()
        last = max(s for s in range(1, steps + 1) if s % main == 0)

        def leaves_of(state):
            return [np.asarray(x) for x in jax.device_get(
                jax.tree.leaves(state))]

        m_ref = CheckpointManager(os.path.join(base, f"blocking_c{main}"))
        ref_state, ref_step = m_ref.restore_latest_valid(abstract)
        if ref_step != last:
            return fail(f"blocking run retained step {ref_step}, "
                        f"expected {last}", "verify")
        ref = leaves_of(ref_state)

        checks = {}
        m_t1 = CheckpointManager(os.path.join(base, f"tiered_c{main}"))
        s_t1, step_t1 = m_t1.restore_latest_valid(abstract)
        checks["tier1"] = (step_t1 == last and all(
            np.array_equal(a, b) for a, b in zip(ref, leaves_of(s_t1))))
        m_t2 = CheckpointManager(mirror_dir)
        s_t2, step_t2 = m_t2.restore_latest_valid(abstract)
        checks["tier2_mirror"] = (step_t2 == last and all(
            np.array_equal(a, b) for a, b in zip(ref, leaves_of(s_t2))))
        ram_mgr = trainers[f"tiered_c{main}"]._tiered_cache[1]
        s_ram, step_ram = ram_mgr.restore_latest_valid(abstract)
        checks["tier0_ram"] = (step_ram == last and all(
            np.array_equal(a, b) for a, b in zip(ref, leaves_of(s_ram))))
        bad = [k for k, ok in checks.items() if not ok]
        if bad:
            return fail(f"resume not bitwise identical to the blocking "
                        f"path from tier(s) {bad}", "verify")
        if speedup < 10.0:
            return fail(
                f"tiered save stall {tiered:.3f} ms/save is only "
                f"{speedup:.1f}x below the blocking path "
                f"({blocking:.3f} ms/save); the gate requires >= 10x",
                "stall")

        _stage("report")
        result = {
            "metric": metric,
            "value": tiered,
            "unit": "ms",
            "vs_baseline": round(speedup, 2),
            "detail": {
                "cadence_sweep": rows,
                "main_cadence": main,
                "blocking_stall_ms_per_save": blocking,
                "tiered_stall_ms_per_save": tiered,
                "tier2_upload_ms": rows[f"tiered_c{main}"].get(
                    "tier2_upload_ms"),
                "tier2_upload_bytes": rows[f"tiered_c{main}"].get(
                    "tier2_upload_bytes"),
                "ram_restores": counters.get("ram_restores"),
                "bitwise": {k: True for k in checks},
                "params_m": round(mc.num_params() / 1e6, 1),
                "steps": steps,
                "dispatch_depth": args.dispatch_depth,
                "n_chips": n_chips,
                "fast": bool(args.fast),
                "wall_s": round(time.monotonic() - _T0, 1),
            },
        }
        _emit(result)
        return 0
    finally:
        for tr in trainers.values():
            if tr._tiered_cache is not None:
                tr._tiered_cache[1].shutdown()
        shutil.rmtree(base, ignore_errors=True)


def _bench_handoff(args, devs) -> int:
    """In-memory train→serve handoff benchmark (docs/serving.md "Live
    weight handoff").

    Drives a fit→serve→fit→serve round trip on one process: train a few
    steps, hand ``state.params`` to a ServeEngine through the compiled
    layout-transfer engine (parallel/transfer.py), serve greedy
    requests, train again, hand off again.  The second handoff MUST be
    a pure cache hit (``transfer_compiles`` unchanged) — a recompile
    per handoff would put trace time back on the RL-loop critical path.
    Correctness gate: the served tokens must be identical to serving
    the SAME weights restored via a checkpoint round-trip (the old
    road), whose wall time is also the ``vs_baseline`` denominator —
    value/vs_baseline read as "handoff_ms" and "checkpoint round trip
    is N× slower".
    """
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import torchacc_tpu as ta
    from torchacc_tpu.models import TransformerLM, get_preset
    from torchacc_tpu.parallel.transfer import (
        cache_stats,
        clear_cache,
        transfer_plan,
    )
    from torchacc_tpu.serve import Request, ServeEngine
    from torchacc_tpu.train import Trainer

    n_chips = len(devs)
    metric = "train_serve_handoff_ms"

    def fail(error: str, stage: str) -> int:
        _emit({"metric": metric, "value": 0.0, "unit": "ms",
               "vs_baseline": 0.0, "error": error, "stage": stage,
               "elapsed_s": round(time.monotonic() - _T0, 1)})
        return 1

    _stage("handoff_build_model")
    if args.fast:
        mc = get_preset(
            "llama-tiny", dtype=jnp.float32, hidden_size=128,
            num_layers=2, num_heads=4, num_kv_heads=4,
            intermediate_size=512, vocab_size=512, max_seq_len=128)
        seq, batch, fit_steps, max_new = 32, 4, 2, 8
    else:
        mc = get_preset(
            "llama-tiny",
            hidden_size=1024, num_layers=24, num_heads=8, num_kv_heads=8,
            intermediate_size=4096, vocab_size=32000, max_seq_len=2048)
        seq, batch, fit_steps, max_new = 512, 4, 5, 32
    cfg = ta.Config()
    # a real train layout when the device count allows: fsdp ZeRO shards
    # + megatron tp — the serving layout gathers fsdp and keeps tp, so
    # the transfer is a genuine multi-axis reshard, not a no-op copy
    if n_chips >= 8:
        cfg.dist.fsdp.size = 2
        cfg.dist.tp.size = 2
        cfg.dist.dp.size = n_chips // 4
        batch = max(batch, cfg.dist.dp.size * cfg.dist.fsdp.size)
    elif n_chips >= 2:
        cfg.dist.fsdp.size = 2
        cfg.dist.dp.size = n_chips // 2
        batch = max(batch, n_chips)
    cfg.serve.block_size = 16
    cfg.serve.max_slots = 4
    cfg.serve.prefill_chunk = 16
    cfg.serve.num_blocks = 128
    clear_cache()

    model = TransformerLM(mc)
    trainer = Trainer(model, cfg, optimizer=optax.adamw(1e-3))
    trainer.init()
    rng = np.random.default_rng(0)
    batch_data = {"input_ids": jnp.asarray(
        rng.integers(0, mc.vocab_size, size=(batch, seq)), jnp.int32)}
    prompts = [rng.integers(1, mc.vocab_size, size=n).tolist()
               for n in (4, 9, 17, 6)]
    reqs = lambda: [Request(prompt_ids=p, max_new_tokens=max_new)  # noqa: E731
                    for p in prompts]

    _stage("handoff_fit_phase_1")
    for _ in range(fit_steps):
        m = trainer.step(batch_data)
    float(m["loss"])

    # handoff #1 (cold: pays the one-time layout-pair compile) + the
    # serving-engine build.  Engine construction (pool allocation,
    # decode program compiles on first generate) is deliberately
    # outside the handoff timer — it happens once per process, not per
    # phase; the per-phase cost is serving_params + load_params.
    _stage("handoff_cold")
    t0 = time.perf_counter()
    params = trainer.serving_params()
    jax.block_until_ready(params)
    handoff_cold_ms = (time.perf_counter() - t0) * 1e3
    stats_cold = cache_stats()
    engine = ServeEngine(model, params, cfg, mesh=trainer.mesh)
    engine.generate(reqs())  # warm the decode/prefill programs
    for r in list(engine._all):
        engine.discard(r)

    _stage("handoff_fit_phase_2")
    for _ in range(fit_steps):
        m = trainer.step(batch_data)
    float(m["loss"])

    # handoff #2 (warm: MUST be a pure cache hit)
    _stage("handoff_warm")
    t0 = time.perf_counter()
    params2 = trainer.serving_params()
    jax.block_until_ready(params2)
    engine.load_params(params2)
    handoff_ms = (time.perf_counter() - t0) * 1e3
    stats_warm = cache_stats()
    if stats_warm["compiles"] != stats_cold["compiles"]:
        return fail(
            f"second handoff recompiled the transfer program "
            f"({stats_cold['compiles']} -> {stats_warm['compiles']} "
            f"compiles) — the layout-pair cache missed", "cache")
    res2 = [r.tokens for r in engine.generate(reqs())]

    # checkpoint round-trip baseline: the pre-PR road from the SAME
    # train state to serving weights (save -> host restore -> dtype
    # cast -> device_put into the serving layout)
    _stage("handoff_ckpt_baseline")
    from torchacc_tpu.checkpoint import restore_checkpoint, save_checkpoint
    tdir = tempfile.mkdtemp(prefix="bench_handoff_")
    try:
        ck = os.path.join(tdir, "params")
        dt = mc.dtype
        t0 = time.perf_counter()
        save_checkpoint(ck, trainer.state.params)
        host = restore_checkpoint(ck)
        host = jax.tree.map(
            lambda x: np.asarray(x, dt)
            if np.issubdtype(np.asarray(x).dtype, np.floating) else x, host)
        ckpt_params = jax.device_put(host, trainer.serving_shardings())
        jax.block_until_ready(ckpt_params)
        ckpt_ms = (time.perf_counter() - t0) * 1e3
    finally:
        shutil.rmtree(tdir, ignore_errors=True)

    _stage("handoff_verify")
    engine.load_params(ckpt_params)
    res_ckpt = [r.tokens for r in engine.generate(reqs())]
    if res2 != res_ckpt:
        return fail("post-handoff greedy serving diverges from serving "
                    "the checkpoint-round-trip weights", "verify")

    _stage("report")
    plan = transfer_plan(trainer.state.params, trainer.serving_shardings(),
                         dtype=mc.dtype)
    moved = sum(r["bytes_moved"] for r in plan)
    result = {
        "metric": metric,
        "value": round(handoff_ms, 2),
        "unit": "ms",
        "vs_baseline": round(ckpt_ms / max(handoff_ms, 1e-6), 2),
        "detail": {
            "handoff_ms": round(handoff_ms, 2),
            "handoff_cold_ms": round(handoff_cold_ms, 2),
            "ckpt_roundtrip_ms": round(ckpt_ms, 2),
            "transfer_compile_ms": round(stats_warm["compile_ms"], 2),
            "transfer_compiles": stats_warm["compiles"],
            "transfer_cache_hits": stats_warm["cache_hits"],
            "bytes_moved_per_handoff": moved,
            "leaves": len(plan),
            "leaves_resharded": sum(1 for r in plan if r["bytes_moved"]),
            "token_identical_to_ckpt_roundtrip": True,
            "mesh": {k: int(v) for k, v in trainer.mesh.shape.items()
                     if int(v) > 1},
            "params_m": round(mc.num_params() / 1e6, 1),
            "fit_steps_per_phase": fit_steps,
            "n_chips": n_chips,
            "fast": bool(args.fast),
            "wall_s": round(time.monotonic() - _T0, 1),
        },
    }
    _emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
