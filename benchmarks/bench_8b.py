"""Llama-3-8B-geometry benchmark on one chip (VERDICT round-2 next-3).

The full 8B model cannot fit a single 16 GB chip with f32 Adam, but its
per-layer arithmetic can be measured exactly: run as many TRUE 8B-geometry
layers as fit (h=4096, 32 heads / 8 kv heads (GQA 4:1), ffn=14336,
vocab=128256, seq 8192) at two depths and difference the step times to
isolate per-layer cost; the remainder is the embed + fused-CE head cost at
128k vocab.  Embeddings are tied (Llama-3's are not) purely to halve the
1.05B embed+head parameter footprint — the head matmul/CE FLOPs measured
are identical.

Reference bar: the reference's headline Llama-3-8B FSDP number
(docs/source/tutorials/hf_transformers.md:340-349, 4044.8 tok/s/GPU on
8xA100 ~= 62% MFU-equivalent); BASELINE.md north star >= 50% MFU.

Writes docs/bench_8b.json and prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _stage, peak_flops  # noqa: E402

_OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "docs", "bench_8b.json")


def make_config(n_layers: int, seq: int, scan_layers: bool,
                smoke: bool = False):
    """The 8B-geometry ModelConfig — single source for both the timed
    trainer and the report's FLOPs math."""
    from torchacc_tpu.models import get_preset

    kw = dict(num_layers=n_layers, max_seq_len=seq, tie_embeddings=True,
              scan_layers=scan_layers)
    if smoke:  # CPU-sized stand-in exercising the same control flow
        kw.update(hidden_size=256, num_heads=4, num_kv_heads=2,
                  intermediate_size=1024, vocab_size=4096)
    return get_preset("llama3-8b", **kw)


def build_trainer(n_layers: int, seq: int, batch: int, gc_policy: str,
                  scan_layers: bool, smoke: bool = False,
                  shadow: bool = True):
    import optax

    import torchacc_tpu as ta
    from torchacc_tpu.train import accelerate

    mc = make_config(n_layers, seq, scan_layers, smoke)
    cfg = ta.Config()
    cfg.memory.gc = True
    cfg.memory.gc_policy = gc_policy
    # same main-params AMP as the headline bench (docs/PERF.md): at this
    # geometry the f32->bf16 cast it removes is ~3 GB/step for the
    # 525M-param embed/head alone.  --no-shadow reproduces the
    # pre-shadow baseline rows.
    cfg.compute.bf16_compute_params = shadow
    trainer, _ = accelerate(mc, None, cfg, optimizer=optax.adamw(1e-4))
    trainer.init()
    return trainer, mc


def time_step(trainer, batch_data, iters: int, warmup: int = 2) -> float:
    import jax
    m = None
    for _ in range(warmup):
        m = trainer.step(batch_data)
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        m = trainer.step(batch_data)
    jax.block_until_ready(m["loss"])
    return (time.perf_counter() - t0) / iters


def run_depth(n_layers, seq, batch, iters, gc_policy, scan_layers,
              smoke=False, shadow=True):
    import jax.numpy as jnp
    import numpy as np

    _stage(f"build_L{n_layers}")
    trainer, mc = build_trainer(n_layers, seq, batch, gc_policy, scan_layers,
                                smoke, shadow)
    rng = np.random.default_rng(0)
    batch_data = {"input_ids": jnp.asarray(
        rng.integers(0, mc.vocab_size, size=(batch, seq)), jnp.int32)}
    _stage(f"compile_L{n_layers}")
    dt = time_step(trainer, batch_data, iters)
    del trainer
    return dt, mc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--gc_policy", default="save_attn")
    ap.add_argument("--scan", action="store_true",
                    help="scan-stacked layers (default: unrolled)")
    ap.add_argument("--depths", type=int, nargs="+", default=[2, 1, 0],
                    help="layer depths to try, deepest first; first two "
                         "that fit are differenced.  Depth 0 (embed + "
                         "fused-CE head only) is a valid rung: L1-L0 "
                         "isolates exactly one true 8B layer.")
    ap.add_argument("--platform", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny stand-in geometry for CPU control-flow tests "
                         "(never writes docs/bench_8b.json)")
    ap.add_argument("--no-shadow", action="store_true",
                    help="disable compute.bf16_compute_params (the "
                         "pre-shadow baseline precision mode)")
    ap.add_argument("--one-depth", type=int, default=None,
                    help="internal: time ONE depth in this process and "
                         "print {'_depth', 'dt'}; used by the parent loop "
                         "so an OOM'd depth's resident buffers (params + "
                         "opt state survive the failed compile) cannot "
                         "poison shallower attempts")
    args = ap.parse_args()

    if args.one_depth is not None:
        jax = _setup_jax(args)
        try:
            _stage("device_init")
            kind = getattr(jax.devices()[0], "device_kind", "")
            dt, _ = run_depth(args.one_depth, args.seq, args.batch,
                              args.iters, args.gc_policy, args.scan,
                              args.smoke, shadow=not args.no_shadow)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"_depth": args.one_depth,
                              "error": f"{type(e).__name__}: {e}"}))
            return 1
        print(json.dumps({"_depth": args.one_depth, "dt": dt,
                          "device_kind": kind}))
        return 0

    return _bench(args)


def _setup_jax(args):
    import jax

    from torchacc_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    return jax


def _is_oom(msg: str) -> bool:
    # the TPU compiler says "Ran out of memory in memory space hbm"
    # (lowercase "out"); the runtime says RESOURCE_EXHAUSTED — match
    # case-insensitively
    msg = msg.lower()
    return ("resource_exhausted" in msg or "out of memory" in msg
            or "exceeds the limit" in msg or "hbm capacity" in msg)


def _bench(args) -> int:
    import subprocess

    # Deepest two depths that fit, each timed in a FRESH subprocess: a
    # depth whose compile OOMs leaves its params + opt state resident on
    # the chip (the failed trainer is unreachable but the device buffers
    # outlive the exception), which would turn every shallower attempt
    # into a runtime OOM.  Process isolation makes the attempts
    # independent; the persistent compile cache keeps retries cheap.
    # The parent never initialises a JAX backend: a chip belongs to one
    # process at a time, and a parent holding it would make every child
    # fail.
    results = {}
    device_kind = ""
    for L in args.depths:
        if len(results) == 2:
            break
        _stage(f"subproc_L{L}")
        cmd = [sys.executable, os.path.abspath(__file__),
               "--one-depth", str(L), "--seq", str(args.seq),
               "--batch", str(args.batch), "--iters", str(args.iters),
               "--gc_policy", args.gc_policy]
        if args.no_shadow:
            cmd.append("--no-shadow")
        if args.scan:
            cmd.append("--scan")
        if args.smoke:
            cmd.append("--smoke")
        if args.platform:
            cmd += ["--platform", args.platform]
        try:
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=1800)
        except subprocess.TimeoutExpired:
            raise RuntimeError(f"depth {L} subprocess hung (1800s)")
        rec = None
        for line in r.stdout.splitlines():
            try:
                cand = json.loads(line)
            except ValueError:
                continue
            if isinstance(cand, dict) and cand.get("_depth") == L:
                rec = cand
        if rec is not None and "dt" in rec:
            results[L] = rec["dt"]
            device_kind = rec.get("device_kind") or device_kind
            print(f"[bench8b] L={L}: {rec['dt']*1e3:.1f} ms/step "
                  f"({device_kind})", file=sys.stderr)
        elif rec is not None and _is_oom(rec.get("error", "")):
            print(f"[bench8b] L={L} OOM; trying shallower", file=sys.stderr)
        elif rec is None and _is_oom(r.stderr or ""):
            # OOM killed the child before it could print its JSON line
            # (libtpu fatal abort).
            print(f"[bench8b] L={L} OOM (child died); trying shallower",
                  file=sys.stderr)
        else:
            err = (rec or {}).get("error") or r.stderr[-2000:]
            raise RuntimeError(f"depth {L} subprocess failed: {err}")
    if len(results) < 2:
        raise RuntimeError(f"needed two depths, got {results}")
    # a --platform cpu run is a control-flow test: it has no MFU
    peak = peak_flops(device_kind) if device_kind != "cpu" else None
    mc = make_config(1, args.seq, args.scan, args.smoke)

    (L_hi, t_hi), (L_lo, t_lo) = sorted(results.items(), reverse=True)
    t_layer = (t_hi - t_lo) / (L_hi - L_lo)
    t_rest = t_hi - L_hi * t_layer  # embed + fused-CE head + step overhead

    h, v = mc.hidden_size, mc.vocab_size
    tokens = args.batch * args.seq
    # per-layer fwd+bwd flops: 6 * per-layer params + causal attention term
    # (qkvo with GQA kv width + swiglu mlp + 2 rmsnorms, matching num_params)
    d = mc.head_size
    layer_params = (h * mc.num_heads * d + 2 * h * mc.kv_heads * d
                    + mc.num_heads * d * h + 3 * h * mc.ffn_size + 2 * h)
    flops_layer = (6.0 * layer_params + 6.0 * h * args.seq) * tokens
    flops_head = 6.0 * h * v * tokens  # tied head matmul fwd+bwd
    mfu_layer = mfu_head = None
    if peak is not None:
        mfu_layer = round(float(flops_layer / t_layer / peak), 4)
        mfu_head = round(float(flops_head / max(t_rest, 1e-9) / peak), 4)

    result = {
        "metric": "llama3_8b_geometry_layer_mfu",
        "value": mfu_layer,
        "unit": "mfu_fraction",
        "vs_baseline": (None if mfu_layer is None
                        else round(mfu_layer / 0.50, 4)),
        "detail": {
            "geometry": {"hidden": h, "heads": mc.num_heads,
                         "kv_heads": mc.num_kv_heads,
                         "ffn": mc.intermediate_size, "vocab": v,
                         "seq": args.seq, "batch": args.batch,
                         "tied_embeddings": True},
            "depths_measured": {str(k): round(v_, 4)
                                for k, v_ in results.items()},
            "per_layer_ms": round(t_layer * 1e3, 2),
            "embed_head_ce_ms": round(t_rest * 1e3, 2),
            "head_mfu_at_128k_vocab": mfu_head,
            "gc_policy": args.gc_policy,
            "scan_layers": bool(args.scan),
            "bf16_compute_params": not args.no_shadow,
            "chip": device_kind,
        },
    }
    if not args.smoke:
        try:
            with open(_OUT, "w") as f:
                json.dump(result, f, indent=1)
        except Exception as e:  # noqa: BLE001
            print(f"[bench8b] could not write {_OUT}: {e}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
