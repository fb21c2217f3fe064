"""One cell, once, in one process: load, warm, measure, print one line.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a data file or a small module found by the names in
``BENCHMARK.json`` (``chipbench/spec.py``); this file holds the parts
every cell shares: the look for the chip, the compile cache, the clock
around the window, the trace, the join of readers to metric names and
the last line.  It exits non-zero without a result when JAX sees no TPU
or another number of chips than the cell asks for, and never falls back
to the CPU.  ``--rehearse`` (sandbox only) runs the same code at a toy
size on CPU devices and can print only ``rehearsal_*`` names.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import warnings  # noqa: E402

from chipbench import spec  # noqa: E402

_COMPILE_EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",
                   "/jax/core/compile/backend_compile_duration")


class Window:
    t0 = t1 = 0.0

    @property
    def seconds(self):
        return self.t1 - self.t0


class Context:
    """What a driver gets from the harness."""

    def __init__(self, cell, seed, seconds, trace, rehearse, control=None):
        self.cell, self.seed, self.trace = cell, int(seed), bool(trace)
        self.rehearse = rehearse
        self.control = control
        self._metric_names = sorted(
            (m["name"] for m in
             cell.bench["end_to_end"] + cell.bench["per_layer"]),
            key=len, reverse=True)
        self.window_seconds = (min(seconds, cell.traffic["trace_seconds"])
                               if trace else seconds)
        self.trace_dir = None
        self.setup_s = None
        self.excluded_s = 0.0
        self.compiles = 0
        self._in_window = False
        self.compiles_in_window = 0

    def note(self, text):
        if self.rehearse:
            # a rehearsal may not print a metric's name: every one gets
            # the prefix, whoever wrote the line
            for name in self._metric_names:
                text = text.replace(name, "rehearsal_" + name)
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
        print(f"[t={time.perf_counter() - _T_PROCESS:7.2f}s host_rss="
              f"{rss:.1f}GB] {text}", flush=True)

    def controls(self):
        return self.control.split(",") if self.control else []

    def control_reading(self, which, name, value, limit):
        """A line of the control (the reference in a lower precision, in
        the program's place): it has to FAIL one of the cell's limits."""
        verdict = "fails, as it must" if value > limit else "would pass"
        self.note(f"[control {which}] {name} = {value:.6g} "
                  f"(limit {limit:.6g}) {verdict}")

    def exclude_from_setup(self, seconds):
        """Time spent before the window on the correctness comparison
        alone: not part of ``setup_s``."""
        self.excluded_s += seconds

    def on_compile_event(self, event, *a, **kw):
        if event in _COMPILE_EVENTS:
            self.compiles += 1
            if self._in_window:
                self.compiles_in_window += 1

    @contextlib.contextmanager
    def window(self):
        """The measured window: stamps set-up's end, counts compile
        requests, and in a traced run records the profiler's trace with
        one ``chipbench/window`` annotation over it."""
        import jax
        win = Window()
        if self.trace:
            self.trace_dir = os.path.join(spec.ROOT, ".cache",
                                          "chipbench_trace")
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans are our own
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        span = (jax.profiler.TraceAnnotation("chipbench/window")
                if self.trace else contextlib.nullcontext())
        self._in_window = True
        win.t0 = time.perf_counter()
        if self.setup_s is None:
            self.setup_s = win.t0 - _T_PROCESS - self.excluded_s
        try:
            with span:
                yield win
                win.t1 = time.perf_counter()
        finally:
            self._in_window = False
            if self.trace:
                jax.profiler.stop_trace()

    def annotate(self, name):
        """A host span on the trace's clock (a no-op in untraced runs)."""
        import jax
        return (jax.profiler.TraceAnnotation(name) if self.trace
                else contextlib.nullcontext())

    def memory_peak(self):
        """Peak bytes in use on the fullest chip, as JAX reports it now."""
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        return int(max(peaks))

    def free_device_memory(self):
        """After the driver dropped its references to the program's
        state: collect the cycles that still hold device buffers, and
        say what is left, before the reference takes the chip."""
        import gc

        import jax
        gc.collect()
        left = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in jax.local_devices())
        self.note(f"[free] program state dropped; {left / 1e9:.2f} GB "
                  f"still in use on the fullest chip")

    def reference_weights_maker(self, published, depth, dtype="float32"):
        """``make()`` -> the seeded canonical weights for the reference,
        on the device (spread over the chips where there are several:
        the reference's float32 state does not fit one of four)."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        weights = self.cell.weights()
        key = weights.base_key(self.seed)
        fn = lambda k: weights.make(k, published, depth,  # noqa: E731
                                    jnp.dtype(dtype))
        devices = jax.devices()
        if len(devices) == 1:
            jitted = jax.jit(fn)
        else:
            n = len(devices)
            mesh = Mesh(np.array(devices), ("x",))

            def sharding(leaf):
                dims = [i for i in range(leaf.ndim)
                        if leaf.shape[i] % n == 0 and leaf.shape[i] >= 1024]
                spec_ = [None] * leaf.ndim
                if dims:
                    spec_[max(dims, key=lambda i: leaf.shape[i])] = "x"
                return NamedSharding(mesh, PartitionSpec(*spec_))
            jitted = jax.jit(fn, out_shardings=jax.tree.map(
                sharding, jax.eval_shape(fn, key)))
        return lambda: jitted(key)


def _device_info():
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _rehearsal_env(chips):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={chips}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox only: toy size on CPU devices, prints "
                         "rehearsal_* names and no metric")
    ap.add_argument("--control", default=None,
                    help="not for the benchmark's runs: also put the "
                         "reference in this lower precision (fp8, int8) "
                         "in the program's place and print its "
                         "readings beside the limits")
    args = ap.parse_args(argv)

    warnings.filterwarnings(
        "ignore", message="Some donated buffers were not usable")
    cell = spec.Cell(args.workload)
    if args.rehearse:
        from chipbench import rehearsal
        rehearsal.shrink(cell)
        _rehearsal_env(cell.chips)
    import jax
    info = _device_info()
    if not args.rehearse:
        if jax.default_backend() != "tpu":
            print(f"chipbench: no TPU: the default backend is "
                  f"{jax.default_backend()!r}", file=sys.stderr)
            return 1
        if info["count"] != cell.chips:
            print(f"chipbench: the cell asks for {cell.chips} chip(s), JAX "
                  f"sees {info['count']}", file=sys.stderr)
            return 1
    peaks = None if args.rehearse else spec.peaks(info["kind"])

    from torchacc_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    ctx = Context(cell, args.seed, args.seconds, args.trace, args.rehearse,
                  args.control)
    jax.monitoring.register_event_listener(ctx.on_compile_event)
    jax.monitoring.register_event_duration_secs_listener(ctx.on_compile_event)
    ctx.note(f"[device] {info['platform']} {info['kind']} x{info['count']} "
             f"jax={jax.__version__} compile_cache_dir={cache_dir}")
    ctx.note(f"[cell] {cell.name}: config={cell.config['name']} "
             f"traffic={cell.workload['traffic']} depth={cell.depth} "
             f"seed={args.seed} seconds={ctx.window_seconds} "
             f"trace={args.trace}")

    out = cell.driver().run(ctx)

    if ctx.compiles_in_window:
        print(f"chipbench: {ctx.compiles_in_window} compile request(s) "
              f"inside the measured window: a shape was not warmed",
              file=sys.stderr)
        return 1
    correct = True
    for name, value, limit in out["checks"]:
        ok = value <= limit
        correct = correct and ok
        ctx.note(f"[check] {name} = {value:.6g} (limit {limit:.6g}) "
                 f"{'ok' if ok else 'FAILED'}")
    ctx.note(f"[setup] setup_s={ctx.setup_s:.3f} (correctness work before "
             f"the window, not counted: {ctx.excluded_s:.3f}s) "
             f"compile_requests={ctx.compiles} in_window=0")

    units = {m["name"]: m["unit"]
             for m in cell.bench["end_to_end"] + cell.bench["per_layer"]}
    device = dict(info, memory_peak_bytes=out["memory_peak_bytes"])
    breakdown = None
    if args.trace:
        values, device_extra, breakdown = _per_layer(ctx, cell, out, peaks)
        device.update(device_extra)
    else:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        missing = [m["name"] for m in cell.end_to_end()
                   if m["name"] not in values]
        if missing:
            print(f"chipbench: the driver reported no {missing}",
                  file=sys.stderr)
            return 1
        values = {m["name"]: values[m["name"]] for m in cell.end_to_end()}
    for name, value in values.items():
        if "_pct" in name or "roofline" in name or "mfu" in name:
            if value > 100.0:
                print(f"chipbench: {name} reads {value:.2f}% of a whole: "
                      f"the count is wrong", file=sys.stderr)
                return 1
    metrics = {k: {"value": v, "unit": units[k]} for k, v in values.items()}
    if args.rehearse:
        line = {"rehearsal": True, "correct": correct,
                "attempted": out["attempted"], "failed": out["failed"],
                "rehearsal_values": {f"rehearsal_{k}": v["value"]
                                     for k, v in metrics.items()},
                "rehearsal_device": info}
    else:
        line = {"correct": correct, "attempted": out["attempted"],
                "failed": out["failed"], "metrics": metrics,
                "device": device}
        if breakdown is not None:
            line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)
    return 0


def _per_layer(ctx, cell, out, peaks):
    """Join the cell's per-layer metrics to their readers."""
    from chipbench import trace_reduce
    observed = dict(out["observed"])
    observed["peaks"] = peaks
    observed["trace"] = None
    device_extra, breakdown = {}, None
    if not ctx.rehearse:
        trace = trace_reduce.load(trace_reduce.find_xplane(ctx.trace_dir))
        lo, hi = trace_reduce.window_of(trace["host"])
        observed["trace"] = dict(trace, lo=lo, hi=hi)
        busy = [trace_reduce.busy(d["ops"], lo, hi)
                for d in trace["devices"].values()]
        device_extra = {"busy_s": sum(busy) / len(busy) * 1e-9,
                        "window_s": (hi - lo) * 1e-9}
        worst = max(trace["devices"].values(),
                    key=lambda d: -trace_reduce.busy(d["ops"], lo, hi))
        breakdown = {
            "device_ops": trace_reduce.top(
                trace_reduce.self_time_by_name(worst["ops"], lo, hi)),
            "idle_gaps": trace_reduce.top(trace_reduce.gaps_by_annotation(
                trace_reduce.idle_gaps(worst["ops"], lo, hi),
                trace["host"]))}
    values = {}
    for metric in cell.per_layer():
        decl = spec.layer_metric(metric["name"])
        value = spec.reader(decl["reader"]).read(observed,
                                                 decl.get("params", {}))
        if value is not None:
            values[metric["name"]] = float(value)
    return values, device_extra, breakdown


if __name__ == "__main__":
    sys.exit(main())
