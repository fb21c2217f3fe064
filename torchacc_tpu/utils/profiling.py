"""Profiling hooks.

Reference: ``torch.profiler`` + tensorboard handler behind ``--profile``
(benchmarks/transformer.py:155-160), XLA HLO dumps via ``--xla_dump_to``
(torchacc/__init__.py:122-127), and the buffer-assignment memory plotter
(tools/plot_mem.py).  TPU-native: jax.profiler traces (viewable in
TensorBoard/XProf), a step timer, and compiled-memory stats straight
from the jitted executable — no log scraping.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Iterator, Optional

import jax


@contextlib.contextmanager
def trace(logdir: str) -> Iterator[None]:
    """jax.profiler trace context (open the logdir in TensorBoard).
    The program's own spans (obs/tracing.py) appear on the trace's
    ``/host:CPU`` plane while it is open, and its device ops carry the
    registered ``jax.named_scope`` names."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Wall-clock per-step timing with warmup discard."""

    def __init__(self, warmup: int = 2):
        self.warmup = warmup
        self.times = []
        self._last: Optional[float] = None
        self._count = 0

    def tick(self) -> None:
        now = time.perf_counter()
        if self._last is not None:
            self._count += 1
            if self._count > self.warmup:
                self.times.append(now - self._last)
        self._last = now

    @property
    def mean(self) -> float:
        return sum(self.times) / len(self.times) if self.times else 0.0


def compiled_memory_stats(fn, *abstract_args) -> Dict[str, Any]:
    """Memory analysis of a jitted function (reference tools/plot_mem.py
    parses XLA buffer-assignment dumps; here it is a first-class API)."""
    lowered = jax.jit(fn).lower(*abstract_args)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    if mem is None:
        return {}
    return {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes",
                                        None),
    }
