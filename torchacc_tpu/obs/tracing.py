"""Structured tracing spans: nestable, thread-aware, Chrome-trace export.

The trainer's hot loop, the tiered-checkpoint trickle and the serving
engine all run concurrent host-side state machines; ``metrics.jsonl``
scalars say *that* something was slow, never *where the time went*.
Spans close the gap: a ``span("name", **attrs)`` context manager records
one completed interval into a bounded in-process ring buffer, with
parent ids propagated through a per-thread stack (the tiered writer
thread's spans nest under its own stack, never under the trainer's),
and the whole buffer exports as Chrome-trace / Perfetto JSON
(``export_chrome_trace``) so spans land on the same timeline viewers
that already open ``jax.profiler`` traces.

Two sinks, one interval.  A span is recorded into the ring (gated by
``ObsConfig.enabled/trace`` — the operator's always-on flight recorder)
AND, whenever anyone has a ``jax.profiler`` trace open (an operator's
``utils/profiling.trace``, a benchmark's traced run), as a
``jax.profiler.TraceAnnotation`` on the ``/host:CPU`` plane of that
trace: the same nanoseconds as the device ops, no merge step.  Only
scalar attributes reach the profiler sink; lists stay ring-only.

Near-free with both sinks idle: ``span()`` returns a shared no-op
context manager — one ``if`` and one ``TraceAnnotation.is_enabled()``
(an atomic load) per call site, no allocation, no lock — so
instrumentation stays in the hot path unconditionally (PERF.md has the
nanoseconds).

``SPAN_NAMES`` (the keys of the ``SPANS`` table) is the one registry
of host span names and ``DEVICE_SCOPES`` (of ``SCOPES``) the one
registry of ``jax.named_scope`` names the device programs carry;
docs/observability.md's tables, the tests and the benchmark's trace
reader all take the names from here.
"""

from __future__ import annotations

import itertools
import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

#: every host span name -> where it is emitted
SPANS: Dict[str, str] = {
    "train/step": "Trainer.step — one whole step call (parent of "
                  "dispatch, resolve and wait).  Attrs, for a model with "
                  "dropless expert layers and only while a sink listens: "
                  "resolved_step and that step's moe_pairs, moe_max, "
                  "moe_hit, moe_slots, moe_layer_steps (as on "
                  "serve/deliver, over every shard's held experts), "
                  "moe_live_rows of moe_buffer_rows (the busiest shard's "
                  "pairs — the rows its sorted buffers' movers copy — of "
                  "the rows those buffers were sized for) and "
                  "aux_loss (the layers' summed load-balance terms)",
    "train/dispatch": "Trainer.step — enqueue of one jitted train step",
    "train/resolve": "Trainer.resolve_oldest — lagged readback of step "
                     "N-k",
    "train/verdict": "inside resolve — guard + SDC verdict "
                     "fetch/compare",
    "train/wait": "every blocking device fetch Trainer.blocked meters",
    "train/data_wait": "Trainer.fit — next(loader): the input "
                       "pipeline's share of a step",
    "train/save": "Trainer.fit — snapshot + checkpoint hand-off on a "
                  "writing step",
    "ckpt/tier0_fetch": "tiered writer thread — device -> host RAM "
                        "fetch",
    "ckpt/tier0_shard_fetch": "tiered writer thread — this host's "
                              "shards only (sharded tier-0)",
    "ckpt/tier1_commit": "tiered writer/pump — orbax commit-marker "
                         "write",
    "ckpt/mirror": "tiered writer — tier-2 mirror copy",
    "serve/step": "ServeEngine.step — one engine iteration (parent of "
                  "everything below)",
    "serve/sweep": "ServeEngine.step — deadline shed/preempt sweeps + "
                   "completion accounting",
    "serve/queue": "admission — submit -> slot (ring only, recorded at "
                   "admit time; the profiler sink carries it as "
                   "serve/admit's queue_ms)",
    "serve/admit": "Scheduler.admit — block reservation + prefix match "
                   "(ring: successful admissions only; state_reset=1 "
                   "where the slot's recurrent state restarts with the "
                   "request)",
    "serve/prefill": "Scheduler — one prefill chunk (single or batched)",
    "serve/decode": "Scheduler._decode_once — one batched decode "
                    "dispatch",
    "serve/deliver": "Scheduler._resolve_one — token readback + stream "
                     "callbacks for one ring entry.  Attrs: kind; the one "
                     "that delivers a request's FIRST token says what "
                     "the wait was made of: sid, prefill_programs, "
                     "queue_steps, wait_steps, "
                     "queue_ms + prefill_ms + lag_ms = ttft_ms; expert "
                     "models: moe_pairs, moe_max, moe_hit, moe_slots, "
                     "moe_layer_steps of the step(s) behind it; an "
                     "indexed selection: sel_attended, sel_cached, "
                     "win_attended; sliding + global grouped-query "
                     "layers: ctx_attended, win_attended; a model "
                     "with state-space layers: ssm_layers, and on a "
                     "decode step state_bytes (the recurrent state it "
                     "read and wrote) and ctx_attended",
    "serve/wait": "inside deliver — the one blocking token fetch",
}

SPAN_NAMES = tuple(SPANS)

#: every ``jax.named_scope`` the device programs carry -> the work under
#: it.  An op belongs to the INNERMOST registered name on its op_name
#: path, so these partition the device's leaf-op time; the same name is
#: the same work in training (Flax module names) and serving (scopes in
#: ``PagedDecoder``).
SCOPES: Dict[str, str] = {
    "embed_tokens": "token embedding lookup (training: the Flax module)",
    "embed": "token (+ position) embedding lookup (serving)",
    "layers": "the layer scan's own ops: per-layer slices of the stacked "
              "weights, the loop counter (serving: the KV pools ride the "
              "carry untouched)",
    "ln1": "pre-attention norm (a single-mixer layer's one pre-norm)",
    "ln2": "pre-MLP norm",
    "attn": "attention block outside its kernels: projections, rope, "
            "relayouts (training)",
    "qkv": "q/k/v projections, qk-norm and rope (serving)",
    "kv_write": "the in-place scatters of the new tokens' rows into the "
                "paged pools at (layer, block, offset): k and v, or a "
                "latent row (and a full layer's index key)",
    "paged_attn": "the paged-attention kernel and its relayouts",
    "window_paged_attn": "the paged-attention kernel of a sliding "
                         "grouped-query layer, bounded to the blocks its "
                         "window reaches, in the sliding layers' own pools",
    "o_proj": "attention output projection + residual (serving)",
    "mlp": "feed-forward block",
    "final_norm": "norm before the head",
    "head": "vocabulary projection (serving)",
    "sample": "next-token choice from the logits (serving)",
    "flash_fwd": "flash-attention forward kernel",
    "flash_dq": "flash-attention backward kernel, dq",
    "flash_dkv": "flash-attention backward kernel, dk and dv",
    "fused_ce": "chunked head matmul + cross-entropy: the chunk loop "
                "(head_fwd / head_dx / head_dw kernels or XLA fusions) that "
                "forms the loss and its gradients, and the backward's scaling",
    "optimizer": "gradient norm, clipping, optimizer update and "
                 "parameter apply",
    "mla_q": "latent attention: query projections, rope and (serving) "
             "the key up-projection folded into the query",
    "mla_kv": "latent attention: the latent projection, its norm and "
              "rope (serving: the row a token banks; plain forward: "
              "every head's k and v expanded from it)",
    "latent_attn": "the latent paged-attention kernel (one shared row a "
                   "token, values = the row's latent part)",
    "index_write": "indexed selection: the index key a token banks "
                   "(projection, LayerNorm, rope); its scatter into "
                   "the index-key pool is a kv_write",
    "indexer": "indexed selection: the indexer's query heads and "
               "weights, and the kernel that scores every visible "
               "cached position from the paged index keys",
    "index_topk": "indexed selection: the exact search for the "
                  "index_topk best scores a query (skipped while no "
                  "slot holds more positions than that)",
    "sparse_latent_attn": "the latent kernel of a full layer under the "
                          "indexer's selection",
    "window_latent_attn": "the latent kernel of a sliding layer, bounded "
                          "to the blocks its window reaches",
    "attn_gate": "the headwise gate on the attention output: its "
                 "projection, sigmoid and product, and the value "
                 "up-projection it is applied to",
    "router": "expert layer: router matmul in f32, scores, group-limited "
              "top-k, weights",
    "moe_dispatch": "expert layer: sort of the (token, expert) pairs on "
                    "held experts and the gather of their rows (a large "
                    "buffer's live rows alone: XLA's gather a live tile "
                    "at a time into moe_rows_alloc's buffer; in the "
                    "backward the kernels moe_rows_pack + moe_rows_back)",
    "moe_exchange": "expert layer under training, experts over 'ep': the "
                    "all-gather of the shards' rows (and their sel / "
                    "weights) before the held experts work them and the "
                    "reduce-scatter of the partial sums after; in the "
                    "backward the same two the other way round",
    "experts": "expert layer: the grouped matmuls over the held experts "
               "(three of a SwiGLU expert, two of a relu2 one; training: "
               "their backward too, the kernels gmm_dx and gmm_dw)",
    "shared_expert": "expert layer: the shared experts' FFN",
    "moe_combine": "expert layer: unsort, weight and sum the pairs' "
                   "outputs (a large buffer's live rows alone: kernels "
                   "moe_rows_pack + moe_rows_back; in the backward a "
                   "loop over the live tiles), add the shared expert",
    "ssm_mixer": "state-space (Mamba-2) layer outside the three scopes "
                 "below: input projection, skip term, gate, grouped norm, "
                 "output projection (serving)",
    "ssm_conv": "state-space layer: the causal depthwise convolution over "
                "the slot's last inputs and the write of the next ones",
    "ssm_scan": "state-space layer, a prefill chunk: softplus and decays, "
                "the chunked-scan kernel from the slot's state, the state "
                "written back in place",
    "ssm_step": "state-space layer, a decode step: every decoding slot's "
                "state read, updated with one token and written back",
}

DEVICE_SCOPES = tuple(SCOPES)

_DEFAULT_BUFFER = 4096
_SCALARS = (int, float, str, bool)
_profiling = TraceAnnotation.is_enabled

_enabled = False
_buf: "deque[Dict[str, Any]]" = deque(maxlen=_DEFAULT_BUFFER)
_ids = itertools.count(1)
_tls = threading.local()

# perf_counter -> wall-clock anchor, taken once at import: exported
# timestamps are (wall0 + (t - perf0)) so every thread/process shares
# one absolute timeline (the same convention the profiler's Chrome
# traces use for their ts fields).
_WALL0 = time.time()
_PERF0 = time.perf_counter()


def configure(enabled: Optional[bool] = None,
              buffer_size: Optional[int] = None) -> None:
    """Flip tracing on/off and/or resize the ring buffer (resizing
    rebuilds the deque, keeping the newest entries that fit)."""
    global _enabled, _buf
    if buffer_size is not None and buffer_size != _buf.maxlen:
        _buf = deque(_buf, maxlen=max(int(buffer_size), 16))
    if enabled is not None:
        _enabled = bool(enabled)


def enabled() -> bool:
    return _enabled


def _stack() -> List[int]:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def current_span_id() -> Optional[int]:
    """Innermost open span id on THIS thread (None outside any span) —
    the hook for explicit cross-thread parent linking."""
    s = getattr(_tls, "stack", None)
    return s[-1] if s else None


class _NullSpan:
    """The disabled-path singleton: enter/exit do nothing."""

    __slots__ = ()
    live = False        # no sink: attributes set on it go nowhere

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass

    def discard(self) -> None:
        pass


_NULL = _NullSpan()


def _scalars(attrs: Dict[str, Any]) -> Dict[str, Any]:
    return {k: v for k, v in attrs.items() if isinstance(v, _SCALARS)}


class _Span:
    """One open interval feeding whichever sinks are live: ``ring``
    (record into the buffer on exit) and/or a profiler annotation."""

    __slots__ = ("name", "attrs", "id", "parent", "_t0", "_ring",
                 "_to_profiler", "_prof")
    live = True

    def __init__(self, name: str, attrs: Dict[str, Any],
                 parent: Optional[int], ring: bool, prof: bool):
        self.name = name
        self.attrs = attrs
        self.parent = parent
        self._t0 = 0.0
        self._ring = ring
        self.id = next(_ids) if ring else 0
        self._to_profiler = prof
        self._prof = None       # the annotation, while entered

    def set(self, **attrs) -> None:
        """Attach attributes after entry (e.g. a result computed
        inside the span)."""
        self.attrs.update(attrs)
        if self._prof is not None:
            self._prof.set_metadata(**_scalars(attrs))

    def discard(self) -> None:
        """Keep this interval out of the ring (the profiler sink, whose
        events cannot be withdrawn, still shows it)."""
        if self._ring:
            self._ring = False
            st = _stack()
            if st and st[-1] == self.id:
                st.pop()

    def __enter__(self) -> "_Span":
        if self._ring:
            st = _stack()
            if self.parent is None and st:
                self.parent = st[-1]
            st.append(self.id)
            self._t0 = time.perf_counter()
        if self._to_profiler:
            # built here, not in __init__: a TraceMe's clock starts
            # when it is built
            self._prof = TraceAnnotation(self.name, **_scalars(self.attrs))
            self._prof.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        if self._prof is not None:
            self._prof.__exit__(*exc)
            self._prof = None
        if not self._ring:
            return False
        t1 = time.perf_counter()
        st = _stack()
        if st and st[-1] == self.id:
            st.pop()
        _buf.append({
            "name": self.name,
            "t0": self._t0,
            "dur": t1 - self._t0,
            "tid": threading.get_ident(),
            "thread": threading.current_thread().name,
            "id": self.id,
            "parent": self.parent,
            "attrs": self.attrs,
        })
        return False


def span(name: str, *, parent: Optional[int] = None, **attrs):
    """Nestable tracing span.  ``parent`` overrides the thread-stack
    parent (cross-thread linking: pass :func:`current_span_id` captured
    on the submitting thread).  No-op (shared singleton, no allocation)
    while the ring is disabled and no ``jax.profiler`` trace is open."""
    prof = _profiling()
    if not (_enabled or prof):
        return _NULL
    return _Span(name, attrs, parent, _enabled, prof)


def record_span(name: str, start: float, end: float, *,
                parent: Optional[int] = None, **attrs) -> None:
    """Record an already-measured interval (``start``/``end`` are
    ``time.perf_counter`` values) — for durations whose start predates
    the call site, like a request's queue wait recorded at admission."""
    if not _enabled:
        return
    _buf.append({
        "name": name,
        "t0": float(start),
        "dur": max(float(end) - float(start), 0.0),
        "tid": threading.get_ident(),
        "thread": threading.current_thread().name,
        "id": next(_ids),
        "parent": parent,
        "attrs": attrs,
    })


def snapshot(n: Optional[int] = None) -> List[Dict[str, Any]]:
    """Completed spans, oldest first (``n``: only the newest n)."""
    spans = list(_buf)
    if n is not None:
        spans = spans[-n:]
    return spans


def clear() -> None:
    _buf.clear()


def chrome_trace_events(spans: Optional[List[Dict[str, Any]]] = None
                        ) -> List[Dict[str, Any]]:
    """The span buffer as Chrome-trace ``traceEvents`` (``ph: "X"``
    complete events, ts/dur in microseconds on the wall-clock anchor,
    span/parent ids in ``args``) plus thread-name metadata events."""
    spans = snapshot() if spans is None else spans
    events: List[Dict[str, Any]] = []
    seen_tids = {}
    for s in spans:
        seen_tids.setdefault(s["tid"], s.get("thread", ""))
    for tid, tname in sorted(seen_tids.items()):
        events.append({"ph": "M", "name": "thread_name", "pid": 1,
                       "tid": tid, "args": {"name": tname or str(tid)}})
    events.append({"ph": "M", "name": "process_name", "pid": 1,
                   "args": {"name": "torchacc_tpu.obs"}})
    for s in spans:
        args = dict(s["attrs"])
        args["span_id"] = s["id"]
        if s["parent"] is not None:
            args["parent_id"] = s["parent"]
        events.append({
            "ph": "X",
            "name": s["name"],
            "cat": s["name"].split("/", 1)[0],
            "pid": 1,
            "tid": s["tid"],
            "ts": (_WALL0 + (s["t0"] - _PERF0)) * 1e6,
            "dur": s["dur"] * 1e6,
            "args": args,
        })
    return events


def export_chrome_trace(path: Optional[str] = None) -> Dict[str, Any]:
    """The whole buffer as a Chrome-trace JSON object (Perfetto and
    chrome://tracing open it directly; merge its ``traceEvents`` with a
    ``jax.profiler`` trace's to see host spans against device lanes).
    ``path`` additionally writes the JSON to a file."""
    doc = {"traceEvents": chrome_trace_events(),
           "displayTimeUnit": "ms"}
    if path is not None:
        with open(path, "w") as f:
            json.dump(doc, f)
    return doc
