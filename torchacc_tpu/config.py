"""Typed configuration tree for the TPU-native acceleration framework.

This mirrors the *semantics* of the reference config system
(``torchacc/config.py:26-444`` — nested dataclasses with per-class
``validate()`` and a lazily constructed device mesh) while being designed
around JAX/XLA: parallelism axes are names on a :class:`jax.sharding.Mesh`
rather than rank process-groups, mixed precision is a dtype policy rather
than an autocast patch, and graph boundaries are jitted step functions so
there is no ``sync``/``mark_step`` knob.

Axis inventory (reference: ``DistConfig`` torchacc/config.py:282-336, plus
context-parallel groups ops/context_parallel/init_group.py:42-91):

==========  =========================================================
axis        meaning
==========  =========================================================
``dp``      pure data parallel (replicated params, sharded batch)
``fsdp``    ZeRO-3 style: params/opt-state sharded, batch sharded too
``sp``      sequence/context parallel (Ulysses / Ring / 2D)
``tp``      tensor parallel (megatron column/row sharding)
``ep``      expert parallel (MoE all-to-all; not in the reference)
``pp``      pipeline parallel (stage-per-mesh-slice, ppermute xfer)
==========  =========================================================

``DistConfig.topology`` orders the axes from the *slowest* network to the
fastest (DCN -> ICI), mirroring the reference's intra-/inter-node axis
ordering (torchacc/config.py:291-303): axes later in the tuple land on
adjacent devices (ICI neighbours), axes earlier span slices/hosts (DCN).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# 'sp' is the outer (ring / DCN-friendly) sequence axis, 'spu' the inner
# (Ulysses all-to-all / ICI) sequence axis — together they realise the
# reference's inter/intra context-parallel 2D grid (init_group.py:42-91).
MESH_AXES: Tuple[str, ...] = ("dp", "pp", "fsdp", "sp", "spu", "ep", "tp")

# Axes along which the *batch* is split.  ``fsdp`` shards data as well as
# params (ZeRO data parallelism); ``ep`` ranks also consume distinct data:
# the experts are laid out across otherwise-data-parallel workers, which
# exchange their rows inside the expert layer (models/moe.routed_experts)
# and hold every other parameter's state as ``fsdp`` ranks do
# (parallel/sharding.DEFAULT_RULES: ``embed`` over ``fsdp`` then ``ep``).
DATA_AXES: Tuple[str, ...] = ("dp", "fsdp", "ep")


class ConfigError(ValueError):
    """Raised when a configuration fails validation."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise ConfigError(msg)


@dataclass
class ComputeConfig:
    """Numerics & kernel selection.

    Reference: ``ComputeConfig`` torchacc/config.py:26-54 (fp16/bf16 flags,
    ``acc_scaled_dot_attn`` SDPA swap, ``disable_kernel_patches``).  On TPU
    the analogue is a dtype policy plus explicit kernel choices.
    """

    dtype: str = "bfloat16"          # activation/compute dtype
    param_dtype: str = "float32"     # master parameter dtype
    # gradient-accumulation buffer dtype (grad_accum > 1): bfloat16 halves
    # the accumulator memory at some summation precision cost.  (Matmul/
    # softmax accumulation is not a knob on TPU: the MXU accumulates f32
    # for bf16 inputs by construction.)
    accum_dtype: str = "float32"
    flash_attention: bool = True     # use the Pallas flash-attention kernel
    # 'auto': pallas on TPU, interpreter elsewhere; 'xla': plain jnp reference
    attention_impl: str = "auto"     # 'auto' | 'pallas' | 'xla'
    fused_kernels: bool = True       # fused (chunked) linear+CE loss path
    # Reference threads a deterministic flag through every flash op
    # (flash_attn.py:421-423).  Kernels here are bit-deterministic by
    # construction (no atomics; dropout uses a stateless coordinate hash
    # reproducible from the checkpointed step).  Setting this True
    # additionally disables attention dropout in train steps.
    deterministic: bool = False
    # 'default' | 'high' | 'highest' — jax default matmul precision
    matmul_precision: str = "default"
    # Megatron-style main-params AMP: keep a bf16 copy of the f32 master
    # params in the optimizer state; forward/backward read the copy (no
    # per-step f32->bf16 cast of the full tree) and gradients flow in
    # bf16 into per-element optimizer math against f32 moments.  Saves
    # ~2.8 GB/step of cast traffic at 468M params (docs/PERF.md).
    # Requires dtype=bfloat16 + param_dtype=float32 (train/amp.py
    # bf16_param_shadow).
    bf16_compute_params: bool = False
    # Quantized forward matmuls (ops/quantized_matmul.py, docs/
    # performance.md "Quantized matmuls"): 'int8' | 'fp8' run the
    # selected dense sites' forward matmul in the low-precision format
    # with delayed per-tensor activation scaling (amax history carried
    # in TrainState.quant, persisted through checkpoints) and
    # just-in-time per-channel weight scales; the backward stays in the
    # compute dtype (straight-through).  'none' (default) is
    # bitwise-identical legacy semantics — no quant state exists.
    quant: str = "none"              # 'none' | 'int8' | 'fp8'
    # which dense sites quantize: 'attn' = q/k/v/o projections, 'mlp' =
    # gate/up/down denses, 'head' = the vocab projection (materialised
    # head only — the fused-CE head stays in the compute dtype)
    quant_sites: Tuple[str, ...] = ("attn", "mlp")
    # rolling amax window per site (Transformer Engine defaults to ~16;
    # longer windows react slower to activation-range shifts but are
    # robust to single-step outliers)
    quant_amax_history_len: int = 16
    # kernel choice for the quantized matmul, like attention_impl:
    # 'auto' = fused Pallas kernel on TPU / XLA dot elsewhere
    quant_impl: str = "auto"         # 'auto' | 'pallas' | 'xla'

    _QUANT_SITES = ("attn", "mlp", "head")

    def validate(self) -> None:
        _check(self.dtype in ("bfloat16", "float16", "float32"),
               f"compute.dtype must be bfloat16|float16|float32, got {self.dtype}")
        _check(not self.bf16_compute_params
               or (self.dtype == "bfloat16"
                   and self.param_dtype == "float32"),
               "compute.bf16_compute_params requires dtype=bfloat16 "
               "with param_dtype=float32 (it IS the bf16-compute/"
               "f32-master split; other combinations have no cast to "
               "save)")
        _check(self.param_dtype in ("bfloat16", "float32"),
               f"compute.param_dtype must be bfloat16|float32, got {self.param_dtype}")
        _check(self.accum_dtype in ("bfloat16", "float32"),
               f"compute.accum_dtype must be bfloat16|float32, got {self.accum_dtype}")
        _check(self.attention_impl in ("auto", "pallas", "xla"),
               f"compute.attention_impl invalid: {self.attention_impl}")
        _check(self.matmul_precision in ("default", "high", "highest"),
               f"compute.matmul_precision invalid: {self.matmul_precision}")
        _check(self.quant in ("none", "int8", "fp8"),
               f"compute.quant must be none|int8|fp8, got {self.quant}")
        _check(self.quant_impl in ("auto", "pallas", "xla"),
               f"compute.quant_impl invalid: {self.quant_impl}")
        _check(self.quant_amax_history_len >= 1,
               "compute.quant_amax_history_len must be >= 1")
        if self.quant != "none":
            _check(len(self.quant_sites) >= 1,
                   "compute.quant_sites must name at least one site")
            for s in self.quant_sites:
                _check(s in self._QUANT_SITES,
                       f"compute.quant_sites entries must be in "
                       f"{self._QUANT_SITES}, got {s!r}")


@dataclass
class MemoryConfig:
    """Rematerialisation + offload policy.

    Reference: ``MemoryConfig`` torchacc/config.py:57-88 (``gc``, ``gc_cls``,
    ``gc_cnt``) and the CPU activation offloader utils/cpu_offload.py.  Here
    ``gc`` maps to :func:`jax.checkpoint` on the transformer block with a
    selectable save policy, and offload uses XLA host memory spaces.
    """

    gc: bool = False                  # gradient/activation checkpointing (remat)
    # layer class names to remat (None = the whole decoder Block); valid:
    # 'Block', 'Attention', 'Mlp', 'MoEMlp' — reference gc_cls semantics
    # (utils/checkpoint.py:67-81) mapped onto the zoo model's modules
    gc_cls: Optional[List[str]] = None
    gc_cnt: Optional[int] = None      # remat only the first N layers
    gc_policy: str = "nothing"        # see utils/remat.py remat_policy()
    # force the host-offload remat policy (overrides gc_policy, implies gc)
    offload_activations: bool = False

    _GC_CLS = ("Block", "Attention", "Mlp", "MoEMlp")
    _GC_POLICIES = ("nothing", "dots", "dots_with_no_batch_dims",
                    "save_attn", "save_attn_mlp", "offload_dots")

    def validate(self) -> None:
        _check(self.gc_policy in self._GC_POLICIES,
               f"memory.gc_policy invalid: {self.gc_policy}")
        if self.gc_cnt is not None:
            _check(self.gc_cnt >= 0, "memory.gc_cnt must be >= 0")
        if self.gc_cls:
            for name in self.gc_cls:
                _check(name in self._GC_CLS,
                       f"memory.gc_cls entries must be in {self._GC_CLS}, "
                       f"got {name!r}")


@dataclass
class DataConfig:
    """Input pipeline: bucketing + async host->device feed.

    Reference: ``DataLoaderConfig`` torchacc/config.py:91-127 and the
    ``AsyncLoader``/``BucketingParallelLoader`` (core/async_loader.py:14-207).
    Padding every batch to one of a small set of bucket lengths bounds the
    number of distinct compiled programs (recompilation control).
    """

    buckets: Optional[List[int]] = None  # explicit bucket lengths (sorted)
    max_length: Optional[int] = None     # with num_buckets -> uniform buckets
    num_buckets: int = 1
    pad_value_dict: Optional[Dict[str, Any]] = None  # per-feature pad value
    prefetch: int = 2                    # device prefetch depth (double buffer)
    drop_last: bool = True

    def validate(self) -> None:
        if self.buckets is not None:
            _check(len(self.buckets) > 0, "data.buckets must be non-empty")
            _check(list(self.buckets) == sorted(self.buckets),
                   "data.buckets must be sorted ascending")
        if self.max_length is not None:
            _check(self.max_length > 0, "data.max_length must be positive")
            _check(self.num_buckets >= 1, "data.num_buckets must be >= 1")
        _check(self.prefetch >= 1, "data.prefetch must be >= 1")

    def bucket_sizes(self) -> Optional[List[int]]:
        """Uniform bucket lengths (reference `_uniform_buckets`
        core/async_loader.py:14-17)."""
        if self.buckets is not None:
            return list(self.buckets)
        if self.max_length is None:
            return None
        step = self.max_length / self.num_buckets
        return [int(math.ceil(step * (i + 1))) for i in range(self.num_buckets)]


@dataclass
class DPConfig:
    """Reference: torchacc/config.py:130-146.  ``size=-1`` (default) infers
    dp as world/(pp*fsdp*sp*ep*tp), mirroring config.py:320-324."""
    size: int = -1

    def validate(self) -> None:
        _check(self.size >= -1 and self.size != 0, "dp.size must be -1 or >= 1")


@dataclass
class TPConfig:
    """Reference: torchacc/config.py:149-161 (GSPMD mark_sharding TP)."""
    size: int = 1

    def validate(self) -> None:
        _check(self.size >= 1, "tp.size must be >= 1")


@dataclass
class FSDPConfig:
    """Reference: ``FSDPConfig`` torchacc/config.py:224-270.

    ``wrap_layer_cls`` / ``flatten_parameters`` are torch-FSDP mechanics that
    do not exist under GSPMD — parameter sharding is a NamedSharding rule set
    (see parallel/sharding.py); ``min_weight_size`` keeps small params
    replicated the way torch-FSDP leaves small modules unwrapped.
    """
    size: int = 1
    min_weight_size: int = 2 ** 12   # params smaller than this stay replicated
    shard_axis_rules: Optional[List[Tuple[str, Any]]] = None  # extra rule overrides

    def validate(self) -> None:
        _check(self.size >= 1, "fsdp.size must be >= 1")


@dataclass
class PPConfig:
    """Reference: ``PPConfig`` torchacc/config.py:164-221 (split points,
    micro-batches, 1F1B PipeDreamFlush schedule pp/schedule.py:156-227).

    On TPU the pipeline is a single SPMD program: layers are stacked on a
    stage axis and micro-batches circulate via ``ppermute`` (see
    parallel/pp.py), so ``split_points`` become a balanced layer
    partition.  ``schedule`` picks between GPipe-under-autodiff and the
    true 1F1B interleaved schedule (a custom-VJP region with the
    PipeDreamFlush warmup/steady/cooldown structure and memory profile).
    """
    size: int = 1
    num_micro_batches: int = 1
    # (the reference's ``broadcast_loss`` knob — a torch.distributed
    # broadcast of the last stage's loss to the other ranks,
    # config.py:164-221 — dissolves here: the schedule's own psum over the
    # 'pp' axis already lands the loss on every device of the one SPMD
    # program; there is no optional host-side step to toggle)
    # 'gpipe': autodiff through the circulating-microbatch scan (simple,
    #          composes with any loss; memory ~ M in-flight carries).
    # '1f1b':  PipeDreamFlush interleaved schedule (pp/schedule.py:156-227)
    #          as a custom-VJP region — backward starts per micro-batch,
    #          residual memory ~ min(2(P-1)+1, M) stage inputs.  Zoo-model
    #          train steps only (head+loss fused into the last stage).
    schedule: str = "gpipe"
    # interleaved (Megatron virtual-pipeline) stages: each device holds
    # this many non-adjacent layer chunks and micro-batches lap the
    # ppermute ring that many times, shrinking the fill/drain bubble to
    # (P-1)/V stage-times; supports the Megatron M = k*P regime via an
    # M-periodic schedule (parallel/pp.py pipeline_blocks docstring)
    virtual_stages: int = 1

    def validate(self) -> None:
        _check(self.size >= 1, "pp.size must be >= 1")
        _check(self.num_micro_batches >= 1, "pp.num_micro_batches must be >= 1")
        _check(self.schedule in ("gpipe", "1f1b"),
               f"pp.schedule must be gpipe|1f1b, got {self.schedule}")
        _check(self.virtual_stages >= 1, "pp.virtual_stages must be >= 1")
        if self.size > 1:
            _check(self.num_micro_batches % self.size == 0,
                   "pp.num_micro_batches must be a multiple of pp.size")
        # virtual_stages > 1 composes with BOTH schedules: gpipe uses the
        # M-periodic interleave, 1f1b the Megatron group schedule (which
        # needs M % P == 0 — already enforced above)


@dataclass
class SPConfig:
    """Sequence/context parallelism.

    Reference: ``SPConfig`` torchacc/config.py:273-279 +
    ``initialize_context_parallel(cp_size, intra_size)``
    ops/context_parallel/init_group.py:42-91.  ``mode`` selects Ulysses
    (all-to-all heads), Ring (ppermute kv), or the 2D composition whose
    intra (Ulysses) group rides ICI and inter (Ring) group rides DCN.
    """
    size: int = 1
    mode: str = "ulysses"             # 'ulysses' | 'ring' | '2d'
    intra_size: Optional[int] = None  # 2D: Ulysses degree (ICI); ring = size/intra

    def validate(self) -> None:
        _check(self.size >= 1, "sp.size must be >= 1")
        _check(self.mode in ("ulysses", "ring", "2d"), f"sp.mode invalid: {self.mode}")
        if self.mode == "2d":
            _check(self.intra_size is not None and self.intra_size >= 1,
                   "sp.intra_size required for 2d mode")
            _check(self.size % self.intra_size == 0,
                   "sp.size must be divisible by sp.intra_size")

    @property
    def ulysses_degree(self) -> int:
        """Extent of the 'spu' (all-to-all) mesh axis."""
        if self.mode == "ulysses":
            return self.size
        if self.mode == "2d":
            return self.intra_size or 1
        return 1

    @property
    def ring_degree(self) -> int:
        """Extent of the 'sp' (ppermute ring) mesh axis."""
        return self.size // self.ulysses_degree


@dataclass
class EPConfig:
    """Expert parallelism for MoE (beyond the reference — SURVEY.md §2.3 notes
    the reference has no EP; the all-to-all primitive cp/utils.py:262-299 is
    the building block it would use)."""
    size: int = 1
    # switch-style expert capacity factor: None = dense grouped dispatch
    # (no token dropping).  Folded into the zoo model's
    # ``moe_capacity_factor`` by accelerate() unless the model config sets
    # its own value explicitly.
    capacity_factor: Optional[float] = None

    def validate(self) -> None:
        _check(self.size >= 1, "ep.size must be >= 1")
        if self.capacity_factor is not None:
            _check(self.capacity_factor > 0, "ep.capacity_factor must be > 0")


@dataclass
class PerfConfig:
    """Hot-loop performance policy: host/device desynchronisation.

    The reference hides host latency behind LazyTensor async execution
    (PAPER.md); the TPU-native analogue is *dispatch pipelining*: the
    host enqueues step N+1 before step N finishes and only ever reads
    back results that are already complete.  Every per-step host fetch
    the resilience layer needs (guard verdicts, SDC digests, logged
    loss) is taken at lag ``dispatch_depth - 1`` from a lagged-readback
    ring buffer (train/trainer.py), so dispatch/trace latency hides
    behind device work instead of landing on step time.  See
    docs/performance.md for the tuning table and the
    guarantee-vs-latency trade-off per resilience feature.
    """

    # How many train steps the host may keep in flight.  1 resolves
    # every step immediately — bitwise-identical records, aborts and
    # SDC verdicts to the pre-pipelining behaviour.  k =
    # dispatch_depth - 1 is the verdict lag: guard abort-after-N becomes
    # abort-within-N+k, SDC verdicts for step S land while step S+k is
    # in flight.  The default of 2 hides one full dispatch latency
    # (bitwise depth-invariant trajectories/params — proven by the PR-5
    # burn-in, tests/test_perf.py); deeper pipelines only help when
    # dispatch/trace time exceeds a step time.  Set 1 to restore
    # immediate per-step verdicts.
    dispatch_depth: int = 2
    # FSDP comm/compute overlap (docs/performance.md "FSDP overlap"):
    # decompose the FSDP boundary so the all-gather of layer i+1's
    # params is ISSUED while layer i computes (and the mirror
    # reduce-scatter in backward), instead of letting GSPMD serialise
    # gather -> compute per layer ("Overlapping Communication with
    # Dependent Computation via Decomposition", Wang et al.,
    # ASPLOS'23).  Implemented as the unrolled layer loop with an
    # explicit one-layer-ahead replication constraint
    # (parallel/sharding.fsdp_gather_params): the forward is
    # bitwise-identical to the non-overlapped unrolled path; backward
    # weight-grad collectives sum in a different order (all-reduce vs
    # reduce-scatter), so trajectories agree to reduction-order
    # tolerance.  Opt-in; only meaningful with a live 'fsdp' mesh
    # axis.  Forces the unrolled layer loop (scan_layers is ignored
    # while overlapping); does not compose with pipeline parallelism
    # or layer_pattern models.
    overlap_fsdp: bool = False

    def validate(self) -> None:
        _check(self.dispatch_depth >= 1,
               "perf.dispatch_depth must be >= 1")


@dataclass
class ServeConfig:
    """Serving engine policy (torchacc_tpu/serve/, docs/serving.md).

    The training side of the framework mirrors the reference; serving is
    native: a paged KV cache (fixed-size blocks in a preallocated pool,
    per-sequence block tables — vLLM's PagedAttention layout expressed
    as JAX arrays), a continuous-batching scheduler that admits new
    requests into free decode slots every iteration and interleaves
    chunked prefill with decode, and a request front-end with admission
    control against KV-pool headroom + per-request SLO metrics.  See
    docs/serving.md for the tuning table.
    """

    # tokens per KV block.  Small blocks waste less memory on the last
    # partial block per sequence; large blocks mean fewer, larger pool
    # reads per attention call.  The Pallas paged-attention kernel tiles
    # a block as (block_size, head_dim), so it needs a multiple of the
    # pool dtype's sublane count (8 for f32, 16 for bf16 — the default
    # tiles both); the engine raises ConfigError otherwise.  The jnp
    # gather path takes any value.
    block_size: int = 16
    # blocks in the pool.  Per-layer KV bytes = num_blocks * block_size
    # * kv_heads * head_dim * 2 (k+v) * dtype_bytes.  Block 0 is
    # reserved as the null block (inactive slots write there), so the
    # usable pool is num_blocks - 1.
    num_blocks: int = 512
    # max sequences decoding in one batched step (the decode batch is a
    # fixed [max_slots] program; free slots run masked on the null
    # block).  Raise until decode step time stops improving — decode is
    # parameter-bandwidth-bound, so batching is nearly free until the
    # MXU saturates.
    max_slots: int = 8
    # chunked prefill: tokens of ONE sequence prefilled per engine
    # iteration, interleaved with the decode step so a long prompt
    # never stalls in-flight decodes for its whole length.
    prefill_chunk: int = 64
    # sequences whose chunks prefill TOGETHER in one dispatched program
    # per iteration (padded to [prefill_batch, prefill_chunk] so the
    # trace count stays 1).  1 = the PR-6 single-sequence prefill
    # programs, bitwise-unchanged.  Raise under bursty arrivals so K
    # waiting prompts cost one dispatch, not K iterations.
    prefill_batch: int = 1
    # shared-prefix KV reuse over the paged pool (docs/serving.md
    # "Prefix cache"): admission maps the longest cached prefix of a
    # new prompt to existing blocks with zero recompute (refcounted
    # sharing + copy-on-write on a fully-matched prompt's last block);
    # refcount-0 blocks park in an LRU and are evicted only under pool
    # pressure.  OFF = the PR-6 allocator exactly.
    prefix_cache: bool = False
    # 'fcfs' (arrival order) | 'sjf' (shortest prompt first — better
    # mean TTFT under mixed lengths, can starve long prompts) |
    # 'priority' (per-request priority class, earliest-deadline-first
    # within a class, starvation-bounded by priority_aging_s)
    policy: str = "fcfs"
    # 'priority' policy aging: a queued request's effective class rises
    # by 1 per priority_aging_s seconds waited, so any request
    # eventually outranks any fixed class (wait bounded by
    # (max_class - its_class) * priority_aging_s).  0 disables aging
    # (pure class order — a saturated high class can starve lower ones).
    priority_aging_s: float = 30.0
    # engine iterations the host may keep in flight before reading
    # tokens back (the PR-5 lagged-readback ring applied to decode):
    # the sampled-token feedback loop stays ON DEVICE between
    # iterations, the host reads iteration i's tokens while i+k is
    # dispatching.  1 = resolve every iteration immediately.
    decode_depth: int = 2
    # default per-request new-token cap (requests may set their own)
    max_new_tokens: int = 128
    # bound on the admission queue; submit() raises when full
    max_queue: int = 4096
    # graceful drain on preemption (docs/serving.md "Graceful drain"):
    # engine.run() watches the SIGTERM preemption flag
    # (resilience/preemption.py) and, once set, stops admission,
    # finishes every in-flight decode (an admitted request always
    # finishes — the whole-reservation guarantee) and reports the
    # queued-but-unserved request ids for resubmission elsewhere.
    # Off: run() ignores preemption entirely (pre-PR-13 behaviour).
    drain_on_preempt: bool = True
    # durable request journal (serve/journal.py, docs/serving.md
    # "Serving under the supervisor"): every accepted request and every
    # completed/shed result appends one strict-JSON line to
    # <journal_dir>/journal.jsonl, and ServeEngine.recover() re-admits
    # the journaled-but-unfinished requests after a restart — a kill -9
    # mid-decode costs latency, never requests (greedy replays are
    # token-identical by construction).  None (the default) = no
    # journal, no replay, serve path byte-identical to pre-journal
    # behaviour.
    journal_dir: Optional[str] = None
    # fsync every journal append (the durable contract: an id submit()
    # returned HAS an accepted record on disk).  False keeps the flush
    # (survives a process kill, not host power loss) when per-request
    # fsync cost matters.
    journal_fsync: bool = True
    # journal rotation + compaction (serve/journal.py): when the active
    # journal.jsonl crosses either bound at an append boundary it is
    # rotated out, terminal records are compacted into
    # journal-archive.jsonl and pending admissions carry forward into
    # the fresh active file — bounding replay cost for long-lived
    # engines.  None/0 (default) = never rotate (pre-rotation layout,
    # byte-identical).
    journal_rotate_bytes: Optional[int] = None
    journal_rotate_age_s: Optional[float] = None
    # deadline shedding (docs/serving.md "Deadline shedding"): a queued
    # request whose deadline has already passed — provably unmeetable,
    # it still needs >= 1 decode step — gets a typed 'shed' result
    # (counted, journaled) instead of being silently served late.
    # Off (default): pre-PR-15 behaviour, late requests serve anyway
    # and count as deadline misses.
    shed_deadlines: bool = False
    # deadline PREEMPTION of ADMITTED work (docs/serving.md "Deadline
    # shedding"): shedding only covers pre-admission; with this opt-in
    # an in-decode slot whose absolute deadline has passed is evicted
    # immediately (blocks freed, typed finish_reason='preempted' with
    # the partial tokens, journaled like a shed so replay never
    # re-serves it).  The one deliberate exception to the
    # whole-reservation guarantee — off (default) keeps "an admitted
    # request always finishes".
    preempt_deadlines: bool = False

    def validate(self) -> None:
        _check(self.block_size >= 1, "serve.block_size must be >= 1")
        _check(self.num_blocks >= 2,
               "serve.num_blocks must be >= 2 (block 0 is the reserved "
               "null block)")
        _check(self.max_slots >= 1, "serve.max_slots must be >= 1")
        _check(self.prefill_chunk >= 1, "serve.prefill_chunk must be >= 1")
        _check(self.prefill_batch >= 1, "serve.prefill_batch must be >= 1")
        _check(self.policy in ("fcfs", "sjf", "priority"),
               f"serve.policy must be fcfs|sjf|priority, got {self.policy}")
        _check(self.priority_aging_s >= 0,
               "serve.priority_aging_s must be >= 0")
        _check(self.decode_depth >= 1, "serve.decode_depth must be >= 1")
        _check(self.max_new_tokens >= 1, "serve.max_new_tokens must be >= 1")
        _check(self.max_queue >= 1, "serve.max_queue must be >= 1")


@dataclass
class ObsConfig:
    """Unified telemetry plane (torchacc_tpu/obs/, docs/observability.md).

    Off (the default), nothing records, nothing serves, and the fit
    trajectory is bitwise identical to a build without the package —
    every seam is host-side and behind this one switch.  On, the
    trainer/tiered-checkpoint/serving paths emit tracing spans into a
    bounded buffer (Chrome-trace exportable), feed the streaming
    histograms, publish gauges + health to the optional HTTP endpoint,
    and arm the crash flight recorder.  bench.py --obs measures the
    enabled hot-loop cost as ``telemetry_overhead_ms_per_step``.
    """

    enabled: bool = False
    # record tracing spans (obs/tracing.py).  Only consulted while
    # enabled; off = span() stays the shared no-op.
    trace: bool = True
    # completed spans retained in the in-process ring buffer (each is a
    # small dict; 4096 spans ~ a few hundred trainer steps of history)
    trace_buffer: int = 4096
    # HTTP telemetry endpoint (obs/server.py): None = no server;
    # 0 = bind an ephemeral port (read it back from obs.server.get());
    # otherwise the literal port.  Serves /metrics (Prometheus text)
    # and /healthz (ok|degraded|unhealthy JSON).
    http_port: Optional[int] = None
    http_host: str = "127.0.0.1"
    # crash flight recorder (obs/flight.py): ring of recent step
    # records + counter deltas, dumped as flight_<step>.json on every
    # typed-error abort (SDCError / HangError / AnomalyError /
    # QuarantinedHostError / BadBatchError / CheckpointError) and on
    # preemption.
    flight_recorder: bool = True
    flight_capacity: int = 256
    # goodput/badput wall-clock ledger (obs/goodput.py): partitions
    # each fit's wall time into productive step time vs badput buckets
    # (data wait, checkpoint, drain...), published as goodput_*_ms
    # counters + the goodput_fraction gauge and summarized in flight
    # bundles and the supervisor's /fleet view.  Only consulted while
    # enabled.
    goodput: bool = True
    # where bundles land; None = the fit's checkpoint_dir or
    # metrics_dir (in that order)
    flight_dir: Optional[str] = None
    # /healthz heartbeat thresholds: the watchdog heartbeat age at
    # which the probe reports degraded / unhealthy.  Tune to a few
    # step times; only consulted while a fit with a watchdog
    # (resilience.step_deadline_s) is running.
    health_degraded_heartbeat_s: float = 60.0
    health_unhealthy_heartbeat_s: float = 300.0

    def validate(self) -> None:
        _check(self.trace_buffer >= 16,
               "obs.trace_buffer must be >= 16")
        _check(self.flight_capacity >= 8,
               "obs.flight_capacity must be >= 8")
        if self.http_port is not None:
            _check(0 <= self.http_port <= 65535,
                   "obs.http_port must be in [0, 65535] (0 = ephemeral)")
        _check(self.health_degraded_heartbeat_s > 0,
               "obs.health_degraded_heartbeat_s must be positive")
        _check(self.health_unhealthy_heartbeat_s
               >= self.health_degraded_heartbeat_s,
               "obs.health_unhealthy_heartbeat_s must be >= "
               "health_degraded_heartbeat_s")


@dataclass
class ResilienceConfig:
    """Fault tolerance: anomaly guards, retries, preemption handling.

    The reference leans on HF Trainer resume + manual restarts; a
    TPU-native framework owns this (resilience/ package, docs/
    resilience.md).  Guards default OFF: the non-finite/spike verdict is
    selected in-graph (no sync to *skip*), but the abort-after-N
    guarantee requires one scalar device fetch per step, which breaks
    async step dispatch — opt in for long unattended runs.
    """

    # skip optimizer updates on non-finite loss/grad (in-jit select, like
    # the fp16 GradScaler skip; under float16 the scaler already owns the
    # overflow skip and only the spike guard adds checks)
    nan_guard: bool = False
    # skip updates whose grad-norm z-score vs an EW mean/var exceeds
    # spike_zscore (after spike_warmup_steps accepted steps)
    spike_guard: bool = False
    spike_zscore: float = 6.0
    spike_ewma_alpha: float = 0.02
    spike_warmup_steps: int = 20
    # abort (AnomalyError, with diagnosis) after this many consecutive
    # anomalous steps — a diverging run, not a glitch
    max_consecutive_anomalies: int = 8
    # checkpoint save/restore I/O retries (jittered exponential backoff)
    ckpt_retries: int = 3
    retry_base_delay_s: float = 0.5
    retry_max_delay_s: float = 8.0
    retry_deadline_s: Optional[float] = None   # total wall-clock budget
    # async-loader batch-fetch retries; after they are exhausted the
    # loader degrades to synchronous (consumer-thread) iteration instead
    # of dying, when loader_sync_fallback is set
    loader_retries: int = 2
    loader_sync_fallback: bool = True
    # write a blocking emergency checkpoint when a preemption signal
    # (SIGTERM / request_preemption) arrives during Trainer.fit with a
    # checkpoint_dir configured
    emergency_checkpoint: bool = True
    # hang/straggler watchdog (resilience/watchdog.py): when set,
    # Trainer.fit arms a per-step deadline around the train step; on
    # expiry the watchdog dumps all-thread stacks, increments the
    # watchdog_stalls counter, and (with abort_on_hang) raises HangError
    # at the next step boundary so a supervisor restarts into
    # fit(resume='auto').  None disables the watchdog entirely.
    step_deadline_s: Optional[float] = None
    # stall deadline for the async loader's consumer wait (a hung
    # producer/source trips the same stack-dump + counter path); None
    # falls back to step_deadline_s semantics in fit and disables the
    # loader-internal deadline
    loader_deadline_s: Optional[float] = None
    # raise HangError once a tripped deadline resolves (False = observe
    # only: stack dump + counter, training continues if the stall clears)
    abort_on_hang: bool = False
    # timeout for cross-host coordination primitives (preemption sync,
    # resume consensus — resilience/coordination.py).  Only consulted
    # when jax.process_count() > 1; single-process runs never arm it.
    coord_timeout_s: float = 120.0
    # multi-host only: run the cross-host preemption sync every N step
    # boundaries instead of every one (the sync is a small blocking
    # allgather — on sub-second steps, raise this to keep the hot path
    # collective-free at the cost of reacting to a peer's SIGTERM up to
    # N-1 steps later).  Single-process runs check the local flag every
    # step regardless.
    preempt_sync_interval_steps: int = 1
    # elastic resume (docs/resilience.md "Elastic resume"): allow
    # fit(resume='auto') to restore a checkpoint saved under a DIFFERENT
    # data-parallel layout / process count (the rescheduled-onto-a-
    # different-slice-shape case) by resharding online into the current
    # mesh.  tp/pp/sp/spu/ep changes are always rejected with a typed
    # TopologyMismatchError — those change the program, not just the
    # data layout.  Off (the default), ANY topology change is rejected
    # with the schema diff instead of an opaque orbax error.
    elastic_resume: bool = False
    # validate every batch in the loader hot path (tree structure,
    # shape/dtype drift vs the first batch, non-finite values); bad
    # batches are skipped + counted (bad_batches_skipped), dumped to
    # quarantine_dir, and after max_consecutive_bad_batches in a row a
    # typed BadBatchError aborts the run (a broken source, not a blip)
    batch_validation: bool = False
    max_consecutive_bad_batches: int = 8
    # where offending batches + provenance are dumped (None = skip the
    # dump, still count/log)
    quarantine_dir: Optional[str] = None
    # SDC defense (resilience/sdc.py, docs/resilience.md "SDC defense"):
    # when set, the jitted train step computes a per-DP-replica digest
    # of the final gradients (xor-fold + wraparound-sum of the bit
    # patterns + a float sum, per leaf) and every N steps the digests
    # are fetched and compared across replicas — a disagreeing replica
    # names the offending host(s) in a typed SDCError.  None = the step
    # program carries no digest at all (zero overhead).
    sdc_check_interval_steps: Optional[int] = None
    # redundant-recompute spot check: every K steps, snapshot the state,
    # re-execute the SAME compiled step on it and compare digests —
    # bitwise-deterministic by construction, so any difference is the
    # hardware flaking (catches single-host SDC that replica comparison
    # cannot see at dp=1).  Costs one extra full step + a state-sized
    # snapshot per check.
    sdc_recompute_interval_steps: Optional[int] = None
    # raise SDCError on a confirmed divergence/mismatch (False: record
    # the quarantine entry, log, and count sdc_mismatches only)
    sdc_abort: bool = True
    # bound the per-leaf digest fold on check steps: leaves with more
    # elements than this fold a deterministic strided subsample of at
    # most this many elements (element 0 — the chaos flip site — is
    # always included).  None (default) folds every element.  At 10B+
    # params the full fold's read traffic is measurable; a 1e6 bound
    # keeps the check O(leaves) while still covering every leaf.  All
    # digest comparisons (replica, recompute, replay) use the same
    # bound, so verdict semantics are unchanged — only coverage within
    # a leaf is sampled.  Digests taken under different bounds are not
    # comparable to each other.
    sdc_digest_max_elems: Optional[int] = None
    # also fold the POST-APPLY param leaves into the per-replica digest
    # matrix (rows double: grads/<leaf> then params/<leaf>): corruption
    # in the optimizer apply then surfaces on the very step it happens,
    # instead of one step late through the next step's gradients — the
    # carried-over PR-4 gap.  Costs a second digest fold (over the
    # params) on every step the digest program runs;
    # sdc_digest_max_elems bounds both folds the same way.  Digest
    # matrices taken with this on are not comparable to ones taken with
    # it off (different row count).
    sdc_digest_optimizer: bool = False
    # tiered zero-stall checkpointing (checkpoint/tiered.py,
    # docs/resilience.md "Tiered checkpointing"): interval saves take a
    # donation-safe device snapshot inside the step gap and return
    # immediately; a background writer fetches it to host RAM (tier 0),
    # then — once the step's lagged guard/SDC verdict has resolved —
    # trickles it to local disk (tier 1, the same commit-marker/digest/
    # manifest protocol as blocking saves) and optionally to a mirror
    # directory (tier 2).  save_blocked_ms drops to the snapshot cost,
    # the verdict drain disappears from the save path, and checkpoint
    # cadence can tighten to per-minute.  Off (default): interval saves
    # drain in-flight verdicts and hand off to orbax synchronously,
    # exactly the pre-tiered behaviour.
    tiered_checkpointing: bool = False
    # tier-2 mirror directory (object store mount / second filesystem):
    # committed tier-1 steps are copied here by the trickle, payload
    # first and the commit marker last, so a torn mirror copy is as
    # invisible as a torn save.  None = no tier 2.
    tiered_mirror_dir: Optional[str] = None
    # newest verdicted tier-0 host-RAM snapshots retained per process
    # (restore-from-RAM / peer-restore candidates).  Each costs one
    # state-sized host allocation; older snapshots are freed as newer
    # ones pass their verdict gate.
    tiered_tier0_keep: int = 2
    # enforce, not warn: make fit() raise a typed QuarantinedHostError
    # when the restarted pod still contains a host recorded in
    # <run_dir>/sdc_quarantine.json (off: the PR-4 loud warning only)
    refuse_quarantined: bool = False

    def validate(self) -> None:
        _check(self.spike_zscore > 0,
               "resilience.spike_zscore must be positive")
        _check(0.0 < self.spike_ewma_alpha <= 1.0,
               "resilience.spike_ewma_alpha must be in (0, 1]")
        _check(self.spike_warmup_steps >= 0,
               "resilience.spike_warmup_steps must be >= 0")
        # with < 2 accepted samples the EW variance is degenerate and
        # every healthy step z-scores as a spike
        _check(not self.spike_guard or self.spike_warmup_steps >= 2,
               "resilience.spike_warmup_steps must be >= 2 when "
               "spike_guard is enabled (the EW variance needs at least "
               "two accepted steps to be meaningful)")
        _check(self.max_consecutive_anomalies >= 1,
               "resilience.max_consecutive_anomalies must be >= 1")
        _check(self.ckpt_retries >= 0, "resilience.ckpt_retries must be >= 0")
        _check(self.loader_retries >= 0,
               "resilience.loader_retries must be >= 0")
        _check(self.retry_base_delay_s >= 0,
               "resilience.retry_base_delay_s must be >= 0")
        _check(self.retry_max_delay_s >= self.retry_base_delay_s,
               "resilience.retry_max_delay_s must be >= retry_base_delay_s")
        if self.retry_deadline_s is not None:
            _check(self.retry_deadline_s > 0,
                   "resilience.retry_deadline_s must be positive")
        if self.step_deadline_s is not None:
            _check(self.step_deadline_s > 0,
                   "resilience.step_deadline_s must be positive")
        if self.loader_deadline_s is not None:
            _check(self.loader_deadline_s > 0,
                   "resilience.loader_deadline_s must be positive")
        _check(self.coord_timeout_s > 0,
               "resilience.coord_timeout_s must be positive")
        _check(self.preempt_sync_interval_steps >= 1,
               "resilience.preempt_sync_interval_steps must be >= 1")
        _check(self.max_consecutive_bad_batches >= 1,
               "resilience.max_consecutive_bad_batches must be >= 1")
        if self.sdc_check_interval_steps is not None:
            _check(self.sdc_check_interval_steps >= 1,
                   "resilience.sdc_check_interval_steps must be >= 1")
        if self.sdc_recompute_interval_steps is not None:
            _check(self.sdc_recompute_interval_steps >= 1,
                   "resilience.sdc_recompute_interval_steps must be >= 1")
        if self.sdc_digest_max_elems is not None:
            _check(self.sdc_digest_max_elems >= 1,
                   "resilience.sdc_digest_max_elems must be >= 1")
        _check(self.tiered_tier0_keep >= 1,
               "resilience.tiered_tier0_keep must be >= 1")

    def retry_policy(self, max_retries: int) -> Any:
        """The shared RetryPolicy view of the delay/deadline knobs."""
        from torchacc_tpu.resilience.retry import RetryPolicy
        return RetryPolicy(max_retries=max_retries,
                           base_delay_s=self.retry_base_delay_s,
                           max_delay_s=self.retry_max_delay_s,
                           deadline_s=self.retry_deadline_s)


@dataclass
class DistConfig:
    """Parallelism composition + topology ordering.

    Reference: ``DistConfig`` torchacc/config.py:282-336.  ``topology``
    orders mesh axes slowest-network-first (DCN -> ICI): the reference's
    intra-node axes map to ICI-adjacent axes here.  ``dp.size = -1`` is
    inferred as world/(pp*fsdp*sp*ep*tp) (reference config.py:320-324).
    """
    dp: DPConfig = field(default_factory=DPConfig)
    tp: TPConfig = field(default_factory=TPConfig)
    fsdp: FSDPConfig = field(default_factory=FSDPConfig)
    pp: PPConfig = field(default_factory=PPConfig)
    sp: SPConfig = field(default_factory=SPConfig)
    ep: EPConfig = field(default_factory=EPConfig)
    # Slowest -> fastest network. Must be a permutation of MESH_AXES.
    topology: Tuple[str, ...] = MESH_AXES
    # Number of DCN-connected slices (multi-pod); axes whose extent exceeds
    # a slice ride DCN. 1 = single slice, everything on ICI.
    num_slices: int = 1

    def validate(self) -> None:
        for sub in (self.dp, self.tp, self.fsdp, self.pp, self.sp, self.ep):
            sub.validate()
        # PP×SP composes: the context-parallel attention opens its own
        # shard_map over ('sp','spu') inside the pp-manual pipeline
        # region (the reference composes CP orthogonally with the other
        # strategies, init_group.py:42-91).  Tested pp×sp ≡ pp ≡ sp.
        _check(tuple(sorted(self.topology)) == tuple(sorted(MESH_AXES)),
               f"dist.topology must be a permutation of {MESH_AXES}, got {self.topology}")
        _check(self.num_slices >= 1, "dist.num_slices must be >= 1")

    def axis_sizes(self, world_size: int) -> Dict[str, int]:
        """Resolve every axis size, inferring dp when dp.size == -1."""
        sizes = {
            "tp": self.tp.size,
            "fsdp": self.fsdp.size,
            "pp": self.pp.size,
            "sp": self.sp.ring_degree,
            "spu": self.sp.ulysses_degree,
            "ep": self.ep.size,
        }
        fixed = math.prod(sizes.values())
        if self.dp.size == -1:
            _check(world_size % fixed == 0,
                   f"world size {world_size} not divisible by pp*fsdp*sp*ep*tp={fixed}")
            sizes["dp"] = world_size // fixed
        else:
            sizes["dp"] = self.dp.size
        total = math.prod(sizes.values())
        _check(total == world_size,
               f"product of parallel sizes {total} != device count {world_size} "
               f"(sizes={sizes})")
        return sizes


@dataclass
class Config:
    """Top-level config (reference: ``Config`` torchacc/config.py:340-444).

    The reference's ``backend='lazy'|'eager'`` switch collapses away: JAX has
    exactly one execution model (trace once under jit, run compiled).
    """
    compute: ComputeConfig = field(default_factory=ComputeConfig)
    memory: MemoryConfig = field(default_factory=MemoryConfig)
    data: DataConfig = field(default_factory=DataConfig)
    dist: DistConfig = field(default_factory=DistConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    perf: PerfConfig = field(default_factory=PerfConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    # Gradient accumulation micro-steps per optimizer step (non-PP path;
    # under PP the pipeline's num_micro_batches plays this role).
    grad_accum: int = 1
    seed: int = 0

    _mesh: Any = field(default=None, repr=False, compare=False)

    def validate(self) -> None:
        self.compute.validate()
        self.memory.validate()
        self.data.validate()
        self.dist.validate()
        self.resilience.validate()
        self.perf.validate()
        self.serve.validate()
        self.obs.validate()
        _check(self.grad_accum >= 1, "grad_accum must be >= 1")
        # quantized matmuls thread delayed-scaling state through the
        # non-pp forward paths only; the 1F1B/GPipe regions apply blocks
        # through raw param trees that do not carry the quant collection
        _check(self.compute.quant == "none" or self.dist.pp.size == 1,
               "compute.quant does not compose with pipeline "
               "parallelism (pp.size > 1) — the pipeline regions do "
               "not thread the delayed-scaling state")
        _check(not self.perf.overlap_fsdp or self.dist.pp.size == 1,
               "perf.overlap_fsdp does not compose with pipeline "
               "parallelism (the pp schedules own their layer loop)")

    # -- mesh ---------------------------------------------------------------
    def get_mesh(self, devices: Optional[Sequence[Any]] = None):
        """Lazily build the device mesh (reference: ``Config.get_mesh``
        torchacc/config.py:389-413 lazily initialises process groups + Mesh).
        """
        if self._mesh is None:
            from torchacc_tpu.parallel.mesh import build_mesh
            self._mesh = build_mesh(self.dist, devices=devices)
        return self._mesh

    # -- (de)serialisation --------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        def _clean(obj):
            if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
                return {
                    f.name: _clean(getattr(obj, f.name))
                    for f in dataclasses.fields(obj)
                    if not f.name.startswith("_")
                }
            if isinstance(obj, (list, tuple)):
                return [_clean(v) for v in obj]
            if isinstance(obj, dict):
                return {k: _clean(v) for k, v in obj.items()}
            return obj
        return _clean(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Config":
        def _build(tp, val, path):
            if dataclasses.is_dataclass(tp) and isinstance(val, dict):
                names = {f.name for f in dataclasses.fields(tp)
                         if not f.name.startswith("_")}
                unknown = set(val) - names
                _check(not unknown,
                       f"unknown config key(s) {sorted(unknown)} at {path or '<root>'}; "
                       f"valid keys: {sorted(names)}")
                kwargs = {}
                for f in dataclasses.fields(tp):
                    if f.name.startswith("_") or f.name not in val:
                        continue
                    sub = _TYPE_MAP.get(f.name)
                    if sub is not None and isinstance(val[f.name], dict):
                        kwargs[f.name] = _build(sub, val[f.name], f"{path}{f.name}.")
                    else:
                        v = val[f.name]
                        if f.name == "topology" and isinstance(v, list):
                            v = tuple(v)
                        kwargs[f.name] = v
                return tp(**kwargs)
            return val
        cfg = _build(cls, d, "")
        cfg.validate()
        return cfg


_TYPE_MAP = {
    "compute": ComputeConfig,
    "memory": MemoryConfig,
    "data": DataConfig,
    "dist": DistConfig,
    "resilience": ResilienceConfig,
    "perf": PerfConfig,
    "serve": ServeConfig,
    "obs": ObsConfig,
    "dp": DPConfig,
    "tp": TPConfig,
    "fsdp": FSDPConfig,
    "pp": PPConfig,
    "sp": SPConfig,
    "ep": EPConfig,
}
