"""The Trainer: sharded init + the jitted train step (the hot loop).

Reference hot loop (SURVEY.md §3.3): LazyTensor records IR, DP hooks queue
all-reduces, `mark_step` cuts and compiles the graph.  TPU-native: ONE
jitted, donated train-step function whose shardings make XLA insert every
collective (psum for DP, all-gather/reduce-scatter for FSDP, all-to-all
for EP) — there is nothing to hook and no graph to cut.

Dispatch pipelining (``perf.dispatch_depth``): the host keeps up to
``dispatch_depth`` steps in flight and reads back only *lagged* results
— the analogue of the reference's LazyTensor async execution, where the
host records IR ahead of the device.  Every per-step host fetch the
resilience layer needs (the StepGuard verdict scalar, SDC digest
matrices, the logged loss) is taken from a ring buffer of in-flight
steps at lag ``k = dispatch_depth - 1``, so it reads an
already-completed value instead of serialising dispatch behind
execution.  ``dispatch_depth=2`` (the default since the PR-5 burn-in
proved bitwise depth-invariance) hides one dispatch latency;
``dispatch_depth=1`` resolves every step immediately —
bitwise-identical behaviour to the unpipelined loop.
See docs/performance.md for the guarantee-vs-latency table.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from torchacc_tpu.config import Config, ConfigError
from torchacc_tpu.errors import TorchAccTPUError, TrainerStateError
from torchacc_tpu.obs import tracing
from torchacc_tpu.models.axes import param_axes as resolve_param_axes
from torchacc_tpu.models.transformer import loss_sum_count
from torchacc_tpu.parallel.sharding import (
    batch_spec,
    make_rules,
    tree_shardings,
)
from torchacc_tpu.train.state import TrainState, init_train_state, state_logical_axes
from torchacc_tpu.utils.logger import logger


def shift_labels(input_ids: jax.Array,
                 segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """Next-token labels from input_ids (last position ignored).

    With packed sequences, positions whose next token belongs to a
    different document (or to padding, segment -1) get label -100 so the
    loss never trains across packing boundaries."""
    labels = jnp.concatenate(
        [input_ids[:, 1:], jnp.full_like(input_ids[:, :1], -100)], axis=1)
    if segment_ids is not None:
        next_seg = jnp.concatenate(
            [segment_ids[:, 1:], jnp.full_like(segment_ids[:, :1], -1)],
            axis=1)
        valid = (next_seg == segment_ids) & (segment_ids >= 0)
        labels = jnp.where(valid, labels, -100)
    return labels


@dataclasses.dataclass
class _InFlightStep:
    """One dispatched-but-unresolved train step in the lagged-readback
    ring buffer.  ``metrics`` are device arrays (futures until the step
    completes); ``rerun`` is the SDC redundant-recompute closure bound
    to the snapshot, batch and compiled executable captured at dispatch
    time, so a recompile mid-flight cannot change what the verdict
    re-executes."""

    step: int
    metrics: Dict[str, jax.Array]
    digests: Optional[jax.Array] = None
    tokens: Optional[int] = None
    sdc_check: bool = False
    sdc_spot: bool = False
    rerun: Optional[Callable[[], Any]] = None


class Trainer:
    """Builds sharded state and a donated jitted train step.

    Parameters
    ----------
    model: a flax Module with ``__call__(input_ids, positions, segment_ids)``
    optimizer: an optax GradientTransformation (default: adamw(1e-4))
    config: the framework Config
    axes_rules: param-path regex rules (models/axes.py) for sharding
    loss: callable(logits, labels) -> scalar; defaults to CE with -100 skip
    """

    def __init__(
        self,
        model,
        config: Config,
        optimizer: Optional[optax.GradientTransformation] = None,
        axes_rules=None,
        loss: Optional[Callable] = None,
        mesh: Optional[Mesh] = None,
    ):
        mcfg = getattr(model, "cfg", None)
        if getattr(mcfg, "kv_lora_rank", 0):
            raise ConfigError(
                "training a latent-attention model is not supported: the "
                "latent attention has no flash path and no backward "
                "under a train step.  ServeEngine serves it; "
                "TransformerLM.apply runs its forward")
        if getattr(mcfg, "moe_dispatch", "") == "grouped":
            # the dropless expert layer trains with its experts over
            # 'ep' and the rows over the data axes (models/moe.
            # routed_experts); what nobody has run is refused here, not
            # found in a trace
            dist = config.dist
            refused = None
            if mcfg.mixer_pattern or (mcfg.layer_pattern
                                      and mcfg.first_dense_layers):
                refused = ("a mixer_pattern, or a layer_pattern beside "
                           "leading dense layers: the module's forward "
                           "does not build them (ServeEngine serves them)")
            elif dist.tp.size > 1 or dist.pp.size > 1 or dist.sp.size > 1:
                refused = ("the dropless expert layer under tp, pp or sp: "
                           "it trains under dp / fsdp / ep")
            elif mcfg.moe_router_width not in (None, mcfg.num_experts) \
                    or mcfg.moe_first_expert:
                refused = ("a held SHARE of the router's experts "
                           "(moe_router_width / moe_first_expert): what "
                           "the absent experts add would be left out; a "
                           "train step holds every expert, spread over "
                           "'ep'")
            elif mcfg.num_experts % dist.ep.size:
                refused = (f"{mcfg.num_experts} experts, which do not "
                           f"spread over ep.size={dist.ep.size}")
            if refused:
                raise ConfigError(f"training a model with moe_dispatch="
                                  f"'grouped' and {refused} is not "
                                  f"supported")
        self.model = model
        self.config = config
        self.optimizer = optimizer or optax.adamw(1e-4)
        # bf16 compute-params shadow (config.compute.bf16_compute_params):
        # wrap BEFORE init so the shadow exists in opt_state from step 0
        self._shadow_on = config.compute.bf16_compute_params
        if self._shadow_on:
            from torchacc_tpu.train.amp import bf16_param_shadow
            if optimizer is not None:
                # grads reach the chain in bf16 (grad_accum=1): any
                # norm-reducing transform must upcast per element —
                # optax.clip_by_global_norm does NOT
                logger.info(
                    "bf16_compute_params with a user optimizer: grads "
                    "arrive bf16; use schedules.clip_by_global_norm_f32 "
                    "(not optax.clip_by_global_norm) for norm clipping")
            self.optimizer = bf16_param_shadow(self.optimizer)
        self.mesh = mesh if mesh is not None else config.get_mesh()
        self.rules = make_rules(config)
        self._axes_rules = axes_rules
        # loss(logits, batch) -> scalar mean OR (sum, valid_count); the
        # sum/count form gives exact big-batch equivalence under grad accum.
        self._custom_loss = loss is not None
        self.loss = loss or (lambda logits, batch: loss_sum_count(
            logits, batch.get("labels", shift_labels(
                batch["input_ids"], batch.get("segment_ids")))))
        self._aux_weight = getattr(getattr(model, "cfg", None),
                                   "router_aux_weight", 0.0)
        # quantized matmuls (compute.quant via the model cfg): the
        # delayed-scaling amax histories ride TrainState.quant through
        # the jitted step — dispatched, donated, checkpointed and
        # restored exactly like the AMP scaler state
        self._quant_on = (getattr(getattr(model, "cfg", None),
                                  "quant", "none") != "none")
        # fused linear+CE (ops/fused.py): default loss only, zoo model only
        from torchacc_tpu.models.transformer import TransformerLM, layer_loop
        # 'scan' | 'unrolled' for a zoo model ('custom' for any other
        # module): which layer loop the step runs, said by the start-up
        # line and on every train/dispatch span
        self.layer_loop = (layer_loop(model.cfg)
                           if isinstance(model, TransformerLM) else "custom")
        self._use_fused_ce = (loss is None
                              and config.compute.fused_kernels
                              and isinstance(model, TransformerLM)
                              # the chunked head has no bias term;
                              # head_bias models (phi-2) use the
                              # materialised-logits loss
                              and not model.cfg.head_bias)
        if (self._quant_on
                and "head" in getattr(model.cfg, "quant_sites", ())
                and self._use_fused_ce):
            # the fused-CE path computes the head inside the chunked
            # loss and never reaches the lm_head module — a 'head'
            # quant site would be silently inert (with a dead amax
            # history riding every checkpoint).  Keep the failure loud.
            raise TrainerStateError(
                "compute.quant_sites includes 'head' but the fused "
                "linear+CE loss path is active — the chunked head "
                "stays in the compute dtype.  Set "
                "compute.fused_kernels=False to quantize the "
                "materialised head, or drop 'head' from quant_sites.")
        # step-level anomaly guards (resilience/guard.py): EW grad-norm
        # statistics threaded through the jitted step, host-side
        # consecutive-anomaly monitor
        res = config.resilience
        # fp16's GradScaler already owns non-finite skipping, so a
        # nan_guard alone would be a permanent no-op there — don't pay
        # the guard's per-step host sync for it
        self._guard_on = res.spike_guard or (
            res.nan_guard and config.compute.dtype != "float16")
        self._guard_state = None
        self._guard_monitor = None
        # SDC defense (resilience/sdc.py): with either sdc interval
        # configured the jitted step also emits a per-DP-replica digest
        # of the final grads; the host compares them on the cadence
        self._sdc_on = (res.sdc_check_interval_steps is not None
                        or res.sdc_recompute_interval_steps is not None)
        self._sdc_monitor = None
        self._sdc_run_dir: Optional[str] = None
        # the last fit's checkpoint dir: resumable_tiers() scans it for
        # the exit disposition even after the abort closed the manager
        self._last_checkpoint_dir: Optional[str] = None
        # dispatch pipelining (perf.dispatch_depth, module docstring):
        # the ring buffer of in-flight steps, the host-side mirror of
        # state.step (no per-step device fetch to learn the index), and
        # the host-blocked-time meter every blocking fetch reports to
        from torchacc_tpu.utils.metrics import BlockedMeter
        self._lag = config.perf.dispatch_depth - 1
        self._inflight: "collections.deque[_InFlightStep]" = \
            collections.deque()
        self.last_resolved: Optional[_InFlightStep] = None
        self._host_step: Optional[int] = None
        self.blocked = BlockedMeter()
        # save-path wall time (snapshot enqueue + checkpoint hand-off
        # on writing steps) metered separately so records attribute the
        # save-step sync gap honestly (save_blocked_ms; the verdict
        # drain between the two is NOT included — its blocking fetches
        # land in host_blocked_ms, and it may legitimately run an eval
        # pass that must not be booked as save cost)
        self.save_blocked = BlockedMeter()
        self.state: Optional[TrainState] = None
        self.state_shardings = None
        # tiered zero-stall checkpointing (checkpoint/tiered.py): the
        # manager is cached per checkpoint-dir so tier-0 host-RAM
        # snapshots survive an in-process supervisor's catch-and-refit
        # (restore-from-RAM); _tiered_active is set only while a fit
        # with tiered saves is running — resolve_oldest advances its
        # verdict watermark there
        self._tiered_cache: Optional[Tuple[Any, Any]] = None
        self._tiered_active = None
        self._abstract: Optional[TrainState] = None
        self.batch_sharding = NamedSharding(self.mesh, batch_spec(config))
        self._train_step = None
        self._train_step_structure = None
        # zero-copy tiered snapshots: a tiered save hands the LIVE state
        # to the background writer instead of paying a state-sized
        # device copy on the hot path; the one step dispatched after it
        # runs a NON-DONATING variant of the same compiled step so the
        # handed-off buffers survive (same transient 2x-state memory
        # the copy would have cost, zero memcpy, bitwise-identical
        # math).  Compiled lazily on the first post-save step.
        self._train_step_nodonate = None
        self._no_donate_once = False
        # telemetry session state (obs/runtime.FitObs): set by fit()
        # while a run is live; _watchdog is published for the heartbeat
        # gauge/health provider
        self._obs_fit = None
        self._watchdog = None
        self._metrics_sharding = NamedSharding(self.mesh, PartitionSpec())

    def _batch_shardings(self, batch) -> Dict[str, Any]:
        """Per-leaf batch shardings: leading dim over the data axes, seq
        dim (rank>=2) over the sequence axes, scalars replicated."""
        full = self.batch_sharding.spec

        def one(leaf):
            ndim = getattr(leaf, "ndim", 0)
            spec = PartitionSpec(*full[:min(ndim, len(full))])
            return NamedSharding(self.mesh, spec)
        return jax.tree.map(one, batch)

    # -- init ---------------------------------------------------------------
    def resolve_shardings(
        self, rng: Optional[jax.Array] = None,
        sample_input: Optional[jax.Array] = None,
    ):
        """Compute abstract state + NamedShardings WITHOUT materialising
        anything on device (restore() uses this directly so a checkpoint
        load never pays for a throwaway init)."""
        if rng is None:
            rng = jax.random.PRNGKey(self.config.seed)
        if sample_input is None:
            # dummy input sized so every sharded dim divides the mesh
            # (params do not depend on batch/seq; this only drives tracing)
            m = self.mesh.shape
            bs = m.get("dp", 1) * m.get("fsdp", 1) * m.get("ep", 1)
            sq = 8 * m.get("sp", 1) * m.get("spu", 1)
            sample_input = jnp.zeros((bs, sq), jnp.int32)
        use_scaler = self.config.compute.dtype == "float16"
        init_fn = lambda r: init_train_state(
            r, self.model, self.optimizer, sample_input,
            use_scaler=use_scaler)
        abstract = jax.eval_shape(init_fn, rng)
        p_axes = (resolve_param_axes(abstract.params)
                  if self._axes_rules is None
                  else resolve_param_axes(abstract.params, self._axes_rules))
        st_axes = state_logical_axes(abstract, p_axes)
        min_sz = self.config.dist.fsdp.min_weight_size
        self.state_shardings = TrainState(
            step=NamedSharding(self.mesh, PartitionSpec()),
            params=tree_shardings(self.mesh, abstract.params, st_axes.params,
                                  self.rules, min_sz),
            opt_state=tree_shardings(self.mesh, abstract.opt_state,
                                     st_axes.opt_state, self.rules, min_sz),
            scaler=tree_shardings(self.mesh, abstract.scaler,
                                  st_axes.scaler, self.rules),
            quant=tree_shardings(self.mesh, abstract.quant,
                                 st_axes.quant, self.rules),
        )
        self._abstract = abstract
        return init_fn, rng

    def init(self, rng: Optional[jax.Array] = None,
             sample_input: Optional[jax.Array] = None) -> TrainState:
        init_fn, rng = self.resolve_shardings(rng, sample_input)
        with jax.sharding.set_mesh(self.mesh):
            self.state = jax.jit(
                init_fn, out_shardings=self.state_shardings)(rng)
        self._host_step = 0
        self._log_initialised()
        return self.state

    def _log_initialised(self) -> None:
        n_params = sum(x.size for x in jax.tree.leaves(self.state.params))
        logger.info(f"initialised {n_params/1e6:.1f}M params on mesh "
                    f"{dict(self.mesh.shape)} layers={self.layer_loop}")

    def init_from_params(self, params: Any) -> TrainState:
        """Sharded state from EXISTING params (e.g. HF-converted
        weights): params land directly in their shards, optimizer state
        initialises sharded, step starts at 0.  Replaces the manual
        resolve_shardings + device_put + TrainState dance."""
        if self.state_shardings is None:
            # the streamed-ingestion path resolves shardings up front
            # (to place weights as they arrive) — don't repeat the full
            # abstract-init trace of an 80-layer state tree here
            self.resolve_shardings()
        sh = self.state_shardings
        params = jax.device_put(params, sh.params)
        use_scaler = self.config.compute.dtype == "float16"

        abstract_quant = self._abstract.quant if self._abstract else None

        def mk(p):
            scaler = None
            if use_scaler:
                from torchacc_tpu.train.amp import scaler_init
                scaler = scaler_init()
            # fresh amax histories (zeros = "no observation yet"; the
            # first quantized step falls back to just-in-time scales)
            quant = jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype),
                                 abstract_quant)
            return TrainState(step=jnp.zeros((), jnp.int32), params=p,
                              opt_state=self.optimizer.init(p),
                              scaler=scaler, quant=quant)

        with jax.sharding.set_mesh(self.mesh):
            # donate: params would otherwise be held twice on device
            # during init (the large-model case this path exists for)
            self.state = jax.jit(mk, out_shardings=sh,
                                 donate_argnums=0)(params)
        self._host_step = 0
        self._log_initialised()
        return self.state

    def swap_params(self, params: Any, *, reinit_opt: bool = True,
                    verify_shadow: bool = False) -> TrainState:
        """Replace ``state.params`` and refresh everything derived from
        them ATOMICALLY — the only supported way to load new weights
        into an initialised trainer.

        Assigning ``state = state.replace(params=...)`` by hand is a
        silent-corruption hazard under ``compute.bf16_compute_params``:
        the bf16 forward shadow lives in ``opt_state`` and is refreshed
        only by ``optimizer.update`` (train/amp.bf16_param_shadow), so a
        bare swap leaves the forward silently training against the OLD
        weights.  This helper upholds the invariant ``shadow ==
        cast(params)`` at every step boundary:

        - in-flight steps drain first (their verdicts belong to the old
          weights);
        - ``reinit_opt=True`` (default) rebuilds ``opt_state`` from the
          new params — moments restart, the shadow is fresh by
          construction (the right call for externally converted
          weights);
        - ``reinit_opt=False`` keeps the optimizer moments and
          re-derives only the shadow (fine-tuning warm-starts where the
          new params are a small perturbation);
        - ``verify_shadow=True`` fetches and asserts the invariant
          bitwise over EVERY leaf after the swap (and holds under
          ``python -O``); an ordinary interpreter run (``__debug__``)
          asserts a small leaf sample for free.

        The new params must match the current state's tree structure,
        shapes and dtypes; they are placed into the existing shardings.
        ``step``/``scaler``/``quant`` are preserved."""
        if self.state is None:
            raise TrainerStateError(
                "swap_params needs an initialised trainer — call "
                "init()/init_from_params()/restore() first")
        self.drain()
        old = jax.tree.structure(self.state.params)
        new = jax.tree.structure(params)
        if old != new:
            raise TrainerStateError(
                f"swap_params: new params tree does not match the "
                f"live state ({new} vs {old})")
        # structure alone is not enough: a shape/dtype drift would pass
        # device_put and surface later as a jit recompile/shape error
        # deep in the train step (or a silent dtype change) — fail HERE
        # with the offending leaves named
        bad = []
        for (path, live), (_, cand) in zip(
                jax.tree_util.tree_leaves_with_path(self.state.params),
                jax.tree_util.tree_leaves_with_path(params)):
            ls, cs = jnp.shape(live), jnp.shape(cand)
            ld = jnp.asarray(live).dtype if not hasattr(live, "dtype") \
                else live.dtype
            cd = jnp.asarray(cand).dtype if not hasattr(cand, "dtype") \
                else cand.dtype
            if ls != cs or ld != cd:
                bad.append(f"{jax.tree_util.keystr(path)}: "
                           f"{cs}/{cd} vs live {ls}/{ld}")
        if bad:
            raise TrainerStateError(
                "swap_params: new params do not match the live state's "
                "leaf shapes/dtypes — " + "; ".join(bad[:8])
                + (f" (+{len(bad) - 8} more)" if len(bad) > 8 else ""))
        sh = (self.state_shardings.params
              if self.state_shardings is not None else None)
        with jax.sharding.set_mesh(self.mesh):
            if sh is not None:
                params = jax.device_put(params, sh)
            if reinit_opt:
                opt_sh = (self.state_shardings.opt_state
                          if self.state_shardings is not None else None)
                opt_state = jax.jit(
                    self.optimizer.init, out_shardings=opt_sh)(params)
            elif self._shadow_on:
                from torchacc_tpu.train.amp import shadow_cast
                inner_state, _stale = self.state.opt_state
                opt_state = (inner_state, jax.jit(shadow_cast)(params))
            else:
                opt_state = self.state.opt_state
        self.state = self.state.replace(params=params,
                                        opt_state=opt_state)
        # verify_shadow=True checks every leaf (and must hold under
        # `python -O` too — explicit raise, not `assert`); the ambient
        # __debug__ path samples a few leaves so routine swaps on
        # multi-GB models do not pay a host sync per leaf
        check = (None if verify_shadow else 4) if (verify_shadow
                                                   or __debug__) else 0
        if check != 0 and not self._shadow_consistent(sample=check):
            raise AssertionError(
                "bf16 shadow != cast(params) after swap_params — "
                "report: the atomic-swap invariant is broken")
        return self.state

    def _shadow_consistent(self, sample: Optional[int] = None) -> bool:
        """Debug probe for the bf16-shadow invariant: every shadow leaf
        equals its master cast to the compute dtype, bitwise
        (``sample=N`` checks an evenly-strided N leaves — the cheap
        ambient-__debug__ mode).  True when the shadow is off (nothing
        to hold)."""
        if not self._shadow_on or self.state is None:
            return True
        from torchacc_tpu.train.amp import shadow_cast, shadow_params
        shadow = shadow_params(self.state.opt_state)
        want = shadow_cast(self.state.params)
        pairs = list(zip(jax.tree.leaves(shadow), jax.tree.leaves(want)))
        if sample is not None and 0 < sample < len(pairs):
            pairs = pairs[::max(1, len(pairs) // sample)]
        return all(bool(jnp.all(a == b)) for a, b in pairs)

    # -- train step ---------------------------------------------------------
    @property
    def _attn_dropout_on(self) -> bool:
        mc = getattr(self.model, "cfg", None)
        return (bool(getattr(mc, "attn_dropout", 0.0))
                and not self.config.compute.deterministic)

    def _forward_sum_count(self, params, batch, dropout_seed=None,
                           quant=None):
        """(loss_sum, token_count, new_quant, stats) incl. sown auxiliary
        losses (MoE router load-balance — models/moe.py) weighted per
        token.  ``stats``: what the forward counted beside the loss, for
        the step's metrics — of a model with dropless expert layers
        ``moe_load`` (int32 [expert layers, 5]: pairs on held experts,
        the busiest one's, held experts hit, the busiest shard's pairs,
        its sorted buffers' rows) and ``moe_aux_loss`` (the
        layers' summed load-balance terms, unweighted); else empty.

        ``dropout_seed`` is passed only on train steps of zoo models with
        attn_dropout configured — eval/inference stays deterministic.
        ``quant`` is the delayed-scaling state (TrainState.quant) when
        quantized matmuls are on; the mutated histories come back as the
        third element (None when quant is off — eval discards them, the
        train step threads them into the next TrainState)."""
        pp = self.config.dist.pp
        if (pp.size > 1 and pp.schedule == "1f1b"
                and hasattr(self.model, "cfg")):
            # 1F1B fuses head+loss into the last pipeline stage, so the
            # whole forward+loss goes through the schedule (the GPipe
            # path below instead autodiffs through model.apply).  A
            # custom Trainer loss runs inside that last stage per
            # micro-batch; it sees {"labels": ...} only (losses needing
            # other batch leaves should use gpipe).
            from torchacc_tpu.models.transformer import (
                pp_1f1b_forward_sum_count,
            )
            l_sum, count = pp_1f1b_forward_sum_count(
                self.model.cfg, params, batch["input_ids"],
                positions=batch.get("positions"),
                segment_ids=batch.get("segment_ids"),
                labels=batch.get("labels"),
                dropout_seed=(dropout_seed if self._attn_dropout_on
                              else None),
                use_fused_ce=self._use_fused_ce,
                custom_loss=(self.loss if self._custom_loss else None))
            return l_sum, count, None, {}
        extra = {}
        variables = {"params": params}
        mutable = ["intermediates"]
        if quant is not None:
            # quantized sites read the delayed scales and append this
            # step's amax; eval callers discard the mutation
            variables["quant"] = quant
            mutable.append("quant")
        if dropout_seed is not None and self._attn_dropout_on:
            extra["dropout_seed"] = dropout_seed
        # labels are needed by the aux-weight block AND the fused-CE
        # head below — derive once so the two cannot drift
        pp_labels = None

        def _labels():
            nonlocal pp_labels
            if pp_labels is None:
                pp_labels = batch.get("labels", shift_labels(
                    batch["input_ids"], batch.get("segment_ids")))
            return pp_labels

        if (pp.size > 1 and self._aux_weight
                and getattr(getattr(self.model, "cfg", None),
                            "num_experts", 0) > 0):
            # MoE x GPipe: per-row aux weights (count_m / count_total of
            # each row's micro) ride the pipeline so router aux follows
            # the same valid-token weighting as 1F1B and the grad-accum
            # loop's DEFAULT loss.  Counts use the labels != -100
            # convention — the same one 1F1B uses — so a custom loss
            # with different validity semantics sees the shared
            # convention, not its own count.
            labels = _labels()
            M = pp.num_micro_batches
            if labels.shape[0] % M:
                raise ValueError(
                    f"batch {labels.shape[0]} not divisible by "
                    f"num_micro_batches {M}")
            mb = labels.shape[0] // M
            lab_m = labels.reshape((M, mb) + labels.shape[1:])
            cnt = jnp.sum(lab_m != -100,
                          axis=tuple(range(1, lab_m.ndim))
                          ).astype(jnp.float32)
            w = cnt / jnp.maximum(jnp.sum(cnt), 1.0)
            extra["moe_aux_row_weights"] = jnp.repeat(w, mb)
        if self._use_fused_ce:
            from torchacc_tpu.ops import fused
            hidden, mutated = self.model.apply(
                variables, batch["input_ids"],
                positions=batch.get("positions"),
                segment_ids=batch.get("segment_ids"),
                return_hidden=True,
                mutable=mutable, **extra)
            if "lm_head" in params:
                w_head = params["lm_head"]["kernel"]
            else:  # tied embeddings
                w_head = params["embed_tokens"]["embedding"].T
            labels = _labels()
            # _use_fused_ce is gated on isinstance(model, TransformerLM),
            # so .cfg is always present here — no defensive default that
            # could silently drop the cap
            l_sum, count = fused.fused_linear_cross_entropy(
                hidden, w_head, labels,
                logit_softcap=self.model.cfg.logit_softcap)
            rows = ("sharded" if fused.head_row_axes(hidden.shape[0])
                    else "whole")
            impl = fused.head_impl(
                hidden, w_head, logit_softcap=self.model.cfg.logit_softcap)
            if (rows, impl) != (self.head_rows, self.head_impl):
                # trace time: once a program
                self.head_rows, self.head_impl = rows, impl
                logger.info(f"traced the loss on mesh "
                            f"{dict(self.mesh.shape)} "
                            f"layers={self.layer_loop} head={rows} "
                            f"kernels={impl}")
        else:
            out = self.model.apply(
                variables, batch["input_ids"],
                positions=batch.get("positions"),
                segment_ids=batch.get("segment_ids"),
                mutable=mutable, **extra)
            logits, mutated = out
            res = self.loss(logits, batch)
            if isinstance(res, tuple):
                l_sum, count = res
            else:
                l_sum, count = res, jnp.asarray(1.0, jnp.float32)
        from torchacc_tpu.models.transformer import (
            _sown_aux_sum,
            sown_expert_load,
        )
        if self._aux_weight:
            l_sum = l_sum + self._aux_weight * _sown_aux_sum(mutated) * count
        stats = {}
        load = sown_expert_load(mutated)
        if load is not None:
            stats = {"moe_load": load,
                     "moe_aux_loss": _sown_aux_sum(mutated)}
        return l_sum, count, (mutated.get("quant")
                              if quant is not None else None), stats

    # 'sharded' | 'whole': where the fused head's rows lived in the
    # program traced last (ops/fused.head_row_axes reads the mesh and
    # the batch at trace time, so None until a step is traced).  A
    # class-level default BELOW _forward_sum_count: the line numbers of
    # its model.apply call are serialized into every Pallas kernel's
    # body, so a line added above it changes each program's bytes
    head_rows: Optional[str] = None
    # 'pallas' | 'xla': what ran a chunk of that head (ops/fused.head_impl
    # reads the backend, the mesh and the shapes the same way)
    head_impl: Optional[str] = None

    def _build_train_step(self, sample_batch, donate: bool = True):
        accum = self.config.grad_accum
        optimizer = self.optimizer
        use_scaler = self.config.compute.dtype == "float16"
        dropout_on = self._attn_dropout_on
        base_fsc = self._forward_sum_count
        from torchacc_tpu.utils.remat import offload_is_live
        offload_live = offload_is_live(self.config.memory)

        shadow_on = self._shadow_on
        res_cfg = self.config.resilience
        guard_on = self._guard_on
        sdc_on = self._sdc_on
        quant_on = self._quant_on

        def train_step(state: TrainState, batch: Dict[str, jax.Array],
                       gstate=None, sdc_flip=None):
            # bf16 compute-params: the forward differentiates the bf16
            # shadow out of opt_state (no full-tree f32->bf16 cast in
            # the step); the optimizer applies the bf16 grads to the f32
            # masters and refreshes the shadow (amp.bf16_param_shadow)
            if shadow_on:
                from torchacc_tpu.train.amp import shadow_params
                fwd_params = shadow_params(state.opt_state)
            else:
                fwd_params = state.params
            # train steps supply a per-step dropout seed (step * accum,
            # deterministic given the checkpointed step, advanced per
            # accumulation micro-step below so every forward draws a
            # fresh mask); eval/inference never passes one
            if dropout_on:
                step_seed = state.step.astype(jnp.int32) * accum
                fsc = lambda p, b, s=None, q=None: base_fsc(
                    p, b, dropout_seed=step_seed if s is None
                    else step_seed + s, quant=q)
            else:
                fsc = lambda p, b, s=None, q=None: base_fsc(p, b, quant=q)
            # fp16: scale the loss so small grads survive the fp16 range
            # (reference GradScaler core/amp.py; here fully in-jit)
            scale = (state.scaler["scale"] if use_scaler
                     else jnp.asarray(1.0, jnp.float32))
            fwd_stats = {}
            if accum > 1:
                bsz = batch["input_ids"].shape[0]
                if bsz % accum != 0:
                    raise ValueError(
                        f"batch size {bsz} not divisible by grad_accum {accum}")

                if quant_on:
                    # the micro-steps chain the delayed-scaling state:
                    # micro i quantizes with the history micro i-1 left
                    # (same sequencing an unaccumulated loop would see)
                    def scaled_sum_q(p, mb, mi, q):
                        l, c, q2, _ = fsc(p, mb, mi, q)
                        return l * scale, (c, q2)
                    grad_sum_q = jax.value_and_grad(scaled_sum_q,
                                                    has_aux=True)
                else:
                    def scaled_sum(p, mb, mi):
                        l, c, _, _ = fsc(p, mb, mi)
                        return l * scale, c

                    grad_sum = jax.value_and_grad(scaled_sum, has_aux=True)

                # grad accumulators in compute.accum_dtype (bfloat16 halves
                # the buffer memory; f32 default keeps exact summation)
                acc_dt = jnp.bfloat16 \
                    if self.config.compute.accum_dtype == "bfloat16" \
                    else jnp.float32

                def micro(carry, xs):
                    mb, mi = xs
                    if quant_on:
                        g_acc, l_acc, c_acc, q = carry
                        (l, (c, q2)), g = grad_sum_q(fwd_params, mb, mi, q)
                        return (jax.tree.map(
                                    lambda a, b: a + b.astype(acc_dt),
                                    g_acc, g),
                                l_acc + l, c_acc + c, q2), None
                    g_acc, l_acc, c_acc = carry
                    (l, c), g = grad_sum(fwd_params, mb, mi)
                    return (jax.tree.map(
                                lambda a, b: a + b.astype(acc_dt), g_acc, g),
                            l_acc + l, c_acc + c), None
                def to_micro(x):
                    if getattr(x, "ndim", 0) == 0:
                        # scalar leaves replicate across micro-steps
                        return jnp.broadcast_to(x, (accum,))
                    return x.reshape((accum, x.shape[0] // accum)
                                     + x.shape[1:])
                mbs = jax.tree.map(to_micro, batch)
                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, acc_dt), state.params)
                carry0 = (zeros, jnp.zeros((), jnp.float32),
                          jnp.zeros((), jnp.float32))
                if quant_on:
                    carry0 = carry0 + (state.quant,)
                    (grads, loss_sum, count, new_quant), _ = jax.lax.scan(
                        micro, carry0,
                        (mbs, jnp.arange(accum, dtype=jnp.int32)))
                else:
                    new_quant = None
                    (grads, loss_sum, count), _ = jax.lax.scan(
                        micro, carry0,
                        (mbs, jnp.arange(accum, dtype=jnp.int32)))
                denom = jnp.maximum(count, 1.0) * scale
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32) / denom, grads)
                loss_val = loss_sum / denom
            else:
                if quant_on:
                    def scalar_q(p):
                        l, c, q2, _ = fsc(p, batch, q=state.quant)
                        return (l / jnp.maximum(c, 1.0)) * scale, q2
                    (loss_s, new_quant), grads = jax.value_and_grad(
                        scalar_q, has_aux=True)(fwd_params)
                else:
                    new_quant = None

                    def scalar(p):
                        l, c, _, stats = fsc(p, batch)
                        return (l / jnp.maximum(c, 1.0)) * scale, stats
                    (loss_s, fwd_stats), grads = jax.value_and_grad(
                        scalar, has_aux=True)(fwd_params)
                grads = jax.tree.map(lambda g: g / scale, grads)
                loss_val = loss_s / scale

            from torchacc_tpu.train.amp import global_norm_f32

            # f32-accumulated: bf16 grad trees (shadow mode) would
            # otherwise norm-reduce in bf16
            with jax.named_scope("optimizer"):
                grad_norm = global_norm_f32(grads)
            ok = kind = new_gstate = None
            if guard_on:
                # anomaly verdict (resilience/guard.py): non-finite loss
                # and/or EW grad-norm spike, selected in-graph below the
                # same way the fp16 scaler skips overflow steps.  Under
                # the scaler, overflow handling stays the scaler's job —
                # a scale backoff is not an anomaly.
                from torchacc_tpu.resilience.guard import guard_apply
                ok, kind, new_gstate = guard_apply(
                    gstate, loss_val, grad_norm, res_cfg,
                    check_finite=not use_scaler)

            new_scaler = state.scaler
            if use_scaler:
                from torchacc_tpu.train.amp import (
                    all_finite,
                    scaler_update,
                    select_tree,
                )
                finite = all_finite(grads)
                safe_grads = jax.tree.map(
                    lambda g: jnp.where(jnp.isfinite(g), g, 0.0), grads)
                with jax.named_scope("optimizer"):
                    updates, opt_candidate = optimizer.update(
                        safe_grads, state.opt_state, state.params)
                    params_candidate = optax.apply_updates(state.params,
                                                           updates)
                # skip the step entirely on overflow — no host sync
                keep = finite if ok is None else finite & ok
                new_params = select_tree(keep, params_candidate,
                                         state.params)
                new_opt = select_tree(keep, opt_candidate, state.opt_state)
                new_scaler = scaler_update(state.scaler, finite)
                if quant_on:
                    # a skipped (overflow/anomalous) step must not poison
                    # the amax history either — its activations may be
                    # the very non-finite values being skipped
                    new_quant = select_tree(keep, new_quant, state.quant)
            else:
                with jax.named_scope("optimizer"):
                    updates, opt_candidate = optimizer.update(
                        grads, state.opt_state, state.params)
                    params_candidate = optax.apply_updates(state.params,
                                                           updates)
                if ok is None:
                    new_params, new_opt = params_candidate, opt_candidate
                else:
                    from torchacc_tpu.train.amp import select_tree
                    new_params = select_tree(ok, params_candidate,
                                             state.params)
                    new_opt = select_tree(ok, opt_candidate,
                                          state.opt_state)
                    if quant_on:
                        new_quant = select_tree(ok, new_quant,
                                                state.quant)

            sdc_digests = None
            if sdc_on:
                # per-DP-replica digest of the final grads (post-psum,
                # logically replicated over dp): each replica folds its
                # OWN physical copy, so a flaky chip's bits diverge
                # here and nowhere upstream can hide them.  With
                # sdc_digest_optimizer the POST-APPLY params ride the
                # same matrix (rows: grads/<leaf> then params/<leaf> —
                # _ensure_sdc_monitor mirrors the order), so corruption
                # in the optimizer apply itself surfaces on the step it
                # happens instead of one step late through the next
                # step's gradients.  Digesting here — after the apply —
                # changes nothing for the grads rows (the fold is a
                # pure function of the grads).
                from torchacc_tpu.resilience.sdc import replica_digests
                digest_tree = grads
                # param shardings steer the bounded subsample's strides
                # onto unsharded dims (shard-local digesting — no GSPMD
                # gather on huge fsdp/tp-sharded leaves); grads share
                # the params' tree structure
                leaf_specs = None
                if (res_cfg.sdc_digest_max_elems is not None
                        and self.state_shardings is not None):
                    leaf_specs = [
                        getattr(s, "spec", None) for s in
                        jax.tree.leaves(self.state_shardings.params)]
                if res_cfg.sdc_digest_optimizer:
                    # dict keys sort 'grads' < 'params' — flatten order
                    # is grads leaves then params leaves
                    digest_tree = {"grads": grads, "params": new_params}
                    if leaf_specs is not None:
                        leaf_specs = leaf_specs + leaf_specs
                sdc_digests = replica_digests(
                    digest_tree, sdc_flip, mesh=self.mesh,
                    max_elems=res_cfg.sdc_digest_max_elems,
                    leaf_specs=leaf_specs)

            metrics = {
                "loss": loss_val,
                "grad_norm": grad_norm,
                # what the forward counted (the expert layers' load; the
                # unaccumulated, unquantized step only)
                **fwd_stats,
            }
            if use_scaler:
                metrics["loss_scale"] = new_scaler["scale"]
            if guard_on:
                metrics["anomaly"] = (~ok).astype(jnp.float32)
                metrics["anomaly_kind"] = kind
            if sdc_on:
                metrics["sdc_digests"] = sdc_digests
            new_state = TrainState(step=state.step + 1, params=new_params,
                                   opt_state=new_opt, scaler=new_scaler,
                                   quant=(new_quant if quant_on
                                          else state.quant))
            if offload_live:
                # pin output shardings in-graph instead of via
                # out_shardings (see the jit below)
                new_state = jax.tree.map(
                    jax.lax.with_sharding_constraint, new_state,
                    self.state_shardings)
                metrics = jax.tree.map(
                    lambda m: jax.lax.with_sharding_constraint(
                        m, self._metrics_sharding), metrics)
                if guard_on:
                    new_gstate = jax.tree.map(
                        lambda g: jax.lax.with_sharding_constraint(
                            g, self._metrics_sharding), new_gstate)
            if guard_on:
                return new_state, new_gstate, metrics
            return new_state, metrics

        # Host-offload remat makes the lowered module contain memory-kind
        # ops, which flips jit's out_shardings handling into annotating
        # EVERY output with an `annotate_device_placement` custom call —
        # and the SPMD partitioner RET_CHECKs on the scalar outputs
        # (step, adam count) whose annotate carries no sharding
        # (spmd_partitioner.cc:5743, 'Side-effect HLO must have
        # sharding').  Pinning the outputs with in-graph
        # with_sharding_constraint instead keeps the layouts AND skips
        # the output-annotate path, so multi-device SPMD offload works.
        in_sh = [self.state_shardings, self._batch_shardings(sample_batch)]
        out_sh = [self.state_shardings]
        if guard_on:
            # guard statistics ride as a donated operand (replicated
            # scalars); deliberately NOT part of TrainState so
            # checkpoint layouts are unchanged — the EW stats persist
            # as an advisory guard_state.json sidecar per committed
            # step instead, and fit(resume='auto') restores them
            in_sh.append(self._metrics_sharding)
            out_sh.append(self._metrics_sharding)
        if sdc_on:
            # the chaos/no-op digest flip operand: tiny replicated
            # arrays rebuilt host-side each step, never donated
            in_sh.append(self._metrics_sharding)
        out_sh.append(self._metrics_sharding)  # metrics dict (prefix)
        if guard_on and sdc_on:
            fn = train_step
        elif guard_on:
            fn = lambda s, b, g: train_step(s, b, g)
        elif sdc_on:
            fn = lambda s, b, f: train_step(s, b, None, f)
        else:
            fn = lambda s, b: train_step(s, b)
        return jax.jit(
            fn,
            in_shardings=tuple(in_sh),
            out_shardings=(None if offload_live else tuple(out_sh)),
            donate_argnums=(() if not donate
                            else (0, 2) if guard_on else (0,)),
        )

    def _ensure_compiled(self, batch: Dict[str, jax.Array]) -> None:
        # keyed on structure AND leaf ranks: in_shardings depend on rank
        structure = (jax.tree.structure(batch),
                     tuple(getattr(x, "ndim", 0)
                           for x in jax.tree.leaves(batch)))
        if self._train_step is None or structure != self._train_step_structure:
            self._train_step = self._build_train_step(batch)
            self._train_step_structure = structure
            self._train_step_nodonate = None

    def _ensure_guard(self) -> None:
        from torchacc_tpu.resilience.guard import GuardMonitor, guard_init
        if self._guard_state is None:
            self._guard_state = jax.device_put(guard_init(),
                                               self._metrics_sharding)
        if self._guard_monitor is None:
            self._guard_monitor = GuardMonitor(self.config.resilience)

    def _ensure_sdc_monitor(self):
        from torchacc_tpu.resilience.sdc import SDCMonitor, leaf_paths_of
        if self._sdc_monitor is None:
            if self._abstract is None:
                self.resolve_shardings()
            paths = leaf_paths_of(self._abstract.params)
            if self.config.resilience.sdc_digest_optimizer:
                # the digest matrix carries grads rows then post-apply
                # param rows (the {'grads':..., 'params':...} flatten
                # order in the jitted step) — name them apart so a
                # divergence report says WHICH side went bad
                paths = ([f"grads/{p}" for p in paths]
                         + [f"params/{p}" for p in paths])
            self._sdc_monitor = SDCMonitor(
                self.config.resilience, self.mesh, paths,
                run_dir=self._sdc_run_dir)
        # fit() learns the run dir after the monitor may exist
        self._sdc_monitor.run_dir = self._sdc_run_dir
        return self._sdc_monitor

    def _export_guard_state(self) -> Optional[Dict[str, Any]]:
        """StepGuard EW statistics as JSON-able scalars (f32 -> f64 ->
        JSON decimal round-trips bit-exactly), persisted with each
        committed checkpoint step."""
        if self._guard_state is None:
            return None
        import numpy as np
        # blocks on the NEWEST dispatched step (save steps are sync
        # points regardless — orbax waits on the arrays); metered so
        # host_blocked_ms attributes the wait honestly
        with self._wait():
            gs = jax.device_get(self._guard_state)
        return {k: np.asarray(v).item() for k, v in gs.items()}

    def _import_guard_state(self, d: Dict[str, Any]) -> None:
        """Restore persisted EW statistics (missing keys keep their
        fresh-init values, so older sidecars stay loadable)."""
        from torchacc_tpu.resilience.guard import guard_init
        init = guard_init()
        gs = {k: jnp.asarray(d.get(k, v), v.dtype)
              for k, v in init.items()}
        self._guard_state = jax.device_put(gs, self._metrics_sharding)

    def _sdc_rerun(self, snap, batch: Dict[str, jax.Array],
                   step_idx: int, fn=None):
        """Re-execute the SAME compiled step on the pre-step snapshot
        (donated — it is disposable) and return the digest matrix: same
        executable + same input bits, so on healthy hardware the result
        is bitwise identical by construction.  ``fn`` pins the compiled
        executable captured at dispatch time (under dispatch pipelining
        the verdict may resolve after a recompile)."""
        state_snap, gstate_snap = snap
        flip = self._sdc_monitor.flips(step_idx, "recompute")
        args = [state_snap, batch]
        if self._guard_on:
            args.append(gstate_snap)
        args.append(flip)
        with jax.sharding.set_mesh(self.mesh):
            out = (fn or self._train_step)(*args)
        return jax.device_get(out[-1]["sdc_digests"])

    @contextlib.contextmanager
    def _wait(self):
        """A blocking device fetch: summed by ``self.blocked`` (the
        ``host_blocked_ms`` meter) and shown as one ``train/wait`` span,
        so both count the same intervals."""
        with tracing.span("train/wait"), self.blocked.blocked():
            yield

    def step(self, batch: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        """One optimizer step; returns (async) metrics.

        Dispatches the step and resolves the step at lag
        ``perf.dispatch_depth - 1`` from the in-flight ring buffer
        (module docstring): guard/SDC verdicts and any metric fetch for
        step N happen while step N+k is already executing, so they read
        completed values.  ``self.last_resolved`` carries the entry
        resolved by this call (None while the pipeline is filling).  At
        the default depth 1 every step resolves immediately — exactly
        the pre-pipelining behaviour, fetch-for-fetch."""
        with tracing.span("train/step") as sp:
            metrics = self._step_impl(batch)
            if sp.live:
                self._note_expert_load(sp)
            return metrics

    def _note_expert_load(self, sp) -> None:
        """On a live ``train/step`` span: the expert layers' load of the
        step this call RESOLVED (``moe_*``, the names ``serve/deliver``
        carries: whole numbers, which the benchmark's reader sums; and
        ``moe_live_rows`` of ``moe_buffer_rows``, the share of the
        busiest shard's sorted buffers that held pairs — what the row
        movers of ops/moe_rows.py were asked to move) and its
        load-balance loss (``aux_loss``).  The fetch reads a finished
        step at ``dispatch_depth`` > 1 and happens only while a sink is
        listening; untraced steps fetch nothing."""
        entry = self.last_resolved
        if entry is None or "moe_load" not in entry.metrics:
            return
        with self._wait():
            load, aux = jax.device_get((entry.metrics["moe_load"],
                                        entry.metrics["moe_aux_loss"]))
        layers = int(load.shape[0])
        sp.set(resolved_step=entry.step,
               moe_pairs=int(load[:, 0].sum()),
               moe_max=int(load[:, 1].sum()),
               moe_hit=int(load[:, 2].sum()),
               moe_layer_steps=layers,
               moe_slots=layers * int(self.model.cfg.num_experts),
               moe_live_rows=int(load[:, 3].sum()),
               moe_buffer_rows=int(load[:, 4].sum()),
               aux_loss=float(aux))

    def _step_impl(self, batch):
        from torchacc_tpu.resilience.chaos import failpoint
        failpoint("trainer.step")
        if self.state is None:
            self.init()
        self._ensure_compiled(batch)
        if self._guard_on:
            self._ensure_guard()
        if self._host_step is None:
            # one-time resync after a restore: the only host<->device
            # step-index round-trip the loop ever pays
            with self._wait():
                self._host_step = int(self.state.step)
        si = self._host_step
        sdc_check = sdc_spot = False
        sdc_snap = flip = None
        if self._sdc_on:
            mon = self._ensure_sdc_monitor()
            res = self.config.resilience
            ci = res.sdc_check_interval_steps
            ri = res.sdc_recompute_interval_steps
            sdc_check = ci is not None and si % ci == 0
            sdc_spot = ri is not None and si % ri == 0
            flip = mon.flips(si, "step")
            if sdc_spot or (sdc_check and mon.needs_arbiter()):
                # donation-safe pre-step snapshot (checkpoint.io
                # machinery): the redundant recompute / tie arbiter
                # re-runs the step on these exact bits
                from torchacc_tpu.checkpoint.io import _snapshot
                sdc_snap = (_snapshot(self.state),
                            _snapshot(self._guard_state)
                            if self._guard_on else None)
        args = [self.state, batch]
        if self._guard_on:
            args.append(self._guard_state)
        if self._sdc_on:
            args.append(flip)
        fn = self._train_step
        if self._no_donate_once:
            # the previous boundary handed the live state to the tiered
            # checkpoint writer: this ONE dispatch must not donate it
            # (the writer still reads those buffers).  Same computation,
            # aliasing stripped — values bitwise identical.
            self._no_donate_once = False
            if self._train_step_nodonate is None:
                self._train_step_nodonate = self._build_train_step(
                    batch, donate=False)
            fn = self._train_step_nodonate
        with tracing.span("train/dispatch", step=si,
                          layers=self.layer_loop):
            with jax.sharding.set_mesh(self.mesh):
                out = fn(*args)
        if self._guard_on:
            self.state, self._guard_state, metrics = out
        else:
            self.state, metrics = out
        digests = metrics.pop("sdc_digests", None)
        # advance BEFORE any verdict resolves: the state already
        # committed this step, and a caller catching SDCError /
        # AnomalyError to keep stepping must not desynchronize the
        # cadence from state.step
        self._host_step = si + 1
        rerun = None
        if sdc_snap is not None:
            # capture the executable ACTUALLY dispatched (which may be
            # the non-donating tiered-save variant): the recompute
            # arbiter's bitwise-by-construction guarantee holds only
            # for the same executable, and aliasing differences could
            # in principle change instruction scheduling.  Also
            # shallow-copy the batch dict (same hazard as the metrics
            # copy below): a caller reusing one dict per step must not
            # change what a lagged arbiter re-executes.
            rerun = (lambda snap=sdc_snap, b=dict(batch), s=si, f=fn:
                     self._sdc_rerun(snap, b, s, fn=f))
        ids = batch.get("input_ids") if hasattr(batch, "get") else None
        # shallow-copy the metrics into the entry: the pre-PR API let
        # callers mutate the returned dict freely (observation was
        # already done); under lag the resolution happens k steps later
        # and must not read a caller-modified dict
        self._inflight.append(_InFlightStep(
            step=si, metrics=dict(metrics), digests=digests,
            tokens=(ids.shape[0] * ids.shape[1]
                    if getattr(ids, "ndim", 0) >= 2 else None),
            sdc_check=sdc_check, sdc_spot=sdc_spot, rerun=rerun))
        self.last_resolved = None
        if len(self._inflight) > self._lag:
            self.last_resolved = self.resolve_oldest()
        return metrics

    # -- lagged readback ----------------------------------------------------
    @property
    def pending(self) -> int:
        """Dispatched-but-unresolved step count (<= perf.dispatch_depth)."""
        return len(self._inflight)

    def resolve_oldest(self) -> Optional[_InFlightStep]:
        """Resolve the oldest in-flight step: fetch its verdict scalars
        (already complete at lag > 0), run the guard/SDC monitors
        attributed to THAT step, and return the entry.

        Raises :class:`AnomalyError` / :class:`SDCError` exactly as the
        unpipelined loop did, at most ``dispatch_depth - 1`` steps late
        (abort-after-N becomes abort-within-N+k); the entry is popped
        first, so a caller catching the error stays consistent."""
        if not self._inflight:
            return None
        e = self._inflight.popleft()
        with tracing.span("train/resolve", step=e.step):
            if self._guard_on or (self._sdc_on
                                  and (e.sdc_check or e.sdc_spot)):
                verdict_span = tracing.span("train/verdict", step=e.step)
            else:
                verdict_span = contextlib.nullcontext()
            with verdict_span:
                if self._guard_on:
                    # the abort guarantee costs one scalar fetch per
                    # resolved step (see ResilienceConfig); raises
                    # AnomalyError with a diagnosis once
                    # max_consecutive_anomalies is reached
                    with self._wait():
                        self._guard_monitor.observe(e.step, e.metrics)
                if self._sdc_on and (e.sdc_check or e.sdc_spot):
                    with self._wait():
                        digests = jax.device_get(e.digests)
                    # verdict from replicated data — identical on every
                    # process, so any raise (and any arbiter
                    # re-execution, a collective) happens in lockstep
                    # pod-wide: every process resolves at the same loop
                    # point because dispatch_depth is config, not
                    # discovered
                    self._sdc_monitor.observe(
                        e.step, digests, check=e.sdc_check,
                        spot=e.sdc_spot, recompute=e.rerun)
        # the verdict is recorded — release the digest matrix and the
        # rerun closure (which captures a state-sized arbiter snapshot
        # at dp<=2) NOW, not when the entry itself dies: last_resolved
        # and drain()'s return keep entries alive past this point, and
        # the snapshot budget is documented as peaking at the in-flight
        # count, never in-flight + resolved
        e.digests = None
        e.rerun = None
        # tiered checkpointing: this step's guard/SDC verdicts are in —
        # background trickle commits gated at or below it may proceed.
        # An abort raises above, so the watermark never passes a
        # flagged step and its snapshot is discarded, never committed.
        if self._tiered_active is not None:
            self._tiered_active.notify_verdicts_through(e.step)
        return e

    def drain(self) -> List[_InFlightStep]:
        """Resolve every in-flight step (end of run, preemption, or
        before anything that must see all verdicts).  Returns the
        resolved entries in step order."""
        out = []
        while self._inflight:
            out.append(self.resolve_oldest())
        return out

    # -- checkpointing ------------------------------------------------------
    def abstract_state(self) -> TrainState:
        """ShapeDtypeStructs with target shardings (for resharded restore).
        Resolves shardings on demand; nothing is materialised."""
        if self.state_shardings is None:
            self.resolve_shardings()

        def one(leaf, sh):
            if leaf is None:
                return None
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sh)
        return jax.tree.map(one, self._abstract, self.state_shardings,
                            is_leaf=lambda x: x is None)

    def save(self, path: str, blocking: bool = True):
        """Sharded checkpoint of the full train state (reference:
        per-rank ``ta.save`` + shard_metadata, docs/source/dist/fsdp.md).
        ``blocking=False`` snapshots and writes in the background;
        call ``.wait()`` on the returned handle before relying on it."""
        if self.state is None:
            raise TrainerStateError(
                "nothing to save — call init() (or step) first")
        from torchacc_tpu.checkpoint import save_checkpoint
        return save_checkpoint(path, self.state, blocking=blocking)

    def _adopt_restored(self, state: TrainState) -> TrainState:
        """Re-materialise restored arrays through a jitted identity.

        Orbax-deserialized buffers donated into a persistent-cache
        executable double-free on some jaxlib CPU builds ("corrupted
        double-linked list" abort on the first post-restore step); the
        copy is bitwise-exact, lands buffers the runtime owns, and costs
        one state-sized copy only at restore time."""
        # any restored state invalidates the cached host-side step index
        # (an in-process supervisor re-entering fit(resume='auto') after
        # a failure must not attribute guard/SDC verdicts to phantom
        # steps) AND the in-flight ring: entries dispatched before the
        # failure refer to a timeline the restore just discarded
        self._host_step = None
        self._inflight.clear()
        self.last_resolved = None
        with jax.sharding.set_mesh(self.mesh):
            state = jax.jit(
                lambda s: s, out_shardings=self.state_shardings)(state)
        jax.block_until_ready(state)
        return state

    def restore(self, path: str) -> TrainState:
        """Restore (and reshard if the mesh/layout changed).  Does NOT
        run init first — restored shards are the only allocation."""
        from torchacc_tpu.checkpoint import restore_checkpoint
        self.state = self._adopt_restored(
            restore_checkpoint(path, self.abstract_state()))
        return self.state

    def _tiered_manager(self, checkpoint_dir: str, checkpoint_every: int,
                        res_cfg):
        """The trainer-cached TieredCheckpointManager for this
        checkpoint dir: reused across fit() calls (same key) so tier-0
        host-RAM snapshots survive an in-process supervisor's
        catch-and-refit — restore-from-RAM needs them alive."""
        import os as _os

        from torchacc_tpu.checkpoint.tiered import TieredCheckpointManager
        # the save interval is a property of the fit CALL, not of the
        # store — deliberately not part of the key, so a resume with a
        # different cadence reuses the manager (and its tier-0 RAM
        # snapshots) instead of discarding them
        key = (_os.path.abspath(checkpoint_dir),
               res_cfg.tiered_mirror_dir, res_cfg.tiered_tier0_keep)
        if self._tiered_cache is not None and self._tiered_cache[0] == key:
            mgr = self._tiered_cache[1]
            mgr.set_interval(checkpoint_every)
            return mgr
        if self._tiered_cache is not None:
            self._tiered_cache[1].shutdown()
        mgr = TieredCheckpointManager(
            checkpoint_dir, save_interval_steps=checkpoint_every,
            mirror_dir=res_cfg.tiered_mirror_dir,
            tier0_keep=res_cfg.tiered_tier0_keep,
            retry_policy=res_cfg.retry_policy(res_cfg.ckpt_retries),
            coord_timeout_s=res_cfg.coord_timeout_s,
            elastic_resume=res_cfg.elastic_resume)
        self._tiered_cache = (key, mgr)
        return mgr

    def resumable_tiers(self) -> Dict[str, Optional[int]]:
        """Newest resumable checkpoint step per tier — the field the
        supervisor's exit disposition carries (obs/runtime.py): tier 0
        = this process's verdicted host-RAM snapshots (survive an
        in-process refit, die with the process), tier 1 = commit-marked
        steps in the last checkpoint dir, tier 2 = the mirror.  None =
        that tier holds nothing; all-filesystem except tier 0, so it
        answers even after an abort closed the managers."""
        from torchacc_tpu.checkpoint.tiered import TieredCheckpointManager
        tiers: Dict[str, Optional[int]] = {
            "tier0": None, "tier1": None, "tier2": None}
        if self._tiered_cache is not None:
            ram = self._tiered_cache[1]._ram_steps()
            tiers["tier0"] = max(ram) if ram else None
        fs = TieredCheckpointManager._fs_valid_steps(
            self._last_checkpoint_dir)
        tiers["tier1"] = max(fs) if fs else None
        mirror = TieredCheckpointManager._mirror_valid_steps(
            self.config.resilience.tiered_mirror_dir)
        tiers["tier2"] = max(mirror) if mirror else None
        return tiers

    # -- train -> serve handoff ---------------------------------------------
    def serving_shardings(self, mesh: Optional[Mesh] = None) -> Any:
        """NamedSharding tree of the SERVING layout for ``state.params``:
        data axes (fsdp ZeRO shards) gathered, megatron 'tp' dims kept
        (parallel/transfer.serving_specs — decode reads every weight
        every token, so a fsdp-sharded serving layout would pay a full
        param all-gather per generated token)."""
        from torchacc_tpu.parallel.transfer import serving_shardings
        if self._abstract is None:
            self.resolve_shardings()
        abstract = self._abstract.params
        axes = (resolve_param_axes(abstract) if self._axes_rules is None
                else resolve_param_axes(abstract, self._axes_rules))
        return serving_shardings(abstract, axes, self.rules,
                                 mesh if mesh is not None else self.mesh)

    def serving_params(self, *, dtype: Any = "auto", donate: bool = False,
                       mesh: Optional[Mesh] = None) -> Any:
        """``state.params`` resharded into the serving layout — the
        in-memory train→serve handoff seam (docs/serving.md "Live
        weight handoff").

        Strips everything serving never reads (opt_state, the AMP
        scaler, the quant amax histories — only the param tree crosses)
        and runs ONE compiled spec-to-spec transfer
        (parallel/transfer.py) from the train layout (fsdp/tp) into the
        decode layout (:meth:`serving_shardings`): compiled once per
        layout pair, every later handoff costs collective time only —
        no checkpoint I/O anywhere on this path.

        ``dtype='auto'`` casts floating leaves to the model's compute
        dtype inside the same program (a quant/AMP-trained f32 master
        state serves compute-dtype, mirroring ``generate()``'s quant
        strip); pass None to keep the stored dtypes, or an explicit
        dtype.  ``donate=True`` is the TERMINAL handoff: the train copy
        is offered to XLA and ``self.state`` is cleared — the trainer
        needs ``init()``/``restore()`` before training again (outputs
        are bitwise identical with donation on or off).

        In-flight verdicts resolve first (:meth:`drain`): a serving
        phase must never start on weights whose guard/SDC verdict is
        still pending — the same verdict-before-durability rule
        checkpoint writes follow."""
        if self.state is None:
            raise TrainerStateError(
                "nothing to hand off — call init() (or restore) first")
        self.drain()
        from torchacc_tpu.parallel.transfer import transfer
        dt = dtype
        if dtype == "auto":
            dt = getattr(getattr(self.model, "cfg", None), "dtype", None)
        target = self.serving_shardings(mesh)
        with jax.sharding.set_mesh(mesh if mesh is not None else self.mesh):
            params = transfer(self.state.params, target,
                              donate=donate, dtype=dt)
        if donate:
            # the donated buffers are gone; keeping a TrainState around
            # them would turn the next step() into a deleted-buffer
            # crash far from the cause
            self.state = None
            self._host_step = None
        return params

    # -- high-level loop ----------------------------------------------------
    def fit(self, loader, *, checkpoint_dir: Optional[str] = None,
            metrics_dir: Optional[str] = None, **kwargs):
        """Run the training loop — see :meth:`_fit_inner` for the full
        parameter/semantics documentation (this wrapper adds only the
        telemetry session).

        With ``config.obs.enabled`` (docs/observability.md) the run is
        wrapped in a telemetry session: gauges + health providers
        registered for the HTTP endpoint, step/blocked-time histograms
        fed, and — on ANY typed-error exit (SDCError, HangError,
        AnomalyError, QuarantinedHostError, BadBatchError,
        CheckpointError...) — a flight-recorder postmortem bundle
        ``flight_<step>.json`` written to ``obs.flight_dir`` (default:
        the checkpoint/metrics dir) before the error propagates.
        Disabled (the default), this delegates straight through and
        the trajectory is bitwise unchanged."""
        obs_cfg = getattr(self.config, "obs", None)
        if obs_cfg is None or not obs_cfg.enabled:
            self._obs_fit = None
            return self._fit_inner(loader, checkpoint_dir=checkpoint_dir,
                                   metrics_dir=metrics_dir, **kwargs)
        from torchacc_tpu.obs.runtime import FitObs
        fo = FitObs(self, obs_cfg, run_dir=checkpoint_dir or metrics_dir)
        self._obs_fit = fo
        try:
            return self._fit_inner(loader, checkpoint_dir=checkpoint_dir,
                                   metrics_dir=metrics_dir, **kwargs)
        except TorchAccTPUError as e:
            # the postmortem bundle rides the abort, never replaces it
            # (a failing dump is logged inside and returns None)
            fo.on_abort(e)
            raise
        finally:
            fo.close()
            self._obs_fit = None

    def _fit_inner(
        self,
        loader,
        *,
        max_steps: Optional[int] = None,
        eval_loader=None,
        eval_every: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 1000,
        log_every: int = 50,
        metrics_dir: Optional[str] = None,
        metrics_step_offset: int = 0,
        resume: Optional[str] = None,
        replay_step: Optional[int] = None,
    ):
        """Run the training loop (reference analogue: the HF-Trainer
        integration the reference enables via accelerate_hf_trainer.py —
        here a native loop with logging/eval/checkpointing built in).

        ``metrics_dir`` streams the same records as TensorBoard scalars
        + metrics.jsonl (utils/metrics.py; reference scalar logging at
        benchmarks/transformer.py:145-201).  ``metrics_step_offset``
        shifts the logged step axis — callers that invoke fit() once per
        epoch (HFTrainerAdapter) pass their global step so the scalar
        charts stay monotonic.

        ``resume='auto'`` (requires ``checkpoint_dir``) restores the
        newest *valid* checkpoint step — commit-marked, manifest digest
        matching this trainer's state structure, payload readable,
        falling back a step on corruption — then skips that many batches
        from ``loader`` so the data stream stays aligned, and continues
        counting steps from there.  With no checkpoint yet it starts
        fresh.  While a ``checkpoint_dir`` is set (and
        ``resilience.emergency_checkpoint`` is on, the default), a
        preemption signal (SIGTERM, or chaos-injected) triggers one
        blocking emergency save at the step boundary and a clean return
        — a rescheduled job resumes losing at most the in-flight step.
        See docs/resilience.md for guarantees and non-guarantees.

        ``replay_step=N`` (requires ``checkpoint_dir``) is the SDC
        triage mode: restore the committed checkpoint at step ``N`` and
        its durable loader state, re-execute that ONE step twice on
        snapshots (the restored state is never consumed), print the
        per-leaf gradient digests, and return the single replay record
        — no training happens.  Same checkpoint + same loader state ⇒
        bitwise-identical digests on healthy hardware, so a suspected
        SDC incident is reproducible offline (docs/resilience.md
        "SDC defense").

        Returns a list of {step, loss, ...} log records."""
        import time as _time

        from torchacc_tpu.utils.metrics import counters, open_metrics
        res_cfg = self.config.resilience
        mgr = None
        tiered = None
        self._last_checkpoint_dir = checkpoint_dir
        if checkpoint_dir is not None:
            if res_cfg.tiered_checkpointing:
                # zero-stall tiered saves (checkpoint/tiered.py): the
                # hot path only snapshots + enqueues; durability
                # trickles in the background, gated on the lagged
                # verdicts — docs/resilience.md "Tiered checkpointing"
                tiered = self._tiered_manager(checkpoint_dir,
                                              checkpoint_every, res_cfg)
                mgr = tiered
            else:
                from torchacc_tpu.checkpoint import CheckpointManager
                mgr = CheckpointManager(
                    checkpoint_dir, save_interval_steps=checkpoint_every,
                    retry_policy=res_cfg.retry_policy(res_cfg.ckpt_retries),
                    coord_timeout_s=res_cfg.coord_timeout_s,
                    elastic_resume=res_cfg.elastic_resume)
        # SDC quarantine records land in the run dir; a restarted pod
        # that still contains a quarantined host gets warned loudly
        self._sdc_run_dir = checkpoint_dir or metrics_dir
        if self._sdc_run_dir:
            from torchacc_tpu.resilience.coordination import (
                process_count,
                process_index,
            )
            from torchacc_tpu.resilience.sdc import read_quarantined_hosts
            q = read_quarantined_hosts(self._sdc_run_dir)
            if q:
                me = process_index()
                # a quarantined id counts as "still in the pod" only if
                # it is a valid index here AND the world has not shrunk
                # below its quarantine-time size: host ids are process
                # indices, which renumber after an elastic shrink — a
                # smaller world means the documented remediation
                # (restart excluding the host) already happened, and
                # refusing on the renumbered id would brick the run.
                # Records without a world (pre-PR-9 files) stay
                # conservative: they refuse until cleared.
                def _still_present(h) -> bool:
                    if h >= process_count():
                        return False
                    world = (q.get(h) or {}).get("world")
                    return world is None or process_count() >= int(world)
                present = sorted(h for h in q if _still_present(h))
                if present and res_cfg.refuse_quarantined:
                    # enforce, not warn: a quarantined chip re-entering
                    # the pod silently re-arms the exact failure the
                    # quarantine ended.  Deterministic pod-wide (shared
                    # quarantine file, same world size) so every
                    # process raises together.
                    import os as _os

                    from torchacc_tpu.errors import QuarantinedHostError
                    raise QuarantinedHostError(
                        f"refusing to train: host(s) {present} of this "
                        f"{process_count()}-process pod are quarantined "
                        f"for silent data corruption in "
                        f"{self._sdc_run_dir}/sdc_quarantine.json — "
                        "restart excluding them (elastic_resume handles "
                        "the smaller world), or clear the quarantine "
                        "file deliberately",
                        hosts=present,
                        quarantine_file=_os.path.join(
                            self._sdc_run_dir, "sdc_quarantine.json"))
                logger.warning(
                    f"run dir {self._sdc_run_dir} quarantines host(s) "
                    f"{sorted(q)} for silent data corruption "
                    "(sdc_quarantine.json); "
                    + ("THIS host is one of them — the restart should "
                       "have excluded it" if me in q else
                       "verify the restart excluded them"))
        if replay_step is not None:
            if mgr is None:
                raise TrainerStateError(
                    "fit(replay_step=N) requires checkpoint_dir")
            try:
                return self._replay(loader, mgr, replay_step)
            finally:
                mgr.close()
        # durable data-pipeline state (docs/resilience.md "Elastic
        # resume"): persisted with every checkpoint when the loader
        # exposes it, restored in place of the O(consumed) skip-replay
        loader_state_fn = getattr(loader, "state_dict", None)
        loader_load_fn = getattr(loader, "load_state_dict", None)
        # StepGuard EW statistics persist with every committed step
        # (guard_state.json, advisory) so the spike guard does NOT
        # re-warm after resume; materialised only on steps that write
        guard_state_fn = (self._export_guard_state if self._guard_on
                          else None)
        # a previous fit that exited exceptionally (AnomalyError /
        # SDCError / HangError — the documented non-draining exits) may
        # have left entries in the ring; they belong to the discarded
        # timeline, and resolving them into THIS run would attribute
        # verdicts and records to phantom steps.  Normal exits drained,
        # so this is a no-op for them.  The blocked meter is discarded
        # with them: time accrued before fit (warm-up steps, a previous
        # run) must not inflate the first record's host_blocked_ms —
        # the triage signal docs/performance.md tunes against.
        self._inflight.clear()
        self.last_resolved = None
        self.blocked.take_ms()
        self.save_blocked.take_ms()
        # a stale no-donate flag (fit exited right after a tiered save)
        # would only waste one donation — but keep entries clean
        self._no_donate_once = False
        # tiered saves listen to this fit's verdict stream from here on
        # (resolve_oldest advances the trickle's commit watermark)
        self._tiered_active = tiered
        resumed_loader_state = None
        start_step = 0
        if resume is not None:
            if resume != "auto":
                raise ValueError(f"resume must be None or 'auto', "
                                 f"got {resume!r}")
            if mgr is None:
                raise TrainerStateError(
                    "fit(resume='auto') requires checkpoint_dir")
            from torchacc_tpu.errors import (
                CheckpointCorruptionError,
                CheckpointNotFoundError,
            )
            try:
                state, start_step = mgr.restore_latest_valid(
                    self.abstract_state())
            except CheckpointNotFoundError:
                logger.info("resume='auto': no checkpoint yet — "
                            "starting fresh")
            except CheckpointCorruptionError as e:
                # every existing step is unreadable (e.g. the run died
                # mid-write of its very first checkpoint): the restart
                # command must still start the run, not crash it
                logger.warning(
                    f"resume='auto': no restorable checkpoint ({e}); "
                    "starting fresh")
            else:
                self.state = self._adopt_restored(state)
                # the restored step is known — no device fetch needed to
                # re-derive the host-side index
                self._host_step = start_step
                counters.inc("resumes")
                if loader_load_fn is not None:
                    resumed_loader_state = mgr.read_loader_state(start_step)
                if self._guard_on:
                    gs = mgr.read_guard_state(start_step)
                    if gs is not None:
                        self._import_guard_state(gs)
                        logger.info(
                            "restored StepGuard EW statistics "
                            f"(count={gs.get('count')}) — the spike "
                            "guard does not re-warm")
                logger.info(
                    f"resume='auto': restored step {start_step} from "
                    f"{checkpoint_dir}; "
                    + ("restoring durable loader state"
                       if resumed_loader_state is not None else
                       f"skipping {start_step} consumed batches"))
        if tiered is not None:
            # this fit is a new timeline from start_step: reset the
            # cached manager's submission cursor / verdict watermark and
            # discard RAM snapshots beyond it — a fresh (resume=None)
            # run on a previously-used dir must save normally, and a
            # discarded timeline's snapshots must never resurface
            tiered.begin_run(start_step)
        preempt_on = mgr is not None and res_cfg.emergency_checkpoint
        if preempt_on:
            from torchacc_tpu.resilience.coordination import (
                process_count as _process_count,
            )
            from torchacc_tpu.resilience.preemption import (
                clear_preemption,
                install_preemption_handler,
                preemption_requested,
                sync_preemption,
            )
            install_preemption_handler()
            if preemption_requested():
                # a stale flag (signal delivered while no preemption-
                # aware fit was running) must not stop this run at its
                # first step boundary; starting fit IS the intent to
                # train
                logger.warning(
                    "clearing a stale preemption request at fit start")
                clear_preemption()
        mw = open_metrics(metrics_dir)
        # hang/straggler watchdog (resilience/watchdog.py): armed around
        # the data fetch and the train step; a deadline expiry dumps
        # all-thread stacks + counts a watchdog_stall, and (with
        # resilience.abort_on_hang) raises HangError at the next step
        # boundary.  step_deadline_s=None (default): no watchdog thread.
        wd = None
        fetch_deadline = None
        if res_cfg.step_deadline_s is not None:
            from torchacc_tpu.resilience.watchdog import Watchdog
            wd = Watchdog(
                dump_dir=metrics_dir or checkpoint_dir,
                abort_on_hang=res_cfg.abort_on_hang,
                poll_interval_s=min(
                    max(res_cfg.step_deadline_s / 4.0, 0.01), 1.0),
            ).start()
            # when loader_deadline_s is set, the loader's OWN consumer-
            # wait deadline (AsyncLoader._get_with_stall_deadline) owns
            # fetch stalls — arming the fit-side watchdog too would trip
            # the same stall twice (two dumps, two counter increments)
            fetch_deadline = (None if res_cfg.loader_deadline_s
                              else res_cfg.step_deadline_s)
        # published for the telemetry session's heartbeat gauge/health
        # provider (obs/runtime.py); cleared in the finally below
        self._watchdog = wd
        # a loader whose source retries store fetches (AsyncLoader over
        # a StreamingDataset) beats the watchdog before every backoff
        # sleep, so a slow-but-retrying source reads as data_wait — the
        # SLO bucket — never as a dead "data_fetch" section
        if wd is not None:
            set_hb = getattr(loader, "set_stall_heartbeat", None)
            if callable(set_hb):
                set_hb(wd.beat)
        history = []
        t0 = _time.perf_counter()
        t_prev, s_prev = t0, start_step
        import itertools
        skip_fn = getattr(loader, "skip_batches", None)
        if start_step and resumed_loader_state is not None:
            # O(1) resume: the loader repositions itself from its
            # durable state (seekable sources seek; non-seekable ones
            # replay internally and count resume_replayed_batches)
            loader_load_fn(resumed_loader_state)
            data_it = iter(loader)
            bounded = (data_it if max_steps is None else
                       itertools.islice(data_it,
                                        max(max_steps - start_step, 0)))
        elif start_step and skip_fn is not None:
            # skip-replay fallback: no durable loader state with this
            # checkpoint — fast-forward the consumed prefix at the
            # source (AsyncLoader: no pad/device-transfer for skipped
            # batches), O(consumed) host iteration
            counters.inc("resume_replayed_batches", start_step)
            logger.warning(
                f"resume='auto': no durable loader state at step "
                f"{start_step} — replaying {start_step} consumed "
                "batches (skip-replay)")
            data_it = skip_fn(start_step)
            bounded = (data_it if max_steps is None else
                       itertools.islice(data_it,
                                        max(max_steps - start_step, 0)))
        else:
            if start_step:
                # no durable state and no skip support: islice replays
                # (and discards) the consumed prefix the slow way
                counters.inc("resume_replayed_batches", start_step)
            data_it = iter(loader)
            bounded = (itertools.islice(data_it, start_step, max_steps)
                       if (max_steps is not None or start_step) else data_it)
        def _emit(entry, allow_eval: bool = True) -> None:
            """Log/eval for a RESOLVED step (lagged by
            perf.dispatch_depth - 1 behind dispatch): the loss fetch
            reads a completed value, so log steps no longer stall the
            pipeline.  Gating on the resolved index keeps the record
            trajectory identical across dispatch depths; under lag the
            eval runs on the newest state (documented in
            docs/performance.md).  ``allow_eval=False`` (the emergency-
            save drain) suppresses the eval pass — the grace window is
            for verdicts and the checkpoint, not a full eval."""
            nonlocal t_prev, s_prev
            r = entry.step
            do_log = log_every and r % log_every == 0
            do_eval = (allow_eval and eval_loader is not None
                       and eval_every and r and r % eval_every == 0)
            if not (do_log or do_eval):
                return
            now = _time.perf_counter()
            with self._wait():
                loss = float(entry.metrics["loss"])
            rec = {"step": r, "loss": loss,
                   "time_s": round(now - t0, 2)}
            if wd is not None:
                # sample the age BEFORE beating: it reports how
                # long this section actually ran (≈ the step +
                # metrics sync), not a freshly-reset zero
                rec["heartbeat_age_s"] = round(
                    wd.heartbeat_age_s(), 3)
                # the step itself finished — liveness proven;
                # eval/logging get their own deadline window
                wd.beat()
            if r > s_prev:
                rec["steps_per_sec"] = round(
                    (r - s_prev) / max(now - t_prev, 1e-9), 3)
                if entry.tokens:
                    rec["tokens_per_sec"] = round(
                        rec["steps_per_sec"] * entry.tokens, 1)
            if do_eval:
                # dispatch the WHOLE eval pass, then resolve all losses
                # in one batched fetch — the host never serialises
                # against the device per eval batch
                evs = [self.eval_step(eb) for eb in eval_loader]
                with self._wait():
                    vals = jax.device_get(evs)
                rec["eval_loss"] = (sum(float(v) for v in vals)
                                    / max(len(vals), 1))
            # restamp AFTER eval so its wall time is not charged
            # to the next interval's steps/tokens-per-sec
            t_prev, s_prev = _time.perf_counter(), r
            # how long the host spent blocked on the device since the
            # last record, and at what pipeline depth — the tentpole's
            # measurement seam (utils/metrics.BlockedMeter)
            rec["host_blocked_ms"] = round(self.blocked.take_ms(), 3)
            # wall time the save path cost this interval (snapshot
            # enqueue + checkpoint hand-off on writing steps; the
            # verdict drain's fetches land in host_blocked_ms) — the
            # save-step sync-gap triage signal
            rec["save_blocked_ms"] = round(self.save_blocked.take_ms(), 3)
            rec["dispatch_depth"] = self._lag + 1
            # degradation counters ride the record so operators
            # see retries/skips/resumes in metrics.jsonl too
            for k, v in counters.snapshot().items():
                rec[k] = v
            history.append(rec)
            if self._obs_fit is not None:
                # histograms + the flight recorder's step ring ride the
                # SAME records metrics.jsonl gets
                self._obs_fit.on_record(rec)
            if mw is not None:
                mw.log(metrics_step_offset + r,
                       {f"train/{k}": v for k, v in rec.items()
                        if k != "step"})
            logger.info(f"step {r}: loss {rec['loss']:.4f}"
                        f"{counters.suffix()}")

        def _drain_all(allow_eval: bool = True) -> None:
            """Resolve every in-flight step, emitting its record, with a
            fresh watchdog window per entry — exactly like an in-loop
            step.  Any pending AnomalyError/SDCError raises HERE."""
            while self.pending:
                if wd is not None:
                    wd.arm("train_step", res_cfg.step_deadline_s)
                entry = self.resolve_oldest()
                if entry is not None:
                    _emit(entry, allow_eval=allow_eval)
                if wd is not None:
                    wd.disarm()

        # goodput ledger (obs/goodput.py): everything from the session
        # open (manager construction, quarantine read, restore, loader
        # seek) up to here is the init_restore bucket; the loop laps
        # the rest.  Host-side and obs-gated — obs off touches nothing.
        fo = self._obs_fit
        if fo is not None:
            fo.lap("init_restore")
        try:
            steps_it = enumerate(bounded, start=start_step)
            while True:
                if wd is not None:
                    wd.arm("data_fetch", fetch_deadline)
                try:
                    with tracing.span("train/data_wait"):
                        step_idx, batch = next(steps_it)
                except StopIteration:
                    if wd is not None:
                        wd.disarm()
                    if fo is not None:
                        fo.lap("data_wait")
                    break
                if fo is not None:
                    fo.lap("data_wait")
                if wd is not None:
                    # the deadline is armed around dispatch + the LAGGED
                    # resolution point: in steady state the blocking
                    # fetch inside step() waits on step N-k, so expiry
                    # still means "a step's device work did not finish
                    # in time" (docs/resilience.md watchdog table)
                    wd.arm("train_step", res_cfg.step_deadline_s)
                if fo is not None:
                    # step wall time (dispatch + lagged resolution) into
                    # the step_time_ms histogram — host-side only
                    _t_step = _time.perf_counter()
                    self.step(batch)
                    fo.on_step_time(
                        (_time.perf_counter() - _t_step) * 1e3)
                    fo.lap("step")
                else:
                    self.step(batch)
                if self.last_resolved is not None:
                    _emit(self.last_resolved)
                if fo is not None:
                    fo.lap("log_eval")
                if wd is not None:
                    # step boundary: a stall detected mid-step surfaces
                    # as HangError HERE (abort_on_hang), where state is
                    # consistent and resume='auto' recovers cleanly
                    wd.disarm()
                saved = False
                if tiered is not None:
                    # zero-stall tiered save (checkpoint/tiered.py):
                    # the hot path hands the LIVE state to the trickle
                    # and marks the next dispatch non-donating so those
                    # buffers survive — no device copy, no verdict
                    # drain, no orbax wait.  Verdict-before-durability
                    # moves into the trickle: tier 1 commits once
                    # resolve_oldest has advanced the watermark past
                    # every step this snapshot contains (verdict_gate =
                    # the newest dispatched step), so an abort discards
                    # the snapshot instead of committing it.  Loader
                    # state is materialised here (it advances with the
                    # loop); the guard statistics ride as live device
                    # scalars the writer fetches off the hot path.
                    if tiered.should_save(step_idx + 1):
                        with tracing.span("train/save", step=step_idx + 1,
                                          tiered=True):
                            with self.save_blocked.blocked():
                                ls = None
                                if loader_state_fn is not None:
                                    try:
                                        ls = loader_state_fn()
                                    except Exception as e:  # noqa: BLE001
                                        logger.warning(
                                            f"loader state_dict() failed "
                                            f"for step {step_idx + 1} "
                                            f"({e!r}); resume will fall "
                                            "back to skip-replay")
                                gs = (self._guard_state if self._guard_on
                                      else None)
                                saved = tiered.submit(
                                    step_idx + 1, self.state,
                                    verdict_gate=step_idx,
                                    loader_state=ls, guard_state=gs)
                        if saved:
                            self._no_donate_once = True
                    # multi-process only (single-process: no-op): run
                    # verdict-cleared tier-1 writes HERE, on the main
                    # thread at a deterministic boundary — the orbax
                    # write's cross-process barriers are device
                    # collectives and must stay sequenced with the
                    # training collectives (tiered.py docstring)
                    with self.save_blocked.blocked():
                        tiered.pump()
                elif mgr is not None:
                    # verdict-before-durability: a checkpoint must never
                    # commit a step whose guard/SDC verdict is still in
                    # flight — the ring drains BEFORE anything becomes
                    # durable, so the abort raises first, exactly as the
                    # unpipelined loop ordered it.  Save-step sync-gap
                    # half-step (ROADMAP #3/#4): the donation-safe
                    # snapshot is ENQUEUED before the drain — it is a
                    # device-side copy with no host fetch, so the copy
                    # executes while the drain's verdict fetches wait
                    # (and while the next step dispatches after save()
                    # hands off to the async writer); only the verdict
                    # ordering is serialised, not the copy.  Label =
                    # completed-step count == state.step after this
                    # step; loader state rides along (callable: only
                    # materialised on steps that write).
                    if mgr.should_save(step_idx + 1):
                        from torchacc_tpu.checkpoint.io import _snapshot
                        # the save span covers snapshot + verdict drain +
                        # hand-off; the drain's train/resolve spans nest
                        # inside it, so the trace shows the breakdown the
                        # save_blocked_ms scalar cannot
                        with tracing.span("train/save", step=step_idx + 1,
                                          tiered=False):
                            with self.save_blocked.blocked():
                                snap = _snapshot(self.state)
                            # the drain stays OUTSIDE the save meter: its
                            # blocking fetches already land in
                            # host_blocked_ms, and a drained entry may run
                            # a whole eval pass (eval_every boundary) —
                            # charging that to save_blocked_ms would
                            # misattribute eval cost to the save path
                            if self.pending:
                                _drain_all()
                            with self.save_blocked.blocked():
                                saved = mgr.save(
                                    step_idx + 1, snap,
                                    presnapshotted=True,
                                    loader_state=loader_state_fn,
                                    guard_state=guard_state_fn)
                    else:
                        # non-writing step: save() only commits pending
                        # manifests of finished background writes
                        saved = mgr.save(step_idx + 1, self.state,
                                         loader_state=loader_state_fn,
                                         guard_state=guard_state_fn)
                if fo is not None:
                    fo.lap("checkpoint")
                # cross-host sync point: the emergency save triggers on
                # EVERY host at this same boundary when ANY host saw the
                # signal (exact local-flag check in single-process runs).
                # The interval gate depends only on step_idx, so every
                # host reaches (or skips) the collective symmetrically.
                sync_every = res_cfg.preempt_sync_interval_steps
                if preempt_on \
                        and (sync_every <= 1
                             or (step_idx + 1) % sync_every == 0
                             or _process_count() == 1) \
                        and sync_preemption(
                            timeout_s=res_cfg.coord_timeout_s):
                    # blocking emergency save (Orbax emergency-checkpoint
                    # pattern): make the just-completed step durable, then
                    # return cleanly — the grace window is for saving,
                    # not for more steps.  Same verdict-before-durability
                    # ordering as interval saves: the in-flight steps'
                    # device work is already done, so resolving them
                    # costs fetches, not step time.  Eval is suppressed
                    # — the grace window must not fund an eval pass
                    if not saved:
                        _drain_all(allow_eval=False)
                        if tiered is not None:
                            # live handoff is donation-safe here: the
                            # loop breaks below, so nothing ever
                            # donates these buffers again
                            with self.save_blocked.blocked():
                                tiered.submit(
                                    step_idx + 1, self.state,
                                    verdict_gate=step_idx,
                                    loader_state=(loader_state_fn()
                                                  if loader_state_fn
                                                  else None),
                                    guard_state=(self._guard_state
                                                 if self._guard_on
                                                 else None))
                        else:
                            mgr.save(step_idx + 1, self.state, force=True,
                                     loader_state=loader_state_fn,
                                     guard_state=guard_state_fn)
                    elif tiered is not None:
                        # the interval submit above is gated on verdicts
                        # still in flight — resolve them now so the
                        # trickle commits inside the grace window
                        _drain_all(allow_eval=False)
                    # for tiered managers this blocks until every
                    # verdict-cleared entry is durable — the grace
                    # window is spent on durability, exactly like the
                    # blocking path
                    mgr.wait_until_finished()
                    if tiered is not None \
                            and not tiered.is_durable(step_idx + 1):
                        # a failed trickle must surface exactly like a
                        # failed blocking save — never as a "durable"
                        # log line the supervisor then trusts
                        from torchacc_tpu.errors import CheckpointError
                        raise CheckpointError(
                            f"emergency checkpoint of step "
                            f"{step_idx + 1} did not become durable "
                            "(the tiered trickle failed — see the "
                            "tiered_write_failures warning above)")
                    counters.inc("preemptions")
                    counters.inc("emergency_saves")
                    # the request is now handled — clear it so an
                    # in-process supervisor can call fit(resume='auto')
                    # again without instantly re-preempting
                    clear_preemption()
                    logger.warning(
                        f"preemption requested: emergency checkpoint at "
                        f"step {step_idx + 1} is durable; stopping fit "
                        "(resume with fit(resume='auto'))")
                    if fo is not None:
                        # the emergency-save window is checkpoint time
                        fo.lap("checkpoint")
                        # preemption is a planned exit, but the operator
                        # still wants the last-minute picture — same
                        # bundle as a typed-error abort
                        fo.on_preempt(step_idx + 1)
                    break
            # drain the dispatch pipeline: the final k in-flight steps
            # still owe their guard/SDC verdicts and log records — a
            # run must never end (or hand off to a preemption restart)
            # with unresolved anomalies.  Exception exits skip this: an
            # abort raise discards younger in-flight steps (their
            # updates are past the abort point and no checkpoint
            # committed them), and a hung device cannot be drained.
            _drain_all()
            if fo is not None:
                fo.lap("drain")
        finally:
            self._watchdog = None
            if wd is not None:
                wd.close()
            # early exits (preemption, max_steps, errors) must shut the
            # async loader's producer thread down NOW — a daemon thread
            # abandoned inside the runtime trips std::terminate at
            # interpreter teardown
            close = getattr(data_it, "close", None)
            if close is not None:
                close()
            self._tiered_active = None
            if mgr is not None:
                # tiered: flush every verdict-cleared entry to
                # durability, then close() discards the unverdicted
                # ones (an abort exit's snapshots must never commit)
                # and stops the writer — the tier-0 RAM store and the
                # tier-1 manager survive on the trainer for
                # restore-from-RAM
                mgr.wait_until_finished()
                mgr.close()
            if mw is not None:
                mw.close()
        return history

    # -- deterministic replay (SDC triage) ----------------------------------
    def _replay(self, loader, mgr, replay_step: int):
        """``fit(replay_step=N)``: restore the committed step ``N`` and
        its durable loader state, re-execute that one step TWICE on
        donation-safe snapshots (``self.state`` is restored but never
        consumed), and print/return the per-leaf digest matrix.  Two
        invocations with the same checkpoint + loader state produce
        bitwise-identical digests on healthy hardware — the offline
        reproduction path for a suspected SDC incident."""
        import itertools

        from torchacc_tpu.checkpoint.io import _snapshot
        from torchacc_tpu.errors import CheckpointNotFoundError
        from torchacc_tpu.resilience.sdc import format_digest_matrix
        forced_sdc = not self._sdc_on
        if forced_sdc:
            # replay IS a digest run: force digests into the step
            # program — for the duration of the replay ONLY (a later
            # fit() on this trainer keeps its zero-overhead program);
            # restored in the finally below even when validation or the
            # restore itself raises
            self._sdc_on = True
            self._train_step = None
        data_it = None
        try:
            if replay_step not in mgr.valid_steps():
                raise CheckpointNotFoundError(
                    f"fit(replay_step={replay_step}): no committed "
                    f"checkpoint at that step (valid: {mgr.valid_steps()})")
            self.state = self._adopt_restored(
                mgr.restore(self.abstract_state(), step=replay_step))
            loader_state = mgr.read_loader_state(replay_step)
            load_fn = getattr(loader, "load_state_dict", None)
            if loader_state is not None and load_fn is not None:
                load_fn(loader_state)
                data_it = iter(loader)
            else:
                skip_fn = getattr(loader, "skip_batches", None)
                if skip_fn is not None and replay_step:
                    data_it = skip_fn(replay_step)
                else:
                    data_it = iter(loader)
                    if replay_step:
                        data_it = itertools.islice(data_it, replay_step,
                                                   None)
            try:
                batch = next(iter(data_it))
            except StopIteration:
                raise TrainerStateError(
                    f"fit(replay_step={replay_step}): the loader is "
                    "exhausted before the replayed step's batch — "
                    "replay needs the same data stream the run used")
            self._ensure_compiled(batch)
            if self._guard_on:
                self._ensure_guard()
            mon = self._ensure_sdc_monitor()
            si = int(self.state.step)
            self._host_step = si
            runs = []
            for where in ("step", "recompute"):
                args = [_snapshot(self.state), batch]
                if self._guard_on:
                    args.append(_snapshot(self._guard_state))
                args.append(mon.flips(si, where))
                with jax.sharding.set_mesh(self.mesh):
                    out = self._train_step(*args)
                metrics = out[-1]
                runs.append((jax.device_get(metrics["sdc_digests"]),
                             float(jax.device_get(metrics["loss"]))))
            (d1, loss), (d2, _) = runs
            deterministic = bool((d1 == d2).all())
            table = format_digest_matrix(d1, mon.leaf_paths)
            logger.info(f"replay of step {si}: loss={loss:.6g} "
                        f"deterministic={deterministic} "
                        f"({d1.shape[0]} replica(s), {d1.shape[1]} leaves)")
            for path, rows in table.items():
                r0 = rows[0]
                agree = all(r == r0 or (r["bits_xor"] == r0["bits_xor"]
                                        and r["bits_sum"] == r0["bits_sum"])
                            for r in rows[1:])
                logger.info(
                    f"  {path}: xor={r0['bits_xor']} sum={r0['bits_sum']} "
                    f"f32_sum={r0['f32_sum']:.6g}"
                    + ("" if agree else "  << replicas DISAGREE"))
            if not deterministic:
                logger.error(
                    f"replay of step {si} is NOT bitwise deterministic "
                    "on this machine — the hardware replaying it is "
                    "itself suspect")
            return [{"replay_step": replay_step, "step": si, "loss": loss,
                     "deterministic": deterministic, "digests": table}]
        finally:
            if forced_sdc:
                self._sdc_on = False
                self._train_step = None
                self._train_step_structure = None
            close = getattr(data_it, "close", None)
            if close is not None:
                close()

    # -- eval ---------------------------------------------------------------
    def eval_step(self, batch: Dict[str, jax.Array]) -> jax.Array:
        if self.state is None:
            self.init()
        # same (structure, leaf-rank) key as step(): in_shardings depend
        # on per-leaf rank, not just the tree structure
        eval_key = (jax.tree.structure(batch),
                    tuple(getattr(x, "ndim", 0)
                          for x in jax.tree.leaves(batch)))
        if (getattr(self, "_eval_step", None) is None
                or getattr(self, "_eval_step_structure", None) != eval_key):
            fsc = self._forward_sum_count

            def ev(state, batch):
                # eval reads the trained delayed scales without mutating
                # them (the returned histories are discarded)
                l, c, _, _ = fsc(state.params, batch, quant=state.quant)
                return l / jnp.maximum(c, 1.0)
            self._eval_step = jax.jit(
                ev, in_shardings=(self.state_shardings,
                                  self._batch_shardings(batch)),
                out_shardings=self._metrics_sharding)
            self._eval_step_structure = eval_key
        with jax.sharding.set_mesh(self.mesh):
            return self._eval_step(self.state, batch)
