"""Continuous-batching scheduler over the paged KV cache.

The design mirrors the PR-5 trainer split (train/trainer.py): a
STATELESS JITTED device step over (params, pools, slot state) and a
HOST-SIDE loop that owns every decision — admission into free slots,
which sequence prefills this iteration, eviction of finished sequences,
block free/reuse.  Three compiled programs cover any request mix:

- ``decode_step``: one token for every slot in one batched program.
  Sampling runs ON DEVICE with per-slot traced (temperature, top_k,
  top_p), and the sampled tokens feed the next iteration's input as a
  device array — the token feedback loop never touches the host.
- ``prefill_chunk``: ``serve.prefill_chunk`` tokens of ONE sequence
  (padded; the pad tail writes to the null block), interleaved with
  decode so a long prompt never stalls in-flight decodes.  With
  ``serve.prefill_batch > 1`` one iteration instead prefills up to
  that many chunks from DISTINCT waiting sequences in a single
  dispatched program (rows padded to the [prefill_batch,
  prefill_chunk] geometry — trace count stays 1; the head projects
  only each row's last valid token, the one row whose logits anyone
  reads).
- ``sample_first`` / ``set_slot``: sample the first token from the
  final prefill chunk's logits and splice it into the decode carry —
  tiny jitted ops, no readback.
- ``cow``: copy one pool block's k/v to another across all layers —
  the copy-on-write step behind a fully-cached prompt (see admit()).

Prefix cache (``serve.prefix_cache`` — kv_cache.PrefixIndex): admit()
maps the longest token-hash-chain match of a new prompt onto resident
blocks (refcount++ — zero recompute, zero copies) and starts prefill
past them; when the match covers the WHOLE prompt, the last matched
block is copy-on-written into a private block and only the final
prompt token re-runs (its logits are needed to sample the first output
token; its k/v write lands in the private copy, never the shared
block), so a warm prompt's TTFT is one final-chunk dispatch.  Blocks
register in the index as their prefill chunk completes, which means a
live sequence's prompt blocks are matchable immediately — concurrent
requests behind the same system prompt share from the first one that
prefilled it, not the first one that finished.

Host reads happen only at lag ``serve.decode_depth - 1`` through the
in-flight ring (the PR-5 lagged-readback pattern): iteration i's
sampled tokens are fetched while iteration i+k is dispatching, so the
per-token host sync sits off the critical path.  Consequences the
engine handles:

- a sequence is noticed finished (eos / max_new) up to k iterations
  late; the extra garbage tokens are dropped on the host;
- its blocks are freed DEFERRED — only after every dispatched
  iteration that could still write through the old block table has
  resolved — so a freed block can never alias a live sequence's cache
  (tested: test_block_free_never_aliases_live_blocks).

Admission therefore reserves ``prompt + max_new + decode_depth``
token slots of blocks up front: the overhang covers in-flight
iterations that keep writing after the finish condition.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from torchacc_tpu.config import ConfigError
from torchacc_tpu.models import block
from torchacc_tpu.models.transformer import (
    MIXER_KINDS,
    embed_ids,
    head_logits,
    layer_kinds,
    layer_plan,
    pattern_period,
)
from torchacc_tpu.obs import tracing
from torchacc_tpu.ops._common import on_tpu
from torchacc_tpu.resilience.chaos import failpoint
from torchacc_tpu.serve.kinds import _check_supported, kinds_of
from torchacc_tpu.serve.kv_cache import (
    BlockPool,
    PrefixIndex,
    WindowBlocks,
    blocks_needed,
    make_pools,
    num_window_blocks,
    window_blocks_bound,
)
from torchacc_tpu.utils.logger import logger
from torchacc_tpu.utils.metrics import counters


#: ``(kinds of the leading dense layers, kinds of one period)`` of a
#: model whose layer_pattern names two kinds of layer (the name
#: chipbench/layouts reads it under)
_period = pattern_period


def _upload(host_mirror: np.ndarray) -> jax.Array:
    """Device copy of a host array the scheduler goes on mutating in
    place.  The CPU backend may alias a numpy buffer instead of copying
    it, and an in-flight step would then read the mutation; a private
    copy that nothing else touches makes the alias harmless."""
    return jnp.asarray(host_mirror.copy())


#: leaves of an expert layer's ``moe`` tree that the layer scan does not
#: slice (PagedDecoder._forward)
_EXPERT_STACKS = ("experts/gate", "experts/up", "experts/down")


class PagedDecoder:
    """The jitted device steps: the model's forward on raw params over
    the paged pool — ``embed_ids`` / ``head_logits`` and the block of
    models/block.py, the definitions the module's own apply runs, over
    the model's layer plan (models/transformer.layer_plan), with the
    attention of each kind of layer from serve/kinds.py."""

    def __init__(self, cfg, serve_cfg, attention_impl: Optional[str] = None):
        _check_supported(cfg)
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        # resolve 'auto' once, here, so a block size or a head size the
        # kernel cannot tile is a typed error at construction and not a
        # lowering failure inside the first request
        impl = attention_impl or cfg.attention_impl
        if impl == "auto":
            impl = "pallas" if on_tpu() else "xla"
        self.plan = layer_plan(cfg)
        # what each kind of the plan's layers keeps in the cache, the
        # config it computes under, and what addresses the pools in all
        self.kinds = kinds_of(cfg)
        self.by = {record.by for record, _, _ in self.kinds.values()}
        if cfg.mixer_pattern:
            # layers of one mixer each, state-space layers among them: their
            # state lives by slot, beside the attention layers' paged pool
            if serve_cfg.prefix_cache:
                raise NotImplementedError(
                    "the serving engine does not yet support prefix "
                    "sharing with state-space layers (serve.prefix_cache "
                    "with a mixer_pattern: a cached block holds keys and "
                    "values, the recurrent state at its end is kept "
                    "nowhere, so a shared prefix could not be resumed)")
            if ("mamba" in cfg.mixer_pattern and serve_cfg.prefill_chunk
                    > cfg.ssm_chunk and serve_cfg.prefill_chunk
                    % cfg.ssm_chunk):
                raise ConfigError(
                    f"serve.prefill_chunk={serve_cfg.prefill_chunk} is not "
                    f"whole sub-chunks of the state-space scan "
                    f"(ssm_chunk={cfg.ssm_chunk})")
        if cfg.layer_pattern and serve_cfg.prefix_cache:
            raise NotImplementedError(
                "the serving engine does not yet support prefix "
                "sharing across window layers (serve.prefix_cache "
                "with windowed layers: a window layer's blocks are "
                "freed as the window passes, so a cached prefix has "
                "no rows left to share there)")
        if impl == "pallas":
            for t in (1, serve_cfg.prefill_chunk):
                try:
                    for record, kind_cfg, _ in self.kinds.values():
                        record.tile(kind_cfg, serve_cfg.block_size, t,
                                    cfg.dtype)
                except ValueError as e:
                    raise ConfigError(
                        f"serve.block_size={serve_cfg.block_size}, "
                        f"serve.prefill_chunk={serve_cfg.prefill_chunk} "
                        f"with {cfg.kv_heads} kv heads of head size "
                        f"{cfg.head_size}: {e}") from e
        self.impl = impl
        self.block_size = serve_cfg.block_size
        self.chunk = serve_cfg.prefill_chunk
        # a mixer_pattern model's final chunk sends its last valid row
        # alone through the head: a chunk's logits over this family's
        # vocabulary are 256 MiB of float32 beside pools that leave no
        # such room
        self._head_last_row = bool(cfg.mixer_pattern)
        # pools are donated: every step consumes and returns them, so
        # XLA updates the one preallocated buffer in place.  all_greedy
        # is static: the all-greedy trace (the serving default) skips
        # the two full-vocab sampling sorts entirely — argmax only —
        # while the mixed trace keeps the one-program-per-request-mix
        # property; both advance the slot PRNG keys identically, so
        # flipping between variants cannot drift a sampled stream.
        # ``addr`` is every step's addressing pytree: the tables by name
        # ('blocks'; 'window' for a model with window layers) and, in a
        # prefill of a model that keeps state by slot, 'slot'
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1, 2),
                               static_argnums=(9,))
        # is_final is static: the non-final trace skips the vocab head
        # entirely (its logits are discarded), the final trace keeps
        # the full-chunk head so first-token numerics are unchanged
        self._prefill = jax.jit(self._prefill_impl, donate_argnums=(1,),
                                static_argnums=(6,))
        # batched multi-sequence prefill: ONE trace for any mix of
        # final/non-final/padded rows (the head projects only the
        # gathered last-valid row of each sequence — [PB, H] x [H, V],
        # a decode-step-sized matmul, so there is no non-final trace to
        # skip it)
        self._prefill_batch = jax.jit(self._prefill_batch_impl,
                                      donate_argnums=(1,))
        self._sample_first = jax.jit(self._sample_first_impl)
        self._set_slot = jax.jit(self._set_slot_impl, donate_argnums=(0,))
        # copy-on-write: clone one pool block across all layers (the
        # fully-cached-prompt path in Scheduler.admit)
        self._cow = jax.jit(self._cow_impl, donate_argnums=(0,))

    # -- model forward ------------------------------------------------------

    def _layer(self, p, at, x, pools, positions, where, ctx_lens, valid,
               expert_stacks):
        """The plan's layer ``at`` over the cache: the model's own block
        (models/block.py) on this layer's raw tree ``p`` — both halves of
        a decoder block, or the ONE mixer of a ``mixer_pattern`` layer
        under its pre-norm (``at.kind`` is a mixer's name) — with the two
        halves that are serving's own.  The attention is its kind's
        record's (serve/kinds.py) on row ``at.of_kind`` of the pools that
        record names, addressed by ``where[record.by]``; the feed-forward
        the model's MLP or the held-expert layer (the layer's tree holds
        ``mlp`` or ``moe``).  ``pools`` are the whole stacks, by name;
        ``ctx_lens`` is the post-write context length per slot; ``valid``
        [S, T] marks the real tokens (the expert layer routes no
        others); ``expert_stacks`` the expert kernels of ALL the layers in
        this layer's stacked tree (:meth:`_forward` keeps them off the
        scan), read by the grouped matmul at this layer's index in it,
        ``at.at``.  Returns ``(x, pools, load)``, ``load`` the expert
        layer's counts or None."""
        record, cfg, _ = self.kinds.get(at.kind, (None, self.cfg, 0))
        if cfg.num_experts and "mlp" in p:
            # a leading dense layer of an expert model: TransformerLM
            # gives that stack's blocks this config too
            cfg = dataclasses.replace(cfg, num_experts=0)
        # what the halves leave besides their output: the updated pools,
        # the expert layer's counts (all traced in this layer's own trace)
        left = {"pools": pools, "load": None}

        # the named scopes are registered device scopes (obs/tracing.py
        # DEVICE_SCOPES): a profiler trace reads each part's device
        # time under the same names the training step's modules carry
        # (a single mixer's one norm, 'ln', under the block's 'ln1')
        def norm(name, t, cfg=cfg):
            with jax.named_scope("ln1" if name == "ln" else name):
                return block.tree_norm(cfg, p)(name, t)

        def attention(h):
            out, own = record.attend(
                cfg, self.impl, p[record.params], at.of_kind, h,
                tuple(pools[name] for name in record.names), positions,
                where[record.by], ctx_lens)
            left["pools"] = {**pools, **dict(zip(record.names, own))}
            return out

        def ffn(h2):
            if not cfg.num_experts:
                with jax.named_scope("mlp"):
                    return block.mlp(cfg, h2, block.tree_proj(cfg, p["mlp"]))
            from torchacc_tpu.models.moe import moe_ffn
            s_, t_, hd = h2.shape
            y, _, _, left["load"] = moe_ffn(
                cfg, {**p["moe"], **expert_stacks},
                h2.reshape(s_ * t_, hd),
                None if valid is None else valid.reshape(-1), layer=at.at)
            return y.reshape(s_, t_, hd)

        if at.kind in MIXER_KINDS:
            x = block.mixer_block(cfg, x, norm,
                                  ffn if at.kind == "moe" else attention,
                                  routed=at.kind == "moe")
        else:
            x = block.block(cfg, x, norm, attention, ffn)
        return x, left["pools"], left["load"]

    def _forward(self, params, pools, ids, positions, where, ctx_lens,
                 valid):
        """(pools', hidden [S, T, H], load): embed -> the plan's runs of
        layers, a ``lax.scan`` of a run's body where it repeats, a walk
        at static indices where it does not.  The stacked pools ride the
        scan's CARRY with the residual — each layer writes its rows in
        place and the kernel reads its pages through the layer index, so
        nothing slices a layer out of the stack or puts it back; ``xs``
        are the stacked params of the body's layers and the run's
        counter.  An expert model's three expert kernel stacks
        [L, E, in, out] are NOT on ``xs``: a scan hands its body a slice
        of every ``xs`` leaf, and a custom call's operand cannot absorb
        that slice, so XLA would copy each layer's 336 MiB stacks into a
        second buffer before every grouped matmul (PERF.md PR 27).  The
        body closes over them whole — loop invariants of the ``while`` —
        and the kernel reads its layer through an index, like the pools.
        The split is made here, once, at trace time, on the jitted
        function's own argument: ``params`` stays the pytree it is and no
        weight is copied (nor by a walk at static indices: an XLA dot
        reads its layer's slice where it lies).  The head projection is
        the caller's: decode projects every slot's single row, prefill
        projects ONLY the last valid row (the full-chunk head would be a
        C x hidden x vocab matmul that is discarded for every row but
        one).  ``where`` is what addresses the pools in this step, by
        what a kind's record says addresses its own (serve/kinds.py);
        ``load`` is the expert layers' counts summed (int32[3],
        models/moe.held_experts_ffn) or None for a model without them."""
        with jax.named_scope("embed"):
            x = embed_ids(self.cfg, params, ids, positions)
        trees, stacks = {}, {}
        for at in (at for run in self.plan for at in run.body):
            if at.tree in trees:
                continue
            tree = at.stack(params)["block"]
            if "moe" in tree:
                # in cfg.dtype the kernel wants them: a no-op on weights
                # cast to serving precision, one conversion outside the
                # scan otherwise
                moe = tree["moe"]
                stacks[at.tree] = {k: moe[k].astype(self.cfg.dtype)
                                   for k in _EXPERT_STACKS if k in moe}
                tree = {**tree, "moe": {k: v for k, v in moe.items()
                                        if k not in _EXPERT_STACKS}}
            trees[at.tree] = tree

        def body(run, carry, per):
            x, pools = carry
            layers, n = per
            load = None
            for at in run.layers(n):
                p = (layers[at.tree] if run.scanned else jax.tree.map(
                    lambda a, at=at: a[at.at], trees[at.tree]))
                x, pools, one = self._layer(
                    p, at, x, pools, positions, where, ctx_lens, valid,
                    stacks.get(at.tree))
                if one is not None:
                    load = one if load is None else load + one
            return (x, pools), load

        total = None
        for run in self.plan:
            step = functools.partial(body, run)
            with jax.named_scope("layers"):
                if run.scanned:
                    (x, pools), load = jax.lax.scan(step, (x, pools), (
                        {at.tree: trees[at.tree] for at in run.body},
                        jnp.arange(run.repeats, dtype=jnp.int32)))
                else:
                    (x, pools), load = step((x, pools), (None, 0))
            if load is not None:
                # a scan stacks its body's counts a repetition
                load = jnp.sum(load, axis=0) if run.scanned else load
                total = load if total is None else total + load
        return pools, x, total

    # -- sampling -----------------------------------------------------------

    def _sample_slots(self, logits, keys, temp, top_k, top_p):
        """Per-slot sampling with TRACED (temperature, top_k, top_p) —
        one compiled program for any request mix (the static-arg
        variant in models/generate._sample would recompile per
        combination).  temperature <= 0 is exact greedy (argmax),
        token-identical to generate()'s."""
        v = logits.shape[-1]
        greedy = jnp.argmax(logits, axis=-1)
        l = logits / jnp.maximum(temp, 1e-6)[:, None]
        # top-k: the k-th largest as cutoff, k <= 0 or >= vocab = off
        sorted_l = jnp.sort(l, axis=-1)[:, ::-1]
        kidx = jnp.clip(
            jnp.where((top_k <= 0) | (top_k >= v), v, top_k) - 1, 0, v - 1)
        kth = jnp.take_along_axis(sorted_l, kidx[:, None], axis=-1)
        l = jnp.where(l < kth, -jnp.inf, l)
        # nucleus on the k-truncated logits (generate._sample order);
        # the argmax is always kept so top_p <= 0 degrades to greedy
        sorted2 = jnp.sort(l, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted2, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p[:, None]
        keep = keep.at[:, 0].set(True)
        pth = jnp.min(jnp.where(keep, sorted2, jnp.inf), axis=-1,
                      keepdims=True)
        # top_p >= 1 is OFF (generate._sample skips it statically) —
        # without the guard, f32 cumsum rounding to >= 1.0 early can
        # truncate tail tokens even at the default top_p=1.0
        l = jnp.where((l < pth) & (top_p[:, None] < 1.0), -jnp.inf, l)
        sampled = jax.vmap(jax.random.categorical)(keys, l)
        return jnp.where(temp <= 0, greedy, sampled).astype(jnp.int32)

    # -- jitted steps -------------------------------------------------------

    def _decode_impl(self, params, pools, carry, addr, seq_lens, active,
                     temp, top_k, top_p, all_greedy):
        """One decode token for every slot.  ``seq_lens`` is the banked
        length BEFORE this token; free slots (active=False) run on the
        null block and their sampled tokens are ignored by the host.
        ``addr`` holds every table by name, [S, MB]; what is kept by slot
        is read at the slot's own index."""
        bs = self.block_size
        tok = carry["tok"]
        positions = seq_lens[:, None]
        off = jnp.where(active, seq_lens % bs, 0)
        where = {name: (table, jnp.where(
            active,
            jnp.take_along_axis(table, (seq_lens // bs)[:, None],
                                axis=1)[:, 0],
            0)[:, None], off[:, None]) for name, table in addr.items()}
        where["slot"] = {"active": active}
        ctx = jnp.where(active, seq_lens + 1, 0)
        pools, x, load = self._forward(params, pools, tok[:, None],
                                       positions, where, ctx,
                                       active[:, None])
        with jax.named_scope("head"):
            logits = head_logits(self.cfg, params, x)
        with jax.named_scope("sample"):
            split = jax.vmap(jax.random.split)(carry["key"])
            if all_greedy:
                toks = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            else:
                toks = self._sample_slots(logits[:, 0], split[:, 1], temp,
                                          top_k, top_p)
        return pools, {"tok": toks, "key": split[:, 0]}, toks, load

    def _prefill_impl(self, params, pools, addr, t0, tokens, n_valid,
                      is_final):
        """One chunk of ONE sequence: bank k/v for tokens
        [t0, t0 + n_valid) and return the last valid row's logits (the
        first-token sampling input when this is the final chunk;
        non-final chunks skip the C x hidden x vocab head matmul — its
        output is 100% discarded — and return None).  The pad tail
        writes to the null block and its positions clamp to the newest
        real position (keeps learned-position table lookups in range
        and longrope's max(positions) regime switch exact).  ``addr``
        holds the sequence's row [MB] of every table by name and, where
        a kind keeps state by slot, its 'slot': the chunk starts from
        that slot's state, from zero where it is the request's first."""
        bs, c = self.block_size, self.chunk
        i = jnp.arange(c, dtype=jnp.int32)
        valid = i < n_valid
        pos = t0 + i
        last_pos = jnp.maximum(t0 + n_valid - 1, 0)
        positions = jnp.where(valid, pos, last_pos)[None]          # [1, C]
        off = jnp.where(valid, pos % bs, 0)
        ctx = (t0 + n_valid)[None]
        where = {name: (row[None], jnp.where(valid, row[pos // bs], 0)[None],
                        off[None])
                 for name, row in addr.items() if name != "slot"}
        if "slot" in addr:
            where["slot"] = {"slots": addr["slot"][None],
                             "fresh": (t0 == 0)[None],
                             "n_valid": n_valid[None]}
        pools, x, load = self._forward(params, pools, tokens[None],
                                       positions, where, ctx, valid[None])
        if not is_final:
            return pools, None, load
        with jax.named_scope("head"):
            if self._head_last_row:
                row = jnp.take_along_axis(
                    x, jnp.maximum(n_valid - 1, 0)[None, None, None], axis=1)
                return pools, head_logits(self.cfg, params, row)[0, 0], load
            logits = head_logits(self.cfg, params, x)
            last = jnp.take_along_axis(
                logits[0], jnp.maximum(n_valid - 1, 0)[None, None],
                axis=0)[0]                                         # [V]
        return pools, last, load

    def _prefill_batch_impl(self, params, pools, addr, t0s, tokens,
                            n_valids):
        """One chunk each of up to ``prefill_batch`` DISTINCT sequences
        in one program: ``addr`` the rows' table rows [PB, MB] by name,
        ``t0s``/``n_valids``
        [PB] (0 valid = padded row: runs on the null block, output
        discarded), ``tokens`` [PB, C].  Returns the last valid row's
        logits per sequence [PB, V] — the only rows anyone reads (final
        rows sample their first token from them; non-final and padded
        rows are ignored by the host), so the head is a [PB, H] x
        [H, V] matmul, not the full-chunk head, and final-vs-non-final
        needs no static flag: trace count is 1.  ``addr['slot']`` [PB]
        are the rows' slots where a kind keeps state by slot (the null
        slot for a padded row, which starts fresh and is read by no
        one)."""
        bs, c = self.block_size, self.chunk
        i = jnp.arange(c, dtype=jnp.int32)[None, :]              # [1, C]
        valid = i < n_valids[:, None]                            # [PB, C]
        pos = t0s[:, None] + i
        last_pos = jnp.maximum(t0s + n_valids - 1, 0)[:, None]
        positions = jnp.where(valid, pos, last_pos)              # [PB, C]
        off = jnp.where(valid, pos % bs, 0)
        ctx = t0s + n_valids                                     # [PB]
        where = {name: (rows, jnp.where(valid, jnp.take_along_axis(
            rows, pos // bs, axis=1), 0), off)
                 for name, rows in addr.items() if name != "slot"}
        if "slot" in addr:
            where["slot"] = {"slots": addr["slot"],
                             "fresh": (t0s == 0) | (n_valids == 0),
                             "n_valid": n_valids}
        pools, x, load = self._forward(params, pools, tokens, positions,
                                       where, ctx, valid)
        with jax.named_scope("head"):
            last = jnp.take_along_axis(
                x, jnp.maximum(n_valids - 1, 0)[:, None, None], axis=1)
            logits = head_logits(self.cfg, params, last)         # [PB, 1, V]
        return pools, logits[:, 0], load

    def _cow_impl(self, pools, src, dst):
        """Copy block ``src``'s rows into block ``dst`` across every
        layer of every pool (blocks are dim 1 of [L, NB, BS, row]) — the
        copy-on-write behind a fully-cached prompt: the final prompt
        token must re-run (its logits seed the first sampled token) and
        its k/v write needs a block this sequence owns; everything
        before it stays shared."""
        return {name: p.at[:, dst].set(p[:, src])
                for name, p in pools.items()}

    def _sample_first_impl(self, logits, key, temp, top_k, top_p):
        with jax.named_scope("sample"):
            return self._sample_slots(logits[None], key[None], temp[None],
                                      top_k[None], top_p[None])[0]

    def _set_slot_impl(self, carry, slot, token, key):
        return {"tok": carry["tok"].at[slot].set(token),
                "key": carry["key"].at[slot].set(key)}


@dataclasses.dataclass
class Sequence:
    """Host-side runtime state of one admitted request."""

    sid: int
    prompt: np.ndarray                       # int32 [P]
    max_new: int
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: Optional[int] = None
    seed: int = 0
    # 'priority' policy inputs: higher priority = more urgent;
    # deadline is ABSOLUTE host monotonic time (engine.submit converts
    # the request's relative deadline_s), inf = none
    priority: int = 0
    deadline: float = float("inf")
    # streaming: called as on_token(token, t_monotonic) when the lagged
    # ring resolves each token (<= decode_depth - 1 iterations after
    # dispatch) — engine.submit(..., on_token=...) plumbs it here
    on_token: Any = None
    # end-to-end trace id (engine.submit assigns it): rides every serve
    # span this request participates in — `trace` on its own spans
    # (queue/admit/single prefill), `traces` on the batched ones
    # (batched prefill, decode, deliver) — and surfaces in
    # RequestResult.trace_id
    trace_id: str = ""
    # runtime
    slot: int = -1
    blocks: List[int] = dataclasses.field(default_factory=list)
    prefilled: int = 0
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False
    finish_reason: str = ""
    key: Any = None                          # host-held PRNG key
    # prefix-cache runtime (admit() fills these)
    block_keys: Optional[List[bytes]] = None  # chain key per full block
    registered: int = 0                      # prompt blocks indexed so far
    cached_tokens: int = 0                   # prompt tokens NOT recomputed
    shared_blocks: int = 0                   # blocks reused via refcount
    cow: bool = False                        # fully-cached prompt path
    # metrics timestamps (all on time.monotonic; engine fills t_submit)
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first_dispatch: float = 0.0            # first token sampled on device
    t_first_token: float = 0.0               # ... and delivered to the host
    t_finish: float = 0.0
    token_times: List[float] = dataclasses.field(default_factory=list)
    # the same way to the first token counted in Scheduler.step() calls
    # (Scheduler._step_idx: the running step's index; between two steps
    # the next one's), always on: what a request waits in steps does not
    # depend on what a step costs
    step_submit: int = 0
    step_admit: int = 0
    step_first: int = -1                     # the step of the last chunk
    prefill_programs: int = 0                # programs that ran a chunk of it
    # expert-layer counts of this request's prefill programs, handed to
    # the ring with its first token (device arrays; empty without experts)
    loads: List[Any] = dataclasses.field(default_factory=list)
    # window layers' blocks held now: logical block -> pool block
    # (kv_cache.WindowBlocks; empty for a model without window layers)
    win_blocks: Dict[int, int] = dataclasses.field(default_factory=dict)
    # positions a full layer attended, positions cached for it and
    # positions a window layer attended in this request's prefill
    # programs (Scheduler._note_selected; None without such layers)
    selected: Any = None

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    # The first token's way, from the stamps alone (0 for a stage the
    # request never reached): queue_s + prefill_s + first_token_lag_s ==
    # ttft_s, and wait_steps >= prefill_programs >= 1 once it is out.

    @property
    def queue_s(self) -> float:
        """submit -> slot."""
        return max(self.t_admit - self.t_submit, 0.0)

    @property
    def queue_steps(self) -> int:
        """submit -> slot in steps: 0 = admitted ahead of the first step
        after submit; each one above it is a step that ran with the
        request still queued (no free slot, or a reservation refused)."""
        return max(self.step_admit - self.step_submit, 0)

    @property
    def prefill_s(self) -> float:
        """slot -> first token sampled on device: the prompt's own
        chunks and the turns other prompts' chunks took."""
        return max(self.t_first_dispatch - self.t_admit, 0.0)

    @property
    def first_token_lag_s(self) -> float:
        """last chunk dispatched (its token sampled on device) ->
        delivered: what the device still owed then (the host runs
        ``decode_depth - 1`` iterations ahead), that chunk's program
        and the one blocking fetch."""
        return max(self.t_first_token - self.t_first_dispatch, 0.0)

    @property
    def ttft_s(self) -> float:
        """submit -> first token delivered."""
        return max(self.t_first_token - self.t_submit, 0.0)

    @property
    def wait_steps(self) -> int:
        """Scheduler steps from the first after submit to the one that
        ran the prompt's last chunk, both counted."""
        return max(self.step_first - self.step_submit + 1, 0)


def priority_key(seq: "Sequence", now: float, aging_s: float):
    """'priority' policy ordering — the ONE home for the semantics, so
    admission (engine._admit) and prefill order (scheduler.
    _prefill_candidates) can never drift apart: effective class
    descending (declared class + 1 per ``aging_s`` seconds waited — the
    starvation bound: any request eventually outranks any fixed class),
    then earliest deadline, then arrival."""
    eff = seq.priority + (int((now - seq.t_submit) / aging_s)
                          if aging_s > 0 else 0)
    return (-eff, seq.deadline, seq.sid)


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unresolved iteration in the readback ring."""

    kind: str                                # 'decode' | 'first'
    tokens: Any                              # device array
    slots: List[Tuple[int, Sequence]] = dataclasses.field(
        default_factory=list)                # decode snapshot
    seq: Optional[Sequence] = None           # 'first' entries
    iter_idx: int = -1                       # decode iteration index
    t_dispatch: float = 0.0
    # expert-layer counts of the step(s) behind this entry: int32[3]
    # device arrays (models/moe.held_experts_ffn), one a program — a
    # decode step's own, or every prefill chunk's of a 'first' entry
    loads: List[Any] = dataclasses.field(default_factory=list)
    # Scheduler._note_selected's counts of the step(s) behind this entry
    # (None for a model without an indexed selection)
    selected: Any = None
    # a mixer_pattern model's decode step: (slots whose state it read and
    # wrote, positions its attention layers' queries attended); else None
    state: Any = None


class Scheduler:
    """Slot + block bookkeeping and the iteration loop.

    One ``step()`` = (at most) one prefill chunk + one batched decode
    step + ring resolution down to ``decode_depth - 1`` in flight.
    """

    def __init__(self, model_cfg, params, serve_cfg,
                 attention_impl: Optional[str] = None, blocked=None):
        self.cfg = model_cfg
        self.serve_cfg = serve_cfg
        self.params = params
        self.blocked = blocked               # optional BlockedMeter
        self.decoder = PagedDecoder(model_cfg, serve_cfg, attention_impl)
        # shared-prefix KV reuse: the index maps token-hash chains to
        # resident blocks; the pool refcounts them and parks refcount-0
        # indexed blocks in its cached LRU instead of freeing
        self.prefix = (PrefixIndex(serve_cfg.block_size)
                       if serve_cfg.prefix_cache else None)
        self.pool = BlockPool(serve_cfg.num_blocks, index=self.prefix)
        # the pools of the model's kinds of layer by name (serve/kinds.py),
        # donated to and returned by every step
        self.pools = make_pools(model_cfg, serve_cfg)
        s = serve_cfg.max_slots
        # table width bounds the LONGEST admissible sequence, not the
        # pool: the attention cost per decode token scales with table
        # width (the fallback gathers [S, MB*BS] per layer; the kernel
        # runs MB grid steps per slot/head), so sizing it num_blocks-1
        # would make growing the pool for more concurrency inflate
        # every slot's per-token cost.  The model's position reach
        # (max_seq_len) plus the in-flight overhang is the natural
        # bound; submit() rejects anything needing more.
        self.max_blocks_per_seq = min(
            serve_cfg.num_blocks - 1,
            blocks_needed(model_cfg.max_seq_len + serve_cfg.decode_depth,
                          serve_cfg.block_size))
        self.tables = np.zeros((s, self.max_blocks_per_seq), np.int32)
        # a model with window layers: their blocks, held only while the
        # window reaches them, and their table
        self.window = None
        if "window" in self.decoder.by:
            self.window = WindowBlocks(
                num_window_blocks(model_cfg, serve_cfg),
                serve_cfg.block_size, model_cfg.window[0],
                window_blocks_bound(model_cfg.window[0],
                                    serve_cfg.prefill_chunk,
                                    serve_cfg.block_size))
            self.win_tables = np.zeros_like(self.tables)
            self._dev_win = None
        # a model with state-space layers (what is kept by slot): how
        # many, and the bytes one slot's state takes in all of them (what
        # a decode step reads and writes a slot)
        by_slot = [(record, n) for record, _, n in self.decoder.kinds.values()
                   if record.by == "slot"]
        self._ssm_layers = sum(n for _, n in by_slot)
        self._slot_state_bytes = sum(
            self.pools[name].nbytes // self.pools[name].shape[1]
            for record, _ in by_slot for name in record.names)
        self.seq_lens = np.zeros((s,), np.int32)
        self.active = np.zeros((s,), bool)
        self.temp = np.zeros((s,), np.float32)
        self.top_k = np.zeros((s,), np.int32)
        self.top_p = np.ones((s,), np.float32)
        self.slot_seq: List[Optional[Sequence]] = [None] * s
        self.carry = {
            "tok": jnp.zeros((s,), jnp.int32),
            "key": jnp.asarray(
                np.stack([np.asarray(jax.random.PRNGKey(i))
                          for i in range(s)]), jnp.uint32),
        }
        self._ring: "collections.deque[_InFlight]" = collections.deque()
        self._iter = 0            # decode iterations dispatched
        self._step_idx = 0        # step() calls completed
        self._resolved = 0        # decode iterations resolved
        # an evicted sequence's blocks, each kind's with who takes it back
        self._deferred: List[Tuple[int, List[int], Any]] = []
        # newly finished sequences, drained by the engine each step —
        # completion accounting stays O(finished this step), never a
        # scan over every request the process has served
        self.finished: List[Sequence] = []
        # device copies of the membership-stable host arrays (tables,
        # active, sampling params), re-uploaded only when admission /
        # prefill-completion / eviction dirties them — seq_lens changes
        # every decode iteration and is always uploaded fresh
        self._dev_stable = None

    # -- admission ----------------------------------------------------------

    def blocks_for(self, seq: Sequence) -> int:
        """Blocks reserved at admission: prompt + max_new + the
        in-flight overhang (a finished slot keeps writing for up to
        decode_depth iterations before the host notices)."""
        return blocks_needed(
            seq.prompt_len + seq.max_new + self.serve_cfg.decode_depth,
            self.serve_cfg.block_size)

    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slot_seq):
            if s is None:
                return i
        return None

    def min_fresh_blocks(self, seq: Sequence) -> int:
        """Cheapest POSSIBLE fresh-block need (best case: every full
        prompt block is a prefix hit) — the engine's O(Q) admission
        early-exit bound.  No hashing, so it may be optimistic; only
        ``admit`` itself is authoritative."""
        total = self.blocks_for(seq)
        if self.prefix is None:
            return total
        return max(1, total - seq.prompt_len // self.serve_cfg.block_size)

    def admit(self, seq: Sequence) -> bool:
        """Give ``seq`` a decode slot + its whole block reservation, or
        return False with NO state change (all-or-nothing; the engine
        retries next iteration).  With the prefix cache on, the longest
        token-hash-chain match replaces that many fresh blocks with
        refcounted shared ones and prefill starts past them."""
        with tracing.span("serve/admit", sid=seq.sid,
                          trace=seq.trace_id) as sp:
            ok = self._admit_impl(seq)
            if not ok:
                # the ring keeps SUCCESSFUL admissions only: a saturated
                # engine re-attempts its queue head every iteration, and
                # one admitted=False span per retry would evict the
                # useful spans from the bounded ring exactly when an
                # operator exports it (failed-admission pressure is
                # visible as serve_queue_depth + kv_pool_free_blocks
                # instead).  A profiler trace shows the attempt, marked.
                sp.discard()
                sp.set(admitted=0)
                return False
            # the queue wait, known only now (submit -> slot admission)
            queue_s = seq.queue_s if seq.t_submit else 0.0
            sp.set(admitted=1, cached_tokens=seq.cached_tokens,
                   queue_ms=queue_s * 1e3, **self.blocks_by_kind())
            if self._ssm_layers:
                # the slot's recurrent state restarts with the request:
                # its first chunk reads zeros, not the last tenant's state
                sp.set(state_reset=1)
        if seq.t_submit and tracing.enabled():
            now = time.perf_counter()
            tracing.record_span("serve/queue", now - queue_s, now,
                                sid=seq.sid, trace=seq.trace_id)
        return True

    def blocks_by_kind(self) -> Dict[str, int]:
        """Blocks in use by kind of layer (and, where window layers free
        theirs as the window passes, how many went back that way)."""
        out = {"blocks_full": self.pool.in_use}
        if self.window is not None:
            out.update(blocks_window=self.window.pool.in_use,
                       window_blocks_freed=self.window.freed)
        return out

    def _advance_window(self, seq: Sequence, first_query: int,
                        upto: int) -> None:
        """Before a program whose queries of ``seq`` are positions
        [first_query, upto): the window layers' table row holds the
        blocks those queries see and write, and no others."""
        if self.window.advance(seq.win_blocks, self.win_tables[seq.slot],
                               first_query, upto):
            self._dev_win = None

    def _before_prefill(self, seq: Sequence, t0: int, n: int) -> None:
        """A model with window layers, before a prefill program of
        ``seq`` over positions [t0, t0 + n): its window table row, and
        the positions the request's prefill has worked through so far."""
        self._advance_window(seq, t0, t0 + n)
        seq.selected = self._note_selected(t0, n) + (
            0 if seq.selected is None else seq.selected)

    def _note_selected(self, t0: int, n: int) -> np.ndarray:
        """:meth:`_note_attended` for queries at positions [t0, t0 + n):
        query t sees t + 1 cached positions."""
        return self._note_attended(
            np.arange(t0 + 1, t0 + n + 1, dtype=np.int64))

    def _note_attended(self, seen: np.ndarray) -> np.ndarray:
        """(positions a full layer attends, positions cached for it,
        positions a window layer attends) for queries that see ``seen``
        cached positions each: a full layer attends all of them, or
        ``index_topk`` at most under an indexed selection, a window
        layer those in its window."""
        topk = self.cfg.index_topk
        return np.array([
            (np.minimum(seen, topk) if topk else seen).sum(), seen.sum(),
            np.minimum(seen, self.cfg.window[0] + 1).sum()])

    def _admit_impl(self, seq: Sequence) -> bool:
        slot = self.free_slot()
        if slot is None:
            return False
        if self.window is not None and not self.window.can_reserve():
            return False
        total = self.blocks_for(seq)
        shared: List[int] = []
        cow_src: Optional[int] = None
        if self.prefix is not None:
            # hash once, not per attempt: a queued request re-attempts
            # admission every engine iteration while it waits for blocks
            if seq.block_keys is None:
                seq.block_keys = self.prefix.keys(seq.prompt)
            shared = self.prefix.match(seq.block_keys)
            if shared and (len(shared) * self.serve_cfg.block_size
                           >= seq.prompt_len):
                # fully cached prompt: the final token must still run
                # (its logits seed the first sampled token) and its k/v
                # write needs a block this sequence owns — copy-on-write
                # the last matched block, share the rest
                cow_src = shared.pop()
        # pin the match BEFORE alloc: alloc may evict cached refcount-0
        # blocks to cover the grant, and it must not reclaim the match
        for b in shared:
            self.pool.share(b)
        if cow_src is not None:
            self.pool.share(cow_src)
        fresh = self.pool.alloc(total - len(shared))
        if fresh is None:
            # roll back the pins — admission never partially grants
            self.pool.free(shared)
            if cow_src is not None:
                self.pool.free([cow_src])
            return False
        blocks = shared + fresh
        if self.window is not None:
            self.window.reserve()
            self.win_tables[slot, :] = 0
            self._dev_win = None
        seq.slot = slot
        seq.blocks = blocks
        seq.key = jax.random.PRNGKey(seq.seed)
        seq.t_admit = time.monotonic()
        seq.step_admit = self._step_idx
        cached = len(shared) * self.serve_cfg.block_size
        if cow_src is not None:
            # dst is fresh[0] == table index len(shared): the copy sits
            # exactly where the popped match sat.  Device program order
            # makes the copy read src before any later program could
            # recycle it, so the pin can drop right after dispatch.
            self.pools = self.decoder._cow(
                self.pools, jnp.asarray(cow_src, jnp.int32),
                jnp.asarray(fresh[0], jnp.int32))
            self.pool.free([cow_src])
            cached = seq.prompt_len - 1
            seq.cow = True
            counters.inc("cow_copies")
        seq.prefilled = cached
        seq.cached_tokens = cached
        seq.shared_blocks = len(shared)
        seq.registered = len(shared)
        if cached:
            # engine.stats() aggregates the per-sequence fields at
            # completion; these global counters are the operator's
            # process-wide degradation/observability surface
            counters.inc("prefix_hits")
            if shared:
                counters.inc("prefix_blocks_reused", len(shared))
        self.slot_seq[slot] = seq
        self.tables[slot, :] = 0
        self.tables[slot, :len(blocks)] = blocks
        self.seq_lens[slot] = cached
        self.active[slot] = False          # decode starts after prefill
        self.temp[slot] = seq.temperature
        self.top_k[slot] = seq.top_k
        self.top_p[slot] = seq.top_p
        self._dev_stable = None
        return True

    def flush_prefix_cache(self) -> int:
        """Drop every cached prefix block + index entry; returns the
        block count.  The weight-swap seam (engine.load_params): k/v
        banked under old weights must never satisfy a prompt served
        under new ones.  The caller guarantees no live sequences."""
        if self.prefix is None:
            return 0
        return self.pool.flush_cached()

    # -- the iteration ------------------------------------------------------

    def _prefill_candidates(self) -> List[Sequence]:
        """Up to ``prefill_batch`` distinct sequences with prompt left
        to prefill, most-urgent first ('priority' policy: class then
        deadline — the same order admission used; otherwise arrival)."""
        cands = [s for s in self.slot_seq
                 if s is not None and not s.finished
                 and s.prefilled < s.prompt_len]
        if not cands:
            return []
        if self.serve_cfg.policy == "priority":
            # the same effective class admission uses, so a request
            # that aged past a higher class keeps its precedence once
            # both occupy slots
            now = time.monotonic()
            aging = self.serve_cfg.priority_aging_s
            cands.sort(key=lambda s: priority_key(s, now, aging))
        else:
            cands.sort(key=lambda s: s.sid)
        return cands[:self.serve_cfg.prefill_batch]

    def step(self) -> bool:
        """One engine iteration.  Returns True when any device work was
        dispatched (False = idle: nothing admitted, prefilling or
        decoding)."""
        did = False
        seqs = self._prefill_candidates()
        if seqs:
            if len(seqs) == 1:
                # a lone prefilling sequence (prefill_batch == 1, or
                # the steady-state trickle under a bigger batch) takes
                # the single-sequence program — no pad rows burning
                # prefill_batch x the FLOPs on the null block
                self._prefill_one(seqs[0])
            else:
                self._prefill_batched(seqs)
            did = True
        if self.active.any():
            self._decode_once()
            did = True
        # lagged resolution: keep at most decode_depth - 1 in flight
        while len(self._ring) >= self.serve_cfg.decode_depth:
            self._resolve_one()
        if not did:
            # nothing in flight can mature on its own — resolve one
            # entry so finishes/evictions make progress
            if self._ring:
                self._resolve_one()
                did = True
        self._release_matured()
        self._step_idx += 1
        return did

    def _addr(self, seqs=None):
        """The addressing pytree a step of PagedDecoder takes: every
        table by name and, in a prefill of a model that keeps state by
        slot, 'slot'.  None: a decode step — every slot's rows, the
        device copies kept until something changes them.  A Sequence:
        its own row and slot.  A list of them: a row each, padded to
        ``prefill_batch`` rows (a padded row runs on the null block and
        on the null slot, behind the real ones)."""
        host = {"blocks": self.tables}
        if self.window is not None:
            host["window"] = self.win_tables
        if seqs is None:
            addr = {"blocks": self._dev_stable_arrays()[0]}
            if self.window is not None:
                if self._dev_win is None:
                    self._dev_win = _upload(self.win_tables)
                addr["window"] = self._dev_win
            return addr
        if isinstance(seqs, Sequence):
            slots = np.int32(seqs.slot)
        else:
            slots = np.full((self.serve_cfg.prefill_batch,),
                            self.serve_cfg.max_slots, np.int32)
            slots[:len(seqs)] = [seq.slot for seq in seqs]
        # (the null slot's row names the null block alone)
        addr = {name: jnp.asarray(
            np.concatenate([t, np.zeros_like(t[:1])])[slots])
            for name, t in host.items()}
        if "slot" in self.decoder.by:
            addr["slot"] = jnp.asarray(slots)
        return addr

    def _prefill_one(self, seq: Sequence) -> None:
        c = self.serve_cfg.prefill_chunk
        t0 = seq.prefilled
        chunk = seq.prompt[t0:t0 + c]
        n_valid = int(chunk.shape[0])
        if n_valid < c:
            chunk = np.pad(chunk, (0, c - n_valid))
        final = (t0 + n_valid) >= seq.prompt_len
        if self.window is not None:
            self._before_prefill(seq, t0, n_valid)
        addr = self._addr(seq)
        with tracing.span("serve/prefill", sid=seq.sid, t0=t0,
                          tokens=n_valid, batched=False,
                          trace=seq.trace_id):
            self.pools, last_logits, load = self.decoder._prefill(
                self.params, self.pools, addr,
                jnp.asarray(t0, jnp.int32), jnp.asarray(chunk, jnp.int32),
                jnp.asarray(n_valid, jnp.int32), final)
        if load is not None:
            seq.loads.append(load)
        seq.prefill_programs += 1
        seq.prefilled += n_valid
        self.seq_lens[seq.slot] = seq.prefilled
        self._register_prefix(seq)
        if seq.prefilled >= seq.prompt_len:
            self._seed_first_token(seq, last_logits)

    def _prefill_batched(self, seqs: List[Sequence]) -> None:
        """One chunk each of up to ``prefill_batch`` sequences in a
        single dispatched program.  Short rows pad to [prefill_batch,
        prefill_chunk] (pad rows run on the null block, outputs
        discarded) so the program traces exactly once."""
        pb = self.serve_cfg.prefill_batch
        c = self.serve_cfg.prefill_chunk
        t0s = np.zeros((pb,), np.int32)
        toks = np.zeros((pb, c), np.int32)
        n_valids = np.zeros((pb,), np.int32)
        taken = []
        for r, seq in enumerate(seqs):
            t0 = seq.prefilled
            chunk = seq.prompt[t0:t0 + c]
            n = int(chunk.shape[0])
            t0s[r] = t0
            toks[r, :n] = chunk
            n_valids[r] = n
            taken.append(n)
            if self.window is not None:
                self._before_prefill(seq, t0, n)
        addr = self._addr(seqs)
        with tracing.span("serve/prefill", batched=True,
                          sids=[s.sid for s in seqs],
                          traces=[s.trace_id for s in seqs],
                          tokens=int(sum(taken))):
            self.pools, logits, load = self.decoder._prefill_batch(
                self.params, self.pools, addr,
                jnp.asarray(t0s), jnp.asarray(toks), jnp.asarray(n_valids))
        if load is not None:
            seqs[0].loads.append(load)       # one program, counted once
        for r, seq in enumerate(seqs):
            seq.prefill_programs += 1
            seq.prefilled += taken[r]
            self.seq_lens[seq.slot] = seq.prefilled
            self._register_prefix(seq)
            if seq.prefilled >= seq.prompt_len:
                self._seed_first_token(seq, logits[r])

    def _register_prefix(self, seq: Sequence) -> None:
        """Index every newly completed FULL prompt block so later (and
        concurrent) prompts can share it.  First writer wins: blocks
        whose chain key is already mapped (the shared match itself, the
        COW copy, a concurrent identical prompt) stay private."""
        if self.prefix is None or not seq.block_keys:
            return
        n_full = min(seq.prefilled, seq.prompt_len) \
            // self.serve_cfg.block_size
        while seq.registered < n_full:
            i = seq.registered
            self.prefix.register(seq.block_keys[i], seq.blocks[i])
            seq.registered += 1

    def _seed_first_token(self, seq: Sequence, last_logits) -> None:
        """Final prefill chunk done: sample the first generated token
        on device and splice it into the decode carry — no readback;
        the host learns it through the ring like any other token."""
        seq.key, sub = jax.random.split(seq.key)
        tok = self.decoder._sample_first(
            last_logits, sub,
            jnp.asarray(seq.temperature, jnp.float32),
            jnp.asarray(seq.top_k, jnp.int32),
            jnp.asarray(seq.top_p, jnp.float32))
        seq.key, slot_key = jax.random.split(seq.key)
        self.carry = self.decoder._set_slot(
            self.carry, jnp.asarray(seq.slot, jnp.int32), tok,
            slot_key.astype(jnp.uint32))
        self.active[seq.slot] = True
        self._dev_stable = None
        seq.step_first = self._step_idx
        seq.t_first_dispatch = time.monotonic()
        self._ring.append(_InFlight(
            kind="first", tokens=tok, seq=seq, loads=seq.loads,
            selected=seq.selected, t_dispatch=seq.t_first_dispatch))
        seq.loads, seq.selected = [], None

    def _dev_stable_arrays(self):
        if self._dev_stable is None:
            self._dev_stable = tuple(
                _upload(x) for x in (self.tables, self.active, self.temp,
                                     self.top_k, self.top_p))
        return self._dev_stable

    def _decode_once(self) -> None:
        # serve chaos seam (resilience/chaos.py): crash-mid-decode
        # (ChaosPlan.kill -> SIGKILL with sequences in flight — the
        # journal-replay gate) and decode-loop hang (ChaosPlan.hang ->
        # the serve_liveness health check flips, the supervisor probe
        # kills).  One global `is None` check when no plan is active.
        failpoint("serve.decode", iter=self._iter)
        snapshot = [(i, s) for i, s in enumerate(self.slot_seq)
                    if self.active[i] and s is not None]
        selected = None
        if self.window is not None:
            for slot, seq in snapshot:
                n = int(self.seq_lens[slot])
                self._advance_window(seq, n, n + 1)
            selected = self._note_attended(
                self.seq_lens[[slot for slot, _ in snapshot]].astype(
                    np.int64) + 1)           # cached positions a query
        state = None
        if self._ssm_layers:
            # every decoding slot's state is read and written once a
            # state-space layer; an attention layer's query sees the
            # slot's cached positions and its own
            state = (len(snapshot), int(self.seq_lens[
                [slot for slot, _ in snapshot]].sum()) + len(snapshot))
        addr = self._addr()
        _, active, temp, top_k, top_p = self._dev_stable_arrays()
        all_greedy = bool((self.temp[self.active] <= 0.0).all())
        # per-request trace ids on the batched span: built only while
        # tracing records (the list comprehension must cost nothing on
        # the disabled hot path)
        _traces = ([s.trace_id for _, s in snapshot]
                   if tracing.enabled() else None)
        with tracing.span("serve/decode", iter=self._iter,
                          slots=len(snapshot), traces=_traces):
            self.pools, self.carry, toks, load = self.decoder._decode(
                self.params, self.pools, self.carry,
                addr, _upload(self.seq_lens),
                active, temp, top_k, top_p, all_greedy)
        # host mirror: every active slot banked one more token
        self.seq_lens[self.active] += 1
        self._ring.append(_InFlight(
            kind="decode", tokens=toks, slots=snapshot,
            loads=[] if load is None else [load], selected=selected,
            state=state, iter_idx=self._iter, t_dispatch=time.monotonic()))
        self._iter += 1

    # -- resolution / eviction ----------------------------------------------

    def _record(self, seq: Sequence, token: int, now: float) -> None:
        if seq.finished:
            return                 # lagged garbage after finish
        if not seq.out_tokens:
            seq.t_first_token = now
        seq.out_tokens.append(token)
        seq.token_times.append(now)
        if seq.on_token is not None:
            # streaming delivery: the callback sees each token at
            # resolution time — <= decode_depth - 1 iterations after
            # its dispatch, never a garbage post-finish token.  A
            # raising callback is disabled, not allowed to corrupt the
            # ring resolution for every other request.
            try:
                seq.on_token(token, now)
            except Exception:
                logger.exception(
                    f"on_token callback for request {seq.sid} raised; "
                    f"disabling the stream callback for this request")
                seq.on_token = None
        if seq.eos_id is not None and token == seq.eos_id:
            self._finish(seq, "eos", now)
        elif len(seq.out_tokens) >= seq.max_new:
            self._finish(seq, "length", now)

    def _finish(self, seq: Sequence, reason: str, now: float) -> None:
        seq.finished = True
        seq.finish_reason = reason
        seq.t_finish = now
        self.finished.append(seq)
        self._evict(seq)

    def preempt(self, seq: Sequence, now: float) -> None:
        """Evict an ADMITTED sequence before its natural finish (the
        engine's opt-in ``serve.preempt_deadlines`` sweep): typed
        ``finish_reason='preempted'`` with whatever tokens resolved so
        far, blocks released through the same deferred-free path as any
        eviction.  Safe mid-flight by the existing machinery: lagged
        ring entries for the evicted slot drop in :meth:`_record`'s
        post-finish guard, and the deferred free holds the blocks until
        every already-dispatched iteration resolves."""
        if seq.finished:
            return
        self._finish(seq, "preempted", now)

    def _evict(self, seq: Sequence) -> None:
        slot = seq.slot
        if slot < 0:
            return
        self.slot_seq[slot] = None
        self.active[slot] = False
        self.tables[slot, :] = 0
        self.seq_lens[slot] = 0
        seq.slot = -1
        self._dev_stable = None
        # DEFERRED free: iterations dispatched before this point may
        # still write through the old table — release only once every
        # decode iteration < self._iter has resolved
        if self.window is not None:
            self.win_tables[slot, :] = 0
            self._dev_win = None
            self._deferred.append((self._iter, list(seq.win_blocks.values()),
                                   self.window.release))
        self._deferred.append((self._iter, seq.blocks, self.pool.free))
        seq.blocks, seq.win_blocks = [], {}
        self._release_matured()

    def _release_matured(self) -> None:
        ring_empty = not any(e.kind == "decode" for e in self._ring)
        keep = []
        for after, blocks, release in self._deferred:
            if self._resolved >= after or ring_empty:
                release(blocks)
            else:
                keep.append((after, blocks, release))
        self._deferred = keep

    def _resolve_one(self) -> None:
        entry = self._ring.popleft()
        # stream-delivery span: token readback (the lagged blocking
        # fetch) + per-request recording incl. on_token callbacks
        _traces = None
        if tracing.enabled():
            _traces = ([entry.seq.trace_id] if entry.kind == "first"
                       else [s.trace_id for _, s in entry.slots])
        with tracing.span("serve/deliver", kind=entry.kind,
                          traces=_traces) as deliver:
            # the (only) blocking fetch: deliver's time outside this
            # span is the host's own work, inside it the device's
            with tracing.span("serve/wait"):
                if self.blocked is not None:
                    with self.blocked.blocked():
                        toks = np.asarray(entry.tokens)
                else:
                    toks = np.asarray(entry.tokens)
            if entry.loads and deliver.live:
                # the steps' expert-layer counts: computed with the
                # tokens just fetched, so reading them waits on nothing
                pairs, largest, hit = np.sum(
                    [np.asarray(x) for x in entry.loads], axis=0)
                layer_steps = len(entry.loads) * (
                    layer_kinds(self.cfg).count("moe")
                    if self.cfg.mixer_pattern else
                    self.cfg.num_layers - self.cfg.first_dense_layers)
                deliver.set(
                    moe_pairs=int(pairs), moe_max=int(largest),
                    moe_hit=int(hit), moe_layer_steps=layer_steps,
                    moe_slots=layer_steps * self.cfg.num_experts)
            if entry.selected is not None and deliver.live:
                # the queries behind these tokens, one layer of each
                # kind: positions a full layer attended and had cached,
                # positions a window layer attended
                att, cached, win_att = (int(x) for x in entry.selected)
                if self.cfg.index_topk:
                    deliver.set(sel_attended=att, sel_cached=cached,
                                win_attended=win_att)
                else:
                    deliver.set(ctx_attended=cached, win_attended=win_att)
            if self._ssm_layers and deliver.live:
                deliver.set(ssm_layers=self._ssm_layers)
                if entry.state is not None:
                    # a decode step over state-space layers: the state
                    # bytes it read and wrote (every decoding slot's, once
                    # a layer) beside the positions an attention layer
                    # attended
                    slots, attended = entry.state
                    deliver.set(
                        state_bytes=2 * slots * self._slot_state_bytes,
                        ctx_attended=attended)
            now = time.monotonic()
            if entry.kind == "first":
                seq = entry.seq
                self._record(seq, int(toks), now)
                if deliver.live and seq.out_tokens:
                    # what this request's wait for its first token was
                    # made of (Sequence's stamps; docs/observability.md)
                    deliver.set(
                        sid=seq.sid,
                        prefill_programs=seq.prefill_programs,
                        queue_steps=seq.queue_steps,
                        wait_steps=seq.wait_steps,
                        queue_ms=seq.queue_s * 1e3,
                        prefill_ms=seq.prefill_s * 1e3,
                        lag_ms=seq.first_token_lag_s * 1e3,
                        ttft_ms=seq.ttft_s * 1e3)
            else:
                for slot, seq in entry.slots:
                    self._record(seq, int(toks[slot]), now)
                self._resolved = entry.iter_idx + 1
        self._release_matured()

    def drain(self) -> None:
        """Resolve every in-flight iteration (engine shutdown / idle)."""
        while self._ring:
            self._resolve_one()
        self._release_matured()

    @property
    def pending(self) -> int:
        return len(self._ring)

    def busy(self) -> bool:
        return (any(s is not None for s in self.slot_seq)
                or bool(self._ring))
