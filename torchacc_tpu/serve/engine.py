"""Request-level serving front-end: queue, admission control, SLO metrics.

``ServeEngine`` is the surface a serving binary drives:

    engine = ServeEngine(model, params, config)
    rid = engine.submit(Request(prompt_ids=[...], max_new_tokens=64))
    while engine.step():
        ...                       # or engine.run() / engine.generate()
    result = engine.result(rid)   # tokens + per-request SLO metrics

Admission control: a request enters a decode slot only when the block
pool has headroom for its WHOLE reservation (prompt + max_new +
in-flight overhang, scheduler.blocks_for) — a sequence admitted is a
sequence that can always finish; there is no mid-decode OOM or
preemption path to handle.  Until then it waits in the queue
(``serve.policy``: 'fcfs' arrival order, 'sjf' shortest prompt first,
'priority' per-request class + earliest-deadline-first within a class,
starvation-bounded by ``serve.priority_aging_s``).

Streaming: ``submit(req, on_token=...)`` invokes the callback as the
lagged decode ring resolves each token, and ``stream(rid)`` is the
pull-style generator over the same seam — tokens surface at most
``decode_depth - 1`` engine iterations after the device produced them
(the documented readback lag; docs/serving.md "Streaming").

Per-request SLO metrics (each ``RequestResult``): queue wait, TTFT
(submit -> first token RESOLVED on the host — readback lag included,
it is real user-visible latency), per-token inter-arrival latencies,
and tokens/s.  Aggregates ride ``utils/metrics``: the shared Counters
(serve_requests_completed, serve_tokens_generated) and an optional
MetricsWriter (``metrics_dir=``) receiving one record per completed
request — the same observability seam the trainer uses.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import os
import time
from typing import Any, Dict, List, Optional, Sequence as Seq

import numpy as np

from torchacc_tpu.config import Config
from torchacc_tpu.obs import tracing
from torchacc_tpu.serve.journal import RequestJournal, read_journal, replay_state
from torchacc_tpu.serve.scheduler import Scheduler, Sequence, priority_key
from torchacc_tpu.utils.logger import logger
from torchacc_tpu.utils.metrics import BlockedMeter, counters, open_metrics


@dataclasses.dataclass
class Request:
    """One generation request.  Sampling params default to greedy."""

    prompt_ids: Seq[int]
    max_new_tokens: Optional[int] = None     # None = config.serve default
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: Optional[int] = None
    seed: int = 0
    # 'priority' policy inputs (ignored under fcfs/sjf): higher
    # priority = more urgent; deadline_s is seconds from submit() by
    # which the request wants to FINISH — within a priority class the
    # earliest deadline admits first (EDF), and stats()/metrics count
    # the misses.  Neither field drops or preempts work.
    priority: int = 0
    deadline_s: Optional[float] = None
    # end-to-end trace id: threaded through every serve span of this
    # request's lifecycle (queue -> admit -> prefill -> decode ->
    # deliver) so one request's timeline is filterable out of the
    # Chrome-trace export (docs/observability.md "Per-request serve
    # traces").  None = the engine assigns one at submit; a caller
    # propagating an upstream id (gateway, RPC) sets it here.
    trace_id: Optional[str] = None


@dataclasses.dataclass
class RequestResult:
    """Tokens + the per-request SLO metrics (docs/serving.md)."""

    request_id: int
    prompt_ids: List[int]
    tokens: List[int]                        # generated tokens only
    finish_reason: str                       # 'eos' | 'length'
    queue_wait_s: float                      # submit -> slot admission
    ttft_s: float                            # submit -> first token
    total_s: float                           # submit -> finish
    token_latencies_s: List[float]           # inter-token gaps
    tokens_per_sec: float
    # prompt tokens served from the prefix cache (0 = cold / cache off)
    cached_prompt_tokens: int = 0
    # finish beat the request's deadline (None = no deadline given)
    deadline_met: Optional[bool] = None
    # the id every serve span of this request carried (filter the
    # Chrome-trace export on it to see this request's full timeline)
    trace_id: str = ""
    # what ttft_s was made of beside queue_wait_s (docs/serving.md "SLO
    # metrics"): queue_wait_s + prefill_s + first_token_lag_s == ttft_s
    prefill_s: float = 0.0                   # slot -> first token sampled
    first_token_lag_s: float = 0.0           # sampled -> delivered
    # ... and counted in scheduler steps, which no step's cost moves
    queue_steps: int = 0                     # submit -> slot
    wait_steps: int = 0                      # submit -> last chunk's step
    prefill_programs: int = 0                # programs that ran a chunk


def _percentile(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


#: process-global trace-id sequence: request ids restart at 0 per
#: engine, but co-located engines (bench's control engine, an A/B
#: pair) share one tracing ring — ids must be unique per PROCESS or
#: filtering the exported timeline mixes two requests' spans
_trace_seq = itertools.count()


class ServeEngine:
    """Continuous-batching serving engine over a paged KV cache.

    Parameters
    ----------
    model: a zoo ``TransformerLM`` (or its ``ModelConfig``)
    params: the model's param tree (cast to serving precision by the
        caller — see examples/serve.py)
    config: the framework :class:`Config`; ``config.serve`` is the
        tuning block
    mesh: optional device mesh entered around every dispatch so the
        pool/param shardings resolve (single-chip runs omit it)
    metrics_dir: optional MetricsWriter directory for per-request
        SLO records
    """

    def __init__(self, model, params, config: Optional[Config] = None,
                 mesh=None, metrics_dir: Optional[str] = None):
        cfg = getattr(model, "cfg", model)
        config = config or Config()
        config.serve.validate()
        self.cfg = cfg
        self.config = config
        self.mesh = mesh
        self.blocked = BlockedMeter()
        with self._mesh_ctx():
            self.scheduler = Scheduler(cfg, params, config.serve,
                                       attention_impl=cfg.attention_impl,
                                       blocked=self.blocked)
        self._queue: "collections.deque[Sequence]" = collections.deque()
        self._all: Dict[int, Sequence] = {}
        self._next_id = 0
        # graceful drain (docs/serving.md "Graceful drain"): once set,
        # admission stops; in-flight decodes finish; queued requests
        # are reported unserved — the serving half of preemption.
        # _drain_reported keeps the unserved accounting one-shot: a
        # second run() on a drained engine must not re-count the same
        # ids into serve_requests_unserved
        self._draining = False
        self._drain_reported = False
        self._metrics = open_metrics(metrics_dir)
        self._completed = 0
        # durable request journal + replay (serve/journal.py,
        # docs/serving.md "Serving under the supervisor"): None = off,
        # serve path byte-identical to the journal-free engine
        self._journal = (RequestJournal(
            config.serve.journal_dir,
            fsync=config.serve.journal_fsync,
            rotate_bytes=config.serve.journal_rotate_bytes,
            rotate_age_s=config.serve.journal_rotate_age_s)
            if config.serve.journal_dir else None)
        self._journal_fold = None
        if self._journal is not None:
            # one read at construction serves both consumers: the id
            # reservation here (a submit() BEFORE recover() must never
            # reuse a journaled id — a collision would poison the
            # replay dedupe: a new request's 'completed' record would
            # mark the old one done) and recover()'s replay fold,
            # which consumes and releases it.  Records this engine
            # appends after construction never matter to either — its
            # own requests live in self._all and recover() skips them.
            # read the DIR, not just the active file: a predecessor may
            # have rotated, leaving history in the archive/segments
            pending, completed, shed = replay_state(
                read_journal(self._journal.dir))
            # keep only what recover() needs: the pending records
            # (bounded by outstanding work, not history) and the
            # terminal ID sets — never the terminal bodies (full token
            # payloads) for the lifetime of an engine that may never
            # call recover()
            self._journal_fold = (pending, set(completed), set(shed))
            known = [rid for part in self._journal_fold for rid in part]
            if known:
                self._next_id = max(known) + 1
        self._recovered: Optional[Dict[str, List[int]]] = None
        # recovery progress across recover() RETRIES (a mid-loop
        # journal error leaves the attempt partial): ids the replay
        # loop already enqueued / already shed, so the attempt that
        # finally succeeds reports the full recovery, not its own slice
        self._replay_enqueued: set = set()
        self._replay_shed: set = set()
        self._shed_ids: List[int] = []
        self._preempted_ids: List[int] = []
        # liveness heartbeat for the /healthz serve check: stamped at
        # the end of every engine iteration; _running marks a live
        # run() loop (a paused caller between phases is not a hang)
        self._t_heartbeat = time.monotonic()
        self._running = False
        self._agg = self._fresh_agg()
        self._evict_base = 0                 # pool.evictions at window start
        # telemetry session (docs/observability.md): queue/KV-pool
        # gauges on the HTTP endpoint + TTFT/inter-token histograms.
        # Off by default; never touches the token path.
        self._obs = None
        if getattr(config, "obs", None) is not None and config.obs.enabled:
            from torchacc_tpu.obs.runtime import ServeObs
            self._obs = ServeObs(self, config.obs)

    @staticmethod
    def _fresh_agg() -> Dict:
        return {"ttft": [], "waits": [], "gaps": [], "tokens": 0,
                "requests": 0, "t0": None, "t1": None,
                "prefix_hits": 0, "cached_tokens": 0, "shared_blocks": 0,
                "cow": 0, "deadline_total": 0, "deadline_miss": 0,
                "shed": 0, "preempted": 0}

    def _mesh_ctx(self):
        import contextlib
        import jax
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.sharding.set_mesh(self.mesh)

    # -- live weights (train -> serve handoff) ------------------------------

    @classmethod
    def from_train_state(cls, trainer, config: Optional[Config] = None, *,
                         dtype: Any = "auto", donate: bool = False,
                         metrics_dir: Optional[str] = None) -> "ServeEngine":
        """Engine over a live ``Trainer``'s weights — the in-memory
        train→serve handoff (docs/serving.md "Live weight handoff").

        ``trainer.serving_params()`` reshards ``state.params`` from the
        train layout (fsdp/tp) into the decode layout through the
        compiled layout-transfer engine (parallel/transfer.py) — no
        checkpoint I/O anywhere on this path; the transfer program
        compiles once per layout pair, so alternating fit()/serve
        phases pay collective time only after the first handoff.
        ``donate=True`` is the terminal handoff (the trainer's state is
        relinquished — see ``Trainer.serving_params``)."""
        config = config or trainer.config
        # validate BEFORE the handoff: a donating handoff relinquishes
        # the training state, and a bad ServeConfig must fail while the
        # state is still intact — not after the buffers are gone
        config.serve.validate()
        params = trainer.serving_params(dtype=dtype, donate=donate)
        return cls(trainer.model, params, config,
                   mesh=trainer.mesh, metrics_dir=metrics_dir)

    def load_params(self, params) -> None:
        """Swap the live weights in place — NO pool reallocation, no
        scheduler rebuild: the paged KV pools, block tables, decode
        carry and every compiled program survive (the params operand is
        traced by shape/dtype, which the handoff preserves).  The
        fit→serve→fit loop hands each new phase's weights here.

        Requires an idle engine (queued-but-unadmitted requests are
        fine): a weight swap under sequences mid-decode would splice
        two models' logits into one stream, so occupied decode slots
        raise instead.  In-flight ring entries are resolved first —
        they were computed under the old weights and their tokens are
        still valid.

        The prefix cache is FLUSHED before the swap: cached blocks hold
        k/v computed under the old weights, and a prefix hit after the
        handoff would splice stale keys/values under every new-weight
        decode step — a correctness bug, not a perf detail
        (regression-tested: a post-handoff warm-prefix request is
        token-identical to a cold one).  ``from_train_state`` builds a
        fresh engine, so its cache starts empty by construction."""
        self.scheduler.drain()
        self._drain_events()
        if self.scheduler.busy():
            # the ring is drained, so busy == sequences occupy slots
            busy = [s.sid for s in self.scheduler.slot_seq if s is not None]
            raise RuntimeError(
                f"cannot swap weights while sequences {busy} occupy "
                f"decode slots — run() the engine to completion (or let "
                f"them finish) first")
        flushed = self.scheduler.flush_prefix_cache()
        if flushed:
            logger.info(
                f"prefix cache flushed on weight swap ({flushed} cached "
                f"blocks dropped: k/v banked under the old weights must "
                f"never serve the new ones)")
        self.scheduler.params = params

    # -- submission ---------------------------------------------------------

    def submit(self, req: Request, on_token=None) -> int:
        """Queue a request; returns its id.  Raises when the request
        can NEVER be served (pool too small, position table exceeded)
        or the queue is full — fail at the front door, not mid-decode.

        ``on_token``: optional ``f(token: int, t_monotonic: float)``
        streaming callback, invoked as the lagged ring resolves each
        token (<= ``decode_depth - 1`` iterations after dispatch; never
        a post-finish garbage token).  Runs inside the engine loop —
        keep it cheap, hand off to a queue/socket for real delivery.

        With ``serve.journal_dir`` set, the accepted request is
        journaled (durably, before this returns) so a process death
        never loses it: a restarted engine's :meth:`recover` re-admits
        it under the same id."""
        serve = self.config.serve
        seq = self._build_seq(req, self._next_id, on_token)
        if len(self._queue) >= serve.max_queue:
            raise RuntimeError(
                f"admission queue full ({serve.max_queue}); shed load "
                f"upstream or raise serve.max_queue")
        seq.t_submit = time.monotonic()
        seq.step_submit = self.scheduler._step_idx
        if req.deadline_s is not None:
            seq.deadline = seq.t_submit + req.deadline_s
        # the id is BURNED from here on, even if the journal append
        # fails: a raise from fsync does not prove the line missed the
        # disk, and reusing the id for a different request would let
        # the phantom 'accepted' record hijack it on replay
        # (replay_state keeps the FIRST accepted record per id)
        self._next_id += 1
        if self._journal is not None:
            # journal BEFORE the engine takes the request: a failed
            # append (disk full) raises with nothing enqueued — the
            # engine never serves a request that has no accepted
            # record, and the caller's retry cannot double-serve.
            # seq.max_new is _build_seq's resolution — the journal
            # must record what will actually be SERVED, or a replay
            # diverges from the original run
            self._journal.accepted(
                rid=seq.sid, trace_id=seq.trace_id,
                prompt_ids=req.prompt_ids, max_new_tokens=seq.max_new,
                temperature=req.temperature, top_k=req.top_k,
                top_p=req.top_p, eos_id=req.eos_id, seed=req.seed,
                priority=req.priority,
                deadline_unix=(None if req.deadline_s is None
                               else time.time() + req.deadline_s))
        self._all[seq.sid] = seq
        self._queue.append(seq)
        counters.inc("serve_requests_submitted")
        return seq.sid

    def _build_seq(self, req: Request, rid: int, on_token) -> Sequence:
        """Validate a request and build its scheduler ``Sequence``
        (shared by :meth:`submit` and journal replay — one home for the
        front-door rules)."""
        prompt = np.asarray(list(req.prompt_ids), np.int32)
        if prompt.ndim != 1 or prompt.shape[0] < 1:
            raise ValueError("prompt_ids must be a non-empty 1-D sequence")
        max_new = (req.max_new_tokens
                   if req.max_new_tokens is not None
                   else self.config.serve.max_new_tokens)
        if max_new < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new} (a decode "
                f"slot always generates at least one token)")
        if req.deadline_s is not None and req.deadline_s <= 0:
            raise ValueError(
                f"deadline_s must be > 0 seconds from submit, got "
                f"{req.deadline_s}")
        # trace id: pid x process-global sequence — unique across
        # processes AND across co-located engines in one process
        trace_id = (req.trace_id if req.trace_id
                    else f"{os.getpid():x}-{next(_trace_seq):x}")
        seq = Sequence(sid=rid, prompt=prompt, max_new=max_new,
                       temperature=req.temperature, top_k=req.top_k,
                       top_p=req.top_p, eos_id=req.eos_id, seed=req.seed,
                       priority=req.priority, on_token=on_token,
                       trace_id=trace_id)
        need = self.scheduler.blocks_for(seq)
        if need > self.scheduler.max_blocks_per_seq:
            raise ValueError(
                f"request needs {need} KV blocks (prompt "
                f"{prompt.shape[0]} + max_new {max_new}) but a sequence "
                f"may own at most {self.scheduler.max_blocks_per_seq} "
                f"(min of pool size serve.num_blocks - 1 and the model's "
                f"position reach max_seq_len); raise serve.num_blocks / "
                f"the model max_seq_len or lower max_new_tokens")
        total = prompt.shape[0] + max_new
        if self.cfg.pos_emb == "learned" and total > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt + max_new_tokens = {total} exceeds the learned "
                f"position table max_seq_len {self.cfg.max_seq_len}")
        return seq

    # -- journal replay ------------------------------------------------------

    def recover(self) -> Dict[str, List[int]]:
        """Re-admit every journaled-but-unfinished request after a
        restart (docs/serving.md "Serving under the supervisor").

        Idempotent: completed/shed ids are deduped (never served
        twice), replayed requests keep their ORIGINAL ids (the id a
        dead incarnation returned to its caller stays valid), and a
        second call is a no-op.  Greedy replays are token-identical by
        construction (same prompt, params, seed); the prefix cache —
        if enabled — re-warms as the replays prefill.  A pending
        request whose ABSOLUTE deadline passed while the process was
        down is shed with a typed result when ``serve.shed_deadlines``
        is on (otherwise it replays and counts as a deadline miss,
        exactly as if it had been served late in one life).

        Returns ``{"replayed": [...], "completed": [...],
        "shed": [...], "shed_on_recovery": [...]}`` (ids).  No journal
        configured -> all empty."""
        if self._journal is None:
            return {"replayed": [], "completed": [], "shed": [],
                    "shed_on_recovery": []}
        if self._recovered is not None:
            return self._recovered
        pending, completed, shed = self._journal_fold
        replayed: List[int] = []
        shed_now: List[int] = []
        now_wall = time.time()
        now_mono = time.monotonic()
        for rid in sorted(pending):
            if rid in self._all:
                # already live: either a PREVIOUS recover() attempt
                # enqueued/shed it before raising (report it — the
                # successful attempt must describe the whole recovery)
                # or this engine accepted it itself (submit() raced
                # ahead of recover(); not a replay)
                if rid in self._replay_enqueued:
                    replayed.append(rid)
                elif rid in self._replay_shed:
                    shed_now.append(rid)
                continue
            rec = pending[rid]
            req = Request(
                prompt_ids=rec["prompt_ids"],
                max_new_tokens=rec.get("max_new_tokens"),
                temperature=rec.get("temperature", 0.0),
                top_k=rec.get("top_k", 0), top_p=rec.get("top_p", 1.0),
                eos_id=rec.get("eos_id"), seed=rec.get("seed", 0),
                priority=rec.get("priority", 0),
                trace_id=rec.get("trace_id") or None)
            try:
                seq = self._build_seq(req, rid, None)
            except (ValueError, RuntimeError) as e:
                # a journaled request this engine can no longer serve
                # (shrunken pool, changed model) is accounted, loudly —
                # never silently dropped.  A stub finished Sequence
                # keeps the result() contract: the caller holding the
                # original id gets the same typed shed result a
                # deadline shed produces, not a KeyError.
                stub = Sequence(
                    sid=rid,
                    prompt=np.asarray(rec.get("prompt_ids") or [],
                                      np.int32),
                    max_new=int(rec.get("max_new_tokens") or 0),
                    trace_id=rec.get("trace_id") or "")
                stub.t_submit = stub.t_admit = now_mono
                stub.t_first_dispatch = stub.t_first_token = now_mono
                # shed (journal-first) BEFORE registering the stub: a
                # failed append leaves no half-shed record for a
                # recover() retry to skip over
                self._shed(stub, f"unservable-after-restart: {e}")
                self._all[rid] = stub
                self._replay_shed.add(rid)
                shed_now.append(rid)
                continue
            # re-anchor the wall-clock deadline onto this process's
            # monotonic clock; queue-wait/TTFT metrics restart at
            # recovery (the dead incarnation's wall time is not
            # observable here — the journal's t_accept is, for audits)
            seq.t_submit = now_mono
            seq.step_submit = self.scheduler._step_idx
            dl = rec.get("deadline_unix")
            if dl is not None:
                seq.deadline = now_mono + (float(dl) - now_wall)
            self._all[seq.sid] = seq
            self._queue.append(seq)
            self._replay_enqueued.add(rid)
            replayed.append(rid)
        if replayed or shed_now:
            logger.warning(
                f"request journal replay: {len(replayed)} request(s) "
                f"re-admitted ({len(completed)} already completed, "
                f"{len(shed)} already shed, {len(shed_now)} shed on "
                f"recovery) from {self._journal.path}")
        # expired deadlines among the replays shed immediately (typed,
        # journaled) instead of waiting for the first step()'s sweep —
        # and they report under shed_on_recovery, not replayed: a
        # consumer resubmitting/accounting off this dict must see them
        # as dropped, not as about-to-be-served
        self._shed_expired()
        still_live = []
        for rid in replayed:
            if self._all[rid].finish_reason == "shed":
                shed_now.append(rid)
            else:
                still_live.append(rid)
        # counted AFTER the expiry sweep so the counter always agrees
        # with the returned "replayed" list (an expired replay is a
        # shed, not a replay)
        counters.inc("serve_requests_replayed", len(still_live))
        self._recovered = {
            "replayed": still_live, "completed": sorted(completed),
            "shed": sorted(shed), "shed_on_recovery": sorted(shed_now),
        }
        # released only on success: a recover() that raised mid-loop
        # (journal disk error while shedding) must stay retryable —
        # the already-enqueued prefix is skipped via the self._all
        # guard above, the remainder replays on the retry
        self._journal_fold = None
        return self._recovered

    # -- deadline shedding ---------------------------------------------------

    def _shed_record(self, rid: int, reason: str) -> None:
        """Journal + count one shed (no Sequence state to finish).
        Journal-first, like submit(): a failed append (disk full)
        raises with NOTHING recorded, so the shed stays retryable and
        the engine never accounts a shed the journal does not have."""
        if self._journal is not None:
            self._journal.shed(rid=rid, reason=reason)
        self._shed_ids.append(rid)
        counters.inc("serve_requests_shed")

    def _shed(self, seq: Sequence, reason: str) -> None:
        """Typed shed result for a QUEUED sequence: finished with
        ``finish_reason='shed'``, zero tokens, deadline_met False —
        counted and journaled, never a silent timeout.  The journal
        append comes FIRST (via _shed_record): if it raises, the
        sequence is untouched and the shed retries cleanly."""
        self._shed_record(seq.sid, reason)
        seq.finished = True
        seq.finish_reason = "shed"
        seq.t_finish = time.monotonic()
        self._agg["shed"] = self._agg.get("shed", 0) + 1
        logger.warning(f"serve: shed request {seq.sid} ({reason})")

    def _shed_expired(self) -> None:
        """Shed every queued request whose deadline has provably
        passed (``serve.shed_deadlines``): it still needs >= 1 decode
        step, so no schedule can meet it — the one case shedding never
        second-guesses a recovery.  In-flight sequences are never shed
        (the whole-reservation guarantee: an admitted request always
        finishes) — relaxing THAT is the separate
        ``serve.preempt_deadlines`` opt-in (:meth:`_preempt_expired`)."""
        if not self.config.serve.shed_deadlines or not self._queue:
            return
        now = time.monotonic()
        expired = [s for s in self._queue
                   if s.deadline != float("inf") and now >= s.deadline]
        for seq in expired:
            # shed first (journal-first append may raise), THEN drop
            # from the queue — a failed append must never leave a
            # request neither queued nor shed
            self._shed(seq, "deadline-unmeetable"
                            + (" (drain)" if self._draining else ""))
            self._queue.remove(seq)

    def _preempt_expired(self) -> None:
        """Opt-in ``serve.preempt_deadlines`` (ROADMAP 3(d)): evict an
        ADMITTED sequence whose absolute deadline has passed — the one
        deliberate exception to the whole-reservation guarantee.  The
        slot and its KV blocks free immediately (deferred-release
        machinery makes mid-ring eviction safe), the request finishes
        with typed ``finish_reason='preempted'`` carrying the partial
        tokens, and :meth:`_drain_events` journals it like a shed so a
        replay never re-serves it.  Never silent: counted
        (``serve_requests_preempted``) and logged."""
        if not self.config.serve.preempt_deadlines:
            return
        now = time.monotonic()
        for seq in self.scheduler.slot_seq:
            if (seq is not None and not seq.finished
                    and seq.deadline != float("inf")
                    and now >= seq.deadline):
                self.scheduler.preempt(seq, now)
                logger.warning(
                    f"serve: preempted in-flight request {seq.sid} "
                    f"(deadline passed; {len(seq.out_tokens)} token(s) "
                    "resolved so far returned as a typed partial)")

    # -- the loop -----------------------------------------------------------

    def _admit(self) -> None:
        """Move queue entries into free slots while headroom lasts.
        'fcfs' preserves arrival order (no request is skipped past);
        'sjf' reorders by prompt length (better mean TTFT under mixed
        lengths); 'priority' orders by effective class then deadline
        (see :meth:`_priority_key`) — both may skip a request that does
        not fit when a later one fits the remaining headroom.
        ``scheduler.admit`` is all-or-nothing with no side effects on
        failure, so attempting it IS the fit check (and the only one
        that sees prefix-cache hits, which shrink the fresh-block
        need)."""
        if self._draining:
            # drain: the queue is frozen — nothing new enters a slot
            return
        if not self._queue or self.scheduler.free_slot() is None:
            # at capacity: don't copy/sort the (possibly thousands
            # deep) queue on the per-token hot loop when nothing can
            # possibly admit
            return
        if self.config.serve.policy == "fcfs":
            # fcfs admits only from the head — stop at the first miss
            while self._queue and self.scheduler.admit(self._queue[0]):
                self._queue.popleft()
                counters.inc("serve_requests_admitted")
            return
        # sjf/priority: one O(Q) min beats the O(Q log Q) sort + scan
        # when even the cheapest BEST-CASE reservation (full prefix
        # hit) cannot fit
        if not self.scheduler.pool.can_alloc(
                min(self.scheduler.min_fresh_blocks(s)
                    for s in self._queue)):
            return
        order = list(self._queue)
        if self.config.serve.policy == "sjf":
            order.sort(key=lambda s: (s.prompt_len, s.sid))
        else:
            # scheduler.priority_key is the ONE home for the effective-
            # class/EDF/aging semantics (prefill ordering uses it too)
            now = time.monotonic()
            aging = self.config.serve.priority_aging_s
            order.sort(key=lambda s: priority_key(s, now, aging))
        admitted = []
        for seq in order:
            if self.scheduler.free_slot() is None:
                break
            if self.scheduler.admit(seq):
                admitted.append(seq)
                counters.inc("serve_requests_admitted")
        for seq in admitted:
            self._queue.remove(seq)

    def step(self) -> bool:
        """One engine iteration (admission + scheduler.step + completion
        accounting).  Returns True while there is work anywhere."""
        with tracing.span("serve/step"):
            return self._step_impl()

    def _step_impl(self) -> bool:
        with tracing.span("serve/sweep"):
            self._shed_expired()
            self._preempt_expired()
        with self._mesh_ctx():
            # admission inside the mesh context too: a fully-cached
            # prompt's admit dispatches the copy-on-write program over
            # the (possibly tp-sharded) pools
            self._admit()
            self.scheduler.step()
        with tracing.span("serve/sweep"):
            self._drain_events()
        # liveness heartbeat (the serve /healthz check): every completed
        # iteration proves the loop is alive; a decode wedged on device
        # blocks INSIDE this method, so the age grows while it hangs
        self._t_heartbeat = time.monotonic()
        # scheduler.busy() == False already implies the ring drained
        # (an empty slot table with entries in flight is impossible:
        # eviction only happens at resolution), so nothing to flush.
        # Draining: queued requests will never admit — only in-flight
        # work counts as "work left"
        if self._draining:
            return self.scheduler.busy()
        return bool(self._queue) or self.scheduler.busy()

    def run(self, max_iters: int = 1_000_000) -> None:
        """Drive until every submitted request completed — or, after a
        preemption signal (SIGTERM) with ``serve.drain_on_preempt``,
        until the in-flight decodes finish (queued requests stay
        unserved and are reported; docs/serving.md "Graceful drain")."""
        watch_preempt = self.config.serve.drain_on_preempt
        if watch_preempt:
            from torchacc_tpu.resilience.preemption import (
                install_preemption_handler,
            )
            install_preemption_handler()
        # re-stamp the heartbeat as the loop STARTS: the liveness age
        # must measure loop progress, not the gap since construction
        # (a long warmup/recover() before run() is not a hang)
        self._t_heartbeat = time.monotonic()
        self._running = True
        try:
            self._run_loop(max_iters, watch_preempt)
        except Exception as e:
            # serve-flavored postmortem through the flight-bundle
            # channel (the supervisor's exit-disposition reader): the
            # bundle rides the abort, never replaces it
            self._emit_disposition(type(e).__name__, err=e)
            raise
        finally:
            self._running = False

    def _run_loop(self, max_iters: int, watch_preempt: bool) -> None:
        idle = 0
        for _ in range(max_iters):
            if watch_preempt and not self._draining:
                from torchacc_tpu.resilience.preemption import (
                    preemption_requested,
                )
                if preemption_requested():
                    self.begin_drain("preemption signal")
            if not self.step():
                if self._draining:
                    self._log_drain_report()
                    self._emit_disposition("preemption")
                return
            # defensive no-progress detection: queued work that can
            # never admit while nothing is running is a config error
            if (self._queue and not self.scheduler.busy()
                    and not self._draining):
                idle += 1
                if idle > 3:
                    raise RuntimeError(
                        "serving stalled: queued requests cannot be "
                        "admitted and no sequence is running (pool "
                        "fragmentation should be impossible — report)")
            else:
                idle = 0
        raise RuntimeError(f"run() exceeded {max_iters} iterations")

    # -- graceful drain ------------------------------------------------------

    def begin_drain(self, reason: str = "") -> None:
        """Stop admission NOW; in-flight decodes run to completion
        (an admitted request always finishes — the whole-reservation
        guarantee), queued requests stay queued and are reported
        unserved.  Idempotent.  The serving-side half of preemption:
        the supervisor's SIGTERM grace window finishes what the users
        are already waiting on, never starts new work."""
        if self._draining:
            return
        self._draining = True
        self._drain_reported = False
        counters.inc("serve_drains")
        logger.warning(
            f"serve engine draining"
            + (f" ({reason})" if reason else "")
            + f": admission stopped with {len(self._queue)} queued, "
            f"{sum(s is not None for s in self.scheduler.slot_seq)} "
            "in flight — in-flight decodes will finish")

    @property
    def draining(self) -> bool:
        return self._draining

    def unserved_ids(self) -> List[int]:
        """Request ids admitted to the QUEUE but never to a decode
        slot (drain report; empty while not draining unless callers
        inspect mid-flight)."""
        return [s.sid for s in self._queue]

    def drain_report(self) -> Dict[str, Any]:
        """The machine-readable drain summary a supervisor (or the
        operator restarting the pod) consumes: what finished, what
        never started — resubmit the unserved ids elsewhere."""
        return {
            "draining": self._draining,
            "completed": self._completed,
            "in_flight": sorted(
                s.sid for s in self.scheduler.slot_seq if s is not None),
            "unserved": self.unserved_ids(),
            "shed": list(self._shed_ids),
            "preempted": list(self._preempted_ids),
            "journal": (self._journal.path if self._journal is not None
                        else None),
        }

    def _emit_disposition(self, reason: str,
                          err: Optional[BaseException] = None
                          ) -> Optional[str]:
        """Write the serve-flavored ``exit_disposition`` flight bundle
        the supervisor's reader consumes (supervisor/policy.py): what
        finished, what is still in flight, what was never admitted,
        what was shed, and where the journal lives — the serving
        equivalent of the trainer's resumable-tiers block.  No-op
        unless the flight recorder is armed and a dump dir is known
        (``obs.flight_dir``, else the journal dir)."""
        obs = getattr(self.config, "obs", None)
        if obs is None or not obs.enabled or not obs.flight_recorder:
            return None
        d = obs.flight_dir or (self._journal.dir
                               if self._journal is not None else None)
        if not d:
            return None
        from torchacc_tpu.obs import flight
        from torchacc_tpu.resilience.coordination import (
            process_count,
            process_index,
        )
        report = self.drain_report()
        disposition = {
            "reason": reason,
            "error_type": type(err).__name__ if err is not None else None,
            "flagged_step": None,
            "hosts": [],
            "resumable": {},
            "quarantine": {},
            "quarantine_delta": [],
            "preempted": reason == "preemption",
            "process_index": process_index(),
            "world_size": process_count(),
            "serve": report,
        }
        return flight.recorder.dump(
            reason, error=err, dump_dir=d,
            filename=f"flight_serve_{os.getpid()}.json",
            extra={"serve": report},
            disposition=disposition)

    def _log_drain_report(self) -> None:
        if self._drain_reported:
            return
        self._drain_reported = True
        r = self.drain_report()
        counters.inc("serve_requests_unserved", len(r["unserved"]))
        logger.warning(
            f"serve drain complete: {r['completed']} request(s) "
            f"finished, {len(r['unserved'])} never admitted "
            f"(unserved ids: {r['unserved']}) — resubmit them on the "
            "replacement pod")

    def generate(self, requests: List[Request]) -> List[RequestResult]:
        """Convenience batch API: submit everything, run to completion,
        return results in submission order."""
        ids = [self.submit(r) for r in requests]
        self.run()
        return [self.result(i) for i in ids]

    def stream(self, request_id: int):
        """Yield request ``request_id``'s tokens as the lagged decode
        ring resolves them, driving the engine loop in between (every
        other queued/running request progresses too — interleave
        multiple ``stream()`` generators or mix with :meth:`step` at
        will).  Each token surfaces at most ``decode_depth - 1`` engine
        iterations after the device produced it — the documented
        readback lag; resolution timestamps feed the same TTFT /
        per-token-gap SLO metrics as non-streamed requests.  Returns
        when the request finishes; its :class:`RequestResult` stays
        available via :meth:`result`.  For push-style delivery use
        ``submit(req, on_token=...)`` instead."""
        seq = self._all[request_id]
        sent = 0
        idle = 0
        while True:
            if sent < len(seq.out_tokens):
                yield seq.out_tokens[sent]
                sent += 1
                continue
            if seq.finished:
                return
            if not self.step():
                raise RuntimeError(
                    f"request {request_id} streamed {sent} tokens but "
                    f"the engine ran out of work before it finished")
            # mirror run()'s no-progress defense: queued work that can
            # never admit while nothing runs is a config error, not a
            # reason to spin forever
            if self._queue and not self.scheduler.busy():
                idle += 1
                if idle > 3:
                    raise RuntimeError(
                        "serving stalled: queued requests cannot be "
                        "admitted and no sequence is running (pool "
                        "fragmentation should be impossible — report)")
            else:
                idle = 0

    # -- results / metrics --------------------------------------------------

    def _drain_events(self) -> None:
        """Account every sequence the scheduler finished since the last
        drain — O(newly finished), never a scan over every request the
        engine has ever served."""
        fin = self.scheduler.finished
        while fin:
            seq = fin.pop()
            if seq.finish_reason == "preempted":
                # deadline preemption terminal: journaled as a shed
                # (same dedupe semantics — replay must never re-serve
                # it), counted separately, partial tokens readable via
                # result() with finish_reason='preempted'
                if self._journal is not None:
                    self._journal.shed(rid=seq.sid, reason="preempted")
                self._preempted_ids.append(seq.sid)
                counters.inc("serve_requests_preempted")
                a = self._agg
                a["preempted"] = a.get("preempted", 0) + 1
                a["deadline_total"] += 1
                a["deadline_miss"] += 1
                if self._obs is not None and seq.out_tokens:
                    # zero-token preempts have no real TTFT — keep the
                    # latency histograms clean of clamped zeros
                    self._obs.on_request_done(seq)
                continue
            self._completed += 1
            counters.inc("serve_requests_completed")
            counters.inc("serve_tokens_generated", len(seq.out_tokens))
            if self._journal is not None:
                # the completion record is the replay dedupe key: once
                # it is durable, no restart ever serves this id again
                self._journal.completed(rid=seq.sid,
                                        tokens=seq.out_tokens,
                                        finish_reason=seq.finish_reason)
            # SLO aggregates accumulate HERE, at completion — stats()
            # stays correct for long-running servers that pop/discard
            # results to bound memory (the aggregate sample lists grow
            # with completed tokens; reset_stats() starts a fresh
            # window)
            a = self._agg
            a["requests"] += 1
            a["tokens"] += len(seq.out_tokens)
            a["ttft"].append(seq.ttft_s)
            a["waits"].append(seq.queue_s)
            a["gaps"].extend(b - x for x, b in
                             zip(seq.token_times, seq.token_times[1:]))
            a["t0"] = (seq.t_submit if a["t0"] is None
                       else min(a["t0"], seq.t_submit))
            a["t1"] = (seq.t_finish if a["t1"] is None
                       else max(a["t1"], seq.t_finish))
            a["prefix_hits"] += 1 if seq.cached_tokens else 0
            a["cached_tokens"] += seq.cached_tokens
            a["shared_blocks"] += seq.shared_blocks
            a["cow"] += 1 if seq.cow else 0
            if seq.deadline != float("inf"):
                a["deadline_total"] += 1
                a["deadline_miss"] += (1 if seq.t_finish > seq.deadline
                                       else 0)
            if self._obs is not None:
                self._obs.on_request_done(seq)
            if self._metrics is not None:
                r = self.result(seq.sid)
                rec = {
                    "serve/ttft_s": r.ttft_s,
                    "serve/queue_wait_s": r.queue_wait_s,
                    "serve/total_s": r.total_s,
                    "serve/tokens": len(r.tokens),
                    "serve/tokens_per_sec": r.tokens_per_sec,
                    "serve/cached_prompt_tokens": r.cached_prompt_tokens,
                }
                if r.deadline_met is not None:
                    rec["serve/deadline_met"] = float(r.deadline_met)
                self._metrics.log(self._completed, rec)

    def result(self, request_id: int, pop: bool = False) -> RequestResult:
        """The finished request's tokens + SLO metrics.  ``pop=True``
        also releases the engine's record of the request — long-running
        servers must pop (or call :meth:`discard`) or completed-request
        state accumulates for the process lifetime."""
        seq = self._all[request_id]
        if not seq.finished:
            raise RuntimeError(f"request {request_id} not finished")
        gaps = [b - a for a, b in zip(seq.token_times, seq.token_times[1:])]
        total = max(seq.t_finish - seq.t_submit, 1e-9)
        r = RequestResult(
            request_id=request_id,
            prompt_ids=[int(t) for t in seq.prompt],
            tokens=list(seq.out_tokens),
            finish_reason=seq.finish_reason,
            queue_wait_s=seq.queue_s,
            ttft_s=seq.ttft_s,
            total_s=total,
            token_latencies_s=gaps,
            tokens_per_sec=len(seq.out_tokens) / total,
            cached_prompt_tokens=seq.cached_tokens,
            deadline_met=(None if seq.deadline == float("inf")
                          else bool(seq.t_finish <= seq.deadline)),
            trace_id=seq.trace_id,
            prefill_s=seq.prefill_s,
            first_token_lag_s=seq.first_token_lag_s,
            queue_steps=seq.queue_steps,
            wait_steps=seq.wait_steps,
            prefill_programs=seq.prefill_programs,
        )
        if pop:
            del self._all[request_id]
        return r

    def discard(self, request_id: int) -> None:
        """Drop a finished request's record without building the
        result (the pop=False counterpart for fire-and-forget calls)."""
        seq = self._all[request_id]
        if not seq.finished:
            raise RuntimeError(f"request {request_id} not finished")
        del self._all[request_id]

    def stats(self) -> Dict[str, float]:
        """Aggregate SLO view over every request completed since the
        engine started (or the last :meth:`reset_stats`) — the
        ``make serve-smoke`` / bench --serve payload.  Accumulated at
        completion time, so popping/discarding results (the documented
        long-running-server hygiene) never shrinks the aggregates."""
        a = self._agg
        if not a["requests"]:
            # a shed-only window (deadline storm, recovery sweep) is
            # exactly what shedding exists to make visible — never
            # collapse it to "nothing happened"
            return {"requests": 0, "shed": a.get("shed", 0),
                    "preempted": a.get("preempted", 0)}
        pool = self.scheduler.pool
        return {
            "requests": a["requests"],
            "tokens": a["tokens"],
            "tokens_per_sec": a["tokens"] / max(a["t1"] - a["t0"], 1e-9),
            # host time spent blocked on token readback since engine
            # construction / reset_stats — collapses toward transfer
            # cost alone when decode_depth > 1 (the lagged ring reads
            # completed values)
            "host_blocked_ms": self.blocked.peek_ms(),
            "ttft_s_p50": _percentile(a["ttft"], 50),
            "ttft_s_p95": _percentile(a["ttft"], 95),
            "queue_wait_s_p50": _percentile(a["waits"], 50),
            "queue_wait_s_p95": _percentile(a["waits"], 95),
            "per_token_s_p50": _percentile(a["gaps"], 50),
            "per_token_s_p95": _percentile(a["gaps"], 95),
            # prefix cache (docs/serving.md "Prefix cache"): all window
            # counts accrue at request COMPLETION except evictions
            # (pool lifetime delta since the window opened)
            "prefix_hits": a["prefix_hits"],
            "prefix_hit_rate": a["prefix_hits"] / a["requests"],
            "prefill_tokens_saved": a["cached_tokens"],
            "prefix_blocks_reused": a["shared_blocks"],
            "cow_copies": a["cow"],
            "prefix_evictions": pool.evictions - self._evict_base,
            "prefix_cached_blocks": pool.cached,
            # 'priority' policy deadline accounting (requests that set
            # deadline_s; misses finished after their deadline)
            "deadline_requests": a["deadline_total"],
            "deadline_misses": a["deadline_miss"],
            # deadline shedding (serve.shed_deadlines): queued requests
            # dropped with a typed result because their deadline had
            # provably passed (this stats window)
            "shed": a.get("shed", 0),
            # deadline preemption (serve.preempt_deadlines): admitted
            # sequences evicted mid-decode with a typed partial result
            "preempted": a.get("preempted", 0),
            # blocks in use now by kind of layer (a model that mixes
            # windowed and full latent layers also says how many window
            # blocks went back as windows passed; serve/kv_cache.py)
            **self.scheduler.blocks_by_kind(),
        }

    def admission_snapshot(self) -> Dict[str, Any]:
        """The strict-JSON ``/admission`` payload (ServeObs registers
        it on the worker's telemetry endpoint): the instantaneous load
        signal the router tier routes on — queue depth, slot and
        KV-block headroom, TTFT p95, drain state — and ROADMAP 1(c)'s
        autoscaling input in the same place."""
        sched = self.scheduler
        pool = sched.pool
        ttft = self._agg["ttft"]
        return {
            "queue_depth": len(self._queue),
            "slots_busy": sum(s is not None for s in sched.slot_seq),
            "slots_total": len(sched.slot_seq),
            "free_blocks": int(pool.available - pool.cached),
            "cached_blocks": int(pool.cached),
            "blocks_in_use": int(pool.in_use),
            "block_size": int(self.config.serve.block_size),
            "ttft_p95_ms": round(_percentile(ttft, 95) * 1e3, 3),
            "draining": bool(self._draining),
            "completed": int(self._completed),
            "shed": len(self._shed_ids),
            "preempted": len(self._preempted_ids),
            # warm-cache evidence for the router's affinity gate: a
            # replica receiving same-template traffic shows hits here
            "requests": int(self._agg["requests"]),
            "prefix_hits": int(self._agg["prefix_hits"]),
            "pid": os.getpid(),
        }

    def reset_stats(self) -> None:
        """Start a fresh stats() window and zero the blocked-time
        meter — call after warmup so compile waits and warmup requests
        never pollute the reported SLOs (bench.py --serve does)."""
        self._agg = self._fresh_agg()
        self._evict_base = self.scheduler.pool.evictions
        self.blocked.take_ms()

    def close(self) -> None:
        self.scheduler.drain()
        self._drain_events()
        if self._obs is not None:
            self._obs.close()
            self._obs = None
        if self._metrics is not None:
            self._metrics.close()
        if self._journal is not None:
            self._journal.close()
        if self._queue:
            logger.warning(
                f"ServeEngine closed with {len(self._queue)} queued "
                f"requests unserved")
