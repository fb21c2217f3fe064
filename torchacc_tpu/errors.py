"""Typed exception hierarchy for the framework.

The reference raises bare ``RuntimeError``/``ValueError`` from its save
and dist paths (e.g. torchacc/utils/checkpoint.py); a fault-tolerance
layer needs error types a supervisor can branch on — "checkpoint step is
corrupt, fall back" is a different action from "the trainer was asked to
save before init".  Everything derives from :class:`TorchAccTPUError` so
``except TorchAccTPUError`` catches any framework-originated failure
without swallowing genuine bugs (TypeError, AttributeError, ...).

``ConfigError`` (config.py) predates this module and stays where it is;
it is re-exported here so one import site covers the whole hierarchy.
"""

from __future__ import annotations

from typing import Optional

from torchacc_tpu.config import ConfigError  # noqa: F401  (re-export)


class TorchAccTPUError(Exception):
    """Base class for framework-raised errors."""


class CheckpointError(TorchAccTPUError):
    """Checkpoint save/restore failed (I/O, corruption, retry exhausted)."""


class CheckpointNotFoundError(CheckpointError, FileNotFoundError):
    """No (valid) checkpoint exists where one was requested.

    Also a ``FileNotFoundError`` so pre-existing ``except
    FileNotFoundError`` callers of ``CheckpointManager.restore`` keep
    working.
    """


class CheckpointCorruptionError(CheckpointError):
    """A checkpoint step exists but failed integrity validation
    (missing/unparseable manifest, tree-structure digest mismatch, or an
    unreadable array payload)."""


class TopologyMismatchError(CheckpointError):
    """The checkpoint was saved under a different mesh/process topology
    than the one restoring it, and the change is not one elastic resume
    supports (tp/pp/sp/spu/ep reshapes, or a data-parallel reshape with
    ``resilience.elastic_resume`` off).

    Carries the list of differing axes and the human-readable schema
    diff so the operator sees *which* axes changed without decoding an
    orbax traceback."""

    def __init__(self, message: str, *, axes: Optional[list] = None,
                 diff: Optional[list] = None):
        super().__init__(message)
        self.axes = list(axes or [])
        self.diff = list(diff or [])


class StateSchemaError(CheckpointError):
    """The checkpoint's state-tree schema (leaf paths, shapes, dtypes)
    does not match the target state.  Carries a human-readable diff —
    the typed replacement for orbax's structure-mismatch traceback."""

    def __init__(self, message: str, *, diff: Optional[list] = None):
        super().__init__(message)
        self.diff = list(diff or [])


class TrainerStateError(TorchAccTPUError):
    """The Trainer was driven in an invalid order (e.g. ``save()`` before
    ``init()``/``step()``)."""


class DataLoaderError(TorchAccTPUError):
    """The input pipeline failed fatally (batch fetch retries exhausted
    with synchronous fallback disabled or also failing)."""


class BadBatchError(DataLoaderError):
    """Too many consecutive batches failed validation (tree structure,
    shape/dtype drift, non-finite values) — the *source* is broken, not
    one batch.  Individual offenders are skipped, counted
    (``bad_batches_skipped``) and dumped to the quarantine directory;
    this error fires only after ``max_consecutive_bad_batches`` in a
    row.  Carries the last offender's index and reason."""

    def __init__(self, message: str, *, index: Optional[int] = None,
                 reason: Optional[str] = None, consecutive: int = 0):
        super().__init__(message)
        self.index = index
        self.reason = reason
        self.consecutive = consecutive


class ShardCorruptionError(DataLoaderError):
    """A shard fetched from the object store failed integrity
    validation (checksum mismatch against the manifest — a torn/short
    read or bit-rot — or an undecodable payload).  Transient forms are
    retried; a shard that stays corrupt across the retry budget is
    quarantined and skipped.  Carries the source/shard names and the
    reason so the quarantine manifest names the evidence."""

    def __init__(self, message: str, *, source: Optional[str] = None,
                 shard: Optional[str] = None,
                 reason: Optional[str] = None):
        super().__init__(message)
        self.source = source
        self.shard = shard
        self.reason = reason


class DataSourceError(DataLoaderError):
    """A streaming data source exhausted its failure budget (its
    per-source circuit breaker opened): every recent shard fetch
    failed or came back corrupt — the *source* is down, not one shard.
    When other sources survive, the stream sheds this one (re-normalized
    mixture weights) and this error is recorded, not raised; it
    propagates only when no source remains.  Carries the source name
    and the consecutive-failure count."""

    def __init__(self, message: str, *, source: Optional[str] = None,
                 consecutive: int = 0):
        super().__init__(message)
        self.source = source
        self.consecutive = consecutive


class StoreError(TorchAccTPUError):
    """The shared object-store plane (``torchacc_tpu/store/``) failed.

    Base for the write-side and commit-protocol errors; the read side
    keeps raising :class:`ShardCorruptionError` / ``OSError`` so the
    streaming data plane's quarantine classification is unchanged."""


class StoreWriteError(StoreError, OSError):
    """A PUT did not stick: the verify-after-put read-back disagreed
    with the bytes written (a torn/partial upload, or an object store
    that acknowledged a write it lost).  ``OSError`` so the shared
    retry policy treats it as transient — a re-upload usually heals
    it; retries exhausted means the destination is failing writes."""


class StoreCommitError(StoreError):
    """A two-phase commit under ``prefix`` is unusable: the commit
    marker is missing (a torn upload — never valid, by protocol), the
    marker is unparseable, or a payload object disagrees with the
    marker's sha256 manifest (marker-without-verified-payload — the
    quarantine case).  Carries the prefix and whether the damage was
    a missing marker (``torn=True``) or failed verification."""

    def __init__(self, message: str, *, prefix: Optional[str] = None,
                 torn: bool = False):
        super().__init__(message)
        self.prefix = prefix
        self.torn = torn


class CoordinationError(TorchAccTPUError):
    """A cross-host coordination primitive failed or timed out.

    Carries the primitive name and the timeout so an operator can tell a
    dead coordinator ("broadcast timed out") from a logic error without
    re-running.  Raised only in multi-process runs — every primitive is
    an exact no-op when ``jax.process_count() == 1``."""

    def __init__(self, message: str, *, primitive: Optional[str] = None,
                 timeout_s: Optional[float] = None):
        super().__init__(message)
        self.primitive = primitive
        self.timeout_s = timeout_s


class HangError(TorchAccTPUError):
    """A watched section (train step, data fetch) exceeded its deadline.

    The watchdog (resilience/watchdog.py) dumps all-thread stacks and
    increments ``watchdog_stalls`` when the deadline expires; with
    ``resilience.abort_on_hang`` it raises this error so a supervisor
    can restart the job into ``fit(resume='auto')``.  Carries the
    section label, the configured deadline, the observed wait, and the
    stack-dump path (when one was written to disk)."""

    def __init__(self, message: str, *, label: Optional[str] = None,
                 deadline_s: Optional[float] = None,
                 waited_s: Optional[float] = None,
                 dump_path: Optional[str] = None):
        super().__init__(message)
        self.label = label
        self.deadline_s = deadline_s
        self.waited_s = waited_s
        self.dump_path = dump_path


class SDCError(TorchAccTPUError):
    """Confirmed silent data corruption (resilience/sdc.py): a DP
    replica's gradient digest disagrees with its peers (cross-replica
    divergence) or a deterministic re-execution of the same step on the
    same inputs produced different bits (redundant-recompute mismatch).

    Either way the arithmetic — not the software — is suspect ("Cores
    that don't count", Hochschild et al.).  Carries the step, the kind
    (``'replica'`` | ``'recompute'``), the suspect host id(s) so a
    supervisor can restart excluding them (elastic resume handles the
    smaller world), and the per-leaf first-divergence report."""

    def __init__(self, message: str, *, step: Optional[int] = None,
                 kind: Optional[str] = None, hosts: Optional[list] = None,
                 report: Optional[list] = None):
        super().__init__(message)
        self.step = step
        self.kind = kind
        self.hosts = list(hosts or [])
        self.report = list(report or [])


class QuarantinedHostError(TorchAccTPUError):
    """The restarted pod still contains a host recorded in
    ``sdc_quarantine.json`` and ``resilience.refuse_quarantined`` is on.
    A quarantined chip re-entering the pod silently re-arms the exact
    failure mode the quarantine exists to end; the enforcing error
    carries the offending host id(s) so the supervisor can reschedule
    excluding them (elastic resume handles the smaller world)."""

    def __init__(self, message: str, *, hosts: Optional[list] = None,
                 quarantine_file: Optional[str] = None):
        super().__init__(message)
        self.hosts = list(hosts or [])
        self.quarantine_file = quarantine_file


class AnomalyError(TorchAccTPUError):
    """Too many consecutive anomalous steps — the run is diverging, not
    glitching.  Carries a diagnosis so the operator sees *what* tripped
    (non-finite loss vs gradient-norm spike) without re-running."""

    def __init__(self, message: str, *, step: Optional[int] = None,
                 kind: Optional[str] = None, consecutive: int = 0,
                 loss: Optional[float] = None,
                 grad_norm: Optional[float] = None):
        super().__init__(message)
        self.step = step
        self.kind = kind
        self.consecutive = consecutive
        self.loss = loss
        self.grad_norm = grad_norm
