"""Obs wiring: per-fit and per-engine sessions over the global seams.

The tracer/histogram/flight/server modules are process-global (like
``utils.metrics.counters``); what is NOT global is who publishes into
them.  :class:`FitObs` is one training run's publication session —
``Trainer.fit`` opens it when ``config.obs.enabled``, it registers the
trainer's gauges and health providers, feeds the step histograms and
the flight recorder, and unregisters everything on close so a finished
fit stops answering for a process that may go on to serve.
:class:`ServeObs` is the serving engine's equivalent.

Health policy (the ``/healthz`` the future supervisor consumes):

- ``watchdog_heartbeat``: heartbeat age > ``health_degraded_heartbeat_s``
  -> degraded, > ``health_unhealthy_heartbeat_s`` -> unhealthy (no
  watchdog armed -> ok; liveness is then unknown, not bad).
- ``guard_anomalies``: any consecutive anomalous steps -> degraded;
  at ``max_consecutive_anomalies`` (the abort threshold) -> unhealthy.
- ``sdc``: this host quarantined in the run dir -> unhealthy; any host
  quarantined or any ``sdc_mismatches`` counted -> degraded.
"""

from __future__ import annotations

from typing import Optional

from torchacc_tpu.obs import flight, hist, server, tracing


def apply_config(obs_cfg, run_dir: Optional[str] = None,
                 flight_owner: bool = False) -> None:
    """Apply an ``ObsConfig`` to the global seams.  Only acts when the
    config is enabled — a default-config constructor must never switch
    off a session someone else enabled.  Use :func:`shutdown_all` for
    an explicit teardown.

    ``flight_owner``: this session owns the flight recorder's dump dir
    — it is SET (possibly to None, honestly triggering the no-dump-dir
    warning on abort) rather than left over from a previous fit whose
    run dir would misfile this run's postmortem.  Only the fit session
    passes True; a serving engine never repoints the recorder."""
    if obs_cfg is None or not obs_cfg.enabled:
        return
    tracing.configure(enabled=obs_cfg.trace,
                      buffer_size=obs_cfg.trace_buffer)
    hist.configure(enabled=True)
    if obs_cfg.flight_recorder:
        if flight_owner:
            # taking ownership starts a fresh timeline: the previous
            # run's step records / counter baseline / context must not
            # dominate THIS run's postmortem bundle (the abort dumped
            # its own bundle already; history lives in metrics.jsonl)
            flight.recorder.clear()
        flight.recorder.configure(capacity=obs_cfg.flight_capacity)
        if flight_owner:
            flight.recorder.dump_dir = obs_cfg.flight_dir or run_dir
    if obs_cfg.http_port is not None:
        try:
            server.start(port=obs_cfg.http_port, host=obs_cfg.http_host)
        except OSError as e:
            # telemetry must never replace the run it observes: a busy
            # port (stale scraper, unreaped previous run) degrades to
            # no-endpoint, it does not abort training/serving
            from torchacc_tpu.utils.logger import logger
            logger.warning(
                f"telemetry server could not bind "
                f"{obs_cfg.http_host}:{obs_cfg.http_port} ({e}); "
                "continuing WITHOUT the /metrics//healthz endpoint")


def shutdown_all() -> None:
    """Disable every global obs seam and stop the server (tests /
    explicit process teardown; nothing in the framework calls this
    implicitly)."""
    tracing.configure(enabled=False)
    hist.configure(enabled=False)
    server.stop()
    server.clear_registries()


class FitObs:
    """One training run's telemetry session (see module docstring)."""

    def __init__(self, trainer, obs_cfg, run_dir: Optional[str] = None):
        self.trainer = trainer
        self.cfg = obs_cfg
        self.run_dir = run_dir
        apply_config(obs_cfg, run_dir, flight_owner=True)
        if obs_cfg.flight_recorder:
            flight.recorder.set_context(
                "config", trainer.config.to_dict())
            flight.recorder.set_context("run_dir", run_dir)
        # goodput/badput wall-clock ledger (obs/goodput.py): the fit
        # loop laps into it (trainer._fit_inner), counters publish on
        # every record, and the summary rides flight bundles + /fleet.
        # Host-side only; obs.goodput=False leaves it None and every
        # hook a no-op.
        self.goodput = None
        if getattr(obs_cfg, "goodput", True):
            from torchacc_tpu.obs.goodput import GoodputLedger
            self.goodput = GoodputLedger()
            self.goodput.start()
        t = trainer
        # quarantine baseline at session open: the exit disposition
        # reports the DELTA (hosts quarantined during THIS run) — the
        # field the supervisor's exclusion rule acts on, distinct from
        # hosts an earlier incident already removed
        from torchacc_tpu.resilience.sdc import read_quarantined_hosts
        self._quarantine_at_start = set(read_quarantined_hosts(run_dir))
        # registered callables are remembered so close() removes ONLY
        # them: if a newer session replaced a name (last owner wins),
        # this session's close must not delete the replacement
        self._gauges: dict = {}
        self._checks: dict = {}

        def gauge(name, fn, help=""):
            self._gauges[name] = fn
            server.register_gauge(name, fn, help=help)

        def check(name, fn):
            self._checks[name] = fn
            server.register_health(name, fn)

        gauge("train_inflight_depth", lambda: t.pending,
              help="dispatched-but-unresolved train steps in the ring")
        gauge("train_host_step",
              lambda: -1 if t._host_step is None else t._host_step,
              help="host-side mirror of state.step (-1 before resync)")
        gauge("watchdog_heartbeat_age_s", self._heartbeat_age,
              help="seconds since the fit loop last proved liveness "
                   "(0 when no watchdog is armed)")
        if self.goodput is not None:
            gauge("goodput_fraction", self.goodput.fraction,
                  help="productive step time / wall clock this fit "
                       "(obs/goodput.py bucket definitions)")
        check("watchdog_heartbeat", self._h_heartbeat)
        check("guard_anomalies", self._h_guard)
        check("sdc", self._h_sdc)

    # -- gauge / health providers -------------------------------------------

    def _heartbeat_age(self) -> float:
        wd = getattr(self.trainer, "_watchdog", None)
        return wd.heartbeat_age_s() if wd is not None else 0.0

    def _h_heartbeat(self):
        wd = getattr(self.trainer, "_watchdog", None)
        if wd is None:
            return "ok", None
        age = wd.heartbeat_age_s()
        if age > self.cfg.health_unhealthy_heartbeat_s:
            return "unhealthy", (
                f"no fit-loop heartbeat for {age:.1f}s "
                f"(> {self.cfg.health_unhealthy_heartbeat_s:.1f}s)")
        if age > self.cfg.health_degraded_heartbeat_s:
            return "degraded", (
                f"no fit-loop heartbeat for {age:.1f}s "
                f"(> {self.cfg.health_degraded_heartbeat_s:.1f}s)")
        return "ok", None

    def _h_guard(self):
        mon = getattr(self.trainer, "_guard_monitor", None)
        if mon is None:
            return "ok", None
        consec = mon.consecutive
        limit = self.trainer.config.resilience.max_consecutive_anomalies
        if consec >= limit:
            return "unhealthy", (
                f"{consec} consecutive anomalous steps (abort "
                f"threshold {limit})")
        if consec > 0:
            return "degraded", (
                f"{consec}/{limit} consecutive anomalous steps")
        return "ok", None

    def _h_sdc(self):
        from torchacc_tpu.resilience.coordination import process_index
        from torchacc_tpu.resilience.sdc import read_quarantined_hosts
        from torchacc_tpu.utils.metrics import counters
        q = read_quarantined_hosts(self.run_dir)
        if q:
            if process_index() in q:
                return "unhealthy", (
                    f"THIS host is SDC-quarantined in "
                    f"{self.run_dir}/sdc_quarantine.json")
            return "degraded", f"host(s) {sorted(q)} SDC-quarantined"
        m = counters.get("sdc_mismatches")
        if m:
            return "degraded", f"{m} SDC mismatch(es) this process"
        return "ok", None

    # -- fit hooks -----------------------------------------------------------

    def on_step_time(self, ms: float) -> None:
        hist.observe("step_time_ms", ms)

    def lap(self, bucket: str) -> None:
        """Goodput ledger lap — the trainer's fit loop calls this at
        its phase transitions (no-op when the ledger is off)."""
        if self.goodput is not None:
            self.goodput.lap(bucket)

    def on_record(self, rec: dict) -> None:
        if "host_blocked_ms" in rec:
            hist.observe("host_blocked_ms", rec["host_blocked_ms"])
        if "save_blocked_ms" in rec:
            hist.observe("save_blocked_ms", rec["save_blocked_ms"])
        if self.goodput is not None:
            # the blocked meters overlap the lapped buckets (they run
            # INSIDE step/checkpoint laps) — sub-meters, not buckets
            if "host_blocked_ms" in rec:
                self.goodput.sub_add("host_blocked",
                                     rec["host_blocked_ms"] / 1e3)
            if "save_blocked_ms" in rec:
                self.goodput.sub_add("save_blocked",
                                     rec["save_blocked_ms"] / 1e3)
            # publish per record so any /metrics scrape (incl. the
            # fleet aggregator's last one before this process exits)
            # carries a self-consistent breakdown
            self.goodput.publish()
        if self.cfg.flight_recorder:
            flight.recorder.record_step(rec.get("step", -1), rec)

    def _quarantine_context(self) -> dict:
        from torchacc_tpu.resilience.sdc import read_quarantined_hosts
        ctx = {"quarantine": read_quarantined_hosts(self.run_dir)}
        if self.goodput is not None:
            # the postmortem answers "what fraction of this run was
            # productive, and which badput bucket grew" without a
            # second artefact
            ctx["goodput"] = self.goodput.summary()
        return ctx

    def _disposition(self, reason: str,
                     err: Optional[BaseException] = None,
                     step: Optional[int] = None) -> dict:
        """The strict-JSON ``exit_disposition`` block — the machine
        contract the supervisor's policy engine parses (mirrored by
        ``supervisor.policy.ExitDisposition.from_bundle``): typed
        error, flagged step, newest resumable step per tier, and the
        quarantine delta this run contributed."""
        from torchacc_tpu.resilience.coordination import (
            process_count,
            process_index,
        )
        from torchacc_tpu.resilience.sdc import read_quarantined_hosts
        q = read_quarantined_hosts(self.run_dir)
        tiers_fn = getattr(self.trainer, "resumable_tiers", None)
        tiers = tiers_fn() if callable(tiers_fn) else {}
        flagged = step if step is not None else getattr(err, "step", None)
        return {
            "reason": reason,
            "error_type": type(err).__name__ if err is not None else None,
            "flagged_step": flagged,
            "hosts": list(getattr(err, "hosts", None) or []),
            "resumable": tiers,
            "quarantine": {str(k): v for k, v in q.items()},
            "quarantine_delta": sorted(
                set(q) - self._quarantine_at_start),
            "preempted": reason == "preemption",
            "process_index": process_index(),
            "world_size": process_count(),
        }

    def on_abort(self, err: BaseException) -> Optional[str]:
        """Typed-error exit: write the postmortem bundle (with the
        exit-disposition block the supervisor acts on)."""
        if not self.cfg.flight_recorder:
            return None
        return flight.recorder.dump(
            type(err).__name__, error=err,
            extra=self._quarantine_context(),
            disposition=self._disposition(type(err).__name__, err=err))

    def on_preempt(self, step: int) -> Optional[str]:
        if not self.cfg.flight_recorder:
            return None
        return flight.recorder.dump(
            "preemption", step=step, extra=self._quarantine_context(),
            disposition=self._disposition("preemption", step=step))

    def close(self) -> None:
        if self.goodput is not None:
            # final publish: the tail since the last record (drain,
            # teardown) still lands on /metrics before deregistration
            self.goodput.publish()
        for name, fn in self._gauges.items():
            server.unregister_gauge(name, fn)
        for name, fn in self._checks.items():
            server.unregister_health(name, fn)


class ServeObs:
    """One serving engine's telemetry session: KV-pool/queue gauges +
    the request-latency histograms.  One engine per process publishes
    (a second engine's registration replaces the first — last owner
    wins, documented in docs/observability.md)."""

    def __init__(self, engine, obs_cfg):
        self.cfg = obs_cfg
        self.engine = engine
        apply_config(obs_cfg)
        sched = engine.scheduler
        self._gauges: dict = {}
        self._checks: dict = {}
        self._json: dict = {}

        def gauge(name, fn, help=""):
            self._gauges[name] = fn
            server.register_gauge(name, fn, help=help)

        def check(name, fn):
            self._checks[name] = fn
            server.register_health(name, fn)

        def json_route(path, fn):
            self._json[path] = fn
            server.register_json(path, fn)

        # the router tier's routing signal (and ROADMAP 1(c)'s
        # autoscaling signal): instantaneous queue/slot/KV headroom +
        # TTFT p95 + drain state, strict JSON (docs/serving.md
        # "Router tier")
        json_route("/admission", engine.admission_snapshot)

        # decode-loop liveness (the serve /healthz the supervisor
        # probes): a run() loop with work that has not completed an
        # iteration within the heartbeat thresholds is hung — a wedged
        # device blocks inside engine.step(), so the age grows while
        # the HTTP thread keeps answering
        check("serve_liveness", self._h_liveness)

        gauge("serve_queue_depth", lambda: len(engine._queue),
              help="requests waiting for admission")
        gauge("serve_slots_busy",
              lambda: sum(s is not None for s in sched.slot_seq),
              help="occupied decode slots")
        gauge("serve_ring_depth", lambda: sched.pending,
              help="dispatched-but-unresolved decode iterations")
        gauge("kv_pool_free_blocks",
              lambda: sched.pool.available - sched.pool.cached,
              help="free-list KV blocks (excludes reusable cached ones)")
        gauge("kv_pool_cached_blocks", lambda: sched.pool.cached,
              help="refcount-0 prefix-cached KV blocks (reclaimable)")
        gauge("kv_pool_blocks_in_use", lambda: sched.pool.in_use,
              help="KV blocks held by live sequences")

    def _h_liveness(self):
        """Hung-decode detector: only judges a LIVE ``run()`` loop with
        work pending (an idle engine, or one driven manually between
        phases, is ok — absence of iterations is not a hang there)."""
        import time as _time
        e = self.engine
        if not getattr(e, "_running", False):
            return "ok", None
        has_work = bool(e._queue) or e.scheduler.busy()
        if not has_work:
            return "ok", None
        age = _time.monotonic() - e._t_heartbeat
        if age > self.cfg.health_unhealthy_heartbeat_s:
            return "unhealthy", (
                f"no serve-loop iteration for {age:.1f}s with work "
                f"pending (> {self.cfg.health_unhealthy_heartbeat_s:.1f}s"
                f" — decode loop hung?)")
        if age > self.cfg.health_degraded_heartbeat_s:
            return "degraded", (
                f"no serve-loop iteration for {age:.1f}s with work "
                f"pending (> {self.cfg.health_degraded_heartbeat_s:.1f}s)")
        return "ok", None

    def on_request_done(self, seq) -> None:
        """Feed the latency histograms from a completed scheduler
        ``Sequence`` (called from the engine's completion drain)."""
        hist.observe("serve_ttft_ms", seq.ttft_s * 1e3)
        for a, b in zip(seq.token_times, seq.token_times[1:]):
            hist.observe("serve_token_gap_ms", (b - a) * 1e3)

    def close(self) -> None:
        for name, fn in self._gauges.items():
            server.unregister_gauge(name, fn)
        for name, fn in self._checks.items():
            server.unregister_health(name, fn)
        for path, fn in self._json.items():
            server.unregister_json(path, fn)
