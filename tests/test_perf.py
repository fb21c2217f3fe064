"""Hot-loop desynchronization tests: dispatch pipelining
(``perf.dispatch_depth``), lagged guard/SDC verdicts, host_blocked_ms
accounting, the batched eval fetch, and the SDC digest subsample bound.

The contracts under test (docs/performance.md):

- ``dispatch_depth`` NEVER changes the math: step records (step, loss)
  and final params are bitwise identical at every depth;
- the guard still aborts — within N+k instead of after N — with the
  anomaly attributed to the step that produced it;
- SDC verdicts under lag name the same host and the same step as the
  unpipelined loop, and chaos injections still localize;
- every fit record carries ``host_blocked_ms`` + ``dispatch_depth``.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchacc_tpu as ta
from torchacc_tpu.errors import AnomalyError, SDCError
from torchacc_tpu.models import get_preset
from torchacc_tpu.resilience import ChaosLoader, ChaosPlan, chaos_loss
from torchacc_tpu.train import accelerate
from torchacc_tpu.utils.metrics import BlockedMeter, counters

pytestmark = pytest.mark.perf

CHAOS_SEED = int(os.environ.get("CHAOS_SEED", "0"))


@pytest.fixture(autouse=True)
def _clean_counters():
    counters.reset()
    yield


def _model():
    return get_preset("llama-tiny", vocab_size=64, hidden_size=32,
                      num_layers=1, num_heads=2, num_kv_heads=2,
                      intermediate_size=64, dtype=jnp.float32)


def _batches(n, seed=None):
    rng = np.random.default_rng(CHAOS_SEED if seed is None else seed)
    return [{"input_ids": rng.integers(0, 64, size=(8, 16)).astype(np.int32)}
            for _ in range(n)]


def _trainer(depth=1, dp=None, loss=None, **res_kwargs):
    import optax
    dist = (ta.DistConfig(dp=ta.DPConfig(size=dp)) if dp
            else ta.DistConfig())
    cfg = ta.Config(dist=dist,
                    resilience=ta.ResilienceConfig(**res_kwargs),
                    perf=ta.PerfConfig(dispatch_depth=depth))
    if dp:
        cfg.get_mesh(jax.devices()[:dp])
    tr, _ = accelerate(_model(), None, cfg, optimizer=optax.adam(1e-3),
                       loss=loss)
    return tr


def _det(history):
    """The deterministic projection of a record list."""
    return [(r["step"], r["loss"]) for r in history]


# -- config / units -----------------------------------------------------------

def test_perf_config_validation():
    with pytest.raises(ta.ConfigError):
        ta.Config(perf=ta.PerfConfig(dispatch_depth=0)).validate()
    ta.Config(perf=ta.PerfConfig(dispatch_depth=4)).validate()
    with pytest.raises(ta.ConfigError):
        ta.Config(resilience=ta.ResilienceConfig(
            sdc_digest_max_elems=0)).validate()


def test_blocked_meter_accumulates_and_takes():
    m = BlockedMeter()
    with m.blocked():
        pass
    with m.blocked():
        pass
    assert m.peek_ms() >= 0.0
    v = m.take_ms()
    assert v >= 0.0
    assert m.peek_ms() == 0.0 and m.take_ms() == 0.0


def test_micro_split_spec_natural_factorisations(devices):
    from jax.sharding import Mesh, PartitionSpec as P

    from torchacc_tpu.parallel.sharding import micro_split_spec
    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("dp", "fsdp"))
    # M fully tiled by a leading run -> rows unsharded
    assert micro_split_spec(("dp", "fsdp"), mesh, 4, 2, 4) == \
        P(("dp", "fsdp"), None, None, None)
    # leading run covers M exactly, remainder tiles the rows
    assert micro_split_spec(("dp", "fsdp"), mesh, 2, 4, 3) == \
        P(("dp",), ("fsdp",), None)
    # no per-dim factorisation exists
    assert micro_split_spec(("dp", "fsdp"), mesh, 3, 4, 3) is None


def test_leaf_digest_subsample_deterministic_and_flip_sensitive():
    from torchacc_tpu.resilience.sdc import _leaf_digest
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)),
                    jnp.float32)
    hit_no = jnp.zeros((), bool)
    hit_yes = jnp.ones((), bool)
    mask = jnp.asarray(0x00010000, jnp.uint32)
    full = _leaf_digest(x, hit_no, mask)
    a = _leaf_digest(x, hit_no, mask, max_elems=100)
    b = _leaf_digest(x, hit_no, mask, max_elems=100)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a bound below the leaf size changes what is folded
    assert not np.array_equal(np.asarray(a), np.asarray(full))
    # element 0 (the chaos flip site) is always inside the subsample
    f = _leaf_digest(x, hit_yes, mask, max_elems=100)
    assert not np.array_equal(np.asarray(a)[:2], np.asarray(f)[:2])


# -- pipelining equivalence ---------------------------------------------------

def test_loss_trajectory_bitwise_unchanged_by_dispatch_depth(devices):
    hist = {}
    params = {}
    for depth in (1, 3):
        t = _trainer(depth=depth)
        hist[depth] = t.fit(_batches(7, seed=1), max_steps=7, log_every=1)
        params[depth] = jax.device_get(t.state.params)
        assert t.pending == 0  # fit drains the ring
    assert _det(hist[1]) == _det(hist[3])
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 params[1], params[3])


def test_records_under_lag_cover_the_drained_tail(devices):
    t = _trainer(depth=4)
    h = t.fit(_batches(6, seed=2), max_steps=6, log_every=1)
    assert [r["step"] for r in h] == list(range(6))


def test_step_records_emit_host_blocked_ms_and_depth(devices):
    for depth in (1, 2):
        t = _trainer(depth=depth, nan_guard=True)
        h = t.fit(_batches(4, seed=3), max_steps=4, log_every=1)
        assert h, "no records logged"
        for rec in h:
            assert rec["host_blocked_ms"] >= 0.0
            assert rec["dispatch_depth"] == depth


def test_eval_losses_batched_fetch_match_scalar_path(devices):
    evs = _batches(3, seed=5)
    t1 = _trainer(depth=1)
    h1 = t1.fit(_batches(4, seed=4), max_steps=4, log_every=1,
                eval_loader=evs, eval_every=2)
    t2 = _trainer(depth=3)
    h2 = t2.fit(_batches(4, seed=4), max_steps=4, log_every=1,
                eval_loader=evs, eval_every=2)
    r1 = [r for r in h1 if "eval_loss" in r]
    r2 = [r for r in h2 if "eval_loss" in r]
    assert r1 and [r["step"] for r in r1] == [r["step"] for r in r2]
    # the manual mean of one scalar eval pass must agree with depth 1's
    # batched fetch (same state: eval at record step r ran on the state
    # after r+1 optimizer steps at depth 1)
    t3 = _trainer(depth=1)
    t3.fit(_batches(3, seed=4), max_steps=3, log_every=0)
    want = sum(float(t3.eval_step(b)) for b in evs) / len(evs)
    got = [r["eval_loss"] for r in r1 if r["step"] == 2][0]
    assert got == pytest.approx(want, abs=0.0)


# -- resilience guarantees under lag ------------------------------------------

def test_guard_aborts_within_n_plus_k_with_step_attribution(devices):
    """NaN injected from step 2 on, max_consecutive_anomalies=3: the
    abort names step 4 (the third consecutive anomaly) at EVERY depth;
    with k steps in flight the raise lands while step 4+k is already
    dispatched — abort-within-N+k, never missed."""
    for depth in (1, 3):
        counters.reset()
        bs = _batches(8, seed=6)
        t = _trainer(depth=depth, loss=chaos_loss(), nan_guard=True,
                     max_consecutive_anomalies=3)
        with pytest.raises(AnomalyError) as ei:
            t.fit(ChaosLoader(bs, nan_loss_steps={2, 3, 4, 5, 6, 7}),
                  max_steps=8, log_every=0)
        assert ei.value.step == 4
        assert ei.value.consecutive == 3
        assert counters.get("anomalies_skipped") == 3
        # the state really ran ahead of the verdict (the pipeline), but
        # never past the abort bound N+k
        assert 5 <= int(t.state.step) <= 5 + (depth - 1)


def test_sdc_flip_verdict_names_same_host_and_step_under_lag(devices):
    at = 1 + CHAOS_SEED % 3
    host = 2 + CHAOS_SEED % 3
    got = {}
    for depth in (1, 3):
        counters.reset()
        t = _trainer(depth=depth, dp=8, sdc_check_interval_steps=1)
        with pytest.raises(SDCError) as ei:
            with ChaosPlan(seed=CHAOS_SEED).flip_bits(host=host, at=at):
                t.fit(_batches(6), max_steps=6, log_every=0)
        got[depth] = (ei.value.hosts, ei.value.step, ei.value.kind)
        assert counters.get("sdc_mismatches") == 1
    assert got[1] == got[3] == ([host], at, "replica")


def test_sdc_clean_run_under_lag_never_flags(devices):
    t = _trainer(depth=3, dp=8, sdc_check_interval_steps=1,
                 sdc_recompute_interval_steps=2)
    t.fit(_batches(5), max_steps=5, log_every=0)
    assert counters.get("sdc_checks") == 5
    assert counters.get("sdc_mismatches") == 0


def test_sdc_digest_subsample_bound_still_localizes(devices):
    at = 1 + CHAOS_SEED % 2
    host = 3
    t = _trainer(depth=2, dp=8, sdc_check_interval_steps=1,
                 sdc_digest_max_elems=64)
    with pytest.raises(SDCError) as ei:
        with ChaosPlan(seed=CHAOS_SEED).flip_bits(host=host, at=at):
            t.fit(_batches(5), max_steps=5, log_every=0)
    assert ei.value.hosts == [host] and ei.value.step == at
    # and a clean bounded run never flags
    counters.reset()
    t2 = _trainer(depth=2, dp=8, sdc_check_interval_steps=1,
                  sdc_digest_max_elems=64)
    t2.fit(_batches(4), max_steps=4, log_every=0)
    assert counters.get("sdc_mismatches") == 0


def test_stale_ring_cleared_on_fit_entry(devices, tmp_path):
    """An exceptional exit (abort raise) leaves in-flight entries; a
    later fit on the same Trainer must not resolve them into its own
    timeline (phantom records / misattributed verdicts) — the ring is
    cleared at fit entry even when no restore runs."""
    t = _trainer(depth=3, loss=chaos_loss(), nan_guard=True,
                 max_consecutive_anomalies=1)
    with pytest.raises(AnomalyError):
        t.fit(ChaosLoader(_batches(8, seed=9), nan_loss_steps={2}),
              max_steps=8, log_every=0)
    assert t.pending > 0  # the abort left steps 3,4 unresolved
    # resume='auto' on an empty dir -> "starting fresh" (no restore,
    # so _adopt_restored never runs) — the documented supervisor path
    h = t.fit(_batches(4, seed=10), max_steps=4, log_every=1,
              checkpoint_dir=str(tmp_path / "ckpt"), resume="auto")
    # dispatch had reached step 5 when the abort raised; the new run's
    # records start there — no stale step-3/4 entries leak in
    assert [r["step"] for r in h] == [5, 6, 7, 8]
    assert t.pending == 0


def test_returned_metrics_dict_mutation_safe_under_lag(devices):
    """The pre-PR API let callers mutate the returned metrics dict
    freely (observation completed inside step()); under lag the ring
    keeps its own shallow copy, so caller mutation cannot corrupt the
    resolution k steps later."""
    t = _trainer(depth=2, nan_guard=True)
    for b in _batches(4, seed=11):
        t.step(b).clear()
    t.drain()  # would KeyError on the guard fetch if the entry aliased
    assert counters.get("anomalies_skipped") == 0


def test_rerun_closure_immune_to_batch_dict_reuse(devices):
    """A loader that reuses ONE batch dict per step (mutating it in
    place) must not change what a lagged recompute re-executes — the
    rerun closure captures a shallow copy, so a healthy run never
    raises a spurious SDC mismatch."""
    t = _trainer(depth=2, sdc_recompute_interval_steps=1)
    shared = {}
    for b in _batches(4, seed=12):
        shared.clear()
        shared.update(b)
        t.step(shared)
    t.drain()
    assert counters.get("sdc_checks") == 4
    assert counters.get("sdc_mismatches") == 0


def test_blocked_meter_reset_at_fit_entry(devices):
    """host_blocked_ms on the first fit record must not include time
    accrued before fit (warm-up steps, a previous run)."""
    import time as _t
    t = _trainer(depth=1)
    # the step compiles in a fit of its own: on a busy host the first
    # step's compile and run alone read 230-350 ms of the 250 allowed
    t.fit(_batches(1, seed=13), max_steps=1)
    with t.blocked.blocked():
        _t.sleep(0.3)  # pre-fit blocked time: must be discarded
    h = t.fit(_batches(2, seed=13), max_steps=3, log_every=1)
    assert h and h[0]["host_blocked_ms"] < 250.0


def test_resolved_entry_releases_arbiter_snapshot(devices):
    """resolve_oldest must drop the rerun closure (which captures a
    state-sized dp<=2 arbiter snapshot) and the digest matrix once the
    verdict is recorded — last_resolved keeps the entry alive, and the
    documented memory budget peaks at the in-flight count only."""
    t = _trainer(depth=2, dp=2, sdc_check_interval_steps=1)
    t.fit(_batches(3), max_steps=3, log_every=0)
    assert counters.get("sdc_checks") == 3
    e = t.last_resolved
    assert e is not None and e.sdc_check
    assert e.rerun is None and e.digests is None


def test_checkpoint_never_commits_unverdicted_step(devices, tmp_path):
    """Verdict-before-durability: with k steps in flight, an interval
    save first drains the ring — so a step flagged by SDC can never
    become a durable checkpoint the quarantine->restart flow would
    resume from."""
    from torchacc_tpu.checkpoint.io import CheckpointManager
    at, host = 2, 3
    d = str(tmp_path / "ckpt")
    t = _trainer(depth=4, dp=8, sdc_check_interval_steps=1)
    with pytest.raises(SDCError) as ei:
        with ChaosPlan(seed=CHAOS_SEED).flip_bits(host=host, at=at):
            t.fit(_batches(8), max_steps=8, log_every=0,
                  checkpoint_dir=d, checkpoint_every=1)
    assert ei.value.step == at
    # saves are labelled step+1 (completed-step count): the newest
    # durable checkpoint is from BEFORE the flagged step's update, even
    # though the pipeline had dispatched well past it
    steps = CheckpointManager(d).valid_steps()
    assert steps and max(steps) <= at


def test_chaos_hang_still_trips_watchdog_under_lag(tmp_path):
    bs = _batches(6, seed=7)
    t = _trainer(depth=2, loss=chaos_loss(), step_deadline_s=0.15)
    with ChaosPlan(seed=CHAOS_SEED).hang("trainer.step", seconds=0.6):
        t.fit(ChaosLoader(bs), max_steps=6, log_every=0,
              metrics_dir=str(tmp_path))
    assert counters.get("watchdog_stalls") >= 1


def test_resume_resyncs_host_step_under_lag(devices, tmp_path):
    d = str(tmp_path / "ckpt")
    bs = _batches(6, seed=8)
    t = _trainer(depth=3, dp=8, sdc_check_interval_steps=1)
    t.fit(bs, max_steps=3, log_every=0, checkpoint_dir=d,
          checkpoint_every=3)
    assert t._host_step == 3 and t.pending == 0
    t.fit(bs, max_steps=6, log_every=0, checkpoint_dir=d,
          checkpoint_every=1000, resume="auto")
    assert t._host_step == 6
    assert counters.get("sdc_checks") == 6  # no phantom verdict steps
