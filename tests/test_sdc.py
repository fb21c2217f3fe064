"""SDC-defense tests: per-replica gradient digests, bit-flip chaos
localization (majority + recompute-arbiter + dp=1 spot check),
deterministic replay, bad-host quarantine, and the StepGuard EW-stats
persistence satellite.

``CHAOS_SEED`` (``make chaos-sdc`` runs 0..2) shifts the batch data and
the injected flip step so three different schedules exercise the same
guarantees — in particular that injection-free runs NEVER flag
(``sdc_mismatches == 0``) and that replay digests are bitwise identical
across invocations.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchacc_tpu as ta
from torchacc_tpu.checkpoint import CheckpointManager
from torchacc_tpu.errors import SDCError
from torchacc_tpu.models import get_preset
from torchacc_tpu.resilience import ChaosPlan, read_quarantined_hosts
from torchacc_tpu.resilience.sdc import (
    compare_replicas,
    divergence_report,
    flip_operands,
    host_digests,
    record_quarantine,
    replica_digests,
    zero_flip,
)
from torchacc_tpu.train import accelerate
from torchacc_tpu.utils.metrics import counters

pytestmark = pytest.mark.sdc

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


def _trainer(ndev=8, **res_kwargs):
    """Trainer on the first ``ndev`` emulated devices, all data
    parallel (dp=ndev -> ndev digest replicas / simulated hosts)."""
    import optax
    cfg = ta.Config(dist=ta.DistConfig(dp=ta.DPConfig(size=ndev)),
                    resilience=ta.ResilienceConfig(**res_kwargs))
    cfg.get_mesh(jax.devices()[:ndev])
    tr, _ = accelerate(_model(), None, cfg, optimizer=optax.adam(1e-3))
    return tr


# -- digest fold units --------------------------------------------------------

def test_replica_digest_fold_detects_targeted_bitflip(devices):
    cfg = ta.Config()
    mesh = cfg.get_mesh()
    tree = {"a": jnp.arange(12.0).reshape(3, 4) - 5.0,
            "b": {"c": jnp.full((2,), 0.5)}}

    def run(flip):
        with jax.sharding.set_mesh(mesh):
            return np.asarray(jax.jit(
                lambda f: replica_digests(tree, f, mesh=mesh))(flip))

    clean = run(zero_flip(8))
    assert clean.shape == (8, 2, 3) and clean.dtype == np.uint32
    # all replicas fold the same replicated values -> identical rows
    sus, tie = compare_replicas(clean)
    assert sus is None and not tie

    flip = zero_flip(8)
    flip["mask"][3] = 1
    flip["xor"] = np.uint32(0x00400000)
    flipped = run(flip)
    sus, tie = compare_replicas(flipped)
    assert sus == [3] and not tie
    # every other row is bitwise untouched by the (inactive) flip path
    keep = [r for r in range(8) if r != 3]
    np.testing.assert_array_equal(flipped[keep], clean[keep])

    # leaf-targeted: leaf 0 untouched, leaf 1 diverges
    flip["leaf"] = np.asarray(1, np.int32)
    f2 = run(flip)
    np.testing.assert_array_equal(f2[3, 0], clean[3, 0])
    assert (f2[3, 1] != clean[3, 1]).any()


def test_compare_replicas_majority_and_tie():
    base = np.arange(12, dtype=np.uint32).reshape(1, 4, 3)
    d = np.repeat(base, 5, axis=0)
    assert compare_replicas(d) == (None, False)
    d[2, 1, 0] ^= 0x40
    assert compare_replicas(d) == ([2], False)
    # 2-2 split plus a matching pair is still a strict majority of 3?
    # no — flip two rows the SAME way: groups sized 3 and 2 -> minority
    d[4] = d[2]
    assert compare_replicas(d) == ([2, 4], False)
    # 1-vs-1: a tie, every replica suspect
    d2 = np.repeat(base, 2, axis=0)
    d2[1, 0, 0] ^= 1
    assert compare_replicas(d2) == ([0, 1], True)


def test_f32_sum_word_is_report_only():
    # the f32-sum word is an order-dependent float reduction: a
    # difference confined to it must NEVER flag a divergence (the
    # exact xor/sum words are the verdict)
    base = np.arange(12, dtype=np.uint32).reshape(1, 4, 3)
    d = np.repeat(base, 4, axis=0)
    d[2, 1, 2] ^= 0x1
    assert compare_replicas(d) == (None, False)


def test_unlocalized_tie_raises_but_never_quarantines(devices, tmp_path):
    # dp >= 4 even split: no pre-step snapshot exists, so the verdict
    # names the whole divergent set — and must NOT shrink the pod by
    # quarantining hosts it could not localize
    from torchacc_tpu.resilience.sdc import SDCMonitor
    cfg = ta.Config(resilience=ta.ResilienceConfig(
        sdc_check_interval_steps=1))
    mesh = cfg.get_mesh()
    mon = SDCMonitor(cfg.resilience, mesh, ["a", "b"],
                     run_dir=str(tmp_path))
    d = np.repeat(np.arange(6, dtype=np.uint32).reshape(1, 2, 3),
                  8, axis=0)
    d[4:, 0, 0] ^= 0x40  # 4-4 split
    with pytest.raises(SDCError) as ei:
        mon.observe(5, d, check=True, spot=False, recompute=None)
    assert ei.value.hosts == list(range(8))
    assert "NOT localized" in str(ei.value)
    assert read_quarantined_hosts(str(tmp_path)) == {}
    assert counters.get("replica_divergences") == 1


def test_divergence_report_names_first_leaf():
    d = np.zeros((2, 3, 3), np.uint32)
    d[1, 1] = [0xdead, 2, 3]
    lines = divergence_report(d, d[0], [1], ["p/a", "p/b", "p/c"],
                              [[0], [1]])
    assert len(lines) == 1
    assert "replica 1 (host 1)" in lines[0]
    assert "'p/b'" in lines[0] and "0x0000dead" in lines[0]
    assert "1/3 leaves" in lines[0]


def _dp2_monitor(tmp_path):
    from torchacc_tpu.resilience.sdc import SDCMonitor
    cfg = ta.Config(dist=ta.DistConfig(dp=ta.DPConfig(size=2)),
                    resilience=ta.ResilienceConfig(
                        sdc_check_interval_steps=1))
    mesh = cfg.get_mesh(jax.devices()[:2])
    return SDCMonitor(cfg.resilience, mesh, ["a", "b"],
                      run_dir=str(tmp_path))


def test_dp2_tie_third_execution_localizes_flaky_replica(
        devices, tmp_path):
    # dp=2 even split where in-step digest and recompute AGREE per
    # replica (neither self-localizes): the third execution gives
    # three samples — the replica whose three runs are not unanimous
    # is the intermittently flaky one, majority-voted and quarantined
    mon = _dp2_monitor(tmp_path)
    d = np.repeat(np.arange(6, dtype=np.uint32).reshape(1, 2, 3),
                  2, axis=0)
    d[1, 0, 0] ^= 0x40                        # 1-vs-1 tie
    runs = [d.copy(), d.copy()]               # redo, then third
    runs[1][1, 0, 0] ^= 0x7                   # replica 1 flakes again
    calls = iter(runs)
    with pytest.raises(SDCError) as ei:
        mon.observe(5, d, check=True, spot=False,
                    recompute=lambda: next(calls))
    assert counters.get("sdc_third_executions") == 1
    assert ei.value.kind == "replica"
    assert ei.value.hosts == sorted({h for h in mon.replica_hosts[1]})
    assert read_quarantined_hosts(str(tmp_path))  # localized verdict


def test_dp2_tie_three_way_unanimous_stays_unlocalized(
        devices, tmp_path):
    # every execution of every replica reproduces its own digests:
    # persistent, unattributed corruption — named, never quarantined
    mon = _dp2_monitor(tmp_path)
    d = np.repeat(np.arange(6, dtype=np.uint32).reshape(1, 2, 3),
                  2, axis=0)
    d[1, 0, 0] ^= 0x40
    with pytest.raises(SDCError) as ei:
        mon.observe(5, d, check=True, spot=False,
                    recompute=lambda: d.copy())
    assert counters.get("sdc_third_executions") == 1
    assert ei.value.hosts == [0, 1]           # the whole divergent set
    assert "NOT localized" in str(ei.value)
    assert read_quarantined_hosts(str(tmp_path)) == {}


def test_flip_operands_inactive_without_plan():
    ops = flip_operands(3, 4, [[0], [1], [2], [3]], ["a", "b"], "step")
    assert not ops["mask"].any() and int(ops["leaf"]) == -1
    plan = ChaosPlan(seed=CHAOS_SEED).flip_bits(host=2, at=3, leaf="b")
    with plan:
        # wrong step / wrong where -> zeros
        assert not flip_operands(2, 4, [[0], [1], [2], [3]], ["a", "b"],
                                 "step")["mask"].any()
        assert not flip_operands(3, 4, [[0], [1], [2], [3]], ["a", "b"],
                                 "recompute")["mask"].any()
        ops = flip_operands(3, 4, [[0], [1], [2], [3]], ["a", "b"],
                            "step")
        assert list(ops["mask"]) == [0, 0, 1, 0]
        assert int(ops["leaf"]) == 1
    assert plan.stats()["sdc.flip_bits"]["hits"] == 1


def test_config_sdc_validation():
    with pytest.raises(ta.ConfigError):
        ta.Config.from_dict({"resilience": {"sdc_check_interval_steps": 0}})
    with pytest.raises(ta.ConfigError):
        ta.Config.from_dict(
            {"resilience": {"sdc_recompute_interval_steps": -1}})
    cfg = ta.Config.from_dict(
        {"resilience": {"sdc_check_interval_steps": 5, "sdc_abort": False}})
    assert cfg.resilience.sdc_check_interval_steps == 5
    assert cfg.to_dict()["resilience"]["sdc_abort"] is False


def test_quarantine_record_merges(tmp_path):
    d = str(tmp_path)
    record_quarantine(d, [3], step=10, kind="replica", report=["r3"])
    record_quarantine(d, [5], step=12, kind="recompute", report=["r5"])
    q = read_quarantined_hosts(d)
    assert set(q) == {3, 5}
    assert q[3]["step"] == 10 and q[5]["kind"] == "recompute"
    assert read_quarantined_hosts(str(tmp_path / "nope")) == {}


# -- end-to-end: clean runs never flag ----------------------------------------

def test_clean_run_no_mismatches(devices):
    t = _trainer(sdc_check_interval_steps=1,
                 sdc_recompute_interval_steps=2)
    t.fit(_batches(4), max_steps=4, log_every=0)
    assert counters.get("sdc_checks") == 4
    assert counters.get("sdc_mismatches") == 0
    assert counters.get("replica_divergences") == 0
    assert int(t.state.step) == 4


# -- end-to-end: bit-flip localization ----------------------------------------

def test_flip_bits_localized_by_majority(devices, tmp_path):
    k = 1 + CHAOS_SEED % 3
    host = 2 + CHAOS_SEED % 3
    md = str(tmp_path / "run")
    t = _trainer(sdc_check_interval_steps=1)
    with pytest.raises(SDCError) as ei:
        with ChaosPlan(seed=CHAOS_SEED).flip_bits(host=host, at=k):
            t.fit(_batches(6), max_steps=6, log_every=0, metrics_dir=md)
    e = ei.value
    assert e.hosts == [host]
    assert e.kind == "replica"
    assert e.step == k
    assert e.report and f"host {host}" in e.report[0]
    assert counters.get("replica_divergences") == 1
    assert counters.get("sdc_mismatches") == 1
    # the suspect is on file for the supervisor / the next restart
    q = read_quarantined_hosts(md)
    assert host in q and q[host]["step"] == k


def test_flip_bits_dp2_tie_arbitrated_by_recompute(devices):
    k = 1 + CHAOS_SEED % 2
    t = _trainer(ndev=2, sdc_check_interval_steps=1)
    with pytest.raises(SDCError) as ei:
        with ChaosPlan(seed=CHAOS_SEED).flip_bits(host=1, at=k):
            t.fit(_batches(4), max_steps=4, log_every=0)
    # a 1-vs-1 divergence cannot be localized by majority: the
    # redundant re-execution (clean bits) singles out host 1
    assert ei.value.hosts == [1]
    assert ei.value.step == k
    assert counters.get("replica_divergences") == 1


def test_optimizer_digest_clean_run_never_flags(devices):
    t = _trainer(sdc_check_interval_steps=1, sdc_digest_optimizer=True)
    t.fit(_batches(4), max_steps=4, log_every=0)
    assert counters.get("sdc_checks") == 4
    assert counters.get("sdc_mismatches") == 0
    # the digest matrix carries both regions, named apart
    paths = t._sdc_monitor.leaf_paths
    n = len(paths)
    assert n % 2 == 0
    assert all(p.startswith("grads/") for p in paths[:n // 2])
    assert all(p.startswith("params/") for p in paths[n // 2:])


def test_optimizer_digest_surfaces_post_apply_corruption_same_step(devices):
    """The carried-over PR-4 gap: corruption in the optimizer apply used
    to surface one step late (through the NEXT step's gradients).  With
    sdc_digest_optimizer the post-apply param rows ride the digest
    matrix, so a flip targeted at a params/ leaf is flagged at exactly
    the step it happens — with the report naming the params region."""
    k = 1 + CHAOS_SEED % 3
    host = 2 + CHAOS_SEED % 3
    t = _trainer(sdc_check_interval_steps=1, sdc_digest_optimizer=True)
    with pytest.raises(SDCError) as ei:
        with ChaosPlan(seed=CHAOS_SEED).flip_bits(
                host=host, at=k, leaf="params/"):
            t.fit(_batches(6), max_steps=6, log_every=0)
    e = ei.value
    assert e.hosts == [host]
    assert e.step == k                 # the step it happens, not k + 1
    assert e.report and "params/" in e.report[0]


def test_recompute_spot_check_catches_dp1_flakiness(devices):
    k = 1 + CHAOS_SEED % 2
    t = _trainer(ndev=1, sdc_recompute_interval_steps=1)
    with pytest.raises(SDCError) as ei:
        with ChaosPlan(seed=CHAOS_SEED).flip_bits(host=0, at=k,
                                                  where="recompute"):
            t.fit(_batches(4), max_steps=4, log_every=0)
    assert ei.value.kind == "recompute"
    assert ei.value.hosts == [0]
    assert counters.get("replica_divergences") == 0  # nothing to compare


def test_sdc_abort_off_counts_and_quarantines_only(devices, tmp_path):
    md = str(tmp_path / "run")
    t = _trainer(sdc_check_interval_steps=1, sdc_abort=False)
    with ChaosPlan(seed=CHAOS_SEED).flip_bits(host=4, at=1):
        hist = t.fit(_batches(4), max_steps=4, log_every=1,
                     metrics_dir=md)
    assert int(t.state.step) == 4  # the run was not aborted
    assert counters.get("sdc_mismatches") == 1
    assert 4 in read_quarantined_hosts(md)
    # counters ride the step records / metrics.jsonl
    assert hist[-1]["sdc_mismatches"] == 1
    assert hist[-1]["sdc_checks"] == 4
    rec = [json.loads(l) for l in
           open(os.path.join(md, "metrics.jsonl"))][-1]
    assert rec["train/sdc_mismatches"] == 1


def test_sdc_host_step_resyncs_after_restore(devices, tmp_path):
    """In-process supervisor pattern: a same-Trainer fit(resume='auto')
    must re-derive the SDC step index from the restored state — verdict
    steps and chaos `at=` indices stay aligned with real steps."""
    d = str(tmp_path / "ckpt")
    bs = _batches(4)
    t = _trainer(sdc_check_interval_steps=1)
    t.fit(bs, max_steps=2, log_every=0, checkpoint_dir=d,
          checkpoint_every=2)
    assert t._host_step == 2
    t._host_step = 99  # simulate a stale index from a failed run
    t.fit(bs, max_steps=4, log_every=0, checkpoint_dir=d,
          checkpoint_every=1000, resume="auto")
    assert t._host_step == 4  # re-derived from restored step 2
    assert counters.get("sdc_checks") == 4  # 2 + 2, no phantom indices


# -- deterministic replay -----------------------------------------------------

def test_replay_bitwise_equivalence(devices, tmp_path):
    d = str(tmp_path / "ckpt")
    bs = _batches(6)
    t = _trainer()
    t.fit(bs, max_steps=6, log_every=0, checkpoint_dir=d,
          checkpoint_every=2)

    tr_r = _trainer()
    r1 = tr_r.fit(bs, replay_step=2, checkpoint_dir=d, log_every=0)
    # the forced digest program is scoped to the replay: a later fit on
    # this trainer keeps its zero-overhead (digest-free) step program
    assert tr_r._sdc_on is False and tr_r._train_step is None
    r2 = _trainer().fit(bs, replay_step=2, checkpoint_dir=d, log_every=0)
    assert r1[0]["replay_step"] == 2 and r1[0]["step"] == 2
    assert r1[0]["deterministic"] and r2[0]["deterministic"]
    # same checkpoint + same loader position => identical digests
    assert r1[0]["digests"] == r2[0]["digests"]
    assert r1[0]["loss"] == r2[0]["loss"]
    # a different step replays different grads
    r3 = _trainer().fit(bs, replay_step=4, checkpoint_dir=d, log_every=0)
    assert r3[0]["digests"] != r1[0]["digests"]


def test_replay_requires_checkpoint(tmp_path):
    from torchacc_tpu.errors import (
        CheckpointNotFoundError,
        TrainerStateError,
    )
    t = _trainer()
    with pytest.raises(TrainerStateError):
        t.fit(_batches(2), replay_step=1)
    d = str(tmp_path / "ckpt")
    t2 = _trainer()
    t2.fit(_batches(2), max_steps=2, log_every=0, checkpoint_dir=d,
           checkpoint_every=2)
    t3 = _trainer()
    with pytest.raises(CheckpointNotFoundError):
        t3.fit(_batches(2), replay_step=7, checkpoint_dir=d)
    # the forced digest program must not leak past a FAILED replay
    assert t3._sdc_on is False


# -- CLI `replay` (offline checkpoint digests) --------------------------------

def test_cli_replay_digests(tmp_path, capsys):
    from torchacc_tpu.checkpoint.cli import main
    d = str(tmp_path / "mgr")
    mgr = CheckpointManager(d)
    state = {"a": jnp.arange(4.0), "b": {"c": jnp.ones((2, 2)) * 3}}
    mgr.save(1, state)
    mgr.close()
    assert main(["replay", d, "--step", "1"]) == 0
    out = capsys.readouterr().out
    assert "a: xor=0x" in out and "b/c: xor=0x" in out
    assert main(["replay", d, "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["step"] == 1
    assert set(payload["digests"]) == {"a", "b/c"}
    # order-independent content digest: identical values -> identical
    # words, a changed value -> different words
    again = host_digests(jax.device_get(state))
    assert {k: {w: v[w] for w in ("bits_xor", "bits_sum")}
            for k, v in again.items()} \
        == {k: {w: v[w] for w in ("bits_xor", "bits_sum")}
            for k, v in payload["digests"].items()}
    other = host_digests({"a": np.arange(4.0, dtype=np.float32) + 1,
                          "b": {"c": np.ones((2, 2), np.float32) * 3}})
    assert other["a"]["bits_xor"] != again["a"]["bits_xor"]


# -- satellite: StepGuard EW statistics survive resume ------------------------

def test_guard_statistics_persist_and_restore(tmp_path):
    d = str(tmp_path / "ckpt")
    bs = _batches(6)
    # count is of ACCEPTED steps (guard.py): keep the guard warming for
    # the whole run so none of the six is judged — two steps of
    # statistics z-score this tiny model's rising grad norm as a spike,
    # and which steps that hits depends on the installed JAX's numerics
    kw = dict(spike_guard=True, spike_warmup_steps=100)
    t = _trainer(**kw)
    t.fit(bs, max_steps=6, log_every=0, checkpoint_dir=d,
          checkpoint_every=2)
    want = jax.device_get(t._guard_state)
    assert int(want["count"]) == 6
    # the sidecar rides every committed step
    assert os.path.exists(os.path.join(d, "6", "guard_state.json"))

    t2 = _trainer(**kw)
    t2.fit(bs, max_steps=6, log_every=0, checkpoint_dir=d,
           checkpoint_every=1000, resume="auto")
    got = jax.device_get(t2._guard_state)
    # bit-exact restore: the spike guard does NOT re-warm
    assert int(got["count"]) == 6
    np.testing.assert_array_equal(np.asarray(want["mean"]),
                                  np.asarray(got["mean"]))
    np.testing.assert_array_equal(np.asarray(want["var"]),
                                  np.asarray(got["var"]))


def test_guard_restore_tolerates_missing_sidecar(tmp_path):
    d = str(tmp_path / "ckpt")
    bs = _batches(4)
    kw = dict(spike_guard=True, spike_warmup_steps=2)
    t = _trainer(**kw)
    t.fit(bs, max_steps=4, log_every=0, checkpoint_dir=d,
          checkpoint_every=2)
    os.remove(os.path.join(d, "4", "guard_state.json"))
    t2 = _trainer(**kw)
    t2.fit(bs, max_steps=4, log_every=0, checkpoint_dir=d,
           checkpoint_every=1000, resume="auto")  # re-warms, no crash
    assert int(t2.state.step) == 4


# -- 2-process DP=2 fixture (the acceptance proof) ----------------------------

_SDC_WORKER = """
import os, sys, time
port, pid, base = sys.argv[1], int(sys.argv[2]), sys.argv[3]
flip_at = int(sys.argv[4])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
from torchacc_tpu.parallel.distributed import initialize_distributed
initialize_distributed(coordinator_address=f"localhost:{port}",
                       num_processes=2, process_id=pid)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 2, len(jax.devices())

import numpy as np
import jax.numpy as jnp
import optax
import torchacc_tpu as ta
from torchacc_tpu.models import get_preset
from torchacc_tpu.train import accelerate
from torchacc_tpu.resilience import ChaosPlan, read_quarantined_hosts
from torchacc_tpu.errors import SDCError
from torchacc_tpu.utils.metrics import counters
from jax.experimental import multihost_utils
from jax.sharding import PartitionSpec as PS

cfg = ta.Config(dist=ta.DistConfig(dp=ta.DPConfig(size=2)),
                resilience=ta.ResilienceConfig(sdc_check_interval_steps=1))
mc = get_preset("llama-tiny", vocab_size=64, hidden_size=32, num_layers=1,
                num_heads=2, num_kv_heads=2, intermediate_size=64,
                dtype=jnp.float32)
trainer, _ = accelerate(mc, None, cfg, optimizer=optax.sgd(1e-2))
trainer.init()
trainer._sdc_run_dir = base  # quarantine records land here

def gbatch(i):
    # each process feeds its own dp shard (genuinely different data)
    local = np.random.default_rng(1000 * i + pid).integers(
        0, 64, (4, 16)).astype(np.int32)
    arr = multihost_utils.host_local_array_to_global_array(
        local, trainer.mesh, PS(("dp", "fsdp"), ("sp", "spu")))
    return {"input_ids": arr}

# injection-free steps: checked every step, never flagged
for i in range(flip_at):
    trainer.step(gbatch(i))
assert counters.get("sdc_checks") == flip_at, counters.snapshot()
assert counters.get("sdc_mismatches") == 0, counters.snapshot()

# flip bits on HOST 1 only: the 1-vs-1 replica divergence is
# arbitrated by the recompute and localized to host 1 on BOTH hosts
err = None
try:
    with ChaosPlan(seed=0).flip_bits(host=1, at=flip_at):
        trainer.step(gbatch(flip_at))
except SDCError as e:
    err = e
assert err is not None, "SDCError not raised"
assert err.hosts == [1], err.hosts
assert err.step == flip_at, err.step
assert counters.get("sdc_mismatches") == 1, counters.snapshot()

# the primary recorded the quarantine on the shared run dir
deadline = time.time() + 30
q = {}
while time.time() < deadline:
    q = read_quarantined_hosts(base)
    if q:
        break
    time.sleep(0.2)
assert 1 in q, q
print(f"proc {pid} ok sdc hosts={err.hosts} step={err.step}", flush=True)
"""


@pytest.mark.slow
@pytest.mark.multihost
def test_two_process_dp2_flip_localized_to_host1(tmp_path):
    """The acceptance fixture: two jax.distributed CPU processes form a
    DP=2 mesh (one replica per host).  Injection-free steps pass with
    ``sdc_mismatches == 0``; then ``flip_bits(host=1)`` corrupts host
    1's view of the grads and BOTH processes must raise ``SDCError``
    naming host 1 — localized through the recompute arbiter, with the
    quarantine record visible in the shared run dir."""
    import socket
    import subprocess
    import sys

    flip_at = 1 + CHAOS_SEED % 2
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    procs = [subprocess.Popen(
        [sys.executable, "-c", _SDC_WORKER, str(port), str(i),
         str(tmp_path / "shared_run"), str(flip_at)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
        assert f"proc {i} ok sdc hosts=[1]" in out, out[-2000:]
