"""Tiered zero-stall checkpointing tests (checkpoint/tiered.py,
docs/resilience.md "Tiered checkpointing").

The contracts under test:

- tiered saves NEVER change the math: final params and every committed
  checkpoint are bitwise identical to the blocking orbax path;
- verdict-before-durability survives the move off the hot path: a step
  flagged by SDC under dispatch lag can never become a durable
  checkpoint (its trickle gate never opens);
- a crash between the tier-0 snapshot and the tier-1 commit (chaos
  ``tiered.tier1`` failpoint) restores from the newest *durable* step,
  bitwise — the commit-marker protocol holds;
- restore-from-RAM resumes bitwise with ZERO storage reads (orbax
  restore monkeypatched to raise), and the 2-process fixture proves the
  same for a restarted host rejoining from a peer's tier-0 snapshot;
- loader/guard state ride the tier-1 trickle under the same commit
  marker, never on the hot path;
- ``resilience.refuse_quarantined`` enforces (typed
  QuarantinedHostError) what PR 4 only warned about.
"""

import json
import os
import shutil
import socket
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchacc_tpu as ta
from torchacc_tpu.errors import QuarantinedHostError, SDCError
from torchacc_tpu.models import get_preset
from torchacc_tpu.resilience import ChaosLoader, ChaosPlan, chaos_loss
from torchacc_tpu.train import accelerate
from torchacc_tpu.utils.metrics import counters

pytestmark = pytest.mark.tiered

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


def _trainer(depth=2, dp=None, tiered=True, mirror=None, loss=None,
             **res_kwargs):
    import optax
    dist = (ta.DistConfig(dp=ta.DPConfig(size=dp)) if dp
            else ta.DistConfig())
    cfg = ta.Config(dist=dist,
                    resilience=ta.ResilienceConfig(
                        tiered_checkpointing=tiered,
                        tiered_mirror_dir=mirror, **res_kwargs),
                    perf=ta.PerfConfig(dispatch_depth=depth))
    if dp:
        cfg.get_mesh(jax.devices()[:dp])
    tr, _ = accelerate(_model(), None, cfg, optimizer=optax.adam(1e-3),
                       loss=loss)
    return tr


def _leaves(tree):
    return [np.asarray(x) for x in jax.device_get(jax.tree.leaves(tree))]


def _assert_bitwise(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)


# -- config / units -----------------------------------------------------------

def test_tiered_config_validation():
    with pytest.raises(ta.ConfigError):
        ta.Config(resilience=ta.ResilienceConfig(
            tiered_tier0_keep=0)).validate()
    ta.Config(resilience=ta.ResilienceConfig(
        tiered_checkpointing=True, tiered_tier0_keep=1,
        refuse_quarantined=True)).validate()


def test_broadcast_from_host_single_process_noop():
    from torchacc_tpu.resilience.coordination import broadcast_from_host
    tree = {"a": np.arange(4), "b": None}
    out = broadcast_from_host(tree, is_source=True)
    assert out is tree  # exact no-op, no collective, no copy


# -- bitwise parity with the blocking path ------------------------------------

def test_tiered_saves_match_blocking_bitwise(tmp_path):
    """Same loop, same data: blocking orbax saves vs tiered trickle
    must commit identical steps with identical bits — and the tiered
    hot path must be dramatically cheaper (save_blocked_ms)."""
    from torchacc_tpu.checkpoint import CheckpointManager
    d_b, d_t = str(tmp_path / "blocking"), str(tmp_path / "tiered")
    bs = _batches(6)
    tb = _trainer(tiered=False)
    hb = tb.fit(list(bs), max_steps=6, log_every=1, checkpoint_dir=d_b,
                checkpoint_every=2)
    tt = _trainer(tiered=True)
    ht = tt.fit(list(bs), max_steps=6, log_every=1, checkpoint_dir=d_t,
                checkpoint_every=2)
    _assert_bitwise(tb.state.params, tt.state.params)
    mb, mt = CheckpointManager(d_b), CheckpointManager(d_t)
    assert mb.valid_steps() == mt.valid_steps()
    abstract = tb.abstract_state()
    sb, step_b = mb.restore_latest_valid(abstract)
    st, step_t = mt.restore_latest_valid(abstract)
    assert step_b == step_t == 6
    _assert_bitwise(sb, st)
    # the zero-stall claim: the tiered run's total metered save cost is
    # far below the blocking run's (observed ~100-400x; assert 5x so
    # scheduler noise cannot flake the suite)
    cost_b = sum(r["save_blocked_ms"] for r in hb)
    cost_t = sum(r["save_blocked_ms"] for r in ht)
    assert cost_t < cost_b / 5, (cost_t, cost_b)
    assert counters.get("tiered_saves") == 3


def test_tier2_mirror_commits_and_restores_bitwise(tmp_path):
    """The mirror carries committed steps (marker last) and restores
    them bitwise when the local tier is gone."""
    from torchacc_tpu.checkpoint.io import MANIFEST
    from torchacc_tpu.checkpoint.tiered import TieredCheckpointManager
    d = str(tmp_path / "ckpt")
    mirror = str(tmp_path / "mirror")
    t = _trainer(mirror=mirror)
    t.fit(_batches(4), max_steps=4, log_every=0, checkpoint_dir=d,
          checkpoint_every=2)
    assert counters.get("mirror_writes") == 2
    for s in (2, 4):
        assert os.path.exists(os.path.join(mirror, str(s), MANIFEST))
    abstract = t.abstract_state()
    want = _leaves(t.state)
    shutil.rmtree(d)  # local history gone; the long-horizon tier holds
    mgr = TieredCheckpointManager(d, mirror_dir=mirror)
    try:
        state, step = mgr.restore_latest_valid(abstract)
    finally:
        mgr.shutdown()
    assert step == 4
    for x, y in zip(want, _leaves(state)):
        np.testing.assert_array_equal(x, y)
    assert counters.get("mirror_restores") == 1


# -- crash-mid-trickle / verdict gating ---------------------------------------

def test_crash_mid_trickle_restores_newest_durable_bitwise(tmp_path):
    """Chaos kill between the tier-0 snapshot and the tier-1 commit:
    the dying step is never marked, and a fresh process restores the
    newest DURABLE step bitwise."""
    from torchacc_tpu.checkpoint import CheckpointManager
    d = str(tmp_path / "ckpt")
    bs = _batches(6)
    t = _trainer()
    t.fit(list(bs), max_steps=4, log_every=0, checkpoint_dir=d,
          checkpoint_every=2)
    want = _leaves(t.state)   # == committed step 4
    with ChaosPlan(seed=CHAOS_SEED).fail("tiered.tier1", times=1):
        t.fit(list(bs), max_steps=6, log_every=0, checkpoint_dir=d,
              checkpoint_every=2, resume="auto")
    assert counters.get("tiered_write_failures") == 1
    # process death: a fresh manager has no RAM tier — only durability
    m = CheckpointManager(d)
    assert m.valid_steps() == [2, 4]  # step 6's trickle died uncommitted
    state, step = m.restore_latest_valid(t.abstract_state())
    assert step == 4
    for x, y in zip(want, _leaves(state)):
        np.testing.assert_array_equal(x, y)


def test_verdict_gate_never_commits_unverdicted_step(devices, tmp_path):
    """Verdict-before-durability WITHOUT the hot-path drain: a step
    flagged by SDC under dispatch lag never opens its trickle gate, so
    no tier — disk or RAM — ever offers it for restore."""
    from torchacc_tpu.checkpoint import CheckpointManager
    at, host = 2, 3
    d = str(tmp_path / "ckpt")
    t = _trainer(depth=4, dp=8, sdc_check_interval_steps=1)
    with pytest.raises(SDCError) as ei:
        with ChaosPlan(seed=CHAOS_SEED).flip_bits(host=host, at=at):
            t.fit(_batches(8), max_steps=8, log_every=0,
                  checkpoint_dir=d, checkpoint_every=1)
    assert ei.value.step == at
    steps = CheckpointManager(d).valid_steps()
    assert steps and max(steps) <= at
    # the RAM tier obeys the same gate: nothing past the flagged step
    status = t._tiered_cache[1].tier_status()
    assert not status["ram"] or max(status["ram"]) <= at
    assert status["verdicts_through"] < at


def test_tiered_emergency_save_on_preemption(tmp_path):
    """A preemption under tiered saves still yields a durable emergency
    checkpoint at the step boundary (the grace window blocks on the
    trickle), and resume continues."""
    from torchacc_tpu.checkpoint import CheckpointManager
    d = str(tmp_path / "ckpt")
    bs = _batches(6)
    t = _trainer(loss=chaos_loss())
    t.fit(ChaosLoader(bs, preempt_after_step=2), max_steps=6,
          log_every=0, checkpoint_dir=d, checkpoint_every=1000)
    assert counters.get("emergency_saves") == 1
    assert 3 in CheckpointManager(d).valid_steps()
    h = t.fit(ChaosLoader(bs), max_steps=6, log_every=1,
              checkpoint_dir=d, checkpoint_every=1000, resume="auto")
    assert t._host_step == 6
    assert h and h[-1]["step"] == 5 and np.isfinite(h[-1]["loss"])


# -- RAM restore --------------------------------------------------------------

def test_ram_restore_resumes_bitwise_without_storage_read(
        tmp_path, monkeypatch):
    """An in-process supervisor refit restores the newest verdicted
    tier-0 snapshot from host RAM: orbax restore is monkeypatched to
    raise, and the continued run is bitwise identical to an
    uninterrupted one."""
    import orbax.checkpoint as ocp
    d = str(tmp_path / "ckpt")
    t = _trainer()
    t.fit(_batches(10), max_steps=4, log_every=0, checkpoint_dir=d,
          checkpoint_every=2)

    def boom(*a, **k):
        raise AssertionError("storage restore attempted on the RAM path")
    monkeypatch.setattr(ocp.StandardCheckpointer, "restore", boom)
    monkeypatch.setattr(ocp.CheckpointManager, "restore", boom)
    t.fit(_batches(10), max_steps=10, log_every=0, checkpoint_dir=d,
          checkpoint_every=1000, resume="auto")
    assert counters.get("ram_restores") == 1
    ref = _trainer(tiered=False)
    ref.fit(_batches(10), max_steps=10, log_every=0)
    _assert_bitwise(ref.state.params, t.state.params)


# -- sidecars ride the trickle ------------------------------------------------

class _StatefulLoader:
    """Minimal loader with the durable-state protocol."""

    def __init__(self, batches):
        self._b = batches
        self._start = 0
        self.consumed = 0
        self.loaded = None

    def __iter__(self):
        for i in range(self._start, len(self._b)):
            self.consumed = i + 1
            yield self._b[i]

    def state_dict(self):
        return {"consumed": int(self.consumed)}

    def load_state_dict(self, d):
        self.loaded = dict(d)
        self._start = self.consumed = int(d["consumed"])


def test_loader_and_guard_state_ride_the_trickle(tmp_path):
    """loader_state.json + guard_state.json land in the step dir under
    the same commit marker, written by the tier-1 trickle — and the RAM
    tier serves them too, so a restore-from-RAM resumes the loader."""
    from torchacc_tpu.checkpoint.io import GUARD_STATE, LOADER_STATE
    d = str(tmp_path / "ckpt")
    loader = _StatefulLoader(_batches(4))
    t = _trainer(nan_guard=True, spike_guard=True)
    t.fit(loader, max_steps=4, log_every=0, checkpoint_dir=d,
          checkpoint_every=2)
    for s in (2, 4):
        with open(os.path.join(d, str(s), LOADER_STATE)) as f:
            assert json.load(f) == {"consumed": s}
        with open(os.path.join(d, str(s), GUARD_STATE)) as f:
            gs = json.load(f)
        assert gs["count"] == s  # per-step statistics at the boundary
    mgr = t._tiered_cache[1]
    assert mgr.read_loader_state(4) == {"consumed": 4}
    assert mgr.read_guard_state(4)["count"] == 4
    # resume restores the sidecar (RAM or disk, same dict)
    loader2 = _StatefulLoader(_batches(4))
    t.fit(loader2, max_steps=4, log_every=0, checkpoint_dir=d,
          checkpoint_every=1000, resume="auto")
    assert loader2.loaded == {"consumed": 4}


# -- quarantine enforcement ---------------------------------------------------

def test_refuse_quarantined_enforces(tmp_path):
    from torchacc_tpu.resilience.sdc import record_quarantine
    d = str(tmp_path / "run")
    record_quarantine(d, [0], step=1, kind="replica", report=["leaf x"])
    t = _trainer(refuse_quarantined=True)
    with pytest.raises(QuarantinedHostError) as ei:
        t.fit(_batches(2), max_steps=2, log_every=0, checkpoint_dir=d,
              checkpoint_every=1000)
    assert ei.value.hosts == [0]
    assert ei.value.quarantine_file.endswith("sdc_quarantine.json")
    # default (off) keeps the PR-4 behaviour: warn and train
    t2 = _trainer(refuse_quarantined=False)
    t2.fit(_batches(2), max_steps=2, log_every=0, checkpoint_dir=d,
           checkpoint_every=1000)
    assert t2._host_step == 2


def test_fresh_fit_on_used_dir_still_saves(tmp_path):
    """A second fit with resume=None on the same checkpoint_dir is a
    NEW timeline: the cached manager's submission cursor must reset, so
    interval saves (and emergency saves) are not silently skipped —
    and BOTH durable tiers must replace their stale same-label copies
    (a mirror serving the discarded timeline's bits would silently
    resurrect them if tier 1 were later lost)."""
    from torchacc_tpu.checkpoint import CheckpointManager
    d = str(tmp_path / "ckpt")
    mirror = str(tmp_path / "mirror")
    t = _trainer(mirror=mirror)
    t.fit(_batches(4), max_steps=4, log_every=0, checkpoint_dir=d,
          checkpoint_every=2)
    assert counters.get("tiered_saves") == 2
    t.init()  # fresh params — a genuinely new run on the same dir
    t.fit(_batches(4, seed=9), max_steps=4, log_every=0,
          checkpoint_dir=d, checkpoint_every=2)
    assert counters.get("tiered_saves") == 4  # steps 2,4 saved AGAIN
    # both tiers' re-saved step 4 carry the NEW timeline's bits
    abstract = t.abstract_state()
    state, step = CheckpointManager(d).restore_latest_valid(abstract)
    assert step == 4
    _assert_bitwise(state, t.state)
    m_state, m_step = CheckpointManager(mirror).restore_latest_valid(
        abstract)
    assert m_step == 4
    _assert_bitwise(m_state, t.state)


def test_failed_emergency_trickle_raises(tmp_path):
    """A preemption whose tiered trickle fails must surface as a
    CheckpointError — never a 'durable' log line the supervisor then
    trusts."""
    from torchacc_tpu.errors import CheckpointError
    d = str(tmp_path / "ckpt")
    t = _trainer(loss=chaos_loss())
    with pytest.raises(CheckpointError, match="did not become durable"):
        with ChaosPlan(seed=CHAOS_SEED).fail("tiered.tier1", times=1):
            t.fit(ChaosLoader(_batches(6), preempt_after_step=2),
                  max_steps=6, log_every=0, checkpoint_dir=d,
                  checkpoint_every=1000)
    assert counters.get("tiered_write_failures") == 1


def test_refuse_quarantined_respects_shrunken_world(tmp_path):
    """Host ids renumber after an elastic shrink: a quarantine recorded
    at a LARGER world size must not refuse the shrunken pod (the
    documented remediation — restart excluding the host — would
    otherwise brick the run forever)."""
    d = str(tmp_path / "run")
    os.makedirs(d)
    with open(os.path.join(d, "sdc_quarantine.json"), "w") as f:
        json.dump({"hosts": {"0": {"step": 1, "kind": "replica",
                                   "world": 2}}}, f)
    t = _trainer(refuse_quarantined=True)
    t.fit(_batches(2), max_steps=2, log_every=0, checkpoint_dir=d,
          checkpoint_every=1000)  # world 1 < recorded 2: no refusal
    assert t._host_step == 2


def test_refuse_quarantined_ignores_out_of_pod_hosts(tmp_path):
    """A quarantined host id beyond the current world size is already
    excluded — the enforcement must not refuse the shrunken pod."""
    from torchacc_tpu.resilience.sdc import record_quarantine
    d = str(tmp_path / "run")
    record_quarantine(d, [7], step=1, kind="replica", report=[])
    t = _trainer(refuse_quarantined=True)
    t.fit(_batches(2), max_steps=2, log_every=0, checkpoint_dir=d,
          checkpoint_every=1000)
    assert t._host_step == 2


# -- CLI ----------------------------------------------------------------------

def test_inspect_cli_shows_tier_table(tmp_path, capsys):
    from torchacc_tpu.checkpoint.cli import main as cli_main
    d = str(tmp_path / "ckpt")
    mirror = str(tmp_path / "mirror")
    t = _trainer(mirror=mirror)
    t.fit(_batches(4), max_steps=4, log_every=0, checkpoint_dir=d,
          checkpoint_every=2)
    assert cli_main(["inspect", d, "--mirror", mirror]) == 0
    out = capsys.readouterr().out
    assert "tiers:" in out
    assert "step 4: tier1=committed tier2=committed" in out
    assert "trickle: submitted=4" in out


# -- 2-process peer-RAM restore ----------------------------------------------

_PEER_WORKER = """
import os, sys
port, pid = sys.argv[1], int(sys.argv[2])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
from torchacc_tpu.parallel.distributed import initialize_distributed
initialize_distributed(coordinator_address=f"localhost:{port}",
                       num_processes=2, process_id=pid)
assert jax.process_count() == 2

import numpy as np, optax
import jax.numpy as jnp
import torchacc_tpu as ta
from torchacc_tpu.models import get_preset
from torchacc_tpu.train import accelerate
from torchacc_tpu.utils.metrics import counters

ckpt = sys.argv[3]
def make_trainer():
    cfg = ta.Config(dist=ta.DistConfig(dp=ta.DPConfig(size=4)),
                    resilience=ta.ResilienceConfig(
                        tiered_checkpointing=True),
                    perf=ta.PerfConfig(dispatch_depth=2))
    mc = get_preset("llama-tiny", vocab_size=64, hidden_size=32,
                    num_layers=2, num_heads=4, num_kv_heads=2,
                    intermediate_size=64, dtype=jnp.float32)
    tr, _ = accelerate(mc, None, cfg, optimizer=optax.sgd(1e-2))
    return tr

trainer = make_trainer()
trainer.init()
from jax.experimental import multihost_utils
from jax.sharding import PartitionSpec as PS
def batches(n):
    out = []
    for i in range(n):
        local = np.random.default_rng(100 + 10 * i + pid).integers(
            0, 64, (8, 16)).astype(np.int32)
        out.append({"input_ids":
            multihost_utils.host_local_array_to_global_array(
                local, trainer.mesh, PS(("dp", "fsdp"), ("sp", "spu")))})
    return out

trainer.fit(batches(4), max_steps=4, log_every=0, checkpoint_dir=ckpt,
            checkpoint_every=2)

# --- restart simulation: process 1 loses its trainer (and with it the
# tier-0 RAM store); process 0 stays healthy.  Both re-enter
# fit(resume='auto') together — the tiered restore consensus picks the
# newest RAM step pod-wide and process 0 donates it over the
# coordination layer.  Orbax restore is stubbed to raise on BOTH
# processes: the rejoin must not read checkpoint arrays from storage.
if pid == 1:
    trainer = make_trainer()

import orbax.checkpoint as ocp
def boom(*a, **k):
    raise AssertionError("storage restore attempted on the peer-RAM path")
ocp.StandardCheckpointer.restore = boom
ocp.CheckpointManager.restore = boom

counters.reset()
h = trainer.fit(batches(6), max_steps=6, log_every=0, checkpoint_dir=ckpt,
                checkpoint_every=1000, resume="auto")
assert counters.get("ram_restores") == 1, counters.snapshot()
assert counters.get("peer_restores") == (1 if pid == 1 else 0), \\
    counters.snapshot()

# bitwise agreement across the pod after the rejoin
from torchacc_tpu.resilience.sdc import host_digests
from torchacc_tpu.resilience import coordination as coord
digs = host_digests(jax.device_get(trainer.state.params))
mine = [(k, digs[k]["bits_xor"], digs[k]["bits_sum"])
        for k in sorted(digs)]
import json as _json
blob = np.frombuffer(
    _json.dumps(mine).encode().ljust(65536), dtype=np.uint8)
ref = coord.broadcast_from_primary(blob, name="digest-compare")
assert np.array_equal(np.asarray(ref), blob), "post-rejoin params differ"
print(f"proc {pid} ok peer-ram-restore bitwise", flush=True)
"""


def _run_two_procs(worker_src, worker_arg):
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    procs = [subprocess.Popen(
        [sys.executable, "-c", worker_src, str(port), str(i), worker_arg],
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
        assert f"proc {i} ok" in out, out[-2000:]
    return outs


@pytest.mark.slow
@pytest.mark.multihost
def test_two_process_peer_ram_restore(tmp_path):
    """A restarted host rejoins from a healthy peer's tier-0 host-RAM
    snapshot: bitwise-identical params pod-wide, zero storage restores
    (orbax restore stubbed to raise on both processes)."""
    _run_two_procs(_PEER_WORKER, str(tmp_path / "ckpt"))
