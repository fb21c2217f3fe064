"""Elastic-resume tests: topology-change-safe restore, durable loader
state, and bad-batch quarantine (docs/resilience.md "Elastic resume").

``CHAOS_SEED`` (``make chaos-elastic`` runs 0..2) shifts the corrupt
batch positions and the mid-epoch resume step, so three schedules
exercise the same guarantees.  The subprocess fixtures (slow) prove the
acceptance scenario: a checkpoint saved at DP=2 restores at DP=1 (and
back) with matching loss trajectories at equal global batch, while a
tp change fails with a typed ``TopologyMismatchError`` naming the axis.
"""

import itertools
import json
import os
import socket
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchacc_tpu as ta
from torchacc_tpu.checkpoint import CheckpointManager
from torchacc_tpu.data import AsyncLoader, PackedDataset
from torchacc_tpu.errors import (
    BadBatchError,
    DataLoaderError,
    StateSchemaError,
    TopologyMismatchError,
)
from torchacc_tpu.resilience import ChaosPlan, clear_preemption
from torchacc_tpu.utils.metrics import counters

pytestmark = pytest.mark.elastic

CHAOS_SEED = int(os.environ.get("CHAOS_SEED", "0"))


@pytest.fixture(autouse=True)
def _clean_process_state():
    counters.reset()
    clear_preemption()
    yield
    clear_preemption()


def _docs(n=120, seed=42):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 64, size=int(rng.integers(4, 14)))
            .astype(np.int32) for _ in range(n)]


def _pd(docs, **kw):
    kw.setdefault("seq_len", 16)
    kw.setdefault("batch_rows", 8)
    kw.setdefault("buffer_docs", 32)
    return PackedDataset(docs, kw.pop("seq_len"), kw.pop("batch_rows"), **kw)


def _cfg(**res_kwargs):
    res_kwargs.setdefault("retry_base_delay_s", 0.001)
    res_kwargs.setdefault("retry_max_delay_s", 0.002)
    return ta.Config(dist=ta.DistConfig(dp=ta.DPConfig(size=8)),
                     resilience=ta.ResilienceConfig(**res_kwargs))


def _assert_batches_equal(got, want):
    assert len(got) == len(want), (len(got), len(want))
    for a, b in zip(got, want):
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))


# -- durable PackedDataset state ----------------------------------------------

def test_packed_dataset_state_resume_bitwise():
    """Arbitrary mid-epoch save/restore delivers the identical remaining
    batch sequence, bitwise, via the O(1) seek path."""
    docs = _docs()
    ref = list(_pd(docs))
    k = 2 + CHAOS_SEED % 4
    ds = _pd(docs)
    it = iter(ds)
    for _ in range(k):
        next(it)
    sd = ds.state_dict()
    assert sd["batches_consumed"] == k and sd["seekable"]
    fresh = _pd(docs)
    fresh.load_state_dict(sd)
    _assert_batches_equal(list(fresh), ref[k:])
    assert counters.get("resume_replayed_batches") == 0


def test_packed_dataset_shuffle_resume_bitwise():
    docs = _docs()
    ds = _pd(docs, shuffle_seed=5)
    ref = list(ds)
    # epoch advanced after the completed pass: a new iteration shuffles
    # differently
    second_epoch = list(ds)
    assert any(
        not np.array_equal(a["input_ids"], b["input_ids"])
        for a, b in zip(ref, second_epoch))
    k = 3 + CHAOS_SEED % 3
    ds2 = _pd(docs, shuffle_seed=5)
    it = iter(ds2)
    for _ in range(k):
        next(it)
    fresh = _pd(docs, shuffle_seed=5)
    fresh.load_state_dict(ds2.state_dict())
    _assert_batches_equal(list(fresh), ref[k:])


def test_packed_dataset_shard_slices_compose_global():
    """batch_rows is GLOBAL: the shards' slices concatenate to the
    num_shards=1 stream — the invariant elastic resume relies on."""
    docs = _docs()
    ref = list(_pd(docs))
    s0 = list(_pd(docs, num_shards=2, shard_index=0))
    s1 = list(_pd(docs, num_shards=2, shard_index=1))
    assert len(s0) == len(s1) == len(ref)
    for a, b, r in zip(s0, s1, ref):
        for k in r:
            np.testing.assert_array_equal(
                np.concatenate([a[k], b[k]]), r[k])


def test_packed_dataset_state_geometry_mismatch_typed():
    docs = _docs()
    ds = _pd(docs)
    it = iter(ds)
    next(it)
    sd = ds.state_dict()
    with pytest.raises(DataLoaderError):
        _pd(docs, seq_len=32).load_state_dict(sd)
    with pytest.raises(DataLoaderError):
        _pd(docs, batch_rows=4).load_state_dict(sd)
    with pytest.raises(DataLoaderError):
        _pd(docs, shuffle_seed=1).load_state_dict(sd)
    # a pure shard change is elastic, not an error
    _pd(docs, num_shards=2, shard_index=1).load_state_dict(sd)


def test_packed_dataset_shard_change_resume_matches_global():
    """Save at 2 shards, resume at 1 (and back): the remaining GLOBAL
    batches are identical — the loader half of elastic resume."""
    docs = _docs()
    ref = list(_pd(docs))
    k = 2 + CHAOS_SEED % 3
    ds = _pd(docs, num_shards=2, shard_index=0)
    it = iter(ds)
    for _ in range(k):
        next(it)
    sd = ds.state_dict()
    # 2 shards -> 1
    whole = _pd(docs)
    whole.load_state_dict(sd)
    _assert_batches_equal(list(whole), ref[k:])
    # 1 shard -> 2: slices of the same global remainder
    sd1 = dict(sd)
    sd1.update(num_shards=1, shard_index=0)
    h0, h1 = (_pd(docs, num_shards=2, shard_index=i) for i in (0, 1))
    h0.load_state_dict(sd1)
    h1.load_state_dict(sd1)
    for a, b, r in zip(list(h0), list(h1), ref[k:]):
        for key in r:
            np.testing.assert_array_equal(
                np.concatenate([a[key], b[key]]), r[key])


# -- AsyncLoader durable state ------------------------------------------------

class _CountingDocs:
    """Sequence source recording which document indices were read."""

    def __init__(self, docs):
        self.docs = docs
        self.accessed = []

    def __len__(self):
        return len(self.docs)

    def __getitem__(self, i):
        self.accessed.append(int(i))
        return self.docs[i]


def test_async_loader_state_resume_no_replay(devices):
    """satellite: loader-state resume delivers the identical batches
    bitwise AND provably never re-reads the consumed prefix."""
    docs = _docs()
    cfg = _cfg()
    ref = list(AsyncLoader(_pd(docs), cfg))
    k = 3 + CHAOS_SEED % 3
    al = AsyncLoader(_pd(docs), cfg)
    it = iter(al)
    for _ in range(k):
        next(it)
    sd = al.state_dict()
    it.close()
    assert sd["batches_consumed"] == k

    src = _CountingDocs(docs)
    al2 = AsyncLoader(_pd(src), cfg)
    al2.load_state_dict(sd)
    rest = list(al2)
    assert counters.get("resume_replayed_batches") == 0
    assert len(rest) == len(ref) - k
    for a, b in zip(rest, ref[k:]):
        for key in a:
            np.testing.assert_array_equal(np.asarray(a[key]),
                                          np.asarray(b[key]))
    # O(1) proof: the resumed iteration starts reading documents at the
    # group containing the resume row — the consumed prefix's documents
    # are never touched again
    from bisect import bisect_right
    start_group = bisect_right(sd["source"]["group_cum_rows"], k * 8)
    assert min(src.accessed) == start_group * 32
    if start_group:
        assert min(src.accessed) > 0


def test_skip_replay_vs_state_resume_equivalence(devices):
    """satellite: the two resume paths deliver the SAME batches,
    bitwise, from an arbitrary mid-epoch step."""
    docs = _docs()
    cfg = _cfg()
    k = 2 + CHAOS_SEED % 4
    ref = list(AsyncLoader(_pd(docs), cfg))

    # path A: durable state (O(1) seek)
    al = AsyncLoader(_pd(docs), cfg)
    it = iter(al)
    for _ in range(k):
        next(it)
    sd = al.state_dict()
    it.close()
    a_loader = AsyncLoader(_pd(docs), cfg)
    a_loader.load_state_dict(sd)
    path_a = list(a_loader)
    assert counters.get("resume_replayed_batches") == 0

    # path B: skip-replay
    path_b = list(AsyncLoader(_pd(docs), cfg).skip_batches(k))

    for a, b, r in zip(path_a, path_b, ref[k:]):
        for key in r:
            np.testing.assert_array_equal(np.asarray(a[key]),
                                          np.asarray(b[key]))
            np.testing.assert_array_equal(np.asarray(a[key]),
                                          np.asarray(r[key]))
    assert len(path_a) == len(path_b) == len(ref) - k


def test_async_loader_replay_fallback_counts(devices):
    """Non-seekable source: resume falls back to replay, counted +
    logged, batches still bitwise identical."""
    docs = _docs()
    cfg = _cfg()
    ref = list(AsyncLoader(_pd(docs), cfg))
    k = 3
    al = AsyncLoader(_pd(docs), cfg)
    it = iter(al)
    for _ in range(k):
        next(it)
    sd = al.state_dict()
    it.close()
    counters.reset()
    al2 = AsyncLoader(_pd(iter(docs)), cfg)  # iterator: not seekable
    al2.load_state_dict(sd)
    rest = list(al2)
    assert counters.get("resume_replayed_batches") == k
    assert len(rest) == len(ref) - k
    for a, b in zip(rest, ref[k:]):
        np.testing.assert_array_equal(np.asarray(a["input_ids"]),
                                      np.asarray(b["input_ids"]))


# -- bad-batch quarantine -----------------------------------------------------

def _float_batches(n, seed=0):
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(0, 64, size=(8, 16)).astype(np.int32),
             "weights": rng.random((8,)).astype(np.float32)}
            for _ in range(n)]


def test_bad_batch_quarantined_skipped_and_dumped(tmp_path, devices):
    qdir = str(tmp_path / "quarantine")
    cfg = _cfg(batch_validation=True, max_consecutive_bad_batches=3,
               quarantine_dir=qdir)
    bs = _float_batches(6)
    m = 1 + CHAOS_SEED % 3
    with ChaosPlan(seed=CHAOS_SEED).corrupt_batch(
            at=[m], mode="nonfinite") as plan:
        out = list(AsyncLoader(bs, cfg))
    assert len(out) == 5
    assert counters.get("bad_batches_skipped") == 1
    assert plan.stats()["batch.corrupt"]["raised"] == 1
    # the stream continues with the NEXT batch — nothing reordered
    np.testing.assert_array_equal(np.asarray(out[m]["input_ids"]),
                                  bs[m + 1]["input_ids"])
    # evidence: npz payload + json provenance naming index and reason
    prov_files = sorted(p for p in os.listdir(qdir) if p.endswith(".json"))
    assert prov_files, os.listdir(qdir)
    prov = json.load(open(os.path.join(qdir, prov_files[0])))
    assert prov["index"] == m
    assert "non-finite" in prov["reason"]
    assert os.path.exists(os.path.join(
        qdir, prov_files[0].replace(".json", ".npz")))


def test_bad_batch_error_after_k_consecutive(devices):
    cfg = _cfg(batch_validation=True, max_consecutive_bad_batches=3)
    with ChaosPlan(seed=CHAOS_SEED).corrupt_batch(at=[1, 2, 3],
                                                  mode="shape"):
        with pytest.raises(BadBatchError) as ei:
            list(AsyncLoader(_float_batches(8), cfg))
    assert ei.value.consecutive == 3
    assert "shape" in ei.value.reason
    assert counters.get("bad_batches_skipped") == 3


def test_bad_batch_structure_and_dtype_modes(devices):
    cfg = _cfg(batch_validation=True, max_consecutive_bad_batches=8)
    with ChaosPlan(seed=CHAOS_SEED).corrupt_batch(at=[1], mode="drop_key"):
        out = list(AsyncLoader(_float_batches(4), cfg))
    assert len(out) == 3 and counters.get("bad_batches_skipped") == 1
    counters.reset()
    with ChaosPlan(seed=CHAOS_SEED).corrupt_batch(at=[2], mode="dtype"):
        out = list(AsyncLoader(_float_batches(4), cfg))
    assert len(out) == 3 and counters.get("bad_batches_skipped") == 1


def test_validation_off_passes_corrupt_batches(devices):
    # the guard is opt-in: without batch_validation the corrupted batch
    # flows through (and would poison the loss — the PR-1 nan_guard's
    # job, not the loader's)
    cfg = _cfg()
    with ChaosPlan(seed=CHAOS_SEED).corrupt_batch(at=[1], mode="nonfinite"):
        out = list(AsyncLoader(_float_batches(4), cfg))
    assert len(out) == 4
    assert counters.get("bad_batches_skipped") == 0


def test_state_resume_after_quarantined_batch_seeks_source(devices):
    """A quarantined batch consumes a SOURCE position without being
    delivered: resume must seek past it (source_position), or the
    offender's successor would be trained twice (regression caught by
    the end-to-end verify drive)."""
    docs = _docs()
    cfg = _cfg(batch_validation=True, max_consecutive_bad_batches=4)
    m = 1 + CHAOS_SEED % 2
    # clean reference stream with the offender's position skipped
    ref = list(AsyncLoader(_pd(docs), _cfg()))
    clean = ref[:m] + ref[m + 1:]

    al = AsyncLoader(_pd(docs), cfg)
    with ChaosPlan(seed=CHAOS_SEED).corrupt_batch(at=[m], mode="nonfinite"):
        it = iter(al)
        got = [next(it) for _ in range(m + 2)]  # rides past the offender
        sd = al.state_dict()
        it.close()
    assert counters.get("bad_batches_skipped") == 1
    assert sd["batches_consumed"] == m + 2
    assert sd["source_position"] == m + 3  # offender consumed a slot
    for a, b in zip(got, clean):
        np.testing.assert_array_equal(np.asarray(a["input_ids"]),
                                      np.asarray(b["input_ids"]))

    al2 = AsyncLoader(_pd(docs), cfg)
    al2.load_state_dict(sd)
    rest = list(al2)
    assert len(rest) == len(clean) - (m + 2)
    for a, b in zip(rest, clean[m + 2:]):
        np.testing.assert_array_equal(np.asarray(a["input_ids"]),
                                      np.asarray(b["input_ids"]))


# -- topology-aware checkpoints (fast, mesh-level) ----------------------------

def _mesh_state(mesh, mult=1.0):
    from jax.sharding import NamedSharding, PartitionSpec
    sh = NamedSharding(mesh, PartitionSpec(tuple(mesh.shape.keys())[0]))
    rep = NamedSharding(mesh, PartitionSpec())
    return {"w": jax.device_put(np.arange(32.0, dtype=np.float32)
                                .reshape(8, 4) * mult, sh),
            "step": jax.device_put(np.asarray(mult, np.float32), rep)}


def _mesh_abstract(mesh):
    from jax.sharding import NamedSharding, PartitionSpec
    sh = NamedSharding(mesh, PartitionSpec(tuple(mesh.shape.keys())[0]))
    rep = NamedSharding(mesh, PartitionSpec())
    return {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32, sharding=sh),
            "step": jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)}


def test_topology_mismatch_typed_and_elastic(tmp_path, devices):
    from jax.sharding import Mesh
    d = str(tmp_path / "ckpt")
    mesh_dp8 = Mesh(np.asarray(devices), ("dp",))
    mgr = CheckpointManager(d)
    assert mgr.save(1, _mesh_state(mesh_dp8, 3.0))
    mgr.close()

    # manifest records the schema
    manifest = json.load(open(os.path.join(d, "1", "_MANIFEST")))
    assert manifest["schema"]["mesh"] == {"dp": 8}
    assert manifest["schema"]["process_count"] == 1
    assert manifest["schema"]["leaf_specs"]["w"]["shape"] == [8, 4]

    # dp 8 -> 4 without elastic: typed error naming the axis, not an
    # orbax traceback
    mesh_dp4 = Mesh(np.asarray(devices[:4]), ("dp",))
    strict = CheckpointManager(d)
    with pytest.raises(TopologyMismatchError) as ei:
        strict.restore_latest_valid(_mesh_abstract(mesh_dp4))
    assert ei.value.axes == ["dp"]
    assert "mesh axis 'dp': saved 8 -> current 4" in str(ei.value)
    strict.close()

    # with elastic_resume: restores, resharded, counted
    elastic = CheckpointManager(d, elastic_resume=True)
    state, step = elastic.restore_latest_valid(_mesh_abstract(mesh_dp4))
    assert step == 1
    assert counters.get("elastic_reshards") == 1
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(state["w"])),
        np.arange(32.0, dtype=np.float32).reshape(8, 4) * 3.0)
    elastic.close()

    # tp change: rejected even with elastic_resume, naming the axis
    mesh_tp = Mesh(np.asarray(devices[:2]), ("tp",))
    tp_mgr = CheckpointManager(d, elastic_resume=True)
    with pytest.raises(TopologyMismatchError) as ei:
        tp_mgr.restore_latest_valid(_mesh_abstract(mesh_tp))
    assert "tp" in ei.value.axes
    tp_mgr.close()


def test_state_schema_error_carries_leaf_diff(tmp_path, devices):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    d = str(tmp_path / "ckpt")
    mesh = Mesh(np.asarray(devices), ("dp",))
    mgr = CheckpointManager(d)
    assert mgr.save(1, _mesh_state(mesh))
    mgr.wait_until_finished()
    rep = NamedSharding(mesh, PartitionSpec())
    wrong = {"w": jax.ShapeDtypeStruct((4, 4), jnp.float32, sharding=rep),
             "step": jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)}
    with pytest.raises(StateSchemaError) as ei:
        mgr.restore(wrong, step=1)
    assert any("w" in line and "(8, 4)" in line for line in ei.value.diff)
    mgr.close()


def test_schema_drift_surfaces_typed_not_silent_fresh_start(tmp_path,
                                                            devices):
    """When EVERY retained step's state tree mismatches (the model
    changed), restore_latest_valid must raise the typed StateSchemaError
    with the per-leaf diff — which resume='auto' does NOT swallow —
    instead of a corruption verdict that silently retrains from step 0."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    d = str(tmp_path / "ckpt")
    mesh = Mesh(np.asarray(devices), ("dp",))
    mgr = CheckpointManager(d)
    mgr.save(1, _mesh_state(mesh))
    mgr.save(2, _mesh_state(mesh, 2.0))
    mgr.wait_until_finished()
    rep = NamedSharding(mesh, PartitionSpec())
    drifted = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32, sharding=rep),
               "renamed": jax.ShapeDtypeStruct((), jnp.float32,
                                               sharding=rep)}
    with pytest.raises(StateSchemaError) as ei:
        mgr.restore_latest_valid(drifted)
    assert any("renamed" in line for line in ei.value.diff)
    mgr.close()


def test_loader_state_numpy_scalars_serialise(tmp_path, devices):
    """A source state carrying numpy scalars must not kill the commit
    protocol: either serialised (numbers/lists) or skipped with a
    warning — never an uncaught TypeError that loses pending markers."""
    from jax.sharding import Mesh
    d = str(tmp_path / "ckpt")
    mesh = Mesh(np.asarray(devices), ("dp",))
    mgr = CheckpointManager(d)
    lstate = {"version": 1, "batches_consumed": np.int64(7),
              "source": {"offsets": np.asarray([1, 2, 3])}}
    assert mgr.save(1, _mesh_state(mesh), loader_state=lstate)
    mgr.wait_until_finished()
    assert os.path.exists(os.path.join(d, "1", "_MANIFEST"))
    got = mgr.read_loader_state(1)
    assert got["batches_consumed"] == 7
    assert got["source"]["offsets"] == [1, 2, 3]
    # genuinely unserialisable state: step still commits, state skipped
    bad = {"cb": lambda: None}
    assert mgr.save(2, _mesh_state(mesh, 2.0), loader_state=bad)
    mgr.wait_until_finished()
    assert os.path.exists(os.path.join(d, "2", "_MANIFEST"))
    assert mgr.read_loader_state(2) is None
    mgr.close()


def test_loader_state_rides_the_commit_protocol(tmp_path, devices):
    from jax.sharding import Mesh
    d = str(tmp_path / "ckpt")
    mesh = Mesh(np.asarray(devices), ("dp",))
    mgr = CheckpointManager(d)
    lstate = {"version": 1, "kind": "async_loader", "batches_consumed": 7,
              "source": None}
    assert mgr.save(1, _mesh_state(mesh), loader_state=lstate)
    mgr.wait_until_finished()
    assert os.path.exists(os.path.join(d, "1", "loader_state.json"))
    assert mgr.read_loader_state(1) == lstate
    assert mgr.read_loader_state(99) is None
    # the extra file never confuses the payload probe
    assert mgr._probe_step(1) is None
    mgr.close()


def test_cli_inspect_and_dry_run(tmp_path, devices, capsys):
    from jax.sharding import Mesh

    from torchacc_tpu.checkpoint.cli import main
    d = str(tmp_path / "ckpt")
    mesh = Mesh(np.asarray(devices), ("dp",))
    mgr = CheckpointManager(d)
    mgr.save(2, _mesh_state(mesh))
    mgr.close()

    assert main(["inspect", d, "--leaves"]) == 0
    out = capsys.readouterr().out
    assert "step 2" in out and "dp=8" in out
    assert "w: (8, 4) float32" in out

    # reshard --dry-run: prints the plan + diff, writes nothing
    dst = str(tmp_path / "resharded")
    rc = main(["--ckpt_dir", os.path.join(d, "2", "default"),
               "--save_dir", dst, "--reshard_num", "2", "--dry-run"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "would reshard" in out
    assert not os.path.exists(dst)
    # consolidate --dry-run
    rc = main(["--ckpt_dir", os.path.join(d, "2", "default"),
               "--save_dir", dst, "--dry-run"])
    assert rc == 0
    assert "would consolidate" in capsys.readouterr().out
    assert not os.path.exists(dst)


# -- trainer-level elastic fit (slow, in-process) -----------------------------

def _model():
    from torchacc_tpu.models import get_preset
    return get_preset("llama-tiny", vocab_size=64, hidden_size=32,
                      num_layers=1, num_heads=2, num_kv_heads=2,
                      intermediate_size=64, dtype=jnp.float32)


@pytest.mark.slow
def test_fit_loader_state_resume_bitwise(tmp_path, devices):
    """fit -> checkpoint (with loader_state.json) -> fresh fit resume:
    O(1) loader-state resume, zero replayed batches, final params
    bitwise equal to the uninterrupted run."""
    import optax

    from torchacc_tpu.train import accelerate
    docs = _docs(200)

    def mk():
        cfg = _cfg()
        t, _ = accelerate(_model(), None, cfg, optimizer=optax.adam(1e-3))
        return t, AsyncLoader(_pd(docs), cfg)

    ref, ref_loader = mk()
    ref.fit(ref_loader, max_steps=8, log_every=0)

    d = str(tmp_path / "run")
    t1, l1 = mk()
    t1.fit(l1, max_steps=8, log_every=0, checkpoint_dir=d,
           checkpoint_every=3)
    counters.reset()
    t2, l2 = mk()
    t2.fit(l2, max_steps=8, log_every=0, checkpoint_dir=d,
           checkpoint_every=1000, resume="auto")
    assert counters.get("resumes") == 1
    assert counters.get("resume_replayed_batches") == 0
    assert int(t2.state.step) == 8
    for a, b in zip(jax.tree.leaves(jax.device_get(ref.state.params)),
                    jax.tree.leaves(jax.device_get(t2.state.params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_chaos_corrupt_batch_run_finishes_green(tmp_path, devices):
    """Acceptance: a corrupt-batch chaos run finishes with
    bad_batches_skipped > 0 and a loss history for every clean step."""
    import optax

    from torchacc_tpu.train import accelerate
    docs = _docs(200)
    qdir = str(tmp_path / "q")
    cfg = _cfg(batch_validation=True, max_consecutive_bad_batches=4,
               quarantine_dir=qdir)
    t, _ = accelerate(_model(), None, cfg, optimizer=optax.adam(1e-3))
    loader = AsyncLoader(_pd(docs), cfg)
    m = 2 + CHAOS_SEED % 3
    with ChaosPlan(seed=CHAOS_SEED).corrupt_batch(at=[m], mode="nonfinite"):
        hist = t.fit(loader, max_steps=6, log_every=1,
                     metrics_dir=str(tmp_path / "metrics"))
    assert counters.get("bad_batches_skipped") == 1
    assert int(t.state.step) == 6
    assert all(np.isfinite(rec["loss"]) for rec in hist)
    # the counter rides the metrics.jsonl step records (satellite)
    recs = [json.loads(line) for line in
            open(os.path.join(tmp_path, "metrics", "metrics.jsonl"))]
    assert any(r.get("train/bad_batches_skipped", 0) >= 1 for r in recs)
    assert os.listdir(qdir)


# -- 2-process elastic fixtures (slow, subprocess) ----------------------------

_PREAMBLE = """
import os, sys, json, itertools
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
import jax
import numpy as np
import jax.numpy as jnp
import optax
import torchacc_tpu as ta
from torchacc_tpu.checkpoint import CheckpointManager
from torchacc_tpu.data import AsyncLoader, PackedDataset
from torchacc_tpu.models import get_preset
from torchacc_tpu.train import accelerate
from torchacc_tpu.utils.metrics import counters

def model():
    return get_preset("llama-tiny", vocab_size=64, hidden_size=32,
                      num_layers=1, num_heads=2, num_kv_heads=2,
                      intermediate_size=64, dtype=jnp.float32)

def docs():
    rng = np.random.default_rng(42)
    return [rng.integers(1, 64, size=int(rng.integers(4, 14)))
            .astype(np.int32) for _ in range(120)]

def pd(num_shards=1, shard_index=0):
    return PackedDataset(docs(), 16, 8, buffer_docs=32,
                         num_shards=num_shards, shard_index=shard_index)
"""

# Two jax.distributed processes (1 device each, mesh dp=2) train 3
# steps on the GLOBAL batch (each host feeding its row shard) and save
# step 3 with durable loader state into a shared directory.
_SAVE2_WORKER = _PREAMBLE % 1 + """
port, pid, base = sys.argv[1], int(sys.argv[2]), sys.argv[3]
from torchacc_tpu.parallel.distributed import initialize_distributed
initialize_distributed(coordinator_address=f"localhost:{port}",
                       num_processes=2, process_id=pid)
assert jax.process_count() == 2 and len(jax.devices()) == 2
cfg = ta.Config(dist=ta.DistConfig(dp=ta.DPConfig(size=2)))
trainer, _ = accelerate(model(), None, cfg, optimizer=optax.sgd(1e-2))
trainer.init()
from jax.experimental import multihost_utils
from jax.sharding import PartitionSpec as PS
src = pd(num_shards=2, shard_index=pid)
it = iter(src)
losses = []
for i in range(3):
    local = next(it)
    batch = {k: multihost_utils.host_local_array_to_global_array(
        v, trainer.mesh, PS(("dp", "fsdp"), None)) for k, v in local.items()}
    losses.append(float(trainer.step(batch)["loss"]))
mgr = CheckpointManager(base, coord_timeout_s=120.0)
lstate = {"version": 1, "kind": "async_loader", "batches_consumed": 3,
          "source": src.state_dict()}
mgr.save(3, trainer.state, loader_state=lstate)
mgr.wait_until_finished()
mgr.close()
print(f"proc {pid} ok LOSSES=" + json.dumps(losses), flush=True)
"""

# One process, one device (mesh dp=1): elastic-restore the DP=2
# checkpoint, restore the loader state at the new world size, continue
# steps 4..6 at EQUAL global batch.
_RESUME1_WORKER = _PREAMBLE % 1 + """
base = sys.argv[1]
cfg = ta.Config()
trainer, _ = accelerate(model(), None, cfg, optimizer=optax.sgd(1e-2))
mgr = CheckpointManager(base, elastic_resume=True)
state, step = mgr.restore_latest_valid(trainer.abstract_state())
assert step == 3, step
trainer.state = trainer._adopt_restored(state)
assert counters.get("elastic_reshards") >= 1, counters.snapshot()
lstate = mgr.read_loader_state(3)
assert lstate is not None
mgr.close()
loader = AsyncLoader(pd(), cfg)
loader.load_state_dict(lstate)
losses = [float(trainer.step(b)["loss"])
          for b in itertools.islice(iter(loader), 3)]
assert counters.get("resume_replayed_batches") == 0, counters.snapshot()
print("ok LOSSES=" + json.dumps(losses), flush=True)
"""

# Single process trains 6 uninterrupted reference steps (dp=1).
_REF_WORKER = _PREAMBLE % 1 + """
cfg = ta.Config()
trainer, _ = accelerate(model(), None, cfg, optimizer=optax.sgd(1e-2))
trainer.init()
loader = AsyncLoader(pd(), cfg)
losses = [float(trainer.step(b)["loss"])
          for b in itertools.islice(iter(loader), 6)]
print("ok LOSSES=" + json.dumps(losses), flush=True)
"""

# Single process saves step 3 (dp=1) for the DP=1 -> DP=2 direction.
_SAVE1_WORKER = _PREAMBLE % 1 + """
base = sys.argv[1]
cfg = ta.Config()
trainer, _ = accelerate(model(), None, cfg, optimizer=optax.sgd(1e-2))
trainer.init()
src = pd()
it = iter(src)
losses = []
for i in range(3):
    losses.append(float(trainer.step(next(it))["loss"]))
mgr = CheckpointManager(base)
lstate = {"version": 1, "kind": "async_loader", "batches_consumed": 3,
          "source": src.state_dict()}
mgr.save(3, trainer.state, loader_state=lstate)
mgr.wait_until_finished()
mgr.close()
print("ok LOSSES=" + json.dumps(losses), flush=True)
"""

# Two processes (mesh dp=2) elastic-restore the DP=1 checkpoint and
# continue steps 4..6, each feeding its recomputed row shard.
_RESUME2_WORKER = _PREAMBLE % 1 + """
port, pid, base = sys.argv[1], int(sys.argv[2]), sys.argv[3]
from torchacc_tpu.parallel.distributed import initialize_distributed
initialize_distributed(coordinator_address=f"localhost:{port}",
                       num_processes=2, process_id=pid)
assert jax.process_count() == 2
cfg = ta.Config(dist=ta.DistConfig(dp=ta.DPConfig(size=2)))
trainer, _ = accelerate(model(), None, cfg, optimizer=optax.sgd(1e-2))
mgr = CheckpointManager(base, elastic_resume=True, coord_timeout_s=120.0)
state, step = mgr.restore_latest_valid(trainer.abstract_state())
assert step == 3, step
trainer.state = trainer._adopt_restored(state)
assert counters.get("elastic_reshards") >= 1, counters.snapshot()
lstate = mgr.read_loader_state(3)
assert lstate is not None
mgr.close()
src = pd(num_shards=2, shard_index=pid)
inner = dict(lstate["source"])
inner["batches_consumed"] = lstate["batches_consumed"]
src.load_state_dict(inner)
from jax.experimental import multihost_utils
from jax.sharding import PartitionSpec as PS
it = iter(src)
losses = []
for i in range(3):
    local = next(it)
    batch = {k: multihost_utils.host_local_array_to_global_array(
        v, trainer.mesh, PS(("dp", "fsdp"), None)) for k, v in local.items()}
    losses.append(float(trainer.step(batch)["loss"]))
print(f"proc {pid} ok LOSSES=" + json.dumps(losses), flush=True)
"""

# Primary-gated consolidate on a 2-process pod: only process 0 pays the
# host-RAM copy and writes dst (via a single-process-scoped orbax
# checkpointer — the default one's barriers span the pod and would
# deadlock); both processes return with dst durable.
_CONSOLIDATE_WORKER = _PREAMBLE % 1 + """
port, pid, base = sys.argv[1], int(sys.argv[2]), sys.argv[3]
from torchacc_tpu.parallel.distributed import initialize_distributed
initialize_distributed(coordinator_address=f"localhost:{port}",
                       num_processes=2, process_id=pid)
assert jax.process_count() == 2
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from torchacc_tpu.checkpoint import consolidate_checkpoint, save_checkpoint
mesh = Mesh(np.asarray(jax.devices()), ("x",))
rep = NamedSharding(mesh, PartitionSpec())
state = jax.jit(lambda: {"a": jnp.arange(8.0)}, out_shardings=rep)()
src, dst = base + "/src", base + "/dst"
save_checkpoint(src, state)          # collective: every host writes shards
consolidate_checkpoint(src, dst)     # primary-gated, barrier'd
assert os.path.isdir(dst), os.listdir(base)
host = np.asarray(jnp.arange(8.0))
import orbax.checkpoint as ocp
got = ocp.StandardCheckpointer().restore(dst)
np.testing.assert_array_equal(np.asarray(got["a"]), host)
print(f"proc {pid} ok consolidated", flush=True)
"""

# A tp=2 mesh must be rejected with the axis named, even with elastic.
_TP_REJECT_WORKER = _PREAMBLE % 2 + """
base = sys.argv[1]
from torchacc_tpu.errors import TopologyMismatchError
cfg = ta.Config(dist=ta.DistConfig(tp=ta.TPConfig(size=2)))
trainer, _ = accelerate(model(), None, cfg, optimizer=optax.sgd(1e-2))
mgr = CheckpointManager(base, elastic_resume=True)
try:
    mgr.restore_latest_valid(trainer.abstract_state())
    raise AssertionError("expected TopologyMismatchError")
except TopologyMismatchError as e:
    assert "tp" in e.axes, e.axes
    assert "tp" in str(e)
    print("ok TP_REJECTED axes=" + json.dumps(e.axes), flush=True)
finally:
    mgr.close()
"""


def _run(worker_src, *args, timeout=420):
    p = subprocess.run(
        [sys.executable, "-c", worker_src, *[str(a) for a in args]],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        timeout=timeout)
    assert p.returncode == 0, p.stdout[-4000:]
    return p.stdout


def _run_two(worker_src, *args, timeout=420):
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    procs = [subprocess.Popen(
        [sys.executable, "-c", worker_src, str(port), str(i),
         *[str(a) for a in args]],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out[-4000:]}"
        assert f"proc {i} ok" in out, out[-2000:]
    return outs


def _losses(out):
    line = [ln for ln in out.splitlines() if "LOSSES=" in ln][-1]
    return json.loads(line.split("LOSSES=", 1)[1])


@pytest.mark.slow
@pytest.mark.multihost
def test_elastic_dp2_to_dp1_matches_reference(tmp_path):
    """Acceptance: checkpoint saved at DP=2 (two jax.distributed
    processes) restores at DP=1 with the reference loss trajectory at
    equal global batch, via durable loader state with the shard
    assignment recomputed — and a tp 1->2 restore of the same
    checkpoint fails typed, naming the axis."""
    base = str(tmp_path / "shared_ckpt")
    ref = _losses(_run(_REF_WORKER))
    outs = _run_two(_SAVE2_WORKER, base)
    pre = [_losses(o) for o in outs]
    np.testing.assert_allclose(pre[0], pre[1], rtol=1e-6)  # one SPMD prog
    np.testing.assert_allclose(pre[0], ref[:3], rtol=1e-4)
    resumed = _losses(_run(_RESUME1_WORKER, base))
    np.testing.assert_allclose(resumed, ref[3:], rtol=1e-4)
    # incompatible topology: typed rejection naming 'tp'
    out = _run(_TP_REJECT_WORKER, base)
    assert "TP_REJECTED" in out


@pytest.mark.slow
@pytest.mark.multihost
def test_consolidate_primary_gated_no_deadlock(tmp_path):
    """satellite: 2-process consolidate completes (no pod-wide orbax
    barrier entered by one host alone), only the primary writes, and
    the result restores."""
    outs = _run_two(_CONSOLIDATE_WORKER, str(tmp_path / "shared"))
    for out in outs:
        assert "consolidated" in out


@pytest.mark.slow
@pytest.mark.multihost
def test_elastic_dp1_to_dp2_matches_reference(tmp_path):
    """The reverse direction: DP=1 checkpoint resumes on a DP=2 pod."""
    base = str(tmp_path / "shared_ckpt")
    ref = _losses(_run(_REF_WORKER))
    pre = _losses(_run(_SAVE1_WORKER, base))
    np.testing.assert_allclose(pre, ref[:3], rtol=1e-6)
    outs = _run_two(_RESUME2_WORKER, base)
    post = [_losses(o) for o in outs]
    np.testing.assert_allclose(post[0], post[1], rtol=1e-6)
    np.testing.assert_allclose(post[0], ref[3:], rtol=1e-4)
