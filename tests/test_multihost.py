"""Two-process jax.distributed tests over localhost (VERDICT weak-9:
multi-host init had no executed coverage; reference analogue is the
torchrun-driven init_process_group path, dist/__init__.py:45-98).

Each subprocess owns 2 emulated CPU devices; after
``initialize_distributed`` the global mesh spans 4 devices across the
two processes.  Two legs: a dp-sharded step (cross-process gradient
psum) and a 1F1B pipeline step whose ppermute ring crosses the process
boundary (pp = outermost mesh axis).
"""

import socket
import subprocess
import sys

import pytest

_WORKER = """
import os, sys
port, pid = sys.argv[1], int(sys.argv[2])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
from torchacc_tpu.parallel.distributed import initialize_distributed, is_primary
initialize_distributed(coordinator_address=f"localhost:{port}",
                       num_processes=2, process_id=pid)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4, len(jax.devices())

import jax.numpy as jnp
import numpy as np
import optax
import torchacc_tpu as ta
from torchacc_tpu.models import get_preset
from torchacc_tpu.train import accelerate

mode = sys.argv[3]
if mode == "dp":
    cfg = ta.Config(dist=ta.DistConfig(dp=ta.DPConfig(size=4)))
else:  # the 1F1B ppermute ring spans the two PROCESSES (pp outermost)
    cfg = ta.Config(dist=ta.DistConfig(
        pp=ta.PPConfig(size=2, num_micro_batches=2, schedule="1f1b"),
        dp=ta.DPConfig(size=2),
        topology=("pp", "dp", "fsdp", "sp", "spu", "ep", "tp")))
mc = get_preset("llama-tiny", vocab_size=64, hidden_size=32, num_layers=2,
                num_heads=4, num_kv_heads=2, intermediate_size=64,
                dtype=jnp.float32)
trainer, _ = accelerate(mc, None, cfg, optimizer=optax.sgd(1e-2))
trainer.init()
from jax.experimental import multihost_utils
from jax.sharding import PartitionSpec as PS
# dp mode: each process feeds its local dp shard of the global batch.
# pp mode: pp spans the processes, the batch axes are process-local, so
# both processes feed the SAME global batch (seed 0).
seed = pid if mode == "dp" else 0
local = np.random.default_rng(seed).integers(0, 64, (8, 16)).astype(np.int32)
arr = multihost_utils.host_local_array_to_global_array(
    local, trainer.mesh, PS(("dp", "fsdp"), ("sp", "spu")))
loss = float(trainer.step({"input_ids": arr})["loss"])
assert np.isfinite(loss), loss
print(f"proc {pid} ok loss={loss:.4f} primary={is_primary()}", flush=True)
"""


def _run_two_procs(worker_arg, worker_src=None):
    worker_src = worker_src or _WORKER
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    procs = [subprocess.Popen(
        [sys.executable, "-c", worker_src, str(port), str(i),
         worker_arg],
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
def test_two_process_dp_step(tmp_path):
    _run_two_procs("dp")


_CONSENSUS_WORKER = """
import os, sys
port, pid, base = sys.argv[1], int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
from torchacc_tpu.parallel.distributed import initialize_distributed
initialize_distributed(coordinator_address=f"localhost:{port}",
                       num_processes=2, process_id=pid)
assert jax.process_count() == 2, jax.process_count()

import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from torchacc_tpu.checkpoint import CheckpointManager
from torchacc_tpu.resilience import ChaosPlan, preemption
from torchacc_tpu.resilience import coordination as coord
from torchacc_tpu.resilience.retry import RetryPolicy

# -- agreement primitives under genuinely divergent host inputs
assert coord.min_over_hosts(10 + pid) == 10
assert coord.max_over_hosts(10 + pid) == 11
assert coord.any_host(pid == 1) is True
assert coord.all_agree(pid == 1) is False
assert coord.all_agree(True) is True
assert int(coord.broadcast_from_primary(100 + pid)) == 100

# -- preemption sync point: a signal on host 0 reaches BOTH hosts
if pid == 0:
    preemption.request_preemption("chaos: host-0 eviction")
assert preemption.sync_preemption(timeout_s=120) is True
assert preemption.preemption_requested()   # the joined host latched it
preemption.clear_preemption()

# -- save two steps of replicated GLOBAL state into one shared dir
mesh = Mesh(np.asarray(jax.devices()), ("x",))
rep = NamedSharding(mesh, PartitionSpec())
mk = jax.jit(lambda m: {"a": jnp.arange(4.0) * m,
                        "b": {"c": jnp.ones((2, 2)) * m}},
             out_shardings=rep)
mgr = CheckpointManager(
    base, retry_policy=RetryPolicy(max_retries=0, base_delay_s=0.0,
                                   max_delay_s=0.0),
    coord_timeout_s=120.0)
mgr.save(1, mk(1.0))
mgr.save(2, mk(2.0))
mgr.wait_until_finished()
coord.barrier("saved")          # primary's commit markers are visible
abstract = jax.tree.map(
    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
    mk(0.0))

# -- divergent quarantine: ONLY this host fails to read the newest step
# (injected at the collective-free readability probe — the seam where a
# divergent local view is survivable; see io._restore_consensus)
plan = None
if pid == 1:
    plan = ChaosPlan(seed=0).fail("checkpoint.probe", times=1)
    plan.__enter__()
try:
    state, step = mgr.restore_latest_valid(abstract)
finally:
    if plan is not None:
        plan.__exit__(None, None, None)
assert step == 1, step
# the quarantine decision replicated: the shared step-2 dir is renamed
assert os.path.exists(os.path.join(base, "2.corrupt")), os.listdir(base)
assert not os.path.exists(os.path.join(base, "2")), os.listdir(base)
np.testing.assert_array_equal(np.asarray(state["a"]), np.arange(4.0))

# -- bitwise agreement across hosts on every restored leaf AND the step
from jax.experimental import multihost_utils
flat = np.concatenate(
    [np.asarray(x).ravel() for x in jax.tree.leaves(state)])
g = np.asarray(multihost_utils.process_allgather(flat))
assert g.shape[0] == 2, g.shape
np.testing.assert_array_equal(g[0], g[1])
gs = np.asarray(multihost_utils.process_allgather(
    np.asarray(step, np.int64)))
assert int(gs.min()) == int(gs.max()) == 1, gs
mgr.close()
print(f"proc {pid} ok consensus step={step}", flush=True)
"""


@pytest.mark.slow
@pytest.mark.multihost
def test_two_process_resume_consensus(tmp_path):
    """The acceptance fixture for multi-host resilience: two
    jax.distributed CPU processes share a checkpoint directory, save
    steps 1 and 2, then host 1 alone fails to read step 2 (chaos
    failpoint — the divergent-view scenario).  Both hosts must agree on
    the SAME fallback step (min over hosts, broadcast from process 0),
    quarantine the bad step everywhere, and end up with bitwise-equal
    restored params — no split-brain resume."""
    outs = _run_two_procs(str(tmp_path / "shared_ckpt"),
                          worker_src=_CONSENSUS_WORKER)
    for out in outs:
        assert "consensus step=1" in out, out[-2000:]


@pytest.mark.slow
def test_two_process_pp_1f1b_step(tmp_path):
    """The 1F1B ppermute ring crosses the PROCESS boundary: pp is the
    outermost (slowest-network) mesh axis over two jax.distributed
    processes — the multi-host story for the flagship schedule
    (reference analogue: NCCL send/recv between stage processes,
    pp/p2p.py)."""
    outs = _run_two_procs("pp")
    # one SPMD program: both processes report the identical loss
    l0 = outs[0].split("proc 0 ok loss=")[1].split()[0]
    l1 = outs[1].split("proc 1 ok loss=")[1].split()[0]
    assert l0 == l1, (l0, l1)


_STREAM_WORKER = """
import os, sys
port, pid, path = sys.argv[1], int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
from torchacc_tpu.parallel.distributed import initialize_distributed
initialize_distributed(coordinator_address=f"localhost:{port}",
                       num_processes=2, process_id=pid)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4, len(jax.devices())
import numpy as np
import optax
import torchacc_tpu as ta
from torchacc_tpu.train import accelerate

# fsdp=4 spans BOTH processes: every streamed tensor must land with
# shards on non-addressable devices too
cfg = ta.Config(dist=ta.DistConfig(fsdp=ta.FSDPConfig(size=4,
                                                      min_weight_size=0)))
cfg.compute.dtype = "float32"
cfg.compute.param_dtype = "float32"
trainer, _ = accelerate(path, None, cfg, optimizer=optax.sgd(1e-2))
emb = trainer.state.params["embed_tokens"]["embedding"]
assert "fsdp" in str(emb.sharding.spec), emb.sharding.spec

from jax.experimental import multihost_utils
from jax.sharding import PartitionSpec as PS
# each process feeds its local half of the fsdp-sharded global batch
local = np.random.default_rng(pid).integers(0, 128, (4, 16)).astype(np.int32)
arr = multihost_utils.host_local_array_to_global_array(
    local, trainer.mesh, PS(("dp", "fsdp"), ("sp", "spu")))
loss = float(trainer.step({"input_ids": arr})["loss"])
assert np.isfinite(loss), loss
print(f"proc {pid} ok loss={loss:.4f}", flush=True)
"""


@pytest.mark.slow
def test_two_process_streamed_ingestion(tmp_path):
    """Streamed safetensors ingestion onto a mesh that SPANS processes:
    every tensor's device_put targets shards this process cannot
    address — the multi-host half of the 70B ingestion story."""
    import torch
    import transformers

    torch.manual_seed(0)
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=False)
    hf_model = transformers.LlamaForCausalLM(hf_cfg).eval()
    path = str(tmp_path / "ckpt")
    hf_model.save_pretrained(path, safe_serialization=True)

    outs = _run_two_procs(path, worker_src=_STREAM_WORKER)
    l0 = outs[0].split("proc 0 ok loss=")[1].split()[0]
    l1 = outs[1].split("proc 1 ok loss=")[1].split()[0]
    assert l0 == l1, (l0, l1)
