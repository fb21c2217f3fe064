"""Minimal repro harness for the XLA:CPU in-process collective abort.

The emulated-mesh test suite (tests/conftest.py: 8 virtual CPU devices)
can die with SIGABRT inside the XLA:CPU runtime when multi-device
programs give different devices different collective ISSUE ORDERS, or
when the thunk executor's inter-device scheduling desynchronizes the
in-process collective rendezvous.  The library works around every known
trigger (see docs/XLA_CPU_ABORT.md for the list with file:line); this
script reproduces the raw triggers OUTSIDE those mitigations so the
failure can be demonstrated, bisected against jax/jaxlib versions, and
attached to an upstream report.

Modes (each runs the trigger in a killable subprocess and reports the
exit signal):

- ``gated-collective``: a psum issued inside a lax.cond taken only by
  SOME shard_map members (mirrors parallel/pp.py:480-490's description:
  me-gated cond bodies give each pp rank its own collective order).
  This is an invalid-by-construction SPMD program, but the failure mode
  is the point: the runtime ABORTS THE PROCESS (taking an entire test
  suite with it) instead of failing the computation.
- ``scan-in-cond``: a lax.scan (WhileThunk) inside a cond branch whose
  body also runs collectives on other devices — the
  ops/fused.py::scan_free trigger (fused.py:60-66).
- ``stress``: N iterations of a VALID pp-ring × dp-subgroup program
  shaped like the pre-mitigation pipeline tick (ppermute over 'pp'
  chained with dp-subgroup psums, riders dynamically indexed rather
  than riding the ring) — the nondeterministic reorder race.  Reports
  the abort rate over N fresh-process runs.

Usage::

    python scripts/xla_cpu_abort_repro.py gated-collective
    python scripts/xla_cpu_abort_repro.py stress --n 20
"""

import argparse
import os
import subprocess
import sys

_PRELUDE = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
devs = np.array(jax.devices()).reshape(2, 4)
mesh = Mesh(devs, ("pp", "dp"))
"""

_GATED = _PRELUDE + """
# Invalid SPMD by construction: the psum is only issued by pp rank 0.
# A correct runtime would hang-with-timeout or error; XLA:CPU's
# in-process rendezvous aborts the whole process.
def region(x):
    me = jax.lax.axis_index("pp")
    return jax.lax.cond(
        me == 0,
        lambda v: jax.lax.psum(v, "dp"),
        lambda v: v,
        x)

f = jax.jit(jax.shard_map(region, mesh=mesh, in_specs=P("pp", "dp"),
                          out_specs=P("pp", "dp"), check_vma=False))
out = f(jnp.ones((8, 8), jnp.float32))
jax.block_until_ready(out)
print("survived")
"""

_SCAN_IN_COND = _PRELUDE + """
# WhileThunk inside a cond branch while other ranks run a collective:
# the scan's thunk scheduling desynchronizes the rendezvous
# (ops/fused.py:60-66 — why the 1F1B head uses scan_free chunking).
def region(x):
    me = jax.lax.axis_index("pp")

    def scan_branch(v):
        def body(c, _):
            return c * 1.0001, None
        c, _ = jax.lax.scan(body, v, None, length=64)
        return jax.lax.psum(c, "dp")

    def plain_branch(v):
        return jax.lax.psum(v, "dp")

    return jax.lax.cond(me == 0, scan_branch, plain_branch, x)

f = jax.jit(jax.shard_map(region, mesh=mesh, in_specs=P("pp", "dp"),
                          out_specs=P("pp", "dp"), check_vma=False))
out = f(jnp.ones((8, 8), jnp.float32))
jax.block_until_ready(out)
print("survived")
"""

_STRESS = _PRELUDE + """
# VALID program shaped like the pre-mitigation pipeline tick: a ppermute
# ring over 'pp' each step, a dp-subgroup psum from GSPMD-style sharded
# compute, and a tick-dependent dynamic index (the rider lookup the
# library replaced with ring-riding — parallel/pp.py:200-213).
def region(params, x):
    def tick(carry, t):
        cur = carry
        nxt = jax.lax.ppermute(cur, "pp", [(i, (i + 1) % 2)
                                           for i in range(2)])
        p_t = jax.lax.dynamic_index_in_dim(params, t % 4, 0,
                                           keepdims=False)
        val = nxt @ p_t
        val = val - jax.lax.pmean(val, "dp")  # dp-subgroup collective
        return val, jnp.sum(val)

    out, sums = jax.lax.scan(tick, x, jnp.arange(12, dtype=jnp.int32))
    return jnp.sum(sums) + jnp.sum(out)

f = jax.jit(jax.shard_map(region, mesh=mesh,
                          in_specs=(P(), P(None, "dp")),
                          out_specs=P(),
                          axis_names=frozenset({"pp", "dp"}),
                          check_vma=False))
params = jnp.asarray(np.random.default_rng(0).standard_normal((4, 16, 16)),
                     jnp.float32)
x = jnp.ones((8, 64), jnp.float32)  # dp=4 splits dim 1 -> local [8, 16]
g = jax.jit(jax.grad(lambda p, x: f(p, x)))
for _ in range(3):
    jax.block_until_ready(g(params, x))
print("survived")
"""

_A2A = _PRELUDE + """
# MoE-shaped: GSPMD-inserted all_to_alls over 'ep' (the dense dispatch
# einsum sharded over experts) mixed with dp-subgroup reductions, under
# grad — the pattern running when the suite's one observed round-5
# abort fired (tests/test_moe.py, SIGABRT on attempt 1 under machine
# load).
from jax.sharding import NamedSharding
mesh2 = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("ep", "dp"))
E, H, F, N = 4, 32, 64, 64
rngs = np.random.default_rng(0)
we = jax.device_put(
    jnp.asarray(rngs.standard_normal((E, H, F)), jnp.float32),
    NamedSharding(mesh2, P("ep")))
x = jax.device_put(jnp.asarray(rngs.standard_normal((N, H)), jnp.float32),
                   NamedSharding(mesh2, P("dp")))


def loss(we, x):
    # every token through every expert: [N, H] x [E, H, F] -> [E, N, F]
    # forces resharding collectives between the ep- and dp-sharded
    # operands, then a reduction back
    y = jnp.einsum("nh,ehf->enf", x, we)
    return jnp.sum(jax.nn.relu(y) ** 2)


g = jax.jit(jax.grad(loss))
for _ in range(4):
    jax.block_until_ready(g(we, x))
print("survived")
"""

_SRC = {"gated-collective": _GATED, "scan-in-cond": _SCAN_IN_COND,
        "stress": _STRESS, "a2a-stress": _A2A}


def run_once(src: str, timeout: float):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    try:
        r = subprocess.run([sys.executable, "-c", src],
                           capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return "timeout", ""
    if r.returncode == 0 and "survived" in r.stdout:
        return "ok", ""
    if r.returncode < 0:
        return f"signal {-r.returncode}", r.stderr[-500:]
    return f"rc {r.returncode}", r.stderr[-500:]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=sorted(_SRC))
    ap.add_argument("--n", type=int, default=1,
                    help="fresh-process repetitions (stress mode wants "
                         ">= 20: the reorder race is timing-dependent)")
    ap.add_argument("--timeout", type=float, default=120.0)
    args = ap.parse_args()

    import jax
    import jaxlib
    print(f"jax {jax.__version__} / jaxlib {jaxlib.__version__}")
    outcomes = {}
    for i in range(args.n):
        verdict, tail = run_once(_SRC[args.mode], args.timeout)
        outcomes[verdict] = outcomes.get(verdict, 0) + 1
        print(f"run {i}: {verdict}")
        if tail and "ok" not in verdict:
            print("  stderr tail:", tail.replace("\n", " | ")[-300:])
    print("summary:", outcomes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
