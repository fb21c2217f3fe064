"""The trace reduction: on hand-made intervals, and on one small trace
recorded on a TPU v5e (``testdata/``: three train steps of cell 1's
model, python tracer off)."""

import os

import pytest

from chipbench import trace_reduce as T

MS = 1_000_000
RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "train_steps.xplane.pb")


def test_op_name_and_kinds():
    assert T.op_name("%fusion.4 = bf16[8]{0} fusion(bf16[8] %p)") == "fusion.4"
    assert T.op_name("jit_step(123)") == "jit_step"
    assert T.is_collective("all-gather-start.3")
    assert T.is_collective("reduce-scatter.1")
    assert not T.is_collective("fusion.12")
    assert T.is_container("while.14") and not T.is_container("fusion.1")


def test_union_busy_and_gaps():
    ops = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 32, 35)]
    assert T.busy(ops, 0, 50) == 30
    assert T.busy(ops, 8, 33) == 12 + 3
    assert T.idle_gaps(ops, 0, 50) == [(20, 30), (40, 50)]


def test_self_time_does_not_count_a_loop_and_its_body_twice():
    ops = [("while.1", 0, 100), ("fusion.1", 10, 40), ("attn.2", 40, 90),
           ("fusion.1", 120, 130)]
    self_t = T.self_time_by_name(ops, 0, 200)
    assert self_t == {"while.1": 20, "fusion.1": 40, "attn.2": 50}
    assert T.time_by_name(ops, 0, 200, lambda n: n.startswith("attn")) \
        == {"attn.2": 50}


def test_exposed_collectives_on_a_two_lane_case():
    """Ops line: compute 0-40, a sync all-reduce 40-60, compute 60-100.
    Async line: an all-gather in flight 30-70.  The collectives' union
    is 30-70; compute covers 30-40 and 60-70 of it: 20 exposed."""
    ops = [("while.1", 0, 100), ("fusion.1", 0, 40), ("all-reduce.1", 40, 60),
           ("fusion.2", 60, 100)]
    async_ops = [("all-gather-start.1", 30, 70)]
    assert T.collective_exposed(ops, async_ops, 0, 100) == 20
    # fully hidden: the gather lies inside compute
    assert T.collective_exposed(
        [("fusion.1", 0, 100)], [("all-gather-start.1", 10, 50)], 0, 100) == 0
    # fully exposed: nothing else runs
    assert T.collective_exposed(
        [("all-reduce.7", 10, 30)], [], 0, 100) == 20


def test_gaps_go_to_the_innermost_covering_annotation():
    host = [("chipbench/window", 0, 100), ("chipbench/engine_step", 10, 50),
            ("chipbench/deliver_submit", 50, 60)]
    gaps = [(20, 30), (52, 56), (80, 90)]
    assert T.gaps_by_annotation(gaps, host) == {
        "chipbench/engine_step": 10, "chipbench/deliver_submit": 4,
        "(none)": 10}


def test_no_device_plane_is_an_error(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(T.NoDevicePlane):
        T.load(T.find_xplane(str(tmp_path)))


def test_recorded_trace():
    trace = T.load(RECORDED)
    assert list(trace["devices"]) == [0]
    lo, hi = T.window_of(trace["host"])
    dev = trace["devices"][0]
    busy = T.busy(dev["ops"], lo, hi)
    assert 0.95 * (hi - lo) < busy <= hi - lo       # a train step is dense
    assert T.busy(dev["modules"], lo, hi) == pytest.approx(busy, rel=0.01)
    kernels = T.time_by_name(dev["ops"], lo, hi,
                             lambda n: n.startswith("attn."))
    assert len(kernels) == 3                        # flash fwd, dq, dkv
    assert 0.03 * busy < sum(kernels.values()) < 0.3 * busy
    self_t = T.self_time_by_name(dev["ops"], lo, hi)
    assert sum(self_t.values()) == pytest.approx(busy, rel=0.02)
    assert T.total(T.idle_gaps(dev["ops"], lo, hi)) == pytest.approx(
        (hi - lo) - busy)
    assert T.collective_exposed(dev["ops"], dev["async"], lo, hi) == 0
