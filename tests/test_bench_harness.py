"""The 8B-geometry bench's subprocess depth ladder.

`benchmarks/bench_8b.py` times each depth in a fresh subprocess (an
OOM'd depth's resident buffers would otherwise poison shallower
attempts — observed live on the v5e, see the module docstring) and
talks to the children over a one-JSON-line protocol.  These tests pin
the protocol and the OOM classifier off-chip; the smoke geometry runs
the REAL parent/child flow end-to-end on CPU.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH8B = os.path.join(REPO, "benchmarks", "bench_8b.py")
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from bench_8b import _is_oom  # noqa: E402


def _clean_env():
    # The suite's conftest exports XLA_FLAGS=--xla_force_host_platform_
    # device_count=8 for the emulated mesh; the bench runs single-device
    # (dp inference over 8 devices would reject batch 1), so children
    # here get the flag stripped — matching real bench invocation.
    env = dict(os.environ)
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "force_host_platform_device_count" not in f)
    return env


def test_is_oom_matches_the_compilers_hbm_message():
    # What jaxlib 0.9.0's TPU compiler raises (JaxRuntimeError) for a
    # program that does not fit a v5e's HBM — captured from a compile
    # for a described v5e chip.  "Ran out of memory" has a lowercase
    # "out", so a capitalised substring match misses it.
    compiler = ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran "
                "out of memory in memory space hbm. Used 18.64G of 15.75G "
                "hbm. Exceeded hbm capacity by 2.90G.")
    assert _is_oom(compiler)
    assert _is_oom(compiler.replace("RESOURCE_EXHAUSTED: ", ""))
    assert _is_oom("RESOURCE_EXHAUSTED: allocation failed")
    assert _is_oom("Allocation 1.2G exceeds the limit")
    assert not _is_oom("Mosaic lowering failed: unsupported dtype")
    assert not _is_oom("connection reset by peer")


def test_one_depth_child_protocol():
    # A child run prints exactly one {"_depth", "dt", "device_kind"}
    # JSON line on success; the parent parses nothing else.
    r = subprocess.run(
        [sys.executable, BENCH8B, "--one-depth", "1", "--smoke",
         "--seq", "128", "--batch", "1", "--iters", "1",
         "--platform", "cpu"],
        capture_output=True, text=True, timeout=300, env=_clean_env())
    assert r.returncode == 0, r.stderr[-2000:]
    recs = []
    for line in r.stdout.splitlines():
        try:
            cand = json.loads(line)
        except ValueError:
            continue
        if isinstance(cand, dict) and "_depth" in cand:
            recs.append(cand)
    assert len(recs) == 1
    assert recs[0]["_depth"] == 1
    assert recs[0]["dt"] > 0
    assert recs[0]["device_kind"]


@pytest.mark.slow
def test_parent_ladder_end_to_end_smoke():
    # Full parent flow at smoke geometry: two child depths, differenced
    # report, no docs/bench_8b.json write (smoke never persists —
    # _OUT's mtime must not change).
    out_path = os.path.join(REPO, "docs", "bench_8b.json")
    before = os.stat(out_path).st_mtime if os.path.exists(out_path) else None
    r = subprocess.run(
        [sys.executable, BENCH8B, "--smoke", "--seq", "128",
         "--batch", "1", "--iters", "1", "--depths", "2", "1",
         "--platform", "cpu"],
        capture_output=True, text=True, timeout=600, env=_clean_env())
    assert r.returncode == 0, r.stderr[-2000:]
    line = r.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    assert out["metric"] == "llama3_8b_geometry_layer_mfu"
    assert "error" not in out
    d = out["detail"]
    # Protocol, not perf: both depths timed (positive step times); the
    # DIFFERENCED value can round to 0.0 at smoke geometry.
    assert set(d["depths_measured"]) == {"2", "1"}
    assert all(v > 0 for v in d["depths_measured"].values())
    assert d["chip"] == "cpu"
    after = os.stat(out_path).st_mtime if os.path.exists(out_path) else None
    assert before == after
