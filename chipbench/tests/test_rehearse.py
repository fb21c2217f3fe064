"""``--rehearse`` of every cell end to end on the sandbox's CPU (the
four-chip cell on four virtual devices): the whole harness and driver
path at a toy size.  No device metric's name may be printed."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import spec

BENCH = spec.benchmark()
METRIC_NAMES = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


def rehearse(workload, trace, *extra, env=None):
    cmd = [sys.executable, "-m", "chipbench.run", "--workload", workload,
           "--seed", str(2**31 + 17), "--seconds", "2", "--trace", str(trace),
           "--rehearse", *extra]
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=spec.ROOT,
                          env=dict(os.environ, **(env or {})), timeout=900)
    return proc


@pytest.mark.parametrize("trace", (0, 1))
@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_runs_and_prints_no_metric_name(workload, trace):
    proc = rehearse(workload, trace)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert "metrics" not in line and "device" not in line
    assert line["rehearsal_device"]["platform"] == "cpu"
    chips = spec.Cell(workload).chips
    assert line["rehearsal_device"]["count"] == chips
    assert line["rehearsal_values"]
    for key in line["rehearsal_values"]:
        assert key.startswith("rehearsal_")
    # a metric's name appears nowhere in the output except behind the
    # rehearsal_ prefix
    text = proc.stdout
    for name in METRIC_NAMES:
        assert text.count(name) == text.count("rehearsal_" + name), name


def test_without_a_tpu_there_is_no_result():
    cmd = [sys.executable, "-m", "chipbench.run", "--workload",
           BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
           "--trace", "0"]
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=spec.ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout
