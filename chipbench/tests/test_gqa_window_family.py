"""The ``gqa_window_moe_decoder`` family's cell on the sandbox's CPU:
``correct`` can fail for it.  The rehearsal's toy keeps the published
window (128), which few toy contexts pass, and a depth of 2 (no global
layer), so these runs shrink the window to 9 positions and take the
cell's own depth (the dense sliding layer, then s s g s s s g; prompts
of 8-40 tokens and 8-24 outputs cross the window several times): then a
forward that drops the window, one that ropes the global layers — the
two errors this family invites — and the reference one precision down,
each in the program's place, break one of the cell's limits on the
served tokens' logits, while the program itself and the bfloat16 witness
pass.  The cell's driver holds a control to the p95 of the gaps as well
as to the widest."""

import json
import subprocess
import sys
import textwrap

import pytest

from chipbench import spec

CELL = "kexaone.serve.mixed16"
SHRUNK = textwrap.dedent("""
    import sys
    from chipbench import rehearsal
    real = rehearsal.shrink
    def shrink(cell):
        real(cell)
        cell.published.update(sliding_window=9)
        cell.depth = 8                  # the dense layer and s s g s s s g
        # the rehearsal's toy limits (0.008) were read on dense toys; at
        # this family's seeded scales (per-head norms of 2: a query
        # attends a handful of positions) the toy program in bfloat16
        # reads a p95 of 0.04-0.08 and the fp8 control 0.5: limits
        # between them, and the settled positions read as the cell does
        cell.config["limits"]["serve"] = dict(
            cell.config["limits"]["serve"], logit_gap=0.3,
            logit_gap_p95=0.2, route_margin=0.005,
            positions_not_read_share=0.99)
    rehearsal.shrink = shrink
    from chipbench.run import main
    sys.exit(main(sys.argv[1:]))
""")


def run(*extra):
    proc = subprocess.run(
        [sys.executable, "-c", SHRUNK, "--workload", CELL, "--seed",
         str(2**31 + 33), "--seconds", "2", "--trace", "0", "--rehearse",
         *extra], capture_output=True, text=True, cwd=spec.ROOT, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.fixture(scope="module")
def controlled():
    return run("--control", "no_window,rope_global,fp8,bfloat16")


def readings(out, control):
    """{reading's name: verdict} of one control's lines."""
    return {l.split("] ")[2].split(" = ")[0]: l.rsplit(") ", 1)[1]
            for l in out.splitlines()
            if f"[control {control}] served_" in l}


def test_the_cell_is_correct_with_windows_and_global_layers_engaged(
        controlled):
    line, out = controlled
    assert line["correct"] is True and line["failed"] == 0
    assert "depth=8" in out


@pytest.mark.parametrize("control", ["no_window", "rope_global", "fp8"])
def test_a_wrong_forward_in_the_programs_place_fails_a_limit(controlled,
                                                            control):
    _, out = controlled
    got = readings(out, control)
    assert set(got) == {"served_token_logit_gap_widest",
                        "served_token_logit_gap_p95"}, got
    assert "fails, as it must" in got.values(), got
    assert f"[control {control}] in the program's place `correct` would " \
        "be false" in out


def test_the_bfloat16_witness_passes_every_limit(controlled):
    """The reference with its products' operands in the program's
    precision is told from the program by no limit."""
    _, out = controlled
    got = readings(out, "bfloat16")
    assert len(got) == 2 and set(got.values()) == {"would pass"}, got
    assert "[control bfloat16] in the program's place `correct` would " \
        "be TRUE" in out


def test_the_parent_has_no_such_workload():
    """What the driver's first try of the new cell on the parent commit
    meets: ``spec.Cell`` -> ``SystemExit`` at once, for a workload name
    BENCHMARK.json does not hold (with this PR's benchmark files laid
    over it, the parent's ``_check_supported`` refuses the configuration
    instead: PERF.md section 6)."""
    with pytest.raises(SystemExit, match="no workload"):
        spec.Cell(CELL, dict(spec.benchmark(), workloads=[
            w for w in spec.benchmark()["workloads"] if w["name"] != CELL]))
