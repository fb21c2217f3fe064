"""The ``mla_sparse_window_moe_decoder`` family's cell on the sandbox's
CPU: ``correct`` can fail for it.  The rehearsal's toy keeps the
published ``index_topk`` (2,048) and window (513), which no toy context
reaches, so these runs shrink both besides (8 positions selected, a
window of 9; prompts of 8-40 tokens and 8-24 outputs cross both): then a
forward that attends the most recent ``index_topk`` positions instead of
the indexer's, one that drops the headwise gate, and the reference one
precision down — each in the program's place — break one of the cell's
limits on the served tokens' logits, while the program itself and the
bfloat16 witness pass.  The cell's driver holds a control to the p95 of
the gaps as well as to the widest: on the chip the p95 alone separates
the precisions (PERF.md section 2), so the precision control is held to
failing THAT comparison here too, whatever the toy's widest gap does."""

import json
import subprocess
import sys
import textwrap

import pytest

from chipbench import spec

CELL = "dots3.serve.longctx8"
SHRUNK = textwrap.dedent("""
    import sys
    from chipbench import rehearsal
    real = rehearsal.shrink
    def shrink(cell):
        real(cell)
        cell.published.update(index_topk=8, sliding_window_size=9)
        cell.depth = 5                  # the dense layer and one period
        # 16 held of 256 experts leave most toy positions within 0.01 of
        # a routing that differs: read the settled ones, as the cell does
        cell.config["limits"]["serve"] = dict(
            cell.config["limits"]["serve"], positions_not_read_share=0.99)
    rehearsal.shrink = shrink
    from chipbench.run import main
    sys.exit(main(sys.argv[1:]))
""")


def run(*extra):
    proc = subprocess.run(
        [sys.executable, "-c", SHRUNK, "--workload", CELL, "--seed",
         str(2**31 + 29), "--seconds", "2", "--trace", "0", "--rehearse",
         *extra], capture_output=True, text=True, cwd=spec.ROOT, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.fixture(scope="module")
def controlled():
    return run("--control", "recent,no_gate,fp8,bfloat16")


def readings(out, control):
    """{reading's name: verdict} of one control's lines."""
    return {l.split("] ")[2].split(" = ")[0]: l.rsplit(") ", 1)[1]
            for l in out.splitlines()
            if f"[control {control}] served_" in l}


def test_the_cell_is_correct_with_selection_and_window_engaged(controlled):
    line, out = controlled
    assert line["correct"] is True and line["failed"] == 0
    assert "depth=5" in out


@pytest.mark.parametrize("control", ["recent", "no_gate", "fp8"])
def test_a_wrong_forward_in_the_programs_place_fails_a_limit(controlled,
                                                            control):
    _, out = controlled
    got = readings(out, control)
    assert set(got) == {"served_token_logit_gap_widest",
                        "served_token_logit_gap_p95"}, got
    assert "fails, as it must" in got.values(), got
    assert f"[control {control}] in the program's place `correct` would " \
        "be false" in out


def test_the_precision_control_fails_the_comparison_that_judges_precision(
        controlled):
    _, out = controlled
    assert readings(out, "fp8")[
        "served_token_logit_gap_p95"] == "fails, as it must"


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
    BENCHMARK.json does not hold."""
    with pytest.raises(SystemExit, match="no workload"):
        spec.Cell(CELL, dict(spec.benchmark(), workloads=[
            w for w in spec.benchmark()["workloads"] if w["name"] != CELL]))
