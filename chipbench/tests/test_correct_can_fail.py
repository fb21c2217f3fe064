"""``correct`` has been shown to fail: with the timed path broken
underneath (a train step that returns its state unchanged; a served
token altered where it is produced) and with the control (the reference
one precision down, in the program's place).  Toy sizes, CPU."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from chipbench import spec

BREAK_TRAIN = textwrap.dedent("""
    import sys
    from torchacc_tpu.train import trainer as T
    real = T.Trainer._build_train_step
    def broken(self, batch, donate=True):
        step = real(self, batch, donate=False)
        class Unchanged:
            lower = step.lower
            def __call__(self, state, b, *rest):
                out = step(state, b, *rest)
                return (state,) + tuple(out[1:])   # metrics, no update
        return Unchanged()
    T.Trainer._build_train_step = broken
    from chipbench.run import main
    sys.exit(main(sys.argv[1:]))
""")

BREAK_SERVE = textwrap.dedent("""
    import sys
    from torchacc_tpu.serve import scheduler as S
    real = S.Scheduler._record
    def altered(self, seq, token, now):
        if SLOTS is None or seq.slot in SLOTS:
            token = (int(token) + 1) % 500 + 1
        return real(self, seq, token, now)
    S.Scheduler._record = altered
    from chipbench.run import main
    sys.exit(main(sys.argv[1:]))
""")


def run_broken(script, workload):
    proc = subprocess.run(
        [sys.executable, "-c", script, "--workload", workload, "--seed", "9",
         "--seconds", "2", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, cwd=spec.ROOT, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    line, out = run_broken(BREAK_TRAIN, "mistral7b.train.dense4k")
    assert line["correct"] is False
    assert "param_change_norm_worst_leaf_rel" in out and "FAILED" in out


@pytest.mark.parametrize("slots", [None, (2,)],
                         ids=["every_slot", "one_slot_only"])
def test_a_token_altered_where_it_is_produced_is_not_correct(slots):
    # the sample reads a request of every slot, so a fault confined to
    # one slot cannot slip past it
    line, out = run_broken(f"SLOTS = {slots!r}" + BREAK_SERVE,
                           "mistral7b.serve.rollout")
    assert line["correct"] is False
    assert "served_token_logit_gap_widest" in out and "FAILED" in out


@pytest.mark.parametrize("workload,control", [
    ("mistral7b.train.dense4k", "fp8"),
    ("olmo2-1b.train.dense4k", "fp8"),
    ("mistral7b.serve.rollout", "fp8"),
])
def test_the_control_fails_a_limit(workload, control):
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", workload,
         "--seed", "11", "--seconds", "2", "--trace", "0", "--rehearse",
         "--control", control],
        capture_output=True, text=True, cwd=spec.ROOT, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [l for l in proc.stdout.splitlines()
             if f"[control {control}]" in l]
    assert lines and any("fails, as it must" in l for l in lines), lines
    assert json.loads(proc.stdout.strip().splitlines()[-1])["correct"] is True
