"""The ``ssm_attn_moe_decoder`` family's cell on the sandbox's CPU:
``correct`` can fail for it, and its readers and rooflines count what
they say.

The rehearsal's toy keeps a depth of 2 (``M E``: no attention layer), so
these runs take the first six layers of the published order (``M E M E M
*``: every kind, the state carried over several chunks of 16 by prompts
of 8-40 tokens): then a convolution that sees the current input alone,
a gate without its grouped norm — the two errors this family invites —
and the reference one precision down (fp8), each in the program's place,
break one of the cell's limits on the served tokens' logits, while the
program itself and the bfloat16 witness pass.  The toy limits: the
program and the witness read a gap of 0 here, fp8 a widest gap of 0.036
(its p95 0.007), the wrong forwards 0.87 and 1.09."""

import json
import subprocess
import sys
import textwrap

import pytest

from chipbench import program_trace, spec

CELL = "nemotron3nano.serve.chat48"
SHRUNK = textwrap.dedent("""
    import sys
    from chipbench import rehearsal
    real = rehearsal.shrink
    def shrink(cell):
        real(cell)
        cell.depth = 6                  # M E M E M *
        cell.config["limits"]["serve"] = dict(
            cell.config["limits"]["serve"], logit_gap=0.02,
            logit_gap_p95=0.02, route_margin=0.005,
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
    return run("--control", "no_conv,no_gate_norm,fp8,bfloat16")


def readings(out, control):
    """{reading's name: verdict} of one control's lines."""
    return {l.split("] ")[2].split(" = ")[0]: l.rsplit(") ", 1)[1]
            for l in out.splitlines()
            if f"[control {control}] served_" in l}


def test_the_cell_is_correct_with_every_kind_of_layer_engaged(controlled):
    line, out = controlled
    assert line["correct"] is True and line["failed"] == 0
    assert "depth=6" in out


@pytest.mark.parametrize("control", ["no_conv", "no_gate_norm", "fp8"])
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
    _, out = controlled
    got = readings(out, "bfloat16")
    assert len(got) == 2 and set(got.values()) == {"would pass"}, got
    assert "[control bfloat16] in the program's place `correct` would " \
        "be TRUE" in out


def test_the_parent_fails_at_once_and_by_name():
    """What the driver's first try of the new cell on the parent commit
    meets.  Without this PR's BENCHMARK.json: ``spec.Cell`` ->
    ``SystemExit`` for a workload name it does not hold.  With this PR's
    benchmark files laid over it: the parent's ingest reads model_type
    'nemotron_h' as a plain dense decoder (no ``mixer_pattern``), and the
    family's layout stops the run before anything is built."""
    import types

    from chipbench.layouts import ssm_attn_moe_decoder as layout
    with pytest.raises(SystemExit, match="no workload"):
        spec.Cell(CELL, dict(spec.benchmark(), workloads=[
            w for w in spec.benchmark()["workloads"] if w["name"] != CELL]))
    with pytest.raises(SystemExit, match="no mixer_pattern"):
        layout.to_program_params({}, types.SimpleNamespace())


# -- the readers and rooflines this family brings -----------------------------

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
A_SLOT = 2 * 23 * (4 * 64 * 64 * 128 + 2 * 3 * 6144)    # state_bytes a slot


def observed(spans, chunks=()):
    """What a traced run hands a reader, with ``spans`` as the program's
    ``serve/deliver`` spans of the window."""
    pub = spec.Cell(CELL).published
    program_trace._cache["parsed"] = dict(
        host=[("serve/deliver", 10 + i, 11 + i, st)
              for i, st in enumerate(spans)],
        devices={}, lo=0, hi=10**6)
    return dict(kind="serve", trace={"lo": 0, "hi": 10**6}, published=pub,
                depth=52, kv_heads=2, peaks=PEAKS,
                prefill_chunks=list(chunks))


@pytest.fixture
def decode_spans():
    yield [dict(kind="decode", state_bytes=48 * A_SLOT, ssm_layers=23,
                ctx_attended=48 * 1000, moe_pairs=36 * 23, moe_hit=14 * 23,
                moe_max=70, moe_slots=16 * 23, moe_layer_steps=23),
           dict(kind="decode", state_bytes=40 * A_SLOT, ssm_layers=23,
                ctx_attended=40 * 1000, moe_pairs=30 * 23, moe_hit=13 * 23,
                moe_max=60, moe_slots=16 * 23, moe_layer_steps=23),
           dict(kind="first", ssm_layers=23, moe_pairs=384 * 23,
                moe_hit=16 * 23, moe_max=900, moe_slots=16 * 23,
                moe_layer_steps=23)]
    program_trace._cache.pop("parsed", None)


def test_the_step_roofline_counts_each_slots_state_once_read_once_written(
        decode_spans):
    need = spec.roofline("ssm_step").required(observed(decode_spans))
    values = (48 + 40) * 23 * 64 * 64 * 128
    assert need["bytes"] == 8 * values and need["flops"] == 5 * values
    # 48 slots: 2 MiB read and written in 23 layers = 193 MiB a step
    assert abs(need["bytes"] / 88 * 48 / 2**20 - 4416) < 1


def test_state_bytes_share_sets_the_state_against_the_keys_and_values(
        decode_spans):
    decl = spec.layer_metric("state_bytes_share.serve")
    share = spec.reader(decl["reader"]).read(observed(decode_spans),
                                             decl["params"])
    state = 88 * A_SLOT
    kv = 88 * 1000 * 6 * 2 * 2 * 128 * 2        # 6 layers, k and v, bf16
    assert abs(share - 100 * state / (state + kv)) < 1e-9
    assert 90 < share < 99
    # another family's spans (no state_bytes): nothing to read
    assert spec.reader(decl["reader"]).read(observed(
        [dict(kind="decode", ctx_attended=5, win_attended=3)]),
        decl["params"]) is None


def test_the_relu2_roofline_counts_two_matrices_an_expert(decode_spans):
    obs = observed(decode_spans)
    two = spec.roofline("relu2_expert_matmul").required(obs)
    three = spec.roofline("expert_matmul").required(obs)
    pairs, hit = (36 + 30 + 384) * 23, (14 + 13 + 16) * 23
    assert two["flops"] == 4 * 2688 * 1856 * pairs
    assert two["bytes"] == 2 * 2 * 2688 * 1856 * hit
    assert three["flops"] == 1.5 * two["flops"]


def test_the_scan_roofline_counts_whole_sub_chunks_of_the_real_positions():
    obs = observed([], chunks=[(0, 512), (512, 512), (1024, 130), (0, 100)])
    need = spec.roofline("ssm_scan").required(obs)
    subs = 4 + 4 + 2 + 1
    a_head = (2 * 128 * 128 * 128 / 8 + 2 * 128 * 128 * 64
              + 4 * 128 * 128 * 64)
    assert need["flops"] == 23 * subs * 64 * a_head
    state = 4 * 23 * 2 * 4 * 64 * 64 * 128
    rows = subs * 128 * 2 * (2 * 4096 + 2 * 1024)
    assert need["bytes"] == state + 23 * rows
    assert need["least_s"] >= need["bytes"] / PEAKS["hbm_bytes_per_s"]
    program_trace._cache.pop("parsed", None)


def test_on_a_program_without_the_family_the_new_readers_find_nothing():
    """The parent, or another cell: no ``mamba_num_heads`` in the
    configuration, no ``state_bytes`` on a span — every new reader
    returns None or zeros and raises nothing."""
    pub = spec.Cell("kexaone.serve.mixed16").published
    program_trace._cache["parsed"] = dict(
        host=[("serve/deliver", 10, 11, dict(kind="decode", ctx_attended=9,
                                             win_attended=4))],
        devices={}, lo=0, hi=100)
    obs = dict(kind="serve", trace={"lo": 0, "hi": 100}, published=pub,
               depth=8, kv_heads=8, peaks=PEAKS, prefill_chunks=[(0, 512)])
    try:
        assert spec.roofline("ssm_scan").required(obs)["least_s"] == 0.0
        assert spec.roofline("ssm_step").required(obs)["bytes"] == 0.0
        decl = spec.layer_metric("state_bytes_share.serve")
        assert spec.reader(decl["reader"]).read(obs, decl["params"]) is None
        # (no kernel event named ssm_chunk_scan*: the kernel reader's own
        # rule, readers/kernel_common.py)
        decl = spec.layer_metric("ssm_scan_roofline")
        assert spec.reader(decl["reader"]).read(
            dict(obs, trace=dict(obs["trace"], devices={})),
            decl["params"]) is None
    finally:
        program_trace._cache.pop("parsed", None)
