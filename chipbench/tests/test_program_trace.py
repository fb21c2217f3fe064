"""``program_trace.py`` and the readers built on it (PR 24): the xplane
decoder and the scope reduction on the recorded trace
``testdata/train_steps.xplane.pb`` (a trace from before the program had
its own scopes: the Flax module names are what it carries), the host
span arithmetic and the module share on hand-made tuples."""

import os

import pytest

from chipbench import program_trace as pt
from chipbench import spec, trace_reduce
from chipbench.readers import module_time_share, span_self_time

TRACE = os.path.join(spec.HERE, "testdata", "train_steps.xplane.pb")
SCOPES = ("attn", "mlp", "gate_proj", "up_proj", "down_proj", "o_proj",
          "ln1", "ln2", "embed_tokens", "final_norm")


@pytest.fixture(scope="module")
def parsed():
    out = pt.parse(TRACE, ("chipbench/window",), SCOPES)
    (window,) = [(a, b) for n, a, b, _ in out["host"]]
    return dict(out, lo=window[0], hi=window[1])


def test_decoder_agrees_with_profile_data(parsed):
    """Same events, same names, same nanoseconds as ``trace_reduce.load``
    (``jax.profiler.ProfileData``) reads from the file."""
    old = trace_reduce.load(TRACE)
    ops_old, ops_new = old["devices"][0]["ops"], parsed["devices"][0]
    assert len(ops_old) == len(ops_new) == 2664
    for (n0, a0, b0), (n1, a1, b1, *_) in zip(ops_old, ops_new):
        assert n0 == n1 and abs(a0 - a1) < 5 and abs(b0 - b1) < 5
    lo, hi = trace_reduce.window_of(old["host"])
    assert (parsed["lo"], parsed["hi"]) == (lo, hi)


def test_ops_carry_path_flops_and_bytes(parsed):
    by_name = {op: rest for op, _, _, *rest in parsed["devices"][0]}
    scope, tf_op, flops, bytes_, program = \
        by_name["convolution_convert_fusion.3"]
    assert tf_op.endswith("layers/block/attn/q_proj/dot_general:")
    assert scope == "attn" and flops == 550091358208 and bytes_ == 436289536
    assert program == "jit__lambda"
    assert sum(f for *_, f, _, _ in parsed["devices"][0]) == \
        pytest.approx(269.985e12, rel=1e-4)


@pytest.mark.parametrize("scope,share", [
    ("gate_proj", 15.664), ("up_proj", 13.840), ("down_proj", 17.493),
    ("attn", 16.458), ("o_proj", 3.794), ("mlp", 0.0),
    (pt.UNATTRIBUTED, 30.090)])
def test_scope_shares_of_the_recorded_trace(parsed, scope, share):
    part = pt.partition(parsed["devices"][0], parsed["lo"], parsed["hi"],
                        SCOPES)
    assert part[scope] == pytest.approx(share, abs=0.002)


def test_partition_sums_to_the_whole(parsed):
    part = pt.partition(parsed["devices"][0], parsed["lo"], parsed["hi"],
                        SCOPES)
    assert sum(part.values()) == pytest.approx(100.0, abs=0.01)
    # coarser registry, same whole: mlp takes its projections' time
    coarse = pt.partition(
        [(op, a, b, pt.scope_of(tf_op, {"mlp", "attn"}), tf_op, f, by, prog)
         for op, a, b, _, tf_op, f, by, prog in parsed["devices"][0]],
        parsed["lo"], parsed["hi"], ("mlp", "attn"))
    assert sum(coarse.values()) == pytest.approx(100.0, abs=0.01)
    assert coarse["mlp"] == pytest.approx(15.664 + 13.840 + 17.493,
                                          abs=0.01)


@pytest.mark.parametrize("path,scope", [
    ("jit(f)/jvp(TransformerLM)/layers/while/body/closed_call/layers/"
     "block/mlp/down_proj/dot_general:", "mlp"),
    ("jit(f)/transpose(jvp(fused_ce))/while/body/dot_general:", "fused_ce"),
    ("jit(f)/jvp(TransformerLM)/layers/while/body/dynamic_slice:",
     "layers"),
    ("jit(f)/jvp(TransformerLM)/layers/while/body/closed_call/layers/"
     "block/attn/flash_fwd/flash_fwd/pallas_call:", "flash_fwd"),
    ("jit(f)/optimizer/mul:", "optimizer"),
    ("jit(f)/jvp()/mul:", None), ("", None),
    ("jit(mlp_like)/not_mlp/add:", None)])
def test_scope_of_takes_the_innermost_registered_name(path, scope):
    names = {"mlp", "layers", "fused_ce", "flash_fwd", "attn", "optimizer"}
    assert pt.scope_of(path, names) == scope


def test_a_while_counts_its_own_time_not_its_body():
    ops = [("while.1", 0, 100, "layers", "", 0, 0, "jit_f"),
           ("fusion.1", 10, 40, "mlp", "", 0, 0, "jit_f"),
           ("fusion.2", 40, 70, None, "", 0, 0, "jit_f"),
           ("copy.1", 120, 140, "layers", "", 0, 0, "jit_f")]
    own = pt.scope_self_time(ops, 0, 200)
    assert own == {"layers": 60.0, "mlp": 30.0, pt.UNATTRIBUTED: 30.0}
    part = pt.partition(ops, 0, 200, ("layers", "mlp", "attn"))
    assert part == {"layers": 50.0, "mlp": 25.0, "attn": 0.0,
                    pt.UNATTRIBUTED: 25.0}


HOST = [("serve/step", 0, 1000, {}), ("serve/admit", 10, 110,
                                      {"admitted": 1, "queue_ms": 2.5}),
        ("serve/admit", 110, 130, {"admitted": 0}),
        ("serve/deliver", 200, 900, {}), ("serve/wait", 250, 850, {}),
        ("serve/step", 1000, 3000, {}), ("serve/deliver", 1100, 2900, {}),
        ("serve/wait", 1200, 2800, {}),
        ("serve/step", 9000, 9900, {})]          # outside the window


@pytest.mark.parametrize("kw,expected", [
    (dict(span="serve/step", minus=["serve/wait"]), (3000 - 2200) / 2),
    (dict(span="serve/deliver", minus=["serve/wait"], per="serve/step"),
     (2500 - 2200) / 2),
    (dict(span="serve/admit", where={"admitted": 1}), 100.0),
    (dict(span="serve/admit"), 60.0),
    (dict(span="train/dispatch"), None)])
def test_span_self_time_on_hand_made_spans(kw, expected):
    got = span_self_time.self_time_ms(HOST, 0, 5000, **kw)
    assert got == (None if expected is None
                   else pytest.approx(expected * 1e-6))


def test_span_time_clips_to_the_window():
    assert pt.span_time(HOST, 500, 2000, "serve/step") == (1500.0, 2)


def test_module_time_share_on_hand_made_modules():
    ops = [("fusion.1", 0, 40), ("fusion.2", 50, 100),
           ("fusion.3", 100, 180)]
    modules = [("jit__decode_impl", 0, 100), ("jit__prefill_impl", 100, 180),
               ("jit__prefill_impl", 500, 600)]
    assert module_time_share.share(modules, ops, 0, 200, "jit__prefill") \
        == pytest.approx(100.0 * 80 / 170)
    assert module_time_share.share(modules, ops, 0, 200, "jit__sample") \
        is None


def test_readers_read_nothing_without_a_trace_or_a_registry(monkeypatch):
    from chipbench.readers import scope_time_share
    params = {"kind": "train", "scopes": ["fused_ce"]}
    assert scope_time_share.read({"kind": "train", "trace": None},
                                 params) is None
    monkeypatch.setattr(pt, "registries", lambda: None)   # the parent
    assert scope_time_share.read({"kind": "train", "trace": {"lo": 0}},
                                 params) is None
    assert span_self_time.read(
        {"kind": "serve", "trace": {"lo": 0}},
        {"kind": "serve", "span": "serve/step"}) is None


def test_fused_ce_roofline_counts_six_flops_a_weight_a_token():
    need = spec.roofline("fused_ce").required(dict(
        published={"hidden_size": 4096, "vocab_size": 32768},
        tokens=4 * 4096 * 10, chips=1, steps=10))
    assert need["flops"] == 6.0 * 163840 * 4096 * 32768
    assert need["bytes"] < need["flops"] / 100
