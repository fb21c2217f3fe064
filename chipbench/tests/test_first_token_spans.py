"""``readers/first_token_spans.py`` (PR 37) on hand-made host tuples, its
four metric files against ``BENCHMARK.json``, and the run's join of
them.  (``test_benchmark_json.py`` holds each entry to the contract and
``test_rehearse.py`` rehearses every cell: both take the new entries from
``BENCHMARK.json`` as they take the others.)"""

import pytest

from chipbench import program_trace, spec
from chipbench.readers import first_token_spans as fts

SPAN = "serve/deliver"
METRICS = ("ttft_ms_per_wait_step.serve", "ttft_wait_steps_per_chunk.serve",
           "ttft_prompt_chunks_p50.serve", "first_token_lag_ms.serve")


def first(sid, programs, steps, ttft, lag=10.0):
    return {"kind": "first", "sid": sid, "prefill_programs": programs,
            "queue_steps": sid % 2, "wait_steps": steps, "queue_ms": 0.5,
            "prefill_ms": ttft - lag - 0.5, "lag_ms": lag, "ttft_ms": ttft}


# the window is [100, 200]: a span counts by where it STARTS
HOST = [
    (SPAN, 90.0, 110.0, first(1, 9, 30, 900.0)),     # started before it
    (SPAN, 100.0, 101.0, first(2, 1, 2, 100.0, lag=20.0)),   # on its edge
    (SPAN, 120.0, 121.0, {"kind": "decode", "moe_pairs": 4}),
    (SPAN, 130.0, 131.0, first(3, 2, 6, 240.0, lag=30.0)),
    ("serve/admit", 140.0, 141.0, {"admitted": 1, "queue_ms": 0.1}),
    # the profiler hands values back as it likes: strings read the same
    (SPAN, 150.0, 260.0, {k: str(v) for k, v in
                          first(4, 6, 12, 660.0, lag=40.0).items()}),
    (SPAN, 200.0, 201.0, first(5, 3, 4, 200.0, lag=50.0)),   # on its edge
    (SPAN, 201.0, 202.0, first(6, 9, 30, 900.0)),    # started after it
    # a 'first' span of a program without the stamps (before PR 37)
    (SPAN, 160.0, 161.0, {"kind": "first", "moe_pairs": 7}),
]


def found():
    return fts.rows(HOST, 100.0, 200.0, SPAN)


def test_the_windows_edges():
    assert [int(st["sid"]) for st in found()] == [2, 3, 4, 5]


@pytest.mark.parametrize("params,expected", [
    ({"mode": "ratio", "num": "ttft_ms", "den": "wait_steps"},
     (100.0 + 240.0 + 660.0 + 200.0) / (2 + 6 + 12 + 4)),
    ({"mode": "ratio", "num": "wait_steps", "den": "prefill_programs"},
     (2 + 6 + 12 + 4) / (1 + 2 + 6 + 3)),
    ({"mode": "mean", "attr": "lag_ms"}, (20.0 + 30.0 + 40.0 + 50.0) / 4),
    ({"mode": "median", "attr": "prefill_programs"}, 2.5),
    ({"mode": "median", "attr": "wait_steps"}, 5.0)])
def test_modes_on_hand_made_spans(params, expected):
    assert fts.value(found(), params) == pytest.approx(expected)


def test_nothing_to_read_is_none_and_an_unknown_mode_raises():
    assert fts.rows(HOST, 300.0, 400.0, SPAN) == []
    assert fts.value([], {"mode": "mean", "attr": "lag_ms"}) is None
    # the parent's program: 'first' spans, none of the attributes
    old = [(n, a, b, st) for n, a, b, st in HOST if "wait_steps" not in st]
    assert fts.rows(old, 0.0, 1000.0, SPAN) == []
    with pytest.raises(ValueError):
        fts.value(found(), {"mode": "p95", "attr": "lag_ms"})


def test_the_line_names_every_first_token_in_order():
    assert fts.line(found()) == (
        "[first_token] sid:prefill_programs:queue_steps:wait_steps:ttft_ms "
        "2:1:0:2:100.0 3:2:1:6:240.0 4:6:0:12:660.0 5:3:1:4:200.0")


@pytest.mark.parametrize("metric", METRICS)
def test_metric_file_reads_the_run_through_the_reader(
        metric, monkeypatch, capsys):
    """As ``run._per_layer`` joins them: each file's parameters give its
    number from the parsed trace; the line is printed once a run; a
    rehearsal (no trace), a program without registries and a train cell
    read nothing."""
    decl = spec.layer_metric(metric)
    assert decl["reader"] == "first_token_spans"
    assert decl["params"]["span"] == SPAN
    parsed = {"host": HOST, "lo": 100.0, "hi": 200.0}
    monkeypatch.setattr(program_trace, "get", lambda observed: parsed)
    read = spec.reader(decl["reader"]).read
    value = read({"kind": "serve"}, decl["params"])
    assert value == pytest.approx({
        "ttft_ms_per_wait_step.serve": 50.0,
        "ttft_wait_steps_per_chunk.serve": 2.0,
        "ttft_prompt_chunks_p50.serve": 2.5,
        "first_token_lag_ms.serve": 35.0}[metric])
    assert read({"kind": "serve"}, decl["params"]) == value
    assert capsys.readouterr().out.count("[first_token]") == 1
    assert read({"kind": "train"}, decl["params"]) is None
    monkeypatch.setattr(program_trace, "get", lambda observed: None)
    assert read({"kind": "serve", "trace": None}, decl["params"]) is None


@pytest.mark.parametrize("metric", METRICS)
def test_entry_follows_the_cells_that_report_the_median_ttft(metric):
    """No ``workloads`` list of its own, as ``ttft_p95_ms.serve`` has
    none: a cell reports it where it reports ``ttft_p50_ms``, a serve
    cell a later PR adds too."""
    bench = spec.benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
    (moved,) = [m for m in bench["end_to_end"]
                if m["name"] == "ttft_p50_ms"]
    assert entry["moves"] == "ttft_p50_ms"
    assert entry["layer"] == "serve scheduler"
    assert entry["source"] == "program_span"
    assert "workloads" not in entry
    reporting = [w["name"] for w in bench["workloads"]
                 if spec.Cell(w["name"], bench).reports(entry)]
    assert reporting == [w["name"] for w in bench["workloads"]
                         if w["name"] in moved["workloads"]]
    assert len(reporting) == 5
