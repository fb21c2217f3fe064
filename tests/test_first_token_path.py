"""A request's way to its first token (PR 37): the stamps ``Sequence``
carries whether or not anything traces, what ``RequestResult`` derives
from them, and the decomposition on the
``serve/deliver`` span that delivers the first token (ring sink here; the
profiler sink is read in ``tests/test_tracing_timeline.py``).

A toy engine on the CPU under a closed loop of a few clients with prompts
of 1-6 chunks.  The property the on-chip metrics rest on: counted in
``Scheduler.step()`` calls a request's wait is the same whatever a step
costs.
"""

import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import torchacc_tpu as ta
from torchacc_tpu.models import TransformerLM, get_preset
from torchacc_tpu.obs import tracing
from torchacc_tpu.serve import Request, ServeEngine

CHUNK = 8
# prompts of 1-6 chunks, a closed loop's worth: 14 requests
PROMPT_LENS = (5, 12, 20, 31, 40, 47, 8, 17, 33, 3, 44, 24, 9, 38)
FIRST_TOKEN_ATTRS = ("sid", "prefill_programs", "queue_steps",
                     "wait_steps", "queue_ms", "prefill_ms", "lag_ms",
                     "ttft_ms")


@pytest.fixture(scope="module")
def toy():
    mc = get_preset(
        "llama-tiny", dtype=jnp.float32, num_layers=1, hidden_size=64,
        num_heads=4, num_kv_heads=2, intermediate_size=128,
        vocab_size=257, max_seq_len=128)
    model = TransformerLM(mc)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _engine(toy, **serve):
    kw = dict(block_size=8, num_blocks=64, max_slots=3,
              prefill_chunk=CHUNK, decode_depth=2)
    kw.update(serve)
    return ServeEngine(*toy, ta.Config(serve=ta.config.ServeConfig(**kw)))


def _requests(lens=PROMPT_LENS):
    rng = np.random.default_rng(0)
    return [Request(prompt_ids=rng.integers(1, 257, size=n).tolist(),
                    max_new_tokens=3 + i % 4) for i, n in enumerate(lens)]


def _closed_loop(engine, requests, clients, step_sleep=None):
    """``clients`` callers, each sending its next request when its reply
    is complete; ``step_sleep(i)`` seconds are slept inside scheduler
    step ``i``.  -> results by request id, engine steps taken."""
    sched = engine.scheduler
    if step_sleep is not None:
        inner = sched.step

        def slowed():
            time.sleep(step_sleep(sched._step_idx))
            return inner()
        sched.step = slowed
    todo = list(requests)
    live = {engine.submit(todo.pop(0)) for _ in range(clients)}
    results, steps = {}, 0
    while live:
        engine.step()
        steps += 1
        for rid in [r for r in live if engine._all[r].finished]:
            live.remove(rid)
            results[rid] = engine.result(rid, pop=True)
            if todo:
                live.add(engine.submit(todo.pop(0)))
    return results, steps


@pytest.fixture(scope="module")
def served(toy):
    """One closed loop of three clients with both sinks idle."""
    assert not tracing.enabled()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    engine = _engine(toy)
    results, steps = _closed_loop(engine, _requests(), clients=3)
    out = {"results": results, "steps": steps,
           "step_idx": engine.scheduler._step_idx}
    engine.close()
    return out


def test_the_stamps_exist_with_tracing_off(served):
    assert len(served["results"]) == len(PROMPT_LENS)
    assert served["step_idx"] == served["steps"]   # one add a step()
    assert all(r.wait_steps >= 1 and r.prefill_programs >= 1
               and r.ttft_s > 0 for r in served["results"].values())


@pytest.mark.parametrize("rid", range(len(PROMPT_LENS)))
def test_three_durations_sum_to_the_ttft(served, rid):
    r = served["results"][rid]
    assert min(r.queue_wait_s, r.prefill_s, r.first_token_lag_s) >= 0
    assert r.queue_wait_s + r.prefill_s + r.first_token_lag_s == \
        pytest.approx(r.ttft_s, abs=1e-6)      # one clock: time.monotonic
    # the lag holds a dispatch and a blocking fetch: never nothing
    assert r.first_token_lag_s > 0 and r.prefill_s > 0


@pytest.mark.parametrize("rid", range(len(PROMPT_LENS)))
def test_a_request_waits_at_least_its_own_programs(served, rid):
    r = served["results"][rid]
    chunks = math.ceil(PROMPT_LENS[rid] / CHUNK)
    assert r.prefill_programs == chunks
    assert r.wait_steps >= r.prefill_programs + r.queue_steps


def test_other_prompts_chunks_take_turns(served):
    """One chunk a step and three clients: the first request's chunks run
    back to back, a later one waits for the lower ids ahead of it."""
    results = served["results"]
    assert results[0].wait_steps == results[0].prefill_programs
    assert any(r.wait_steps > r.prefill_programs for r in results.values())


@pytest.mark.parametrize("slots", (3, 1))
def test_queue_steps_count_the_steps_spent_without_a_slot(toy, slots):
    """Three clients: with a slot each nobody queues, and counted in
    steps that reads 0 for every request; with ONE slot the two behind
    the first wait whole steps, and the steps they queued lie inside
    their wait_steps."""
    engine = _engine(toy, max_slots=slots)
    results, _ = _closed_loop(engine, _requests(PROMPT_LENS[:6]), clients=3)
    engine.close()
    queued = [results[rid].queue_steps for rid in sorted(results)]
    if slots == 3:
        assert queued == [0] * 6
    else:
        assert queued[0] == 0 and min(queued[1:]) >= 1
        assert all(r.queue_wait_s > 0 for r in results.values()
                   if r.queue_steps)
    assert all(r.wait_steps >= r.queue_steps + r.prefill_programs
               for r in results.values())


@pytest.mark.parametrize("clients", (2, 3))
def test_wait_steps_do_not_depend_on_what_a_step_costs(toy, clients):
    """The same closed loop with steps slowed by different sleeps: request
    n waits the same number of steps for its first token and takes the
    same number of programs (its TTFT in seconds holds the sleeps)."""
    runs = []
    for sleep in (None, lambda i: 0.002 * (i % 3),
                  lambda i: 0.004 if i % 5 == 0 else 0.0005):
        engine = _engine(toy)
        results, steps = _closed_loop(engine, _requests(), clients, sleep)
        engine.close()
        runs.append((results, steps))
    (fast, n_fast), (slow_a, n_a), (slow_b, n_b) = runs
    assert n_fast == n_a == n_b
    for rid, r in fast.items():
        for other in (slow_a[rid], slow_b[rid]):
            assert other.tokens == r.tokens
            assert other.wait_steps == r.wait_steps
            assert other.queue_steps == r.queue_steps
            assert other.prefill_programs == r.prefill_programs


def test_a_cached_prefix_lowers_prefill_programs(toy):
    engine = _engine(toy, prefix_cache=True)
    (cold,), (warm,) = (
        _closed_loop(engine, _requests((44,)), clients=1)[0].values()
        for _ in range(2))
    engine.close()
    assert cold.cached_prompt_tokens == 0 and cold.prefill_programs == 6
    assert warm.cached_prompt_tokens == 40      # five full blocks of 8
    assert warm.prefill_programs == 1 and warm.wait_steps == 1
    assert warm.tokens == cold.tokens


def test_the_batched_prefill_counts_each_row(toy):
    """Two prompts in one program a step: each row's sequence counts the
    program, and neither waits for the other's chunks."""
    engine = _engine(toy, prefill_batch=2)
    results, _ = _closed_loop(engine, _requests((12, 20)), clients=2)
    engine.close()
    assert [results[i].prefill_programs for i in (0, 1)] == [2, 3]
    assert [results[i].wait_steps for i in (0, 1)] == [2, 3]


# -- the decomposition on the span, ring sink ---------------------------------

@pytest.fixture(scope="module")
def ring(toy):
    """serve/deliver spans of a closed loop with the ring on, and what
    RequestResult said of the same requests."""
    engine = _engine(toy)
    tracing.configure(enabled=True)
    try:
        tracing.clear()
        results, _ = _closed_loop(engine, _requests(PROMPT_LENS[:6]),
                                  clients=3)
        spans = [s["attrs"] for s in tracing.snapshot()
                 if s["name"] == "serve/deliver"]
    finally:
        tracing.configure(enabled=False)
        tracing.clear()
    engine.close()
    return {"results": results, "spans": spans}


@pytest.mark.parametrize("attr", FIRST_TOKEN_ATTRS)
def test_first_deliver_span_carries_the_decomposition(ring, attr):
    firsts = [a for a in ring["spans"] if a["kind"] == "first"]
    decodes = [a for a in ring["spans"] if a["kind"] == "decode"]
    assert len(firsts) == 6 and decodes
    assert all(attr in a for a in firsts)
    assert not any(attr in a for a in decodes)


def test_span_and_result_tell_the_same_numbers(ring):
    for a in (a for a in ring["spans"] if a["kind"] == "first"):
        r = ring["results"][a["sid"]]
        assert (a["queue_steps"], a["wait_steps"],
                a["prefill_programs"]) == \
            (r.queue_steps, r.wait_steps, r.prefill_programs)
        assert a["ttft_ms"] == pytest.approx(r.ttft_s * 1e3)
        assert a["queue_ms"] + a["prefill_ms"] + a["lag_ms"] == \
            pytest.approx(a["ttft_ms"], abs=1e-3)
