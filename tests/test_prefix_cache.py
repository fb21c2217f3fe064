"""Prefix-cache / batched-prefill / priority / streaming tests (ISSUE 11).

The load-bearing guarantees:

- the refcounted allocator: releasing a shared block once per sharer is
  legal, once more raises; copy-on-write never mutates a block another
  sequence reads; LRU eviction only ever takes refcount-0 cached blocks
  (the whole-reservation admission guarantee survives the cache).
- GREEDY serving stays token-identical to ``models.generate`` for
  prefix-hit, partial-hit, COW (fully-cached prompt), evict-then-
  readmit, batched-prefill, priority-policy and streamed request mixes,
  under decode_depth 1/2/3.
- ``load_params`` flushes the prefix cache: a post-handoff warm-prefix
  request is token-identical to a cold one under the NEW weights.
- streaming surfaces tokens at resolution time (the lagged ring), in
  order, exactly the tokens ``result()`` reports.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchacc_tpu.config import Config, ServeConfig
from torchacc_tpu.models import TransformerLM, get_preset
from torchacc_tpu.models.generate import generate
from torchacc_tpu.serve import BlockPool, PrefixIndex, Request, ServeEngine

pytestmark = pytest.mark.serving

VOCAB = 257


@pytest.fixture(scope="module")
def tiny():
    cfg = get_preset(
        "llama-tiny", dtype=jnp.float32, num_layers=2, hidden_size=64,
        num_heads=4, num_kv_heads=2, intermediate_size=128,
        vocab_size=VOCAB, max_seq_len=128)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _cfg(**kw):
    base = dict(block_size=8, num_blocks=64, max_slots=4, prefill_chunk=8,
                decode_depth=2, prefix_cache=True)
    base.update(kw)
    return Config(serve=ServeConfig(**base))


def _ref(model, params, prompts, max_new):
    p_max = max(len(p) for p in prompts)
    ids = np.zeros((len(prompts), p_max), np.int32)
    mask = np.zeros((len(prompts), p_max), np.int32)
    for i, p in enumerate(prompts):
        ids[i, p_max - len(p):] = p
        mask[i, p_max - len(p):] = 1
    out = np.asarray(generate(model, params, jnp.asarray(ids),
                              max_new_tokens=max_new,
                              prompt_mask=jnp.asarray(mask)))
    return [out[i, p_max:].tolist() for i in range(len(prompts))]


# ---------------------------------------------------------------------------
# allocator refcount + index units
# ---------------------------------------------------------------------------

def test_shared_block_free_per_sharer_then_double_free_raises():
    idx = PrefixIndex(8)
    pool = BlockPool(8, index=idx)
    (b,) = pool.alloc(1)
    pool.share(b)                            # second sequence joins
    assert pool.refcount(b) == 2
    pool.free([b])                           # sharer 1 releases: legal
    assert pool.refcount(b) == 1
    pool.free([b])                           # sharer 2 releases: legal
    assert pool.refcount(b) == 0
    with pytest.raises(ValueError):
        pool.free([b])                       # one more is a double free
    with pytest.raises(ValueError):
        pool.share(99)                       # foreign block


def test_indexed_block_parks_in_cache_and_revives():
    idx = PrefixIndex(8)
    pool = BlockPool(8, index=idx)
    (b,) = pool.alloc(1)
    key = idx.keys(np.arange(8))[0]
    assert idx.register(key, b)
    pool.free([b])
    assert pool.cached == 1 and pool.refcount(b) == 0
    assert idx.match([key]) == [b]           # still matchable
    pool.share(b)                            # prefix hit revives it
    assert pool.cached == 0 and pool.refcount(b) == 1
    pool.free([b])
    assert pool.flush_cached() == 1
    assert len(idx) == 0 and pool.available == 7


def test_eviction_takes_only_refcount_zero_lru_oldest_first():
    idx = PrefixIndex(4)
    pool = BlockPool(8, index=idx)           # usable: 7
    live = pool.alloc(3)
    parked = pool.alloc(4)
    keys = idx.keys(np.arange(16))           # 4 chain keys
    for k, b in zip(keys, parked):
        idx.register(k, b)
    for b in parked:                         # park one at a time: LRU order
        pool.free([b])
    assert pool.cached == 4 and pool.available == 4
    got = pool.alloc(2)                      # must evict 2 cached blocks
    assert got is not None
    assert set(got) == set(parked[:2])       # oldest-parked evicted first
    assert all(pool.refcount(b) == 1 for b in live)   # untouched
    assert idx.match(keys) == []             # chain broken at its root
    assert pool.evictions == 2
    assert pool.alloc(10) is None            # all-or-nothing still holds
    with pytest.raises(ValueError):
        pool.free([parked[2]])               # cached = no outstanding ref


def test_prefix_index_chain_semantics():
    idx = PrefixIndex(4)
    a = np.array([1, 2, 3, 4, 5, 6, 7, 8])
    b = np.array([1, 2, 3, 4, 9, 9, 9, 9])
    ka, kb = idx.keys(a), idx.keys(b)
    assert len(ka) == 2
    assert ka[0] == kb[0]                    # shared first block
    assert ka[1] != kb[1]                    # divergent second block
    # position is part of the chain: same tokens at a different depth
    # must not collide
    assert idx.keys(np.array([5, 6, 7, 8]))[0] != ka[1]
    assert idx.keys(np.array([1, 2, 3])) == []   # no full block
    assert idx.register(ka[0], 3)
    assert not idx.register(ka[0], 4)        # first writer wins
    assert not idx.register(kb[1], 3)        # block already keyed
    assert idx.match(ka) == [3]              # chain stops at the miss
    idx.forget(3)
    assert idx.match(ka) == []


# ---------------------------------------------------------------------------
# token identity: hit / partial / COW / evict-readmit under lag
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("depth", [1, 2, 3])
def test_prefix_request_streams_token_identical(tiny, depth):
    """Cold -> warm partial-hit -> full-match COW -> evict -> readmit,
    all token-identical to generate() at every decode depth."""
    model, params = tiny
    rng = np.random.default_rng(3)
    sys_a = rng.integers(1, VOCAB, size=16).tolist()   # 2 full blocks
    sys_b = rng.integers(1, VOCAB, size=24).tolist()   # 3 full blocks
    prompts = [
        sys_a + rng.integers(1, VOCAB, size=5).tolist(),   # cold A
        sys_a + rng.integers(1, VOCAB, size=9).tolist(),   # partial hit
        list(sys_a),                                       # full match: COW
        sys_b + rng.integers(1, VOCAB, size=3).tolist(),   # cold B
        list(sys_a),                                       # warm COW again
    ]
    max_new = 6
    eng = ServeEngine(model, params, _cfg(decode_depth=depth))
    ids = []
    for p in prompts:                        # waves: each completes before
        rid = eng.submit(Request(prompt_ids=p, max_new_tokens=max_new))
        eng.run()                            # the next submits -> warm hits
        ids.append(rid)
    refs = _ref(model, params, prompts, max_new)
    res = [eng.result(r) for r in ids]
    for r, ref in zip(res, refs):
        assert r.tokens == ref
    assert res[0].cached_prompt_tokens == 0
    assert res[1].cached_prompt_tokens == 16
    assert res[2].cached_prompt_tokens == 15           # COW: all but last
    assert res[4].cached_prompt_tokens == 15
    st = eng.stats()
    assert st["prefix_hits"] == 3 and st["cow_copies"] == 2
    assert st["prefill_tokens_saved"] == 16 + 15 + 15
    # pool conserved, nothing leaked into the cache accounting
    pool = eng.scheduler.pool
    assert pool.available + pool.in_use == eng.config.serve.num_blocks - 1
    eng.close()


def test_evict_then_readmit_token_identical(tiny):
    model, params = tiny
    rng = np.random.default_rng(4)
    sys_a = rng.integers(1, VOCAB, size=16).tolist()
    p_a = sys_a + rng.integers(1, VOCAB, size=4).tolist()
    # pool sized so serving the B wave MUST evict A's cached blocks:
    # usable 11, A takes 4 (16+4 prompt + 4 new + 2 depth = 26 -> 4
    # blocks), each B takes 5 (24+8 prompt + 4 new + 2 depth)
    conf = _cfg(num_blocks=12, max_slots=1)
    eng = ServeEngine(model, params, conf)
    r1 = eng.submit(Request(prompt_ids=p_a, max_new_tokens=4))
    eng.run()
    assert eng.scheduler.pool.cached > 0     # A's prompt blocks parked
    # each B is 40 + 4 + 2 = 46 tokens -> 6 blocks; B1 leaves 5 of its
    # own blocks cached, so B2's grant must evict A's parked chain
    b_prompts = [rng.integers(1, VOCAB, size=40).tolist() for _ in range(2)]
    rb = [eng.submit(Request(prompt_ids=p, max_new_tokens=4))
          for p in b_prompts]
    eng.run()
    assert eng.stats()["prefix_evictions"] > 0
    r2 = eng.submit(Request(prompt_ids=p_a, max_new_tokens=4))  # readmit
    eng.run()
    refs = _ref(model, params, [p_a] + b_prompts, 4)
    assert eng.result(r1).tokens == refs[0]
    assert eng.result(r2).tokens == refs[0]  # identical after eviction
    assert eng.result(r2).cached_prompt_tokens == 0   # and genuinely cold
    for rid, ref in zip(rb, refs[1:]):
        assert eng.result(rid).tokens == ref
    eng.close()


def test_cow_never_mutates_block_other_sequences_read(tiny):
    """A COW request decodes WHILE the original owner still runs and
    while a third request shares the same blocks — everyone stays
    token-identical, so the shared blocks were never written."""
    model, params = tiny
    rng = np.random.default_rng(5)
    sys_a = rng.integers(1, VOCAB, size=16).tolist()
    prompts = [
        sys_a + rng.integers(1, VOCAB, size=7).tolist(),   # the owner
        list(sys_a),                                       # COW off live blocks
        sys_a + rng.integers(1, VOCAB, size=3).tolist(),   # shares live too
    ]
    max_new = 10
    eng = ServeEngine(model, params, _cfg(max_slots=3))
    r0 = eng.submit(Request(prompt_ids=prompts[0], max_new_tokens=max_new))
    for _ in range(4):                       # owner prefills + decodes a bit
        eng.step()
    r1 = eng.submit(Request(prompt_ids=prompts[1], max_new_tokens=max_new))
    r2 = eng.submit(Request(prompt_ids=prompts[2], max_new_tokens=max_new))
    eng.run()
    refs = _ref(model, params, prompts, max_new)
    for rid, ref in zip((r0, r1, r2), refs):
        assert eng.result(rid).tokens == ref
    assert eng.result(r1).cached_prompt_tokens == 15    # COW hit
    assert eng.result(r2).cached_prompt_tokens == 16    # live sharing
    eng.close()


# ---------------------------------------------------------------------------
# batched prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batch", [2, 4])
def test_batched_prefill_token_identical(tiny, batch):
    model, params = tiny
    rng = np.random.default_rng(6)
    lens = [6, 19, 11, 25, 9, 14]            # mixed, some multi-chunk
    prompts = [rng.integers(1, VOCAB, size=n).tolist() for n in lens]
    max_new = 6
    for prefix in (False, True):
        eng = ServeEngine(model, params,
                          _cfg(prefill_batch=batch, prefix_cache=prefix,
                               max_slots=4))
        ids = [eng.submit(Request(prompt_ids=p, max_new_tokens=max_new))
               for p in prompts[:4]]
        for _ in range(3):                   # second wave lands mid-flight
            eng.step()
        ids += [eng.submit(Request(prompt_ids=p, max_new_tokens=max_new))
                for p in prompts[4:]]
        eng.run()
        refs = _ref(model, params, prompts, max_new)
        for rid, ref in zip(ids, refs):
            assert eng.result(rid).tokens == ref
        eng.close()


def test_batched_prefill_single_candidate_takes_single_seq_path(tiny):
    # one waiting sequence under prefill_batch=4 falls back to the
    # single-sequence program (no pad rows burning 4x the FLOPs) and
    # stays token-identical
    model, params = tiny
    rng = np.random.default_rng(7)
    p = rng.integers(1, VOCAB, size=21).tolist()
    eng = ServeEngine(model, params, _cfg(prefill_batch=4))
    calls = []
    orig = eng.scheduler._prefill_batched
    eng.scheduler._prefill_batched = \
        lambda seqs: (calls.append(len(seqs)), orig(seqs))[1]
    rid = eng.submit(Request(prompt_ids=p, max_new_tokens=5))
    eng.run()
    assert calls == []                       # batched program never ran
    assert eng.result(rid).tokens == _ref(model, params, [p], 5)[0]
    eng.close()


# ---------------------------------------------------------------------------
# priority / deadline policy
# ---------------------------------------------------------------------------

def _admit_order(eng, reqs):
    """Submit everything while one slot is occupied, run, and return
    request ids in admission (t_admit) order."""
    ids = [eng.submit(r) for r in reqs]
    eng.run()
    return sorted(ids, key=lambda i: eng._all[i].t_admit)


def test_priority_class_then_deadline_orders_admission(tiny):
    model, params = tiny
    rng = np.random.default_rng(8)
    mk = lambda **kw: Request(  # noqa: E731
        prompt_ids=rng.integers(1, VOCAB, size=6).tolist(),
        max_new_tokens=3, **kw)
    eng = ServeEngine(model, params,
                      _cfg(max_slots=1, policy="priority",
                           priority_aging_s=0.0, prefix_cache=False))
    # a running request pins the single slot so the queue builds up
    blocker = eng.submit(mk())
    eng.step()
    order = _admit_order(eng, [
        mk(priority=0, deadline_s=1000.0),               # low class
        mk(priority=5, deadline_s=1000.0),               # high, late ddl
        mk(priority=5, deadline_s=10.0),                 # high, EDF winner
        mk(priority=1),                                  # mid, no deadline
    ])
    # ids are submit-ordered after the blocker (1..4): high class + EDF
    # winner first, then its later-deadline classmate, then the mid
    # class, then the starved-without-aging low class
    assert order == [3, 2, 4, 1]
    assert eng.result(blocker).finish_reason in ("length", "eos")
    st = eng.stats()
    assert st["deadline_requests"] == 3 and st["deadline_misses"] >= 0
    eng.close()


def test_priority_aging_bounds_starvation(tiny):
    model, params = tiny
    rng = np.random.default_rng(9)
    mk = lambda prio: Request(  # noqa: E731
        prompt_ids=rng.integers(1, VOCAB, size=6).tolist(),
        max_new_tokens=3, priority=prio)
    eng = ServeEngine(model, params,
                      _cfg(max_slots=1, policy="priority",
                           priority_aging_s=0.05, prefix_cache=False))
    blocker = eng.submit(mk(9))
    eng.step()
    low = eng.submit(mk(0))                  # would starve without aging
    time.sleep(0.6)                          # low's effective class rises
    high = eng.submit(mk(5))
    eng.run()
    assert eng._all[low].t_admit < eng._all[high].t_admit
    for rid in (blocker, low, high):
        assert eng.result(rid).finish_reason
    eng.close()


def test_submit_rejects_nonpositive_deadline(tiny):
    model, params = tiny
    eng = ServeEngine(model, params, _cfg())
    with pytest.raises(ValueError, match="deadline_s"):
        eng.submit(Request(prompt_ids=[1, 2], deadline_s=0.0))
    eng.close()


def test_deadline_met_and_miss_accounting(tiny):
    model, params = tiny
    rng = np.random.default_rng(10)
    p = rng.integers(1, VOCAB, size=6).tolist()
    eng = ServeEngine(model, params, _cfg(policy="priority"))
    hit = eng.submit(Request(prompt_ids=p, max_new_tokens=3,
                             deadline_s=1000.0))
    miss = eng.submit(Request(prompt_ids=p, max_new_tokens=3,
                              deadline_s=1e-7))
    eng.run()
    assert eng.result(hit).deadline_met is True
    assert eng.result(miss).deadline_met is False
    st = eng.stats()
    assert st["deadline_requests"] == 2 and st["deadline_misses"] == 1
    eng.close()


def test_serve_config_validates_new_fields():
    with pytest.raises(ValueError, match="policy"):
        ServeConfig(policy="edf").validate()
    with pytest.raises(ValueError, match="prefill_batch"):
        ServeConfig(prefill_batch=0).validate()
    with pytest.raises(ValueError, match="priority_aging_s"):
        ServeConfig(priority_aging_s=-1.0).validate()


# ---------------------------------------------------------------------------
# streaming
# ---------------------------------------------------------------------------

def test_stream_and_callback_deliver_exactly_result_tokens(tiny):
    model, params = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, VOCAB, size=n).tolist() for n in (7, 13)]
    eng = ServeEngine(model, params, _cfg(decode_depth=3))
    pushed = []
    r0 = eng.submit(Request(prompt_ids=prompts[0], max_new_tokens=8),
                    on_token=lambda t, ts: pushed.append((t, ts)))
    r1 = eng.submit(Request(prompt_ids=prompts[1], max_new_tokens=8))
    pulled = list(eng.stream(r1))            # drives r0 to completion too
    eng.run()
    refs = _ref(model, params, prompts, 8)
    assert eng.result(r0).tokens == refs[0]
    assert [t for t, _ in pushed] == refs[0]             # pushed in order
    assert pulled == refs[1] == eng.result(r1).tokens    # pulled in order
    ts = [t for _, t in pushed]
    assert ts == sorted(ts)                  # resolution timestamps ordered
    # callback timestamps ARE the SLO timestamps (streaming feeds the
    # same metrics)
    assert ts == eng._all[r0].token_times
    eng.close()


def test_raising_callback_disabled_not_fatal(tiny):
    model, params = tiny
    rng = np.random.default_rng(12)
    p = rng.integers(1, VOCAB, size=6).tolist()
    eng = ServeEngine(model, params, _cfg())
    seen = []

    def bad(tok, ts):
        seen.append(tok)
        raise RuntimeError("consumer went away")

    rid = eng.submit(Request(prompt_ids=p, max_new_tokens=6), on_token=bad)
    eng.run()
    assert len(seen) == 1                    # disabled after the first raise
    assert eng.result(rid).tokens == _ref(model, params, [p], 6)[0]
    eng.close()


# ---------------------------------------------------------------------------
# weight-swap flush (the PR-8 handoff seam)
# ---------------------------------------------------------------------------

def test_load_params_flushes_prefix_cache_token_identical_to_cold(tiny):
    model, params = tiny
    params2 = jax.tree.map(lambda x: x * 1.25, params)   # different model
    rng = np.random.default_rng(13)
    sys_a = rng.integers(1, VOCAB, size=16).tolist()
    warm = sys_a + rng.integers(1, VOCAB, size=5).tolist()
    eng = ServeEngine(model, params, _cfg())
    r0 = eng.submit(Request(prompt_ids=warm, max_new_tokens=5))
    eng.run()
    assert eng.scheduler.pool.cached > 0     # prefix parked
    eng.load_params(params2)                 # weight swap MUST flush
    assert eng.scheduler.pool.cached == 0
    assert len(eng.scheduler.prefix) == 0
    r1 = eng.submit(Request(prompt_ids=warm, max_new_tokens=5))
    eng.run()
    res = eng.result(r1)
    assert res.cached_prompt_tokens == 0     # served cold, not stale
    assert res.tokens == _ref(model, params2, [warm], 5)[0]
    # sanity: the two weight sets disagree on this prompt, so a stale
    # prefix hit WOULD have been observable as a token mismatch
    assert eng.result(r0).tokens != res.tokens
    eng.close()


# ---------------------------------------------------------------------------
# TPU block-size hygiene
# ---------------------------------------------------------------------------

def test_tpu_block_size_is_an_error_not_a_warning(tiny, monkeypatch):
    # on a TPU backend 'auto' resolves to the paged kernel, which tiles
    # a block as (block_size, head_dim): a size it cannot tile is a
    # typed error at construction — there is no gather fallback to
    # warn about.  The tiny model's pool is f32: multiples of 8.
    import torchacc_tpu as ta
    import torchacc_tpu.serve.scheduler as sched_mod
    model, params = tiny
    monkeypatch.setattr(sched_mod, "on_tpu", lambda: True)
    with pytest.raises(ta.ConfigError, match="multiple of 8"):
        ServeEngine(model, params, _cfg(block_size=4, prefix_cache=False))
    eng = ServeEngine(model, params, _cfg(block_size=8, prefix_cache=False))
    assert eng.scheduler.decoder.impl == "pallas"
    eng.close()
    # off the chip 'auto' is the jnp gather path, which takes any size
    monkeypatch.setattr(sched_mod, "on_tpu", lambda: False)
    eng = ServeEngine(model, params, _cfg(block_size=4, prefix_cache=False))
    assert eng.scheduler.decoder.impl == "xla"
    eng.close()
