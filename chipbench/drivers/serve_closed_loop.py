"""Driver ``serve_closed_loop``: ``ServeEngine`` under a closed loop of clients.

``clients`` callers each wait for their reply before sending the next
request (RL-rollout / batch-generation workers), all driven from this
one thread: ``engine.step()``, hand finished replies back, submit each
freed client's next request.  Every token's delivery is stamped by the
engine's own streaming seam (``submit(..., on_token=)``) on the host's
monotonic clock; the benchmark computes rates, gaps and percentiles from
those stamps with its own arithmetic.

Warm-up runs until every client has completed one request (every
program the traffic uses is compiled by then and the clients are out of
step with each other), then the window; requests in flight when it
closes are drained so that each has an outcome.  After that the engine
is closed and freed and the plain reference reads a seeded sample of the
finished requests.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import program, stats, traffic_gen


class _Live:
    __slots__ = ("rid", "t_submit", "prompt", "out_len", "times",
                 "in_window", "result", "slot")

    def __init__(self, rid, t_submit, prompt, out_len, in_window):
        self.rid, self.t_submit = rid, t_submit
        self.prompt, self.out_len = prompt, out_len
        self.times = []
        self.in_window = in_window
        self.slot = None


def run(ctx):
    import jax
    import jax.numpy as jnp

    from torchacc_tpu.models import TransformerLM
    from torchacc_tpu.serve import Request, ServeEngine

    cell, traffic = ctx.cell, ctx.cell.traffic
    weights, layout = cell.weights(), cell.layout()
    published, depth = cell.published, cell.depth
    mc = program.model_config(published, depth,
                              max_seq_len=traffic["max_seq_len"],
                              param_dtype=traffic["param_dtype"])
    cfg = program.framework_config(traffic["settings"], ctx.seed)
    key = weights.base_key(ctx.seed)
    params = jax.jit(lambda k: layout.to_program_params(
        weights.make(k, published, depth,
                     jnp.dtype(traffic["param_dtype"])), mc))(key)
    engine = ServeEngine(TransformerLM(mc), params, cfg)
    del params
    sched = engine.scheduler
    impl = sched.decoder.impl
    if not ctx.rehearse and impl != traffic["require_impl"]:
        raise SystemExit(f"chipbench: paged attention resolved to {impl!r}, "
                         f"the cell requires {traffic['require_impl']!r}")
    max_slots = cfg.serve.max_slots
    ctx.note(f"[serve] depth={depth} "
             f"params={weights.param_count(published, depth) / 1e6:.1f}M "
             f"impl={impl} slots={max_slots} clients={traffic['clients']} "
             f"block_size={cfg.serve.block_size} "
             f"num_blocks={cfg.serve.num_blocks} "
             f"prefill_chunk={cfg.serve.prefill_chunk}")

    stream = traffic_gen.Requests(traffic, ctx.seed, published["vocab_size"])
    live, done = {}, []
    state = {"in_window": False}

    def submit():
        prompt, out_len = stream.next()
        entry = _Live(None, time.monotonic(), prompt, out_len,
                      state["in_window"])
        entry.rid = engine.submit(
            Request(prompt_ids=prompt, max_new_tokens=out_len),
            on_token=lambda tok, t, e=entry: e.times.append(t))
        live[entry.rid] = entry

    def collect(resubmit):
        """Hand finished replies back; each freed client sends again."""
        for rid in [r for r, e in live.items()
                    if len(e.times) >= e.out_len]:
            entry = live.pop(rid)
            entry.result = engine.result(rid, pop=True)
            done.append(entry)
            if resubmit:
                submit()

    def step():
        """One engine iteration; note which slot serves each request
        (the correctness sample reads every slot)."""
        engine.step()
        for slot, seq in enumerate(sched.slot_seq):
            entry = live.get(seq.sid) if seq is not None else None
            if entry is not None:
                entry.slot = slot

    for _ in range(traffic["clients"]):
        submit()
    # warm-up: until every client has had one reply
    while len(done) < traffic["clients"]:
        step()
        collect(resubmit=True)

    iters, occupancy, kv_reads, prefill_chunks = 0, [], [], []
    seen_prefilled = {}
    kh = published.get("num_key_value_heads") or \
        published["num_attention_heads"]

    def observe(decode_iter_before):
        """What this iteration made the paged kernel read (trace runs)."""
        for seq in sched.slot_seq:
            if seq is None:
                continue
            before = seen_prefilled.get(seq.sid, 0)
            if seq.prefilled > before:
                prefill_chunks.append((before, seq.prefilled - before))
                seen_prefilled[seq.sid] = seq.prefilled
        if sched._iter > decode_iter_before:
            kv_reads.append(int(sched.seq_lens[sched.active].sum()))

    state["in_window"] = True
    with ctx.window() as win:
        m0 = time.monotonic()
        deadline = win.t0 + ctx.window_seconds
        while time.perf_counter() < deadline:
            occupancy.append(int(sched.active.sum()))
            it0 = sched._iter
            with ctx.annotate("chipbench/engine_step"):
                step()
            iters += 1
            if ctx.trace:
                observe(it0)
            with ctx.annotate("chipbench/deliver_submit"):
                collect(resubmit=True)
        m1 = time.monotonic()
    state["in_window"] = False
    peak = ctx.memory_peak()
    # drain: every request in flight gets its outcome; nobody sends again
    while live:
        step()
        collect(resubmit=False)

    times = [t for e in done for t in e.times if m0 <= t <= m1]
    gaps = [b - a for e in done for a, b in zip(e.times, e.times[1:])
            if m0 <= b <= m1]
    ttft = [e.times[0] - e.t_submit for e in done
            if e.in_window and e.times]
    in_window = [e for e in done if e.in_window]
    bad = [e for e in done if e.result.finish_reason != "length"
           or len(e.result.tokens) != e.out_len
           or len(e.times) != e.out_len]
    seconds = m1 - m0
    ctx.note(f"[serve] window {seconds:.3f}s iterations={iters} "
             f"tokens_delivered={len(times)} requests_submitted_in_window="
             f"{len(in_window)} finished_total={len(done)} "
             f"gap_samples={len(gaps)} ttft_samples={len(ttft)}")
    if gaps and ttft:
        ctx.note(f"[serve] itl_ms median={stats.median(gaps) * 1e3:.3f} "
                 f"p95={stats.percentile(gaps, 95) * 1e3:.3f} "
                 f"max={max(gaps) * 1e3:.3f}; ttft_ms median="
                 f"{stats.median(ttft) * 1e3:.3f} "
                 f"p95={stats.percentile(ttft, 95) * 1e3:.3f} "
                 f"max={max(ttft) * 1e3:.3f}; decode occupancy mean "
                 f"{np.mean(occupancy):.2f} of {max_slots}")
        ctx.note("[serve] ttft_ms longest 16 of the window: " + " ".join(
            f"{t * 1e3:.1f}" for t in sorted(ttft)[-16:]))

    engine.close()
    block_size = cfg.serve.block_size
    del engine, sched
    ctx.free_device_memory()

    # -- the reference over a seeded sample of what the window served ------
    checks = [("requests_not_completed_at_forced_length", float(len(bad)),
               0.5)]
    ref = cell.reference()
    served = [e for e in done if e.in_window] or done
    rng = np.random.default_rng(ctx.seed)
    longest = max(served, key=lambda e: len(e.prompt) + e.out_len)
    picks = [longest]
    for slot in sorted({e.slot for e in served if e.slot is not None}):
        of_slot = [e for e in served if e.slot == slot and e is not longest]
        picks += [of_slot[i] for i in rng.permutation(len(of_slot))[
            :traffic["check_requests_per_slot"]]]
    max_prompt, max_out = stream.longest
    t_ref = time.perf_counter()
    ref_weights = ctx.reference_weights_maker(
        published, depth, traffic["param_dtype"])()
    limit = cell.config["limits"]["serve"]["logit_gap"]
    gap, n_tokens = served_token_gap(
        ref, ref_weights, ref.sizes_of(published), picks,
        max_prompt + max_out, max_out)
    t_ref = time.perf_counter() - t_ref
    for which in ctx.controls():
        ctx.control_reading(
            which, "served_token_logit_gap_widest", served_token_gap(
                ref, ref_weights, ref.sizes_of(published), picks,
                max_prompt + max_out, max_out,
                put_first=ref.lower_precision_dot(which))[0], limit)
    ctx.note(f"[serve] reference read {len(picks)} requests from slots "
             f"{sorted({e.slot for e in picks if e.slot is not None})}, "
             f"{n_tokens} served tokens "
             f"(the longest: {len(longest.prompt)} + {longest.out_len}) in "
             f"{t_ref:.1f}s")
    checks.append(("served_token_logit_gap_widest", gap, limit))

    return dict(
        checks=checks, attempted=len(done), failed=len(bad),
        memory_peak_bytes=peak,
        end_to_end={
            "serve_tokens_per_s": len(times) / seconds,
            "itl_p95_ms": stats.percentile(gaps, 95) * 1e3,
            "ttft_p50_ms": stats.median(ttft) * 1e3},
        observed=dict(kind="serve", window_s=seconds, iterations=iters,
                      occupancy=occupancy, max_slots=max_slots,
                      kv_tokens_read=kv_reads, prefill_chunks=prefill_chunks,
                      ttft_in_window_s=[
                          e.times[0] - e.t_submit for e in done
                          if e.in_window and e.times and e.times[0] <= m1],
                      depth=depth, published=published, chips=cell.chips,
                      block_size=block_size, kv_heads=kh,
                      family=cell.config["family"]))


def served_token_gap(ref, ref_weights, sizes, entries, pad_to, max_out,
                     put_first=None):
    """Widest gap by which a served token's logit lies below the
    reference's best, over every served token of ``entries``.

    One forward per request over its prompt and served tokens, padded at
    the end to one fixed length (causal: the padding changes nothing
    before it) so that one compiled program serves every request.
    ``put_first`` (the control) replaces the served tokens by those a
    second ``dot`` puts first at each position."""
    import jax
    import jax.numpy as jnp
    pad_to = -(-pad_to // 128) * 128

    def jitted(dot):
        return jax.jit(lambda w, ids, pos: ref.logits_at(w, sizes, ids, pos,
                                                         dot))

    fn = jitted(ref.lower_precision_dot("float32"))
    fn_ctrl = jitted(put_first) if put_first is not None else None
    worst, count = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for e in entries:
            tokens = list(e.result.tokens)
            p, t = len(e.prompt), len(tokens)
            ids = np.zeros((pad_to,), np.int32)
            ids[:p + t - 1] = np.asarray(e.prompt + tokens[:-1], np.int32)
            positions = np.full((max_out,), p + t - 2, np.int32)
            positions[:t] = np.arange(p - 1, p + t - 1)
            z = fn(ref_weights, jnp.asarray(ids), jnp.asarray(positions))[:t]
            picked = jnp.asarray(tokens, jnp.int32)
            if fn_ctrl is not None:
                picked = jnp.argmax(fn_ctrl(
                    ref_weights, jnp.asarray(ids),
                    jnp.asarray(positions))[:t], axis=-1)
            gaps = jnp.max(z, axis=-1) - jnp.take_along_axis(
                z, picked[:, None], axis=-1)[:, 0]
            worst = max(worst, float(jnp.max(gaps)))
            count += t
    return worst, count
