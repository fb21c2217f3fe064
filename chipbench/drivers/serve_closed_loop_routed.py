"""Driver ``serve_closed_loop_routed``: the closed loop of
``serve_closed_loop`` for a family with a discrete router, with that
family's own comparison.

Why the dense family's comparison does not carry over (my chip runs, PR
26, ``axk1.serve.rollout32``): the widest gap of a served token's logit
below the float32 reference's best read 0.66 / 0.96 / 1.07 on three
sound seeds and 1.33 / 1.48 / 1.23 for the int8 control — no limit lies
between.  The sound runs' widest gaps are not rounding noise: a token
whose 8th and 9th best experts tie to bfloat16 precision is routed to
another expert by the program than by the reference, both within their
precision, and swapping one of eight experts moves that token's logits
by more than int8 arithmetic moves a typical one.  Such positions are
few (the 90th percentile of a sound run's gaps is 0, the 99th 0.15-0.18
— the edge of the flipped tail; the control's 0.24 and 0.64), so the
comparison is two readings:

- ``served_token_logit_gap_widest`` over the positions whose routing
  the reference calls settled: it says, for every position, how far its
  routing is from one that would change what this chip computes
  (``route``'s margin in ``reference/mla_moe_decoder.py``), and only
  positions whose margin is at least ``limits.serve.route_margin`` are
  read.  There the program is held to the reference's routing and to
  its logits alike, token by token.  The share of served positions NOT
  read is printed and is itself held to a limit
  (``served_positions_not_read_share``), so the margin cannot be set to
  read nothing.
- ``served_token_logit_gap_p95`` over EVERY served position, ties and
  all: the 95th percentile of the gaps, which the one or two flipped
  tokens in a hundred cannot move and arithmetic of lower precision
  does.

Everything else — load, clock, sample of requests, the control — is
``serve_closed_loop``'s and runs its code, with one piece of hygiene
before the window (as ``timeit`` switches the collector off): the heap
that set-up and warm-up left (some millions of objects: modules, traces,
compiled programs' wrappers) is collected once and frozen, so that a
generation-2 pass of Python's collector inside the window scans what
the window made and not all of that.  Without it one such pass of
45-100 ms fell into five of six windows (my chip runs, PR 26: the
window's longest gap between tokens 133-187 ms against 90 without), and
at ~1,010 iterations a window each costs 0.1-0.3% of
``serve_tokens_per_s`` — as much as half its bound allows in all.
"""

from __future__ import annotations

import contextlib
import functools
import gc

import numpy as np

from chipbench.drivers import serve_closed_loop as base

MARGIN_GRID = (0.0, 0.005, 0.0075, 0.01, 0.015, 0.02, 0.03, 0.04)
FLIPPED = 0.2                # a gap this wide on a sound run is a flip
# where the limits in force state none of this driver's own (a rehearsal
# puts chipbench/rehearsal.json's toy limits in the configuration's place)
DEFAULT_MARGIN, DEFAULT_NOT_READ = 0.01, 0.5


def run(ctx):
    limits = ctx.cell.config["limits"]["serve"]
    seen = {}                # the program's readings beside the widest
    gap = functools.partial(served_token_gap, note=ctx.note, seen=seen,
                            margin=limits.get("route_margin", DEFAULT_MARGIN))
    original, base.served_token_gap = base.served_token_gap, gap
    window = ctx.window

    @contextlib.contextmanager
    def quiet_window():
        gc.collect()
        gc.freeze()
        try:
            with window() as win:
                yield win
        finally:
            gc.unfreeze()

    ctx.window = quiet_window
    try:
        out = base.run(ctx)
    finally:
        base.served_token_gap = original
        del ctx.window
    out["checks"] += [
        ("served_positions_not_read_share", 1.0 - seen["share"],
         limits.get("positions_not_read_share", DEFAULT_NOT_READ)),
        ("served_token_logit_gap_p95", seen["p95"],
         limits.get("logit_gap_p95", limits["logit_gap"]))]
    return out


def served_token_gap(ref, ref_weights, sizes, entries, pad_to, max_out,
                     put_first=None, *, margin, note, seen):
    """``serve_closed_loop.served_token_gap`` over the positions whose
    routing margin is at least ``margin``: ``(widest gap, positions
    read)``."""
    import jax
    import jax.numpy as jnp
    pad_to = -(-pad_to // 128) * 128

    def jitted(dot):
        return jax.jit(lambda w, ids, pos: ref.logits_and_margin_at(
            w, sizes, ids, pos, dot))

    fn = jitted(ref.lower_precision_dot("float32"))
    fn_ctrl = jitted(put_first) if put_first is not None else None
    gaps, margins = [], []
    with jax.default_matmul_precision("highest"):
        for e in entries:
            tokens = list(e.result.tokens)
            p, t = len(e.prompt), len(tokens)
            ids = np.zeros((pad_to,), np.int32)
            ids[:p + t - 1] = np.asarray(e.prompt + tokens[:-1], np.int32)
            positions = np.full((max_out,), p + t - 2, np.int32)
            positions[:t] = np.arange(p - 1, p + t - 1)
            z, m = fn(ref_weights, jnp.asarray(ids), jnp.asarray(positions))
            picked = jnp.asarray(tokens, jnp.int32)
            if fn_ctrl is not None:
                picked = jnp.argmax(fn_ctrl(
                    ref_weights, jnp.asarray(ids),
                    jnp.asarray(positions))[0][:t], axis=-1)
            z = z[:t]
            gaps.append(np.asarray(jnp.max(z, axis=-1) - jnp.take_along_axis(
                z, picked[:, None], axis=-1)[:, 0]))
            margins.append(np.asarray(m[:t]))
    gaps, margins = np.concatenate(gaps), np.concatenate(margins)
    who = "control" if put_first is not None else "program"
    note(f"[routed] {who}: widest gap by least routing margin read "
         "(margin: gap, share of positions): " + "; ".join(
             f"{g}: {gaps[margins >= g].max(initial=0.0):.4f}, "
             f"{np.mean(margins >= g):.3f}" for g in MARGIN_GRID)
         + "; gap quantiles 50/90/95/99/100%: "
         + " ".join(f"{np.quantile(gaps, q):.4f}"
                    for q in (0.5, 0.9, 0.95, 0.99, 1.0))
         + f"; widest margin of a position with a gap over {FLIPPED}: "
         f"{margins[gaps > FLIPPED].max(initial=0.0):.5f} "
         f"({int(np.sum(gaps > FLIPPED))} such positions of {gaps.size})")
    read = margins >= margin
    if put_first is None:
        seen["share"] = float(np.mean(read))
        seen["p95"] = float(np.quantile(gaps, 0.95))
    return float(gaps[read].max(initial=0.0)), int(read.sum())
