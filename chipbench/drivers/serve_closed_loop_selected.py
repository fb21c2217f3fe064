"""Driver ``serve_closed_loop_selected``: ``serve_closed_loop_routed``
for a family that also SELECTS what it attends, with a control held to
every limit the program is held to.

Why the routed driver's control does not carry over (my chip runs, PR
30, ``dots3.serve.longctx8``): that driver compares a control's WIDEST
gap alone, and in this cell no limit on the widest gap lies between the
program and the lower precisions — sound runs read 1.42-2.85 over the
positions whose routing and selection are settled, the int8 control
3.28-5.12 and fp8 4.95-6.01, and the program's widest is the largest of
~550 draws from a tail that does not thin out with the margin, so the
limit on it (7.0) is held against a gross fault and not against
precision.  What separates precisions here is
``served_token_logit_gap_p95`` over every served position (sound
0.51-0.67, int8 1.56-1.72, fp8 3.26), which ``run.py`` holds the
program to and the routed driver never held a control to: both controls
printed 'would pass'.  This driver reads a control as the program is
read — the widest gap over the settled positions and the p95 over all
of them, each beside the cell's own limit — and says whether the
comparison would have called it correct.

The load, the clock, the sample of requests and the program's own
readings are ``serve_closed_loop_routed``'s, which runs; this file
replaces the function that walks the sample.  It walks it ONCE: the
float32 reference's logits and margins of a request serve the program's
reading and every control's (the routed driver runs the float32 forward
again for each control: ~93 s of a ~190 s control in this cell).

``--control`` takes, beside the precisions, what the family's reference
names (``reference/mla_sparse_window_moe_decoder.lower_precision_dot``):
two wrong forwards ('recent', 'no_gate') that have to fail, and the
WITNESS 'bfloat16' — the reference with every product's operands
rounded to the program's precision — which has to PASS and read about
what the program reads, if the program's distance from float32 is its
precision and not a fault.
"""

from __future__ import annotations

import numpy as np

from chipbench.drivers import serve_closed_loop_routed as routed


def run(ctx):
    limits = ctx.cell.config["limits"]["serve"]
    p95_limit = limits.get("logit_gap_p95", limits["logit_gap"])
    controls = []            # (name, gaps) in ``ctx.controls()``' order

    def gap(ref, ref_weights, sizes, entries, pad_to, max_out,
            put_first=None, *, margin, note, seen):
        if put_first is None:
            gaps, margins = walk(ref, ref_weights, sizes, entries, pad_to,
                                 max_out, ctx.controls())
            controls.extend(zip(ctx.controls(), gaps[1:]))
            seen["margins"] = margins
            return read(note, "program", gaps[0], margins, margin, seen)
        # the base driver asks for one control after the other
        which, gaps = controls.pop(0)
        widest, n = read(note, f"control {which}", gaps, seen["margins"],
                         margin, {})
        p95 = float(np.quantile(gaps, 0.95))
        ctx.control_reading(which, "served_token_logit_gap_p95", p95,
                            p95_limit)
        ctx.note(f"[control {which}] in the program's place `correct` "
                 "would be " + ("false" if p95 > p95_limit
                                or widest > limits["logit_gap"] else
                                "TRUE: no limit tells it from the program"))
        return widest, n

    original, routed.served_token_gap = routed.served_token_gap, gap
    try:
        return routed.run(ctx)
    finally:
        routed.served_token_gap = original


def walk(ref, ref_weights, sizes, entries, pad_to, max_out, controls):
    """One float32 forward a request, and one of each control's:
    ``([gaps of the served tokens, gaps of control 1's tokens, ...],
    margins)`` over every served position of ``entries``."""
    import jax
    import jax.numpy as jnp
    pad_to = -(-pad_to // 128) * 128

    def jitted(dot):
        return jax.jit(lambda w, ids, pos: ref.logits_and_margin_at(
            w, sizes, ids, pos, dot))

    fn = jitted(ref.lower_precision_dot("float32"))
    fn_ctrl = [jitted(ref.lower_precision_dot(c)) for c in controls]
    gaps, margins = [[] for _ in range(1 + len(controls))], []
    with jax.default_matmul_precision("highest"):
        for e in entries:
            tokens = list(e.result.tokens)
            p, t = len(e.prompt), len(tokens)
            ids = np.zeros((pad_to,), np.int32)
            ids[:p + t - 1] = np.asarray(e.prompt + tokens[:-1], np.int32)
            positions = np.full((max_out,), p + t - 2, np.int32)
            positions[:t] = np.arange(p - 1, p + t - 1)
            ids, positions = jnp.asarray(ids), jnp.asarray(positions)
            z, m = fn(ref_weights, ids, positions)
            z = z[:t]
            margins.append(np.asarray(m[:t]))
            picks = [jnp.asarray(tokens, jnp.int32)] + [
                jnp.argmax(f(ref_weights, ids, positions)[0][:t], axis=-1)
                for f in fn_ctrl]
            for kept, picked in zip(gaps, picks):
                kept.append(np.asarray(
                    jnp.max(z, axis=-1) - jnp.take_along_axis(
                        z, picked[:, None], axis=-1)[:, 0]))
    return [np.concatenate(g) for g in gaps], np.concatenate(margins)


def read(note, who, gaps, margins, margin, seen):
    """``serve_closed_loop_routed``'s two readings of ``gaps``: the line
    it prints, ``seen`` filled, ``(widest gap over the positions whose
    margin is at least margin, positions read)``."""
    note(f"[routed] {who}: widest gap by least routing margin read "
         "(margin: gap, share of positions): " + "; ".join(
             f"{g}: {gaps[margins >= g].max(initial=0.0):.4f}, "
             f"{np.mean(margins >= g):.3f}" for g in routed.MARGIN_GRID)
         + "; gap quantiles 50/90/95/99/100%: "
         + " ".join(f"{np.quantile(gaps, q):.4f}"
                    for q in (0.5, 0.9, 0.95, 0.99, 1.0))
         + f"; widest margin of a position with a gap over {routed.FLIPPED}"
         f": {margins[gaps > routed.FLIPPED].max(initial=0.0):.5f} "
         f"({int(np.sum(gaps > routed.FLIPPED))} such positions of "
         f"{gaps.size})")
    held = margins >= margin
    seen["share"] = float(np.mean(held))
    seen["p95"] = float(np.quantile(gaps, 0.95))
    return float(gaps[held].max(initial=0.0)), int(held.sum())
