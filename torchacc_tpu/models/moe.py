"""Mixture-of-experts MLP with expert parallelism.

Beyond the reference: TorchAcc has no MoE/EP implementation (SURVEY.md
§2.3 — its differentiable all-to-all cp/utils.py:262-299 is the building
block it would need).  Here experts live on an 'expert' logical axis
(sharded over the 'ep' mesh axis); token routing uses a dense
dispatch/combine einsum formulation, which GSPMD lowers to all-to-alls
across 'ep' automatically — the idiomatic TPU MoE (switch-transformer
style) rather than a hand-written NCCL a2a.
"""

from __future__ import annotations

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from torchacc_tpu.ops import moe_rows
from torchacc_tpu.ops._common import ambient_mesh, batch_axes, needs_shard_map
from torchacc_tpu.ops.grouped_matmul import grouped_matmul

# the most (token, expert) pairs the dropless layer sorts at once when it
# trains: the sorted buffer is sized for the worst case — every pair of
# the rows at hand on the experts held here — so the rows are taken in
# chunks whose worst case is this many (routed_experts)
MAX_SORTED_PAIRS = 64 * 1024

# a sorted buffer of more rows than this is read back (and its cotangent
# weighed) by ops/moe_rows.py's movers, which touch its LIVE rows only
# (the pairs on the experts held here: a quarter of a training chunk's
# buffer at balance, a sixteenth of a serving one's); XLA's gathers,
# which move every row, stay below it.  The crossover on a v5e (my chip
# run, PR 47, rows of 2,304 bf16): at 16,384 rows XLA's fused gather and
# sum takes 0.36 ms and the movers 0.36-0.53; at 32,768 rows 1.73 ms
# against 0.69-1.04 — XLA holds a 75 MB buffer in VMEM and gathers from
# there, and a 151 MB one it cannot
LIVE_ROWS_FROM = 16 * 1024


def _sort_dispatch(xf, sel_f, w_f, e, cap):
    """Scale-proof capacity dispatch: argsort by expert instead of
    one-hot slot tensors.

    The einsum path materialises [n, e, cap] (and transiently
    [n, k, e, cap]) one-hots — tens of GB at Mixtral-8x7B geometry
    (n~8k, e=8, cap~2k, VERDICT r3 weak-4).  Here intermediates are
    O(n*k) index/weight vectors plus the [e*cap, h] expert buffer:

    - flatten (slot, token) claims SLOT-MAJOR, so a stable argsort by
      expert reproduces the switch/GShard drop priority exactly (every
      token's top-1 claim fills before any token's top-2);
    - position inside the expert buffer = sorted index - expert start
      (exclusive cumsum of per-expert counts);
    - dispatch/combine are scatter-add/gather on the flat [e*cap, h]
      buffer — differentiable wrt x and the expert outputs, with the
      integer routing naturally non-differentiable.

    Returns (ex_in [e, cap, h], dest [n*k], tok_sorted [n*k],
    w_keep [n*k] f32 combine weights, zero where dropped).
    """
    n, k = sel_f.shape
    h = xf.shape[1]
    nk = n * k
    sel_sm = sel_f.T.reshape(nk)            # slot-major flatten
    w_sm = w_f.T.reshape(nk)
    tok_sm = jnp.tile(jnp.arange(n, dtype=jnp.int32), k)
    order = jnp.argsort(sel_sm, stable=True)
    e_sorted = sel_sm[order]
    counts = jnp.bincount(sel_sm, length=e)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(nk, dtype=jnp.int32) - starts[e_sorted].astype(jnp.int32)
    keep = pos < cap
    dest = e_sorted * cap + jnp.where(keep, pos, 0)
    tok_sorted = tok_sm[order]
    w_keep = jnp.where(keep, w_sm[order], 0.0).astype(jnp.float32)
    gathered = xf[tok_sorted] * keep[:, None].astype(xf.dtype)
    ex_in = jnp.zeros((e * cap, h), xf.dtype).at[dest].add(gathered)
    return ex_in.reshape(e, cap, h), dest, tok_sorted, w_keep


def route(cfg, logits, bias=None):
    """Token-choice routing over ``cfg.router_width`` experts.

    ``logits`` [n, E] float32.  Returns ``(sel [n, k] int32, weights
    [n, k] float32, scores [n, E])``.

    - ``moe_scoring='softmax'``: top-k of the logits, weights the softmax
      over the selected logits (``moe_renorm_topk``, mixtral) or the
      plain full-softmax probabilities (qwen3-moe).
    - ``moe_scoring='sigmoid'``: scores ``s = sigmoid(logits)``;
      selection runs on ``s + bias`` (``bias`` optional, selection only),
      limited to the ``moe_topk_group`` best of ``moe_n_group`` groups of
      adjacent experts (a group's score: the sum of its two largest
      selection scores); weights are the UNbiased ``s`` of the selected,
      divided by their sum (+1e-20) under ``moe_renorm_topk``, times
      ``moe_route_scale``.  ``moe_n_group=1`` is plain top-k.
    """
    k = cfg.num_experts_per_tok
    n, e = logits.shape
    if cfg.moe_scoring == "softmax":
        if cfg.moe_renorm_topk:
            weights, sel = jax.lax.top_k(logits, k)
            return sel, jax.nn.softmax(weights, axis=-1), logits
        probs = jax.nn.softmax(logits, axis=-1)
        weights, sel = jax.lax.top_k(probs, k)
        return sel, weights, probs
    if cfg.moe_scoring != "sigmoid":
        raise ValueError(f"moe_scoring must be 'softmax' | 'sigmoid', got "
                         f"{cfg.moe_scoring!r}")
    scores = jax.nn.sigmoid(logits)
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    g = cfg.moe_n_group
    if g > 1:
        if e % g or e // g < 2:
            raise ValueError(f"moe_n_group={g} does not split {e} experts "
                             f"into groups of at least two")
        grouped = choice.reshape(n, g, e // g)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(group_score, cfg.moe_topk_group)  # [n, tg]
        kept = jnp.any(keep[:, :, None] == jnp.arange(g)[None, None, :],
                       axis=1)                                    # [n, g]
        choice = jnp.where(jnp.repeat(kept, e // g, axis=1), choice,
                           -jnp.inf)
    _, sel = jax.lax.top_k(choice, k)
    weights = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg.moe_renorm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return sel, weights * cfg.moe_route_scale, scores


def swiglu(x, w_gate, w_up, w_down, dtype):
    """One SwiGLU FFN on raw kernels ([in, f], [in, f], [f, out])."""
    xd = x.astype(dtype)
    ff = nn.silu(xd @ w_gate.astype(dtype)) * (xd @ w_up.astype(dtype))
    return ff @ w_down.astype(dtype)


def relu2(x, w_up, w_down, dtype):
    """One ``down(relu(up(x))**2)`` FFN on raw kernels ([in, f],
    [f, out]): two matrices, no gate."""
    ff = jnp.square(nn.relu(x.astype(dtype) @ w_up.astype(dtype)))
    return ff @ w_down.astype(dtype)


def stored_expert_width(width: int) -> int:
    """The width the held experts' stacked kernels are STORED at: whole
    128-lane tiles, ``up`` (and ``gate``) padded with zero columns and
    ``down`` with zero rows, which change nothing (``relu(0)**2`` and
    ``silu(0) * 0`` are 0).  A TPU keeps an array whose last dimension
    is not whole tiles with its last two dimensions swapped, and a
    Mosaic call wants its operand row-major: at a width of 1856 XLA
    relayouts the whole [L, e, hidden, 1856] stack before every grouped
    matmul (sandbox compile, PR 42: a stack-sized copy in each serve
    program).  The published widths so far are whole tiles but this
    one; the padding is the layout's (chipbench/layouts), the
    arithmetic reads whatever width the kernels have."""
    return -(-width // 128) * 128


def _gated(cfg) -> bool:
    """Whether the experts are gated FFNs of three matrices (SwiGLU) or
    ``relu2`` ones of two."""
    if cfg.activation not in ("swiglu", "relu2"):
        raise ValueError(f"the held-expert layer computes 'swiglu' or "
                         f"'relu2' experts, not {cfg.activation!r}")
    return cfg.activation == "swiglu"


def _moves_live_rows(pairs: int, dtype) -> bool:
    """Whether a sorted buffer of ``pairs`` rows is moved by the movers
    of ``ops/moe_rows.py`` (live rows only) or by XLA's gathers (every
    row): a rule of the buffer's SIZE, the same for whoever calls."""
    return pairs > LIVE_ROWS_FROM and moe_rows.supports(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _take_pairs(x, order, unsort, counts, k):
    """``x[order // k]``: the sorted pairs' rows out of ``x`` [n, h]
    (pair ``p`` is token ``p // k``).  One gather of every row, live or
    not: XLA copies the rows of so small an array at the HBM's pace, and
    nothing that stopped at the live rows was faster (PERF.md section 6,
    PR 47).  ``unsort`` is ``order``'s inverse permutation, so the
    transpose is a gather too — ``g[unsort]`` summed over a token's
    ``k`` pairs, the live ones (``sum(counts)`` of them) — where
    autodiff's would be a scatter-add of ``n * k`` rows."""
    return x[(order // k).astype(jnp.int32)]


def _take_pairs_fwd(x, order, unsort, counts, k):
    return _take_pairs(x, order, unsort, counts, k), (unsort, counts)


def _take_pairs_bwd(k, res, g):
    unsort, counts = res
    if _moves_live_rows(g.shape[0], g.dtype):
        dx = moe_rows.sum_rows(g, unsort, jnp.sum(counts), k=k,
                               dtype=g.dtype)
    else:
        # the cotangent is zero in the rows past the total (the grouped
        # matmul's dX): summed with the rest
        picked = g[unsort].reshape(g.shape[0] // k, k, -1)
        dx = jnp.sum(picked.astype(jnp.float32), axis=1).astype(g.dtype)
    return dx, None, None, None


_take_pairs.defvjp(_take_pairs_fwd, _take_pairs_bwd)


@jax.custom_vjp
def _combine(out, weights, order, unsort, total):
    """``sum_i w_i out_i`` a token: ``out`` [n * k, h] holds the sorted
    pairs' results, defined in its first ``total`` rows; ``weights``
    [n, k] float32 -> [n, h] float32.  The results are gathered back to
    (token, slot) order as they are and weighed, masked and summed in
    one pass over them — a pair past ``total`` (on no held expert) adds
    nothing whatever its row holds, masked, not multiplied by zero (in a
    large buffer it is not even copied).  The backward is written out so
    that it gathers too (autodiff would scatter-add ``n * k`` rows) and
    carries the cotangent's rows in ``out``'s dtype; of a large buffer
    it leaves the rows past the last live tile UNDEFINED, which the
    grouped matmul's backward never reads."""
    n, k = weights.shape
    if _moves_live_rows(n * k, out.dtype):
        return moe_rows.sum_rows(out, unsort, total, weights, k=k,
                                 dtype=jnp.float32)
    held = (unsort < total).reshape(n, k)
    picked = out[unsort].reshape(n, k, -1).astype(jnp.float32)
    return jnp.sum(jnp.where(held[..., None], picked * weights[..., None],
                             0.0), axis=1)


def _combine_fwd(out, weights, order, unsort, total):
    return (_combine(out, weights, order, unsort, total),
            (out, weights, order, unsort, total))


def _combine_bwd(res, dy):
    out, weights, order, unsort, total = res
    n, k = weights.shape
    tok = (order // k).astype(jnp.int32)
    w_sorted = weights.reshape(n * k)[order]
    dy = dy.astype(out.dtype)
    if _moves_live_rows(n * k, out.dtype):
        d_out, d_w = moe_rows.take_rows_weighed(dy, tok, total, w_sorted,
                                                out)
        d_w = jnp.where(unsort < total, d_w[unsort], 0.0)
    else:
        in_group = jnp.arange(n * k) < total
        dys = dy[tok].astype(jnp.float32)
        d_out = jnp.where(in_group[:, None], dys * w_sorted[:, None],
                          0.0).astype(out.dtype)
        d_w = jnp.where(in_group,
                        jnp.sum(dys * out.astype(jnp.float32), axis=-1),
                        0.0)[unsort]
    return d_out, d_w.reshape(n, k), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def held_experts_ffn(cfg, x, sel, weights, w_gate, w_up, w_down,
                     valid=None, layer=None):
    """The held experts' part of ``sum_i w_i E_i(x)``, dropless.

    ``x`` [n, h]; ``sel``/``weights`` [n, k] name experts of the router's
    whole width; this program holds the ``e = w_up.shape[-3]`` experts
    ``[cfg.moe_first_expert, +e)``.  The (token, expert) pairs that chose
    a held expert are sorted by expert and pass through three grouped
    matmuls (two where ``w_gate`` is None: ``relu2`` experts,
    ``down(relu(up(x))**2)``; ``ops/grouped_matmul.py``: a Pallas kernel that visits only
    the row tiles that hold pairs and the weights of the groups they
    belong to), so FLOPs follow the routed pairs and an expert that drew
    no token is not read.  The kernels are one layer's [e, in, out] or,
    with ``layer`` (an int32 scalar, traced in a layer scan), every
    expert layer's [L, e, in, out] in ``cfg.dtype``, which the grouped
    matmul reads at ``layer`` where they lie.  Nothing is dropped under any
    imbalance: the sorted buffer holds all ``n * k`` pairs.  Its rows on
    held experts are the prefix ``[0, sum(counts))``; a buffer of more
    than ``LIVE_ROWS_FROM`` rows is read back (:func:`_combine`, and the
    transpose of :func:`_take_pairs`) and its cotangent weighed by the
    movers of ``ops/moe_rows.py``, which touch that prefix alone — the
    rows behind it are masked wherever a value is made of them, never
    multiplied by zero — a smaller one by XLA's gathers, which move
    every row.  Same pairs, same sort, same
    sums either way.  One layer's
    kernels (no ``layer``) make it differentiable in ``x``, ``weights``
    and the kernels: the grouped matmuls bring their own backward
    kernels, the sort's gathers transpose into gathers.  Pairs on
    experts held elsewhere (and tokens with ``valid`` False: padding,
    free serving slots) sort behind the held groups, are computed by no
    group and add nothing.  What the absent experts would add is left
    out: the exchange that gathers it is not this program's.

    Returns ``(y [n, h] float32, load int32[3])``: pairs on held experts,
    the largest held expert's count, held experts that drew a pair.
    """
    n, k = sel.shape
    e = w_up.shape[-3]
    nk = n * k
    with jax.named_scope("moe_dispatch"):
        local = sel - cfg.moe_first_expert
        held = (local >= 0) & (local < e)
        if valid is not None:
            held &= valid[:, None]
        key = jnp.where(held, local, e).reshape(nk).astype(jnp.int32)
        order = jnp.argsort(key, stable=True)
        counts = jnp.bincount(key, length=e + 1)[:e].astype(jnp.int32)
        unsort = jnp.zeros((nk,), jnp.int32).at[order].set(
            jnp.arange(nk, dtype=jnp.int32))
        xs = _take_pairs(x.astype(cfg.dtype), order, unsort, counts,
                         k)                                     # [nk, h]
    with jax.named_scope("experts"):
        dt = cfg.dtype
        # rows past the held groups belong to no group: what the kernel
        # left there is masked BEFORE the activation (a select, fused
        # into it), so neither it nor its derivative sees it
        in_group = (jnp.arange(nk) < jnp.sum(counts))[:, None]
        held_rows = lambda a: jnp.where(  # noqa: E731
            in_group, a, jnp.zeros_like(a))
        up = held_rows(grouped_matmul(xs, w_up, counts, layer=layer))
        if w_gate is not None:
            gate = held_rows(grouped_matmul(xs, w_gate, counts,
                                            layer=layer))
            ff = nn.silu(gate) * up
        else:
            ff = jnp.square(nn.relu(up))
        out = grouped_matmul(ff.astype(dt), w_down, counts,
                             layer=layer)                       # [nk, h]
    with jax.named_scope("moe_combine"):
        # rows past the held groups belong to no group: whatever the
        # kernel left there is masked, not multiplied by a zero weight
        y = _combine(out, weights.astype(jnp.float32), order, unsort,
                     jnp.sum(counts))
    load = jnp.stack([jnp.sum(counts), jnp.max(counts),
                      jnp.sum(counts > 0)]).astype(jnp.int32)
    return y, load


def _router(cfg, p, x):
    """``(logits, sel, weights, scores)`` of the rows ``x`` [n, h]."""
    with jax.named_scope("router"):
        # float32 at full precision: a TPU's default float32 product
        # rounds its operands to bfloat16, and a rounded router input
        # flips near-tied experts
        logits = jnp.dot(x.astype(jnp.float32),
                         p["router"]["kernel"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        return (logits, *route(cfg, logits, p.get("router_bias")))


def _add_shared(cfg, p, x, y):
    """``y`` [n, h] float32 plus the shared experts' FFN of ``x``, where
    the model has them."""
    if not cfg.moe_shared_experts:
        return y
    with jax.named_scope("shared_expert"):
        sh = p["shared"]
        if _gated(cfg):
            shared = swiglu(x, sh["gate_proj"]["kernel"],
                            sh["up_proj"]["kernel"],
                            sh["down_proj"]["kernel"], cfg.dtype)
        else:
            shared = relu2(x, sh["up_proj"]["kernel"],
                           sh["down_proj"]["kernel"], cfg.dtype)
    with jax.named_scope("moe_combine"):
        return y + shared.astype(jnp.float32)


def moe_ffn(cfg, p, x, valid=None, layer=None):
    """``shared(x) + sum_i w_i E_i(x)`` over the held experts, on the raw
    parameter tree ``p`` of :class:`MoEMlp` (``router``, ``experts/*``,
    ``shared``; SwiGLU experts of three matrices or, under
    ``cfg.activation='relu2'``, of two: no ``experts/gate``, no
    ``gate_proj``) — the one definition behind the module's 'grouped' path
    and the serving decoder's expert layer.  With ``layer`` the three
    ``experts/*`` leaves are the stacks of every expert layer and
    ``layer`` the index into them (:func:`held_experts_ffn`); the other
    leaves are always one layer's.  ``x`` [n, h] ->
    ``(y [n, h] in cfg.dtype, scores, sel, load)``."""
    _, sel, weights, scores = _router(cfg, p, x)
    y, load = held_experts_ffn(cfg, x, sel, weights,
                               p["experts/gate"] if _gated(cfg) else None,
                               p["experts/up"], p["experts/down"], valid,
                               layer)
    y = _add_shared(cfg, p, x, y)
    return y.astype(cfg.dtype), scores, sel, load


def _chunk_rows(n: int, shards: int, k: int) -> int:
    """Rows of a shard taken at once by :func:`routed_experts`: ``n``
    halved until the worst case of a chunk — ``shards * rows * k``
    pairs, all on the experts held here — is at most
    ``MAX_SORTED_PAIRS`` (or the rows stop halving)."""
    rows = n
    while shards * rows * k > MAX_SORTED_PAIRS and rows % 2 == 0:
        rows //= 2
    return rows


def _exchange(ep, dtype):
    """``(gather, gather_rows, scatter_sum)`` over the mesh axis ``ep``
    that holds the experts: the two halves of the expert layer's
    exchange.  ``gather`` is the plain all-gather of a chunk's leading
    dimension (``sel``, ``weights``); ``gather_rows`` the same for the
    rows ([c, h] -> [shards * c, h], in ``dtype``) with its backward
    written out; ``scatter_sum`` adds the shards'
    partial results and leaves each the sum for its own rows (float32
    [shards * c, h] -> [c, h]).  Each is the other's transpose, so the
    backward is written out: what crosses the chips as a COPY crosses in
    ``dtype`` (the rows forward, the result's cotangent backward, which
    arrives rounded to it anyway), what is SUMMED across them is summed
    in float32.  ``ep`` None (every expert held here): identities."""
    if ep is None:
        same = lambda x: x  # noqa: E731
        return same, same, same

    def gather(x):
        return jax.lax.all_gather(x, ep, axis=0, tiled=True)

    def scatter(y):
        return jax.lax.psum_scatter(y.astype(jnp.float32), ep,
                                    scatter_dimension=0, tiled=True)

    @jax.custom_vjp
    def gather_rows(x):
        return gather(x)

    @jax.custom_vjp
    def scatter_sum(y):
        return scatter(y)

    gather_rows.defvjp(lambda x: (gather(x), None),
                       lambda _, g: (scatter(g).astype(g.dtype),))
    scatter_sum.defvjp(lambda y: (scatter(y), None),
                       lambda _, g: (gather(g.astype(dtype))
                                     .astype(jnp.float32),))
    return gather, gather_rows, scatter_sum


def _routed_rows(cfg, p, x, ep, data_axes):
    """:func:`routed_experts` on one shard's rows ``x`` [b, s, h]
    (``ep`` / ``data_axes``: the manual mesh axes that hold the experts
    and split the rows; none on one device)."""
    b, s, h = x.shape
    n, k = b * s, cfg.num_experts_per_tok
    width = cfg.router_width
    xf = x.reshape(n, h)
    logits, sel, weights, scores = _router(cfg, p, xf)
    w_gate = p["experts/gate"] if _gated(cfg) else None
    w_up, w_down = p["experts/up"], p["experts/down"]
    held = w_up.shape[0]                    # experts held by this shard
    shards = jax.lax.axis_size(ep) if ep else 1
    first = cfg.moe_first_expert
    if ep:
        first = first + jax.lax.axis_index(ep) * held
    shard_cfg = dataclasses.replace(cfg, moe_first_expert=first)
    gather, gather_rows, scatter_sum = _exchange(ep, cfg.dtype)

    def chunk(xc, sc, wc):
        with jax.named_scope("moe_exchange"):
            xg, sc, wc = gather_rows(xc), gather(sc), gather(wc)
        y, _ = held_experts_ffn(shard_cfg, xg, sc, wc, w_gate, w_up, w_down)
        with jax.named_scope("moe_exchange"):
            return scatter_sum(y)

    c = _chunk_rows(n, shards, k)
    if c == n:
        y = chunk(xf.astype(cfg.dtype), sel, weights)
    else:
        # a chunk keeps nothing for the backward but its rows: the
        # worst-case buffers exist once, forward and backward
        _, y = jax.lax.scan(
            lambda _, rows: (None, jax.checkpoint(chunk)(*rows)), None,
            (xf.astype(cfg.dtype).reshape(n // c, c, h),
             sel.reshape(n // c, c, k), weights.reshape(n // c, c, k)))
        y = y.reshape(n, h)
    y = _add_shared(cfg, p, xf, y)
    with jax.named_scope("router"):
        # load-balance signal, a row (sequence) at a time over the
        # router's whole width: width * sum_e f_e P_e, f_e the share of
        # the row's s * k pairs on expert e, P_e the row's mean score
        # (normalised to a distribution); the mean over the rows
        picked = jnp.sum(jax.nn.one_hot(sel.reshape(b, s * k), width,
                                        dtype=jnp.float32), axis=1)
        probs = (scores / (jnp.sum(scores, axis=-1, keepdims=True) + 1e-20)
                 if cfg.moe_scoring == "sigmoid"
                 else jax.nn.softmax(logits, -1)).reshape(b, s, width)
        aux = jnp.sum(width * jnp.sum(
            picked / (s * k) * jnp.mean(probs, axis=1), axis=-1))
        counts = jnp.sum(picked, axis=0)
        rows = jnp.asarray(b, jnp.float32)
        if data_axes:
            aux, counts, rows = jax.lax.psum((aux, counts, rows), data_axes)
        aux = aux / rows
        # pairs on the held experts (of every shard), the busiest one's,
        # held experts that drew a pair; then what the sorted buffers of
        # the busiest SHARD held: its pairs — the live rows — and the
        # rows the buffers were sized for, every pair of the rows it saw
        counts = jax.lax.dynamic_slice_in_dim(
            counts, cfg.moe_first_expert, held * shards).astype(jnp.int32)
        by_shard = jnp.sum(counts.reshape(shards, held), axis=-1)
        load = jnp.stack([jnp.sum(counts), jnp.max(counts),
                          jnp.sum(counts > 0), jnp.max(by_shard),
                          (rows * (s * k)).astype(jnp.int32)])
    return y.astype(cfg.dtype).reshape(b, s, h), aux, load


def routed_experts(cfg, p, x):
    """The dropless expert layer as a train step runs it: ``x`` [b, s, h]
    -> ``(y [b, s, h] in cfg.dtype, aux, load int32[5])`` on the raw
    parameter tree of :class:`MoEMlp`'s 'grouped' path, differentiable.

    Under a mesh whose ``ep`` axis is larger than one the experts stay
    where they are — ``experts/*`` enter a ``shard_map`` over the whole
    mesh split on their expert dimension, ``cfg.num_experts / ep`` a
    shard, and are never gathered; their gradients leave it the same
    way — and the rows, which the data axes (``ep`` among them) split,
    are routed where they lie.  Every (token, expert) pair then has to
    reach the shard that holds its expert and its result to come back.
    With ``k`` of ``E`` experts a token over few shards nearly every
    token visits every shard (8 of 64 over 4: nine in ten), so the
    exchange copies ROWS, not pairs: an all-gather of the shards' rows
    (and of their ``sel`` / ``weights``), :func:`held_experts_ffn` on the
    held experts over all of them, and a reduce-scatter of the partial
    sums — ``2 (ep - 1) / ep`` rows a token each way against
    ``2 k (ep - 1) / ep`` for an all-to-all of pairs.

    Static shapes and nothing dropped: a shard's sorted buffer has to
    hold the case that every pair lands on its experts, so the rows are
    taken ``_chunk_rows`` at a time in a scan — gathered, computed,
    scattered, a chunk rematerialised in the backward — and the buffers
    are ``MAX_SORTED_PAIRS`` rows whatever the batch.  The buffer is
    sized for the worst case; what is MOVED is the live rows (the pairs
    on this shard's experts, a quarter of the buffer at balance over four
    shards): buffers of this size are filled and read back by the
    movers of ``ops/moe_rows.py`` (:func:`held_experts_ffn`).

    ``aux`` is the mean over the rows (sequences) of ``width * sum_e f_e
    P_e``; ``load`` the (token, expert) pairs on held experts over all
    shards, the busiest held expert's, the held experts that drew one,
    the busiest SHARD's pairs (the live rows of its sorted buffers, over
    the layer's chunks and the shard's data-parallel replicas) and the
    rows those buffers were sized for."""
    mesh = ambient_mesh()
    if not needs_shard_map(mesh):
        return _routed_rows(cfg, p, x, None, ())
    from jax.sharding import PartitionSpec as P
    axes = batch_axes(mesh, x.shape[0])
    ep = "ep" if mesh.shape.get("ep", 1) > 1 else None
    if ep and ep not in axes:
        raise ValueError(
            f"routed_experts: a batch of {x.shape[0]} rows does not "
            f"split over the data axes of mesh {dict(mesh.shape)} down "
            f"to 'ep': the shards that hold the experts exchange their "
            f"OWN rows")
    specs = {name: (P(ep) if name.startswith("experts/") else P())
             for name in p}
    rows = P(axes or None)
    return jax.shard_map(
        lambda p_, x_: _routed_rows(cfg, p_, x_, ep, axes), mesh=mesh,
        in_specs=(specs, rows), out_specs=(rows, P(), P()),
        check_vma=False)(p, x)


class _Kernel(nn.Module):
    """Holds one ``kernel`` parameter (the tree a bias-free ``nn.Dense``
    of that name would hold) and returns ``{"kernel": value}``: the
    grouped path computes on raw kernels, shared with the serving
    decoder."""
    shape: tuple
    param_dtype: object

    @nn.compact
    def __call__(self):
        return {"kernel": self.param(
            "kernel", nn.initializers.normal(0.02), self.shape,
            self.param_dtype)}


class _SwigluKernels(nn.Module):
    """``gate_proj`` / ``up_proj`` / ``down_proj`` kernels of one SwiGLU
    FFN, named as :class:`Mlp` names them (no ``gate_proj`` where the FFN
    is not ``gated``)."""
    hidden: int
    ffn: int
    param_dtype: object
    gated: bool = True

    @nn.compact
    def __call__(self):
        h, f = self.hidden, self.ffn
        return {name: _Kernel(shape, self.param_dtype, name=name)()
                for name, shape in (("gate_proj", (h, f)),
                                    ("up_proj", (h, f)),
                                    ("down_proj", (f, h)))
                if self.gated or name != "gate_proj"}


class MoEMlp(nn.Module):
    """Top-k token-choice MoE: capacity-free dense dispatch, or
    switch-transformer capacity dispatch (``cfg.moe_capacity_factor``).

    For modest expert counts the dense formulation (every token scored
    against every expert, weighted-combined with a top-k mask) is both
    exactly correct (no token dropping) and MXU-friendly; its FLOPs
    scale with e.  The capacity path computes only
    ``C = ceil(cf * k * tokens / e)`` slots per expert — FLOPs
    independent of e (the mixtral-8x7b regime) — at the cost of
    dropping over-capacity tokens (standard switch behaviour).
    """
    cfg: object  # ModelConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        e = cfg.num_experts
        k = cfg.num_experts_per_tok
        h = cfg.hidden_size
        f = cfg.ffn_size
        b, s, _ = x.shape

        if cfg.moe_dispatch not in ("auto", "einsum", "sort", "grouped"):
            # validate regardless of capacity mode so a typo surfaces at
            # the config that introduced it
            raise ValueError(
                f"moe_dispatch must be 'auto' | 'einsum' | 'sort' | "
                f"'grouped', got {cfg.moe_dispatch!r}")
        if cfg.moe_dispatch == "grouped":
            return self._grouped(x)
        if (cfg.moe_scoring != "softmax" or cfg.moe_shared_experts
                or cfg.router_width != e or cfg.moe_first_expert):
            raise ValueError(
                "sigmoid/grouped routing, shared experts and a held share "
                "of the router's experts run on moe_dispatch='grouped' "
                "only (the dense and capacity paths score and hold every "
                "expert)")
        router = nn.Dense(e, use_bias=False, name="router",
                          dtype=jnp.float32, param_dtype=cfg.param_dtype,
                          kernel_init=nn.initializers.normal(0.02))
        logits = router(x.astype(jnp.float32))            # [b, s, e]
        if cfg.moe_renorm_topk:
            # mixtral: softmax over the selected logits (== HF's
            # softmax-then-topk-then-renormalise)
            weights, sel = jax.lax.top_k(logits, k)       # [b, s, k]
            weights = jax.nn.softmax(weights, axis=-1)
        else:
            # qwen3-moe norm_topk_prob=false: weights are the plain
            # full-softmax probs of the selected experts (they do NOT
            # sum to 1); top_k on probs picks the same experts
            probs = jax.nn.softmax(logits, axis=-1)
            weights, sel = jax.lax.top_k(probs, k)

        init = nn.initializers.normal(0.02)
        w_gate = self.param("experts/gate", init, (e, h, f), cfg.param_dtype)
        w_up = self.param("experts/up", init, (e, h, f), cfg.param_dtype)
        w_down = self.param("experts/down", init, (e, f, h), cfg.param_dtype)
        xd = x.astype(cfg.dtype)

        def experts(gi, ui):
            # shared expert FFN body: silu(gate) * up -> down
            return jnp.einsum(
                "e...f,efh->e...h", nn.silu(gi) * ui,
                w_down.astype(cfg.dtype))

        if cfg.moe_capacity_factor is None:
            # -- dense dispatch: every token through every expert -------
            combine = jnp.sum(
                jax.nn.one_hot(sel, e, dtype=jnp.float32)
                * weights[..., None], axis=-2)            # [b, s, e]
            gate = jnp.einsum("bsh,ehf->ebsf", xd, w_gate.astype(cfg.dtype))
            up = jnp.einsum("bsh,ehf->ebsf", xd, w_up.astype(cfg.dtype))
            out = experts(gate, up)                       # [e, b, s, h]
            y = jnp.einsum("ebsh,bse->bsh", out.astype(jnp.float32),
                           combine)
        else:
            # -- capacity dispatch (switch-transformer; GSPMD lowers the
            # dispatch/combine to all-to-alls over 'ep') ----------------
            import math
            n = b * s
            cap = max(math.ceil(cfg.moe_capacity_factor * k * n / e), 1)
            sel_f = sel.reshape(n, k)
            w_f = weights.reshape(n, k)
            dispatch = cfg.moe_dispatch
            if dispatch == "auto":
                # the einsum path materialises an [n, e, cap] dispatch
                # tensor (plus its [n, k, e, cap] one-hot ancestor if
                # XLA fails to fuse); above ~2^24 elements switch to the
                # sort path, whose intermediates are O(n*k + e*cap*h)
                dispatch = ("sort" if n * e * cap > (1 << 24)
                            else "einsum")
            if dispatch == "sort":
                ex_in, dest, tok_sorted, w_keep = _sort_dispatch(
                    xd.reshape(n, h), sel_f, w_f, e, cap)
            else:
                # position of each (token, slot) inside its expert's
                # buffer, slot-major priority (switch/GShard
                # convention): every token's top-1 claim fills before
                # any token's top-2, so tight capacity drops secondary
                # routes first
                sel_1h = jax.nn.one_hot(sel_f, e, dtype=jnp.int32)
                slot_totals = jnp.sum(sel_1h, axis=0)           # [k, e]
                prev_slots = (jnp.cumsum(slot_totals, axis=0)
                              - slot_totals)                    # [k, e]
                prev_tokens = jnp.cumsum(sel_1h, axis=0) - sel_1h
                pos = jnp.sum(
                    (prev_slots[None, :, :] + prev_tokens) * sel_1h,
                    axis=-1)                                    # [n, k]
                keep = pos < cap
                # [n, k, e, cap] slot one-hots -> summed over k
                slot_1h = (jax.nn.one_hot(sel_f, e, dtype=jnp.float32)[..., None]
                           * jax.nn.one_hot(jnp.where(keep, pos, 0), cap,
                                            dtype=jnp.float32)[:, :, None, :]
                           * keep[..., None, None])
                disp = jnp.sum(slot_1h, axis=1).astype(xd.dtype)
                comb = jnp.sum(slot_1h * w_f[..., None, None], axis=1)
                ex_in = jnp.einsum("nec,nh->ech", disp, xd.reshape(n, h))
            gate = jnp.einsum("ech,ehf->ecf", ex_in,
                              w_gate.astype(cfg.dtype))
            up = jnp.einsum("ech,ehf->ecf", ex_in, w_up.astype(cfg.dtype))
            out = experts(gate, up)                            # [e, cap, h]
            if dispatch == "sort":
                out_flat = out.reshape(e * cap, h).astype(jnp.float32)
                contrib = out_flat[dest] * w_keep[:, None]     # [n*k, h]
                y = jnp.zeros((n, h), jnp.float32).at[tok_sorted].add(
                    contrib).reshape(b, s, h)
            else:
                y = jnp.einsum("ech,nec->nh", out.astype(jnp.float32),
                               comb).reshape(b, s, h)

        # Load-balancing auxiliary loss (switch/mixtral-style top-k)
        # exposed via sow: count all k selections per token, divided by
        # k, so load on secondary experts feeds the balance signal.
        probs = jax.nn.softmax(logits, axis=-1)
        frac_tokens = jnp.mean(
            jnp.sum(jax.nn.one_hot(sel, e, dtype=jnp.float32), axis=-2),
            axis=(0, 1)) / k
        frac_probs = jnp.mean(probs, axis=(0, 1))
        self.sow("intermediates", "moe_aux_loss",
                 e * jnp.sum(frac_tokens * frac_probs))
        return y.astype(cfg.dtype)

    def _grouped(self, x):
        """moe_dispatch='grouped': the dropless held-expert layer as a
        train step runs it (:func:`routed_experts`: the experts over
        'ep' under a mesh) on this module's parameters."""
        cfg = self.cfg
        if cfg.moe_capacity_factor is not None:
            raise ValueError("moe_dispatch='grouped' is dropless: it takes "
                             "no moe_capacity_factor")
        e, h, f = cfg.num_experts, cfg.hidden_size, cfg.expert_ffn_size
        width = cfg.router_width
        if not 0 <= cfg.moe_first_expert <= width - e:
            raise ValueError(
                f"held experts [{cfg.moe_first_expert}, +{e}) are not "
                f"inside the router's {width}")
        init = nn.initializers.normal(0.02)
        p = {"router": _Kernel((h, width), cfg.param_dtype, name="router")()}
        if cfg.moe_router_bias:
            p["router_bias"] = self.param(
                "router_bias", nn.initializers.zeros, (width,), jnp.float32)
        gated = _gated(cfg)
        if gated:
            p["experts/gate"] = self.param("experts/gate", init, (e, h, f),
                                           cfg.param_dtype)
        p["experts/up"] = self.param("experts/up", init, (e, h, f),
                                     cfg.param_dtype)
        p["experts/down"] = self.param("experts/down", init, (e, f, h),
                                       cfg.param_dtype)
        if cfg.moe_shared_experts:
            p["shared"] = _SwigluKernels(h, cfg.shared_ffn_size,
                                         cfg.param_dtype, gated,
                                         name="shared")()
        y, aux, load = routed_experts(cfg, p, x)
        self.sow("intermediates", "moe_aux_loss", aux)
        self.sow("intermediates", "moe_load", load)
        return y
