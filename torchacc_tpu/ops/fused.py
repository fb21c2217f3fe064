"""Fused / memory-lean ops the reference gets from Liger Triton kernels
(ops/liger.py:32-130: RMSNorm, SwiGLU, RoPE, fused linear-cross-entropy).

On TPU, XLA already fuses RMSNorm/SwiGLU/RoPE elementwise chains into
their neighbouring matmuls, so those need no kernels (the reference
itself notes Liger is an eager-backend fallback).  The one that matters
is **fused linear + cross entropy**: computing ``hidden @ W_head`` and
the CE loss per sequence chunk keeps peak memory at O(chunk x vocab)
instead of materialising the full [batch*seq, vocab] float32 logits
(+ its softmax) that otherwise dominates HBM at large vocab.  The
gradient is formed where the logits already are, as Liger does: the
forward's chunk loop computes dlogits, d(hidden) and dW from each
chunk's block and saves d(hidden) and one [hidden, vocab] dW; the
backward scales them by the loss's cotangent and recomputes nothing
(peak memory: O(chunk x vocab) logits plus that one accumulator).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from torchacc_tpu.ops._common import ambient_mesh, batch_axes, scoped


def _scan_free_chunk(n: int, chunk_rows: int) -> int:
    """Pick the scan_free chunk size: the divisor of n nearest chunk_rows.

    When no divisor lies within [chunk_rows/4, 4*chunk_rows] (n prime or
    near-prime), a tiny divisor would unroll n/d python chunks — a
    trace-time blowup — so fall back to the smallest divisor >=
    chunk_rows; worst case n itself, which IS the plain materialized
    head (one chunk).  (ADVICE r3 medium.)
    """
    divisors = [d for d in range(1, int(n ** 0.5) + 1) if n % d == 0]
    divisors += [n // d for d in divisors]
    in_band = [d for d in divisors if chunk_rows // 4 <= d <= 4 * chunk_rows]
    if in_band:
        return min(in_band, key=lambda d: (abs(d - chunk_rows), d))
    return min([d for d in divisors if d >= chunk_rows] or [n])


def _head_chunk(xi, yi, w, logit_softcap: float, with_grads: bool):
    """One chunk of rows through the head.  Returns ``(sums, dx)``:
    sums = (loss_sum, valid_count) and dx = None, or with ``with_grads``
    sums = (loss_sum, valid_count, the chunk's float32 share of
    d(loss_sum)/d(w)) and dx = d(loss_sum)/d(xi) in xi's dtype, formed
    from the logits block while it is there.  ``w`` is the head already
    in xi's dtype.  Knows nothing of where its rows live or how the
    chunks are looped."""
    from torchacc_tpu.models.transformer import softcap
    # operands stay in the model dtype (bf16 MXU throughput); the
    # accumulation and all loss arithmetic are f32
    logits = softcap(jnp.dot(xi, w, preferred_element_type=jnp.float32),
                     logit_softcap)
    lse = jax.nn.logsumexp(logits, axis=-1)
    valid = yi != -100
    safe = jnp.where(valid, yi, 0)
    # the label's logit by a masked row sum, not a gather: a reduction
    # partitions like the max and the sum beside it wherever the vocab
    # dim ends up sharded (a gather there trips the SPMD partitioner
    # inside the 1F1B region)
    onehot = jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, 1) == safe[:, None]
    ll = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    loss = jnp.sum(jnp.where(valid, lse - ll, 0.0))
    count = jnp.sum(valid).astype(jnp.float32)
    if not with_grads:
        return (loss, count), None
    dlogits = jnp.where(valid[:, None],
                        jnp.exp(logits - lse[:, None]) - onehot, 0.0)
    if logit_softcap > 0.0:
        # d/dz of c * tanh(z / c), from the capped logits themselves
        dlogits = dlogits * (1.0 - jnp.square(logits / logit_softcap))
    # the float32 dlogits meets the model-dtype operands under default
    # precision, as autodiff's transpose of the forward dot would have it
    dx = jax.lax.dot_general(dlogits, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dw = jax.lax.dot_general(xi, dlogits, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return (loss, count, dw), dx.astype(xi.dtype)


def _head_chunks(hidden, w_head, labels, chunk_rows: int,
                 logit_softcap: float, scan_free: bool, with_grads: bool):
    """Drive ``_head_chunk`` over the rows, ``chunk_rows`` at a time, by
    a ``lax.scan`` or (``scan_free``) a Python loop.  Returns (loss_sum,
    count) and, with ``with_grads``, d(hidden) in hidden's dtype and dW
    in w_head's dtype, both for a unit cotangent of loss_sum.  dW is
    summed over the chunks in w_head's dtype, as autodiff's transpose
    of a scan sums a closed-over weight's cotangent: a bf16 head
    (``bf16_compute_params``) gets a bf16 sum, float32 weights a
    float32 one.  A float32 sum for a bf16 head fits, but the dW matmul
    then reads and writes twice the bytes every chunk: 6.4 -> 7.8 ms a
    chunk at a 100k vocabulary on a v5e (PERF.md section 6, PR 29)."""
    b, s, h = hidden.shape
    n = b * s
    x = hidden.reshape(n, h)
    y = labels.reshape(n)

    if scan_free:
        # no pad either: the pad+concat of a data-sharded array inside
        # the cond is another resharding-collective source.  Any divisor
        # of n works; pick the chunk size nearest the tuned chunk_rows.
        # Awkward token counts (n = 2 * prime, or prime) degrade
        # smoothly — worst case one chunk of n rows, which IS the plain
        # materialized-logits head — instead of failing at trace time
        # (the old bounded search raised for e.g. n=4106).
        best = _scan_free_chunk(n, chunk_rows)
        if best > 4 * chunk_rows:
            from torchacc_tpu.utils.logger import logger
            logger.warning(
                f"fused CE scan_free: n={n} rows has no divisor near "
                f"chunk_rows={chunk_rows}; using {best}-row chunks "
                f"(memory approaches the unchunked head)")
        chunk_rows = best
    pad = (-n) % chunk_rows
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad, h), x.dtype)], axis=0)
        y = jnp.concatenate(
            [y, jnp.full((pad,), -100, y.dtype)], axis=0)
    chunks = (n + pad) // chunk_rows
    xc = x.reshape(chunks, chunk_rows, h)
    yc = y.reshape(chunks, chunk_rows)
    w = w_head.astype(x.dtype)

    # the sums ride the loop's carry; the chunks' dx are stacked
    zero = jnp.zeros((), jnp.float32)
    acc = (zero, zero)
    if with_grads:
        acc += (jnp.zeros(w.shape, w_head.dtype),)

    def body(acc, xy):
        sums, dx = _head_chunk(*xy, w, logit_softcap, with_grads)
        return tuple(a + s.astype(a.dtype) for a, s in zip(acc, sums)), dx

    if scan_free:
        dxs = []
        for i in range(chunks):
            acc, dx = body(acc, (xc[i], yc[i]))
            dxs.append(dx)
        dxs = jnp.stack(dxs) if with_grads else None
    else:
        acc, dxs = jax.lax.scan(body, acc, (xc, yc))
    if not with_grads:
        return acc
    loss_sum, count, dw = acc
    dx = dxs.reshape(chunks * chunk_rows, h)[:n].reshape(hidden.shape)
    return loss_sum, count, dx, dw


def head_row_axes(batch: int) -> Tuple[str, ...]:
    """The data axes of the ambient mesh over which the head keeps a
    ``[batch, seq, H]`` hidden's rows where they lie (``batch_axes``),
    read at trace time; ``()`` = the rows are taken whole.  Whole too
    where some mesh axis is manual: the caller's region (the pp-manual
    1F1B tick) has made its own arrangement of the devices."""
    mesh = ambient_mesh()
    if mesh is None or mesh.manual_axes:
        return ()
    return batch_axes(mesh, batch)


def _head_rows(hidden, w_head, labels, chunk_rows: int,
               logit_softcap: float, scan_free: bool, with_grads: bool):
    """``_head_chunks`` where the rows live.  Under data axes that shard
    the batch (``head_row_axes``) the chunk loop runs per shard inside
    ONE ``shard_map`` over those axes alone (tp / sp stay the
    partitioner's): each chip loops over its own rows against the whole
    head weight, which enters replicated over the data axes — gathered
    once, before the loop — and the sums leave ``psum``-ed, d(hidden)
    row-sharded as hidden came, dW reduced once after the loop into the
    head parameter's layout (``parallel/sharding``'s ``embed`` rule:
    hidden over ``fsdp``) by a reduce-scatter.  Left to the partitioner
    the loop shards the head matmul's CONTRACTION instead (the weight
    arrives ``[H / fsdp, V]``): every chunk's ``[chunk_rows, V]``
    float32 logits are all-reduced and every chip runs the softmax over
    every row (PERF.md section 6, PR 41)."""
    axes = head_row_axes(hidden.shape[0])
    if not axes:
        return _head_chunks(hidden, w_head, labels, chunk_rows,
                            logit_softcap, scan_free, with_grads)
    from jax.sharding import PartitionSpec as P

    mesh = ambient_mesh()
    scatter = ("fsdp" if "fsdp" in axes
               and w_head.shape[0] % mesh.shape["fsdp"] == 0 else None)

    def local(h, w, y):
        out = _head_chunks(h, w, y, chunk_rows, logit_softcap, scan_free,
                           with_grads)
        sums = jax.lax.psum(out[:2], axes)
        if not with_grads:
            return sums
        dx, dw = out[2:]
        # the shards' partial dW, each summed over its own chunks in
        # w_head's dtype, meet in float32 and are rounded once: XLA:CPU
        # (tier-1's backend) CHECK-crashes on a bf16 cross-device sum,
        # as the tp head below notes, and one reduction a step is cheap
        part = dw.astype(jnp.promote_types(dw.dtype, jnp.float32))
        if scatter:
            part = jax.lax.psum_scatter(part, scatter, scatter_dimension=0,
                                        tiled=True)
        rest = tuple(a for a in axes if a != scatter)
        if rest:
            part = jax.lax.psum(part, rest)
        return (*sums, dx, part.astype(dw.dtype))

    rows = P(axes)
    out_specs = (P(), P())
    if with_grads:
        out_specs += (rows, P(scatter))
    return jax.shard_map(
        local, mesh=mesh, in_specs=(rows, P(), rows), out_specs=out_specs,
        axis_names=frozenset(axes), check_vma=False,
    )(hidden, w_head, labels)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_ce(hidden, w_head, labels, chunk_rows, logit_softcap, scan_free):
    return _head_rows(hidden, w_head, labels, chunk_rows, logit_softcap,
                      scan_free, with_grads=False)


def _fused_ce_fwd(hidden, w_head, labels, chunk_rows, logit_softcap,
                  scan_free):
    loss_sum, count, dx, dw = _head_rows(
        hidden, w_head, labels, chunk_rows, logit_softcap, scan_free,
        with_grads=True)
    return (loss_sum, count), (dx, dw)


def _fused_ce_bwd(chunk_rows, logit_softcap, scan_free, res, cts):
    dx, dw = res
    g = cts[0]  # the count has no gradient, nor have the labels
    return (dx * g).astype(dx.dtype), (dw * g).astype(dw.dtype), None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


@scoped("fused_ce")
def fused_linear_cross_entropy(
    hidden: jax.Array,
    w_head: jax.Array,
    labels: jax.Array,
    *,
    chunk_rows: int = 2048,
    logit_softcap: float = 0.0,
    scan_free: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """(loss_sum, valid_count) of next-token CE without full logits.

    hidden: [batch, seq, H]; w_head: [H, V]; labels: [batch, seq] with
    -100 ignored.  Equivalent to ``loss_sum_count(hidden @ w_head,
    labels)`` but chunked over rows, so the [rows, V] float32 logits
    exist only one chunk at a time.  A ``jax.custom_vjp``: under
    differentiation the forward's one pass over the chunks also forms
    each chunk's dlogits (softmax - onehot, zero on ignored rows), its
    d(hidden) rows and its share of dW while the logits are there —
    three head-sized matmuls a chunk, nothing recomputed — and saves
    d(hidden) (hidden's dtype) and dW (summed and saved in w_head's
    dtype) for a backward that only scales them by the cotangent of
    loss_sum.  Without differentiation (eval) it is the chunk loop
    alone: one matmul a chunk.  Peak memory is O(chunk x vocab) for the
    logits plus one [H, V] accumulator.
    chunk_rows=2048 measured best on v5e (1024 costs ~1.5 MFU points on
    the 32k-vocab bench; 4096 is equal but doubles the chunk buffer).
    ``logit_softcap`` > 0 applies Gemma2's c * tanh(logits / c) before
    the loss.

    Where the rows live is read from the ambient mesh at trace time
    (``head_row_axes``), not set: under data axes (``dp``, ``fsdp``)
    of extent > 1 that divide the batch, each chip loops over its OWN
    rows inside one ``shard_map`` over those axes (``_head_rows``: the
    head weight gathered once a step, the sums ``psum``-ed, d(hidden)
    left row-sharded, dW reduce-scattered once into the parameter's
    ``[H / fsdp, V]`` shards, summed across the shards in float32); on
    one device, under a batch the data extent does not divide, or
    inside a region that has made some mesh axis manual (the 1F1B
    tick), the rows are taken whole and the partitioner shards what it
    will.  The ``custom_vjp`` is around either: nothing is transposed
    across the ``shard_map``.

    ``scan_free=True`` unrolls the chunk loop (a Python loop over the
    same chunk function instead of ``lax.scan``).  Required when this
    runs inside a branch only SOME devices take — the 1F1B last-stage
    ``lax.cond`` — because the scan's WhileThunk desynchronizes
    XLA:CPU's in-process collective rendezvous.  Same math, same
    per-chunk memory profile; only the loop is unrolled.
    """
    return _fused_ce(hidden, w_head, labels, chunk_rows, logit_softcap,
                     scan_free)


@scoped("fused_ce")
def fused_linear_cross_entropy_tp(
    hidden: jax.Array,
    w_head: jax.Array,
    labels: jax.Array,
    *,
    tp_axis: str = "tp",
    chunk_rows: int = 2048,
    logit_softcap: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Vocab-parallel fused linear+CE: each tp rank holds a [H, V/tp]
    head slice and computes its logits chunk; softmax statistics (max,
    denominator, label logit) combine via hand-written pmax/psum over
    ``tp_axis`` inside a shard_map manual over ONLY that axis.

    Built for the 1F1B tick body (parallel/pp.py head_vjp): GSPMD
    auto-sharding a vocab dim over 'tp' inside the pp-manual region
    trips an XLA SPMD-partitioner CHECK (spmd_partitioner_util.cc:495)
    when a data axis is live, which round 3 dodged by replicating the
    head per device — ~1 GB bf16 at Llama-3's 128k vocab, and the head
    matmul didn't scale with tp.  Manual collectives never reach the
    auto partitioner, so the head weight, its gradient, and the head
    FLOPs all stay 1/tp per device.  (Reference capability:
    vocab-parallel projection, torchacc/dist/tp.py:1-5 +
    spmd_fsdp.py:75-77.)

    Grads: dW emerges tp-sharded (each rank owns its vocab slice); the
    shard_map transpose inserts the psum over tp for d(hidden).  Rows
    are chunked like ``fused_linear_cross_entropy(scan_free=True)`` —
    python-unrolled ``jax.checkpoint`` chunks, O(chunk x V/tp) logits
    live at a time on each rank.

    Requires vocab % tp == 0 (callers fall back to the replicated-head
    path otherwise) and runs under an active mesh with ``tp_axis``.
    """
    b, s, h = hidden.shape
    v = w_head.shape[1]
    mesh = jax.sharding.get_abstract_mesh()
    tp = mesh.shape[tp_axis]
    if v % tp != 0:
        raise ValueError(
            f"fused_linear_cross_entropy_tp: vocab {v} not divisible by "
            f"{tp_axis} extent {tp}")
    from jax.sharding import PartitionSpec as P

    n = b * s
    compute_dtype = hidden.dtype
    # f32 across the shard_map boundary: the transpose of the
    # (tp-replicated) hidden input is a psum over tp, and a bf16
    # all-reduce CHECK-crashes XLA:CPU's AllReducePromotion pass
    # (hlo_instruction.cc:1585 'Invalid binary instruction opcode
    # copy').  bf16->f32->bf16 round-trips exactly, and the matmul
    # below casts back to the model dtype for MXU throughput.
    x = hidden.reshape(n, h).astype(jnp.float32)
    y = labels.reshape(n)
    rows = _scan_free_chunk(n, chunk_rows)
    chunks = n // rows
    if rows > 4 * chunk_rows:
        from torchacc_tpu.utils.logger import logger
        logger.warning(
            f"fused CE (tp): n={n} rows has no divisor near "
            f"chunk_rows={chunk_rows}; using {rows}-row chunks (per-rank "
            f"memory approaches the unchunked [n, V/tp] logits)")
    # per-rank vocab offsets ride in as a P(tp)-sharded array: shardy
    # cannot lower jax.lax.axis_index for a nested-manual axis
    offs = jnp.arange(tp, dtype=jnp.int32) * (v // tp)

    def local(off_arr, xf, w_loc, yf):
        off = off_arr[0]
        vloc = w_loc.shape[1]

        def one_chunk(xi, yi):
            z = jnp.dot(xi.astype(compute_dtype),
                        w_loc.astype(compute_dtype),
                        preferred_element_type=jnp.float32)
            if logit_softcap > 0.0:
                from torchacc_tpu.models.transformer import softcap
                z = softcap(z, logit_softcap)
            # the max shift is stability-only: cut the tangent BEFORE
            # pmax (no pmax differentiation rule; exact regardless)
            m = jax.lax.pmax(
                jax.lax.stop_gradient(jnp.max(z, axis=-1)), tp_axis)
            valid = yi != -100
            mine = jnp.logical_and(yi >= off, yi < off + vloc)
            safe = jnp.clip(yi - off, 0, vloc - 1)
            # one combined all-reduce for the denominator and the label
            # logit (independent of each other; only pmax must precede)
            ssum, ll = jax.lax.psum(
                (jnp.sum(jnp.exp(z - m[:, None]), axis=-1),
                 jnp.where(mine,
                           jnp.take_along_axis(z, safe[:, None], 1)[:, 0],
                           0.0)), tp_axis)
            lse = m + jnp.log(ssum)
            loss = jnp.sum(jnp.where(valid, lse - ll, 0.0))
            count = jnp.sum(valid).astype(jnp.float32)
            return loss, count

        one_chunk = jax.checkpoint(
            one_chunk, policy=jax.checkpoint_policies.nothing_saveable)
        loss_sum = jnp.zeros((), jnp.float32)
        count = jnp.zeros((), jnp.float32)
        xc = xf.reshape(chunks, rows, h)
        yc = yf.reshape(chunks, rows)
        for i in range(chunks):
            l, c = one_chunk(xc[i], yc[i])
            loss_sum, count = loss_sum + l, count + c
        return loss_sum, count

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(tp_axis), P(), P(None, tp_axis), P()),
        out_specs=(P(), P()),
        axis_names=frozenset({tp_axis}), check_vma=False,
    )(offs, x, w_head, y)
