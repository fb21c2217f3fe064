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
On a TPU a chunk's three matmuls are Pallas kernels (``head_fwd``,
``head_dx``, ``head_dw``) wherever the call holds one device's arrays;
the XLA body beside them is every other path and their reference.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchacc_tpu.ops._common import (
    NEG_INF,
    ambient_mesh,
    batch_axes,
    interpret_mode as _interpret,
    needs_shard_map,
    on_tpu,
    scoped,
)
from torchacc_tpu.ops.flash_attention import _LANES, _across_lanes


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


# ---------------------------------------------------------------------------
# The chunk as Pallas kernels.  XLA's three fusions above run the MXU at
# its peak and then wait for their side traffic (the float32 logits
# written and read three times, the dW accumulator read and written:
# ~4 GB a 2048-row chunk at a 100k vocabulary).  A kernel's blocks are
# double-buffered, so that traffic goes behind the matmul, and the
# softmax's sums and dlogits are made in VMEM from the tile the MXU
# just produced or is about to consume.
# ---------------------------------------------------------------------------

# the most VMEM a head kernel asks of the compiler (its scoped default is
# 16 MiB of a v5e's 128): the chunk's rows resident beside double-buffered
# float32 logits tiles
_HEAD_VMEM_LIMIT = 100 * 1024 * 1024
# what ``_head_tiles`` lets a kernel's blocks and tiles take of it
_HEAD_VMEM_BUDGET = 72 * 1024 * 1024
# ignored rows enter the backward kernels with this as their lse:
# exp(z - lse) is 0 there and their label (-100) matches no column, so
# dlogits is 0 without a select
_LSE_IGNORED = 1e30


def _label_hits(y, v0, shape):
    """Where the column of a ``shape`` logits tile starting at vocabulary
    id ``v0`` is the row's label (``y`` lane-broadcast [rows, 128])."""
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return col == _across_lanes(y - v0, shape[1])


def _head_fwd_kernel(x_ref, w_ref, y_ref, z_ref, lse_ref, ll_ref,
                     m_scr, l_scr, ll_scr, *, tv, nv):
    """One vocabulary tile of one row tile: the float32 logits tile out,
    the running max and sum (online logsumexp) and the label's logit by
    the masked compare carried lane-broadcast across the tiles."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        ll_scr[...] = jnp.zeros_like(ll_scr)

    z = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    z_ref[...] = z
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(z, axis=1, keepdims=True))
    p = jnp.exp(z - _across_lanes(m_new, tv))
    l_scr[...] = (jnp.exp(m_prev - m_new) * l_scr[...]
                  + jnp.sum(p, axis=1, keepdims=True))
    m_scr[...] = m_new
    hit = _label_hits(y_ref[...], j * tv, z.shape)
    ll_scr[...] += jnp.sum(jnp.where(hit, z, 0.0), axis=1, keepdims=True)

    @pl.when(j == nv - 1)
    def _finalize():
        lse_ref[...] = m_scr[...] + jnp.log(l_scr[...])
        ll_ref[...] = ll_scr[...]


def _dlogits(z_ref, lse_ref, y_ref, v0, dtype):
    """softmax - onehot of a logits tile (0 on ignored rows by their
    lse, ``_LSE_IGNORED``), rounded to the model dtype as default
    precision rounds the float32 dlogits that meets a model-dtype
    operand."""
    z = z_ref[...]
    p = jnp.exp(z - _across_lanes(lse_ref[...], z.shape[1]))
    hit = _label_hits(y_ref[...], v0, z.shape)
    return jnp.where(hit, p - 1.0, p).astype(dtype)


def _head_dx_kernel(z_ref, lse_ref, y_ref, w_ref, dx_ref, acc_scr, *,
                    tv, nv):
    """d(hidden) of one row tile, summed over the vocabulary tiles:
    dlogits [rows, tv] against w[:, tile] [h, tv], the lanes of both
    contracted (the MXU takes the transposed operand as it is pushed:
    no w^T array, no tile transposed)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    dl = _dlogits(z_ref, lse_ref, y_ref, j * tv, w_ref.dtype)
    acc_scr[...] += jax.lax.dot_general(
        dl, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nv - 1)
    def _finalize():
        dx_ref[...] = acc_scr[...].astype(dx_ref.dtype)


def _head_dw_kernel(xt_ref, z_ref, lse_ref, y_ref, sum_ref, dw_ref, *, tv):
    """One vocabulary tile of dW over the chunk's rows, added to the sum
    of the chunks before (aliased in and out): x^T [h, rows] @ dlogits
    [rows, tv], the sum rounded once to its dtype."""
    j = pl.program_id(0)
    dl = _dlogits(z_ref, lse_ref, y_ref, j * tv, xt_ref.dtype)
    dw = jnp.dot(xt_ref[...], dl, preferred_element_type=jnp.float32)
    dw_ref[...] = (sum_ref[...].astype(jnp.float32) + dw).astype(
        dw_ref.dtype)


def _head_tiles(rows: int, h: int, v: int, itemsize: int,
                sum_itemsize: int):
    """(fwd row tile, fwd vocab tile, dx row tile, dx vocab tile, dW vocab
    tile) of the head kernels for a chunk of ``rows`` against a ``[h, v]``
    head of ``itemsize``-byte elements, dW summed in ``sum_itemsize``-byte
    ones: the largest 128-multiples that divide them, up to the size past
    which a grid step's ~0.35 us no longer shows, whose blocks
    (double-buffered) and float32 tiles fit ``_HEAD_VMEM_BUDGET``; None
    where nothing divides or fits (``head_dw`` holds the chunk's rows
    whole)."""
    if rows % _LANES or h % _LANES or v % _LANES:
        return None

    def tiles(n, most):
        return [t for t in (2048, 1024, 512, 256, 128)
                if t <= most and n % t == 0]

    def largest(pairs, vmem):
        fit = [p for p in pairs if vmem(*p) <= _HEAD_VMEM_BUDGET]
        return max(fit, key=lambda p: (p[0] * p[1], p[0]), default=None)

    it = itemsize
    square = [(r, t) for r in tiles(rows, 1024) for t in tiles(v, 1024)]
    fwd = largest(
        square, lambda r, t: 2 * it * (r * h + h * t) + 3 * 4 * r * t)
    dx = largest(
        square,
        lambda r, t: (2 * 4 * r * t + 2 * it * t * h + (4 + 2 * it) * r * h
                      + (4 + it) * r * t))
    dw = largest(
        [(rows, t) for t in tiles(v, 512)],
        lambda r, t: (2 * it * h * r + 2 * 4 * r * t
                      + (4 * sum_itemsize + 4) * h * t + (4 + it) * r * t))
    if not (fwd and dx and dw):
        return None
    return (*fwd, *dx, dw[1])


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_HEAD_VMEM_LIMIT)


def _head_fwd(xi, w, y, fr, fv):
    """(float32 logits [rows, V], lse, the label's logit; the last two
    lane-broadcast [rows, 128]) of a chunk: ``head_fwd``."""
    rows, h = xi.shape
    v = w.shape[1]
    stat = jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)
    row_stat = pl.BlockSpec((fr, _LANES), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_head_fwd_kernel, tv=fv, nv=v // fv),
        grid=(rows // fr, v // fv),
        in_specs=[
            pl.BlockSpec((fr, h), lambda i, j: (i, 0)),
            pl.BlockSpec((h, fv), lambda i, j: (0, j)),
            row_stat,
        ],
        out_specs=[pl.BlockSpec((fr, fv), lambda i, j: (i, j)),
                   row_stat, row_stat],
        out_shape=[jax.ShapeDtypeStruct((rows, v), jnp.float32), stat, stat],
        scratch_shapes=[pltpu.VMEM((fr, _LANES), jnp.float32)] * 3,
        compiler_params=_params("parallel", "arbitrary"),
        interpret=_interpret(),
        name="head_fwd",
    )(xi, w, y)


def _head_dx(z, lse, y, w, dtype, xr, xv):
    """d(hidden) [rows, h] of a chunk from its logits: ``head_dx``."""
    rows, v = z.shape
    h = w.shape[0]
    row_stat = pl.BlockSpec((xr, _LANES), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_head_dx_kernel, tv=xv, nv=v // xv),
        grid=(rows // xr, v // xv),
        in_specs=[
            pl.BlockSpec((xr, xv), lambda i, j: (i, j)),
            row_stat, row_stat,
            pl.BlockSpec((h, xv), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((xr, h), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, h), dtype),
        scratch_shapes=[pltpu.VMEM((xr, h), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=_interpret(),
        name="head_dx",
    )(z, lse, y, w)


def _head_dw(xt, z, lse, y, dw_sum, wv):
    """``dw_sum`` + the chunk's dW, in ``dw_sum``'s buffer: ``head_dw``."""
    h, rows = xt.shape
    v = z.shape[1]
    whole = pl.BlockSpec((rows, _LANES), lambda j: (0, 0))
    tile = pl.BlockSpec((h, wv), lambda j: (0, j))
    return pl.pallas_call(
        functools.partial(_head_dw_kernel, tv=wv),
        grid=(v // wv,),
        in_specs=[
            pl.BlockSpec((h, rows), lambda j: (0, 0)),
            pl.BlockSpec((rows, wv), lambda j: (0, j)),
            whole, whole, tile,
        ],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(dw_sum.shape, dw_sum.dtype),
        input_output_aliases={4: 0},
        compiler_params=_params("arbitrary"),
        interpret=_interpret(),
        name="head_dw",
    )(xt, z, lse, y, dw_sum)


def _head_chunk_kernels(xi, yi, w, dw_sum, tiles):
    """``_head_chunk`` with its gradients, as three kernels: returns
    ``(loss_sum, valid_count, dw_sum + the chunk's dW)`` and dx."""
    fr, fv, xr, xv, wv = tiles
    y = jnp.broadcast_to(yi.astype(jnp.int32)[:, None],
                         (yi.shape[0], _LANES))
    z, lse, ll = _head_fwd(xi, w, y, fr, fv)
    valid = yi != -100
    loss = jnp.sum(jnp.where(valid, lse[:, 0] - ll[:, 0], 0.0))
    count = jnp.sum(valid).astype(jnp.float32)
    lse = jnp.where(valid[:, None], lse, _LSE_IGNORED)
    dx = _head_dx(z, lse, y, w, xi.dtype, xr, xv)
    dw_sum = _head_dw(xi.T, z, lse, y, dw_sum, wv)
    return (loss, count, dw_sum), dx


def _kernel_tiles(rows: int, h: int, v: int, dtype, sum_dtype,
                  logit_softcap: float, per_device: bool):
    """The kernels' tiles where a chunk of ``rows`` runs as kernels, else
    None (``_head_chunk``'s XLA body): the backend is a TPU, the arrays
    at the call are one device's (``per_device``: a Mosaic kernel is
    not the partitioner's to split), the model dtype is bfloat16 or
    float32, the logits are not capped (the kernels take no tanh) and
    the tiles divide the chunk."""
    if (not on_tpu() or not per_device or logit_softcap > 0.0
            or dtype not in (jnp.bfloat16, jnp.float32)):
        return None
    return _head_tiles(rows, h, v, jnp.dtype(dtype).itemsize,
                       jnp.dtype(sum_dtype).itemsize)


def _region_axes(mesh, axes) -> frozenset:
    """The mesh axes the head's ``shard_map`` over the row axes ``axes``
    makes manual: with them every other axis where all of those have
    extent 1 — nothing else is sharded there, and a region manual over
    the whole mesh holds one device's arrays, which the kernels need
    (``_common.needs_shard_map``); else ``axes`` alone, tp / sp left to
    the partitioner."""
    rest = [a for a in mesh.axis_names if a not in axes]
    if all(mesh.shape[a] == 1 for a in rest):
        return frozenset(mesh.axis_names)
    return frozenset(axes)


def head_impl(hidden, w_head, *, chunk_rows: int = 2048,
              logit_softcap: float = 0.0, scan_free: bool = False) -> str:
    """'pallas' | 'xla': what runs a chunk of the differentiated
    ``fused_linear_cross_entropy(hidden, w_head, ...)`` under the
    ambient mesh, read at trace time from the arrays' shapes and dtypes
    as ``_head_rows`` and ``_head_chunks`` read it."""
    b, s, h = hidden.shape
    mesh = ambient_mesh()
    axes = head_row_axes(b)
    n = b * s
    per_device = not needs_shard_map(mesh)
    if axes:
        n //= math.prod(mesh.shape[a] for a in axes)
        per_device = _region_axes(mesh, axes) == frozenset(mesh.axis_names)
    rows = _scan_free_chunk(n, chunk_rows) if scan_free else chunk_rows
    tiles = _kernel_tiles(rows, h, w_head.shape[1], hidden.dtype,
                          w_head.dtype, logit_softcap, per_device)
    return "pallas" if tiles else "xla"


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
    tiles = _kernel_tiles(
        chunk_rows, h, w.shape[1], x.dtype, w_head.dtype, logit_softcap,
        not needs_shard_map(ambient_mesh())) if with_grads else None

    # the sums ride the loop's carry; the chunks' dx are stacked
    zero = jnp.zeros((), jnp.float32)
    acc = (zero, zero)
    if with_grads:
        acc += (jnp.zeros(w.shape, w_head.dtype),)

    def body(acc, xy):
        if tiles:       # the dW sum goes through head_dw, in place
            (loss, count, dw), dx = _head_chunk_kernels(
                *xy, w, acc[2], tiles)
            # kept apart from the loop's stacking of dx: fused into
            # head_dx (XLA writes a kernel's result into the stack in
            # place where it can) the kernel is held to the fusion's
            # 16 MiB of VMEM, not to the limit it states
            dx = jax.lax.optimization_barrier(dx)
            return (acc[0] + loss, acc[1] + count, dw), dx
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
    # the row axes over which the head parameter's hidden dim is sharded
    # (parallel/sharding's 'embed' rule)
    scatter = tuple(a for a in ("fsdp", "ep") if a in axes)
    if w_head.shape[0] % math.prod(mesh.shape[a] for a in scatter):
        scatter = ()

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
        rest = tuple(a for a in axes if a not in scatter)
        if rest:
            part = jax.lax.psum(part, rest)
        return (*sums, dx, part.astype(dw.dtype))

    rows = P(axes)
    out_specs = (P(), P())
    if with_grads:
        out_specs += (rows, P(scatter or None))
    return jax.shard_map(
        local, mesh=mesh, in_specs=(rows, P(), rows), out_specs=out_specs,
        axis_names=_region_axes(mesh, axes), check_vma=False,
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

    What runs a differentiated chunk is read at trace time too
    (``head_impl`` says which; no option chooses).  Three Pallas
    kernels (``_head_chunk_kernels``) where the backend is a TPU, the
    arrays at the call are one device's — one device, or the head's
    ``shard_map`` manual over the whole mesh, which it is wherever the
    row axes are all the mesh shards — the model dtype is bfloat16 or
    float32, ``logit_softcap`` is 0 and 128-multiples tile the chunk:
    ``head_fwd`` sweeps the vocabulary tiles once (the float32 logits
    out, the row max, sum and label logit online, in VMEM), ``head_dx``
    and ``head_dw`` each rebuild dlogits from a logits tile in their
    prologue and are plain tiled matmuls (``head_dx`` contracts the
    lanes of dlogits and of the ``w_head`` tile, ``head_dw`` takes the
    chunk's transpose, made once a chunk), dW added into
    the chunks' sum through one aliased buffer and rounded once; every
    large array crosses HBM once per use, double-buffered behind the
    MXU.  ``_head_chunk``'s XLA body — three fusions that each pay
    their side traffic on top of their matmul — everywhere else: the
    CPU, a ``tp`` / ``sp`` extent left to the partitioner, the 1F1B
    tick's partly manual region, Gemma-2's softcap, eval; it is also
    the kernels' reference in the tests.
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
    ``[H / fsdp, V]`` shards (``fsdp`` and ``ep`` where both split
    it), summed across the shards in float32); on
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
