"""Grouped matmul (Pallas TPU): ``out[rows of group g] = x[rows of g] @ w[g]``.

The dropless expert layer (``models/moe.held_experts_ffn``) sorts its
(token, expert) pairs by expert; the rows of one expert are then one
contiguous group, and the three expert matmuls are one call each over
all groups.  Design as the public "megablox" grouped matmul: the groups
cut the row tiles, and a small schedule computed outside the kernel says
which (group, row tile) each grid step works on, so

- a group with no rows takes no grid step and none of its weights is
  read (an expert that drew no token costs nothing);
- row tiles behind the last group are never visited (in a prefill chunk
  most of the ``tokens * k`` sorted rows belong to experts held
  elsewhere), and what the output holds there is undefined — the caller
  masks it;
- a row tile that two groups share is visited once for each, and each
  visit stores only its own rows.

The weights may be one layer's ``[g, k, n]`` or the stack of every
layer's, ``[L, g, k, n]``, with a layer index.  A layer scan hands its
body a slice of each stacked leaf; an XLA dot absorbs that slice, a
custom call cannot — its operand has to be a whole buffer, so XLA copies
the layer's stack out of the stacked weights before every call (A.X-K1's
serving cell: 336 MiB a stack, three stacks a layer, 15.9 ms of a 30.7 ms
decode step at the HBM's pace — ledger and PERF.md, PR 26).  So the
caller keeps the stacks off its scan (``serve/scheduler.py::_forward``)
and the layer index rides as one more scalar-prefetch operand: the
weight block's index map addresses ``(layer, group, k block, n block)``
in the stack where it lies, as ``ops/paged_attention.py`` addresses a
page of the stacked pool.  One kernel: ``[g, k, n]`` enters as a stack
of one.

``jax.lax.ragged_dot`` computes the same; on the TPU XLA runs it as
instructions that carry no ``op_name`` (my chip run, PR 26: a quarter of
the serving window's device time under no scope), so the program's
``experts`` scope could not be read from a trace.  A Pallas call keeps
the scope it was traced under.

Training differentiates the one-layer form (``[g, k, n]`` weights, no
layer index) through a ``custom_vjp`` whose backward is two more Pallas
kernels over the same schedule: ``gmm_dx``, the same grouped product
against the weights read TRANSPOSED where they lie (``dX_g = dY_g
W_g^T``; no transposed copy of the experts is made), and ``gmm_dw``,
``dW_g = X_g^T dY_g``, which walks a group's row tiles with its
``[tk, tn]`` block of the result resident in VMEM and writes it once
(a group with no rows takes one step that writes zeros and fetches
nothing new).  The rows of no group: the result stays UNDEFINED there
under differentiation too (a caller that feeds it to a nonlinearity
masks it first, as ``held_experts_ffn`` does: a select fused into the
activation), the cotangent's rows there are never read, and dX is zero
there (one select, which XLA fuses into dX's consumer).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchacc_tpu.ops._common import (
    ambient_mesh,
    interpret_mode as _interpret,
    needs_shard_map,
    round_up,
)

ROW_TILE = 128           # rows of x a grid step multiplies
DW_ROW_TILE = 256        # rows a gmm_dw step contracts over
DW_BLOCK = 1024 * 1024   # elements of gmm_dw's resident [tk, tn] block
WEIGHT_BLOCK = 2 * 1024 * 1024   # elements of the [tk, tn] weight block


def _divisor_tile(dim: int, target: int) -> int:
    """Largest multiple of 128 that divides ``dim`` and is at most
    ``target``; ``dim`` itself where there is none (toy widths)."""
    for t in range(min(target, dim) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def weight_tiles(k: int, n: int) -> tuple[int, int]:
    """``(tk, tn)``: the narrower of the two dimensions whole (up to
    2048), the other as wide as ``WEIGHT_BLOCK`` allows — 4 MiB of bf16
    a block, two of them in flight.  My chip run, PR 26, 12 experts of
    7168 x 2048, 16 pairs on 9 of them (the weight-read floor is 0.32 ms
    a matmul): (1024, 2048) reads the way up in 0.57 ms and (2048, 1024)
    the way down in 0.41 ms; square 1024 blocks 0.62 / 0.60 ms."""
    if k <= n:
        tk = _divisor_tile(k, 2048)
        return tk, _divisor_tile(n, max(128, WEIGHT_BLOCK // tk))
    tn = _divisor_tile(n, 2048)
    return _divisor_tile(k, max(128, WEIGHT_BLOCK // tn)), tn


def tile_schedule(group_sizes, m: int, tm: int, visit_empty: bool = False):
    """Which (group, row tile) each grid step works on.

    ``group_sizes`` int32 [g] (its sum may be less than ``m``).  Returns
    int32 ``(group_of [steps], tile_of [steps], starts [g], ends [g],
    num_steps [1])`` with ``steps = m // tm + g - 1`` the static upper bound
    (each group boundary inside a tile adds one visit) and ``num_steps``
    the steps that do work.  Steps past ``num_steps`` repeat the last
    working step's indices (and the index maps hold them to its last k
    block), so the pipeline fetches nothing for them.

    ``visit_empty`` (``gmm_dw``, which has a block of its result to
    write for every group) gives a group with no rows one step too, on
    the row tile the step before it was on, so nothing new is fetched
    for it; the bound is then ``m // tm + 2 * g - 1`` steps."""
    g = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    bound = m // tm + g - 1
    if visit_empty:
        first = jnp.where(group_sizes > 0, first,
                          jnp.maximum(starts - 1, 0) // tm)
        tiles = jnp.maximum(tiles, 1)
        bound += g
    step_ends = jnp.cumsum(tiles)
    step_starts = step_ends - tiles
    num_steps = step_ends[-1]
    step = jnp.minimum(jnp.arange(bound, dtype=jnp.int32),
                       jnp.maximum(num_steps - 1, 0))
    group_of = jnp.minimum(
        jnp.searchsorted(step_ends, step, side="right"), g - 1)
    tile_of = first[group_of] + step - step_starts[group_of]
    return group_of, tile_of, starts, ends, num_steps[None]


def _kernel(group_of, tile_of, starts, ends, num_steps, layer, x_ref, w_ref,
            o_ref, acc_ref, *, tm: int, k_steps: int, transposed: bool):
    s, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(s < num_steps[0])
    def _work():
        @pl.when(ki == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # transposed: the rows meet the weight block's SECOND dimension
        # (x [tm, tn] . w [tk, tn]^T), the block read as it lies
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...],
            (((1,), (1 if transposed else 0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(ki == k_steps - 1)
        def _store():
            g = group_of[s]
            rows = tile_of[s] * tm + jax.lax.broadcasted_iota(
                jnp.int32, acc_ref.shape, 0)
            mine = (rows >= starts[g]) & (rows < ends[g])
            o_ref[...] = jnp.where(mine, acc_ref[...].astype(o_ref.dtype),
                                   o_ref[...])


def _grouped_matmul_pallas(x, w, group_sizes, layer, *, tk, tn,
                           transposed=False):
    """``x [m, k] @ w[layer, g] [k, n]`` a group, or ``transposed``:
    ``x [m, n] @ w[layer, g]^T``.  One kernel body and one weight block
    ``[tk, tn]`` for both; the transposed product contracts over the
    block's second dimension and its result is ``tk`` wide."""
    _, g, k, n = w.shape
    m = x.shape[0]
    tm = min(ROW_TILE, round_up(m, 16))
    m_pad = round_up(m, tm)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    auto = weight_tiles(k, n)
    tk = _divisor_tile(k, tk) if tk else auto[0]
    tn = _divisor_tile(n, tn) if tn else auto[1]
    # (contraction tile, result tile, result width): the weight block's
    # two dimensions swap roles in the transposed product
    tc, to_, width = (tn, tk, k) if transposed else (tk, tn, n)
    c_steps = (n if transposed else k) // tc
    schedule = tile_schedule(group_sizes.astype(jnp.int32), m_pad, tm)

    def c_block(s, ci, num_steps):
        # an idle step stays on the last working step's last k block: an
        # index that moved would fetch a weight block for nothing (my chip
        # run, PR 26: 3.5 us an idle step, 1.5 of a chunk's 2.0 ms)
        return jnp.where(s < num_steps[0], ci, c_steps - 1)

    def w_index(oi, s, ci, go, to, st, en, num, layer):
        c = c_block(s, ci, num)
        return ((layer[0], go[s], oi, c) if transposed
                else (layer[0], go[s], c, oi))

    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, k_steps=c_steps,
                          transposed=transposed),
        out_shape=jax.ShapeDtypeStruct((m_pad, width), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # six scalar-prefetch operands: the schedule's five and the
            # layer index, so the weight block's index map can address
            # (layer, group, k block, n block) in the stack before the
            # body runs (the pattern of ops/paged_attention.py's pools)
            num_scalar_prefetch=6,
            grid=(width // to_, m_pad // tm + g - 1, c_steps),
            in_specs=[
                pl.BlockSpec((tm, tc),
                             lambda oi, s, ci, go, to, st, en, num, layer:
                             (to[s], c_block(s, ci, num))),
                pl.BlockSpec((None, None, tk, tn), w_index),
            ],
            out_specs=pl.BlockSpec(
                (tm, to_), lambda oi, s, ci, go, to, *_: (to[s], oi)),
            scratch_shapes=[pltpu.VMEM((tm, to_), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="gmm_dx" if transposed else "grouped_matmul",
    )(*schedule, layer.reshape(1), x, w)
    return out[:m]


def _dw_kernel(group_of, tile_of, starts, ends, num_steps, x_ref, dy_ref,
               o_ref, acc_ref, *, tm: int, bound: int):
    s = pl.program_id(2)

    @pl.when(s < num_steps[0])
    def _work():
        g = group_of[s]
        first = (s == 0) | (group_of[jnp.maximum(s - 1, 0)] != g)
        last = ((s == num_steps[0] - 1)
                | (group_of[jnp.minimum(s + 1, bound - 1)] != g))

        @pl.when(first)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # rows of the tile that belong to another group (or to none:
        # whatever lies there) leave both factors as zeros
        rows = tile_of[s] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (rows >= starts[g]) & (rows < ends[g])
        xs = jnp.where(mine, x_ref[...], jnp.zeros_like(x_ref))
        dys = jnp.where(mine, dy_ref[...], jnp.zeros_like(dy_ref))
        acc_ref[...] += jax.lax.dot_general(
            xs, dys, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _store():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def dw_tiles(k: int, n: int) -> tuple[int, int]:
    """``gmm_dw``'s resident block: the narrower dimension whole (up to
    1024), the other as wide as ``DW_BLOCK`` allows — 4 MiB of float32
    accumulator beside two result blocks and the row tiles in flight."""
    if k <= n:
        tk = _divisor_tile(k, 1024)
        return tk, _divisor_tile(n, max(128, DW_BLOCK // tk))
    tn = _divisor_tile(n, 1024)
    return _divisor_tile(k, max(128, DW_BLOCK // tn)), tn


def _gmm_dw_pallas(x, dy, group_sizes, dtype, *, tk, tn):
    """``dW[g] = x[rows of g]^T @ dy[rows of g]`` -> [g, k, n] in
    ``dtype``, float32 accumulation over a group's row tiles."""
    m, k = x.shape
    n = dy.shape[1]
    g = group_sizes.shape[0]
    tm = min(DW_ROW_TILE, round_up(m, 16))
    m_pad = round_up(m, tm)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
        dy = jnp.pad(dy, ((0, m_pad - m), (0, 0)))
    auto = dw_tiles(k, n)
    tk = _divisor_tile(k, tk) if tk else auto[0]
    tn = _divisor_tile(n, tn) if tn else auto[1]
    schedule = tile_schedule(group_sizes.astype(jnp.int32), m_pad, tm,
                             visit_empty=True)
    bound = m_pad // tm + 2 * g - 1
    return pl.pallas_call(
        functools.partial(_dw_kernel, tm=tm, bound=bound),
        out_shape=jax.ShapeDtypeStruct((g, k, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            # the steps innermost: a group's steps follow one another,
            # so its result block stays in VMEM from its first row tile
            # to its last and is written back when the group changes
            grid=(k // tk, n // tn, bound),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda ki, ni, s, go, to, *_: (to[s], ki)),
                pl.BlockSpec((tm, tn),
                             lambda ki, ni, s, go, to, *_: (to[s], ni)),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda ki, ni, s, go, *_: (go[s], ki, ni)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="gmm_dw",
    )(*schedule, x, dy)


def _replicated(fn, n_args: int):
    """``fn`` under the ambient mesh.  Outside a region that has made
    every mesh axis manual the call is one chip's share, the same on
    every shard: each holds the same rows and weights and runs the whole
    call."""
    mesh = ambient_mesh()
    if not needs_shard_map(mesh):
        return fn
    from jax.sharding import PartitionSpec as P
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(),) * n_args,
                         out_specs=P(), check_vma=False)


def _in_group(m: int, group_sizes):
    """[m, 1] bool: the rows that belong to some group."""
    return (jnp.arange(m, dtype=jnp.int32) < jnp.sum(group_sizes))[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(x, w, group_sizes, tk, tn):
    """One layer's ``[g, k, n]`` weights: the differentiable form."""
    fn = functools.partial(_grouped_matmul_pallas, tk=tk, tn=tn)
    # a stack of one (a bitcast), so there is one kernel
    return _replicated(fn, 4)(x, w.astype(x.dtype)[None], group_sizes,
                              jnp.zeros((), jnp.int32))


def _gmm_fwd(x, w, group_sizes, tk, tn):
    return _gmm(x, w, group_sizes, tk, tn), (x, w, group_sizes)


def _gmm_bwd(tk, tn, res, dy):
    x, w, group_sizes = res
    dy = dy.astype(x.dtype)
    dx_fn = functools.partial(_grouped_matmul_pallas, tk=tk, tn=tn,
                              transposed=True)
    dx = _replicated(dx_fn, 4)(dy, w.astype(x.dtype)[None], group_sizes,
                               jnp.zeros((), jnp.int32))
    dx = jnp.where(_in_group(x.shape[0], group_sizes), dx,
                   jnp.zeros_like(dx))
    dw_fn = functools.partial(_gmm_dw_pallas, dtype=w.dtype, tk=tk, tn=tn)
    return dx, _replicated(dw_fn, 3)(x, dy, group_sizes), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(x, w, group_sizes, *, layer=None, tk: int | None = None,
                   tn: int | None = None):
    """``x`` [m, k] with its rows sorted by group, ``w`` [g, k, n],
    ``group_sizes`` int [g]: rows ``[sum(sizes[:i]), sum(sizes[:i+1]))``
    are multiplied by ``w[i]`` (float32 accumulation, result in
    ``x.dtype``).  Rows past ``sum(group_sizes)`` belong to no group:
    the result there is UNDEFINED (possibly not finite) — mask it, do
    not multiply it by zero.  This form is differentiable in ``x`` and
    ``w`` (``gmm_dx`` / ``gmm_dw``: module docstring): dX is zero in the
    rows of no group and the cotangent's rows there are not read.

    ``w`` may also be a layer stack [L, g, k, n] with ``layer`` an int32
    scalar (traced inside a layer scan): the call computes with
    ``w[layer]`` and reads only that layer's hit groups out of the stack
    where it lies (serving; no derivative).  A stack has to be in
    ``x.dtype`` already — converting
    it here would convert every layer of it on every call — and one of
    another dtype is a ``TypeError``; the caller converts once, outside
    its scan.  (A [g, k, n] ``w`` of another dtype is converted, as
    before.)  ``tk``/``tn`` override the weight block of
    :func:`weight_tiles` (tests)."""
    if (w.ndim == 4) != (layer is not None):
        raise ValueError(
            f"grouped_matmul: weights of shape {w.shape} with layer="
            f"{layer}; a stack [L, g, k, n] takes a layer index, one "
            f"layer's [g, k, n] takes none")
    if layer is None:
        return _gmm(x, w, group_sizes, tk, tn)
    if w.dtype != x.dtype:
        raise TypeError(
            f"grouped_matmul: a layer stack in {w.dtype} with rows in "
            f"{x.dtype}; convert the stack once, outside the layer scan")
    fn = functools.partial(_grouped_matmul_pallas, tk=tk, tn=tn)
    return _replicated(fn, 4)(x, w, group_sizes,
                              jnp.asarray(layer, jnp.int32))
