"""The dropless expert layer's row movers: passes that touch live rows
only.

``models/moe.held_experts_ffn`` sorts its ``n * k`` (token, expert) pairs
by expert into a buffer sized for the worst case; the pairs on held
experts are the PREFIX ``[0, total)`` of it, the rest belongs to experts
held elsewhere and is computed by nobody.  Gathers written over the whole
buffer move every row of it all the same (my chip run, PR 47, 65,536 rows
of 2,304 bf16, a quarter live: 2.9-3.5 ms each of the three movers
below).  Each got the body the chip measured fastest (PERF.md section 6,
PR 47):

- **rows back** (sorted pair rows -> token rows, summed over a token's
  ``k`` slots in float32 in slot order): :func:`sum_rows`, the combine's
  forward and the transpose of the take.  The live pairs lie scattered
  over the (token, slot) grid, so no prefix can be cut: a Pallas kernel
  (``moe_rows_back``) walks token tiles and copies a row only for a slot
  with ``unsort < total`` — one DMA a live pair out of the buffer in HBM,
  the pair's sorted row fed by scalar prefetch (the pattern of
  ``ops/paged_attention.py::page_dmas``), the tile after this one fetched
  behind this one's sum — and a dead slot is masked (never multiplied by
  zero) and never fetched.  2.07 / 1.95 ms where XLA's fused gather and
  sum takes 3.49 / 2.93.
- **rows out, weighed** (the combine's backward: the cotangent's token
  rows gathered to sorted pair rows, weighed, and reduced against the
  results for ``d_w``): :func:`take_rows_weighed`.  The live rows are a
  prefix, so this is XLA's own gather and arithmetic a row tile at a
  time in a loop that stops at ``ceil(total / tile)`` (a dynamic trip
  count), writing into a buffer nobody zeroed first
  (``moe_rows_alloc``): 1.62 ms against 2.89 over the whole buffer.

The plain rows out (``x[order // k]``, the take's forward) has no mover:
XLA gathers the rows of a small array — one it holds in VMEM — at the
HBM's pace, 0.50 ms for all 65,536, and both bodies that stopped at the
live rows lost to it (a Pallas kernel of one DMA a live row: 0.69 ms, 17
ns a row to issue the copy and as much to turn one-row tiles into
eight-row ones; XLA's gather a live tile at a time: 0.91 ms).

A DMA addresses whole tiles of a tiled array, and a row of a 2-D array is
an eighth of one (a sixteenth in bfloat16, where a 32-bit word holds two
ROWS): Mosaic refuses a one-row slice of ``[rows, h]``.  So
``moe_rows_back`` reads ``[rows, 1, words]`` 32-bit words — tiles of one
row — a bfloat16 row as ``h / 2`` words that hold its left half in their
low and its right half in their high 16 bits (:func:`_to_words`; unpacked
on the tile in VMEM, two shifts and a lane-aligned concatenate), and a
second kernel re-tiles the sorted buffer so, over its live tiles only
(``moe_rows_pack``): nothing here passes over the worst case.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchacc_tpu.ops._common import interpret_mode as _interpret, round_up
from torchacc_tpu.ops.grouped_matmul import _replicated

ROW_TILE = 1024      # sorted rows a turn of the weighing loop gathers
PACK_TILE = 256      # sorted rows a pack step re-tiles
SLOT_ROWS = 256      # (token, slot) rows a sum_rows step gathers
_VMEM_LIMIT = 64 * 2**20


def supports(dtype) -> bool:
    """Rows of 32-bit values, or of bfloat16 (two to a word)."""
    return jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16))


def _to_words(x):
    """``[r, h]`` -> ``[r, words]`` of one 32-bit dtype: float32 as it
    is, bfloat16 as uint32 words of (left half | right half << 16)."""
    if x.dtype == jnp.float32:
        return x
    w = x.shape[1] // 2
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return (bits[:, :w] >> 16) | (bits[:, w:] & jnp.uint32(0xFFFF0000))


def _from_words(words):
    """The rows of :func:`_to_words` as float32 ``[r, h]`` (a bfloat16
    value widened: exact)."""
    if words.dtype == jnp.float32:
        return words
    as_f32 = lambda u: jax.lax.bitcast_convert_type(  # noqa: E731
        u, jnp.float32)
    return jnp.concatenate(
        [as_f32(words << 16), as_f32(words & jnp.uint32(0xFFFF0000))],
        axis=1)


def _row_map(trailing: int):
    """Index map of a row-tiled operand with ``trailing`` whole
    dimensions: step ``i``'s block, or the last live one for the steps
    past it (nothing is fetched or written for them).  The number of
    live tiles is the first entry of the last scalar-prefetch operand."""
    def index(i, *prefetch):
        last = jnp.maximum(prefetch[-1][0] - 1, 0)
        return (jnp.minimum(i, last),) + (0,) * trailing
    return index


# -- rows out, weighed --------------------------------------------------------

def _alloc_kernel(o_ref):
    del o_ref


def _uninitialized(shape, dtype):
    """A buffer nobody has written: what a loop over the live tiles
    fills, with no pass over the worst case to zero the rest first."""
    return pl.pallas_call(
        _alloc_kernel, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        interpret=_interpret(), name="moe_rows_alloc")()


def _over_live_tiles(m: int, total, fill, init):
    """``fill(start, tile, carry)`` for the row tiles ``[start, start +
    tile)`` of an ``m``-row buffer that hold a live row, in order: a loop
    with a DYNAMIC trip count, ``ceil(total / tile)`` — XLA's own
    gathers, asked for the live rows only.  A last tile that would pass
    the buffer's end is taken from the end (the rows it shares with the
    tile before are written twice, the same)."""
    tile = min(ROW_TILE, m)
    return jax.lax.fori_loop(
        0, (total + tile - 1) // tile,
        lambda i, carry: fill(jnp.minimum(i * tile, m - tile), tile, carry),
        init)


def _weigh_call(dy, tok, total, w_sorted, out):
    def fill(start, tile, carry):
        d_out, d_w = carry
        piece = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, start, tile)
        in_group = (start + jnp.arange(tile)) < total
        dys = dy[piece(tok)].astype(jnp.float32)
        rows = jnp.where(in_group[:, None], dys * piece(w_sorted)[:, None],
                         0.0).astype(out.dtype)
        sums = jnp.where(
            in_group, jnp.sum(dys * piece(out).astype(jnp.float32), axis=-1),
            0.0)
        return (jax.lax.dynamic_update_slice(d_out, rows, (start, 0)),
                jax.lax.dynamic_update_slice(d_w, sums, (start,)))
    return _over_live_tiles(
        tok.shape[0], total, fill,
        (_uninitialized(out.shape, out.dtype),
         jnp.zeros((tok.shape[0],), jnp.float32)))


def take_rows_weighed(dy, tok, total, w_sorted, out):
    """The combine's backward on the live rows: with ``dys = dy[tok]``
    (``dy`` in ``out.dtype`` already), ``(dys * w_sorted[:, None])`` in
    ``out.dtype`` and float32 ``sum(dys * out, -1)``, both computed in
    float32 a live tile at a time.  Rows past ``total`` inside the last
    live tile are zeros; behind it the rows are UNDEFINED and the sums
    zero.  ``w_sorted`` float32 [m], ``out`` [m, h] ->
    ``([m, h], float32 [m])``."""
    return _replicated(_weigh_call, 5)(dy, tok, total, w_sorted, out)


# -- rows back -----------------------------------------------------------------

def _pack_kernel(meta, x_ref, o_ref):
    @pl.when(pl.program_id(0) < meta[0])
    def _work():
        o_ref[:, 0, :] = _to_words(x_ref[...])


def _pack_live(src, total):
    """``src`` [m, h] -> ``[m, 1, words]``, the live tiles only."""
    m, h = src.shape
    tile = min(PACK_TILE, m)
    words = jax.eval_shape(_to_words, jax.ShapeDtypeStruct((tile, h),
                                                           src.dtype))
    meta = ((total + tile - 1) // tile)[None]
    return pl.pallas_call(
        _pack_kernel,
        out_shape=jax.ShapeDtypeStruct((m, 1, words.shape[1]), words.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(m // tile,),
            in_specs=[pl.BlockSpec((tile, h), _row_map(1))],
            out_specs=pl.BlockSpec((tile, 1, words.shape[1]),
                                   _row_map(2))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="moe_rows_pack",
    )(meta, src)


def _sum_kernel(codes, meta, src_hbm, unsort_ref, *refs, tokens: int,
                k: int, steps: int, weigh: bool):
    """One tile of ``tokens`` token rows: its ``tokens * k`` pairs'
    sorted rows, the live ones, summed a token.  ``codes``: a tile's
    live pairs first (``_live_pairs``); ``meta``: the tiles' live counts,
    then the total."""
    if weigh:
        w_ref, o_ref, buf, sem = refs
    else:
        o_ref, buf, sem = refs
    i = pl.program_id(0)
    pairs = tokens * k
    total = meta[steps]

    def copies(step, slot, start: bool):
        base = step * pairs

        def pair(c, carry):
            # the wait only counts a row's bytes: any row's descriptor
            code = codes[base + c] if start else 0
            dma = pltpu.make_async_copy(src_hbm.at[code // pairs],
                                        buf.at[slot, code % pairs],
                                        sem.at[slot])
            dma.start() if start else dma.wait()
            return carry

        jax.lax.fori_loop(0, meta[step], pair, 0)

    slot = i % 2

    @pl.when(i == 0)
    def _first():
        copies(0, 0, True)

    @pl.when(i + 1 < steps)
    def _next():
        copies(i + 1, 1 - slot, True)

    copies(i, slot, False)
    # the buffer is slot-major: [k, tokens, h], and the slots' columns
    # of ``unsort`` / ``weights`` beside it.  One product and one
    # reduction over the slots, in slot order, as the gather's sum has
    # them (a chain of multiply-adds a slot would be contracted into
    # fused multiply-adds where the backend has them: another rounding)
    by_slot = lambda ref: jnp.stack(  # noqa: E731
        [ref[:, j:j + 1] for j in range(k)])
    rows = _from_words(buf[slot, :, 0, :]).reshape(k, tokens, -1)
    if weigh:
        rows = rows * by_slot(w_ref)
    # a dead slot's row was never fetched: masked, whatever the buffer
    # holds, not multiplied by zero
    acc = jnp.sum(jnp.where(by_slot(unsort_ref) < total, rows, 0.0), axis=0)
    o_ref[...] = acc.astype(o_ref.dtype)


def _live_pairs(unsort, total, tokens: int):
    """What the scalar core walks, a tile of ``tokens`` tokens at a time:
    ``codes`` int32 [tiles * tokens * k], a tile's LIVE pairs first (in
    pair order), each as ``sorted row * pairs + buffer row`` (the buffer
    is slot-major: pair ``(t, j)`` lands on row ``j * tokens + t``), and
    ``counts`` int32 [tiles], the live pairs of each tile — so the loop
    over a tile's pairs has as many turns as copies, and no branch."""
    n, k = unsort.shape
    pairs = tokens * k
    by_tile = unsort.reshape(n // tokens, pairs)
    held = by_tile < total
    # one sort carries a pair's index and its sorted row along
    _, q, rows = jax.lax.sort(
        ((~held).astype(jnp.int32),
         jax.lax.broadcasted_iota(jnp.int32, by_tile.shape, 1), by_tile),
        dimension=1, is_stable=True, num_keys=1)
    codes = rows * pairs + (q % k) * tokens + q // k
    return codes.reshape(-1), jnp.sum(held, axis=1, dtype=jnp.int32)


def _sum_call(src, unsort, total, weights=None, *, k: int, dtype):
    m, h = src.shape
    n = m // k
    tokens = max(8, SLOT_ROWS // k)
    n_pad = round_up(n, tokens)
    steps = n_pad // tokens
    total = total.astype(jnp.int32)
    m_rows = round_up(m, min(PACK_TILE, m))
    if m_rows != m:
        src = jnp.pad(src, ((0, m_rows - m), (0, 0)))
    packed = _pack_live(src, total)
    unsort = unsort.astype(jnp.int32).reshape(n, k)
    weigh = weights is not None
    if n_pad != n:
        # the padding's pairs are dead: past every total
        unsort = jnp.pad(unsort, ((0, n_pad - n), (0, 0)),
                         constant_values=m)
        if weigh:
            weights = jnp.pad(weights, ((0, n_pad - n), (0, 0)))
    codes, counts = _live_pairs(unsort, total, tokens)
    tile_of = lambda i, *_: (i, 0)  # noqa: E731
    slots = pl.BlockSpec((tokens, k), tile_of)
    got = pl.pallas_call(
        functools.partial(_sum_kernel, tokens=tokens, k=k, steps=steps,
                          weigh=weigh),
        out_shape=jax.ShapeDtypeStruct((n_pad, h), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # the tiles' live pairs, and their counts with the total
            num_scalar_prefetch=2,
            grid=(steps,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), slots]
            + ([slots] if weigh else []),
            out_specs=pl.BlockSpec((tokens, h), tile_of),
            scratch_shapes=[pltpu.VMEM((2, tokens * k) + packed.shape[1:],
                                       packed.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name="moe_rows_back",
    )(codes, jnp.concatenate([counts, total[None]]), packed, unsort,
      *([weights.astype(jnp.float32)] if weigh else []))
    return got[:n]


def sum_rows(src, unsort, total, weights=None, *, k: int, dtype):
    """``sum_j [w_j] src[unsort[t, j]]`` a token ``t`` over its slots
    ``j`` with ``unsort[t, j] < total``, float32 in slot order, as
    ``dtype``.  ``src`` [n * k, h] (bfloat16 or float32; its rows past
    ``total`` are never read), ``unsort`` int32 [n * k] (token-major:
    pair ``t * k + j``), ``weights`` float32 [n, k] or None -> [n, h].
    Row copies: ``total``, not ``n * k``."""
    fn = functools.partial(_sum_call, k=k, dtype=dtype)
    if weights is None:
        return _replicated(fn, 3)(src, unsort, total)
    return _replicated(fn, 4)(src, unsort, total, weights)
