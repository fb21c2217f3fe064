"""Block-table (paged) attention for the serving engine.

The serving KV cache (torchacc_tpu/serve/kv_cache.py) stores keys and
values in fixed-size BLOCKS inside one preallocated pool; each sequence
owns a BLOCK TABLE mapping its logical positions to pool blocks.  This
module computes attention of per-slot queries over that paged layout —
the vLLM PagedAttention computation expressed TPU-natively:

- ``_paged_attention_pallas``: a Pallas TPU kernel (one program per
  (slot, group of kv heads, kv block); the block table + context
  lengths ride as scalar-prefetch operands so each grid step's
  BlockSpec index map can address the pool block directly — no gather
  materialisation in HBM).  The q heads that share a kv head are
  stacked into the row dim, so a pool block is read once per kv head.
  Online softmax over the block sweep, exactly the flash-attention
  decomposition used by ops/flash_attention.py.
- ``_paged_attention_xla``: a pure-jnp gather path, numerically
  matched to ops/attention.attention_reference (f32 scores, NEG_INF
  mask, masked probabilities zeroed) — the correctness anchor the
  kernel is tested against and the path CPU runs take.

``impl`` selection follows ops/attn.py: 'auto' = pallas on TPU, xla
elsewhere; 'pallas' forces the kernel (interpret mode off-TPU);
'xla' forces the gather path.

Geometry: queries are ``[S, T, H, D]`` — S slots, T tokens per slot
(T=1 for decode, T=chunk for chunked prefill), already rope-rotated.
The pool is the WHOLE stack ``[L, NB, BS, KH*D]`` (layers, blocks, block
size, one token's row of all kv heads) plus a ``layer`` index: the
kernel's page BlockSpec addresses ``(layer, table[s, b], 0, head
group)`` in the one preallocated buffer, so the caller's layer loop
never slices a layer out of the stack (a slice of a 64 MiB layer pool is
a copy of it, and so is putting it back).  Rows are contiguous because
the WRITE is XLA's: a scatter of one token's ``[KH, D]`` into a
``[.., KH, BS, D]`` pool is strided by ``BS*D``, and layout assignment
then relayouts the whole pool for the scatter and back for the kernel;
a ``[KH*D]`` row at ``(layer, block, offset)`` is one contiguous
window, written in place.  A kv head is the lane slice
``[:, h*D:(h+1)*D]`` of a page's ``[BS, hb*D]`` block — a legal TPU
tile whenever ``block_size`` is a multiple of the dtype's sublane count
(:func:`min_block_size`) and some group of heads is a 128-lane slice of
the row or the whole row (:func:`heads_per_step`).  ``context_lens[s]``
counts ALL banked tokens of slot s including the T chunk tokens (the
cache write happens before the attention call), and ``q_start[s]`` is
the global position of the slot's first query row — causality is
``kv_pos <= q_start + t``.  Slots with ``context_lens == 0`` (free slots parked on the null block)
produce all-zero outputs.

Two shapes of the same kernel call, both decided from the call's own
arguments: under a LEFT window alone the block axis covers only the
blocks the slot's windows reach (``window_walk_blocks``), from the first
one on, so the table's entries before it are never read (a model that
mixes sliding and global grouped-query layers frees them,
serve/kv_cache.WindowBlocks); and a chunk whose rows exceed what one
step holds in VMEM runs as tiles of its queries (``query_tile``), each a
slot of the grid with its own first position.  With neither, the grid is
(slots, kv-head groups, every block of the table).
"""

from __future__ import annotations

import functools
import types
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from torchacc_tpu.ops._common import NEG_INF, interpret_mode as _interpret
from torchacc_tpu.ops._common import ambient_mesh, needs_shard_map
from torchacc_tpu.ops._common import on_tpu as _on_tpu
from torchacc_tpu.ops._common import round_up


def _repeat_kv_heads(x: jax.Array, num_q_heads: int) -> jax.Array:
    """[.., KH, D] -> [.., H, D] for GQA/MQA (same broadcast as
    ops/attention._repeat_kv, axis adjusted for the paged layout)."""
    kh = x.shape[-2]
    if kh == num_q_heads:
        return x
    assert num_q_heads % kh == 0, (num_q_heads, kh)
    return jnp.repeat(x, num_q_heads // kh, axis=-2)


def min_block_size(dtype) -> int:
    """Smallest ``block_size`` step the kernel tiles for a pool of
    ``dtype``: the TPU's sublane count for that width (8 rows of 32
    bits; narrower types pack 16 or 32 rows into a tile)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


# ---------------------------------------------------------------------------
# jnp gather path (the correctness anchor; runs everywhere)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "logit_softcap"))
def _paged_attention_xla(q, k_pool, v_pool, block_tables, context_lens,
                         q_start, layer, scale, window, logit_softcap):
    s_, t_, h, d = q.shape
    bs, kh = k_pool.shape[2], k_pool.shape[3] // d
    mb = block_tables.shape[1]
    # gather each slot's pages into a dense [S, MB*BS, ...] view; the
    # pool read is O(S * MB * BS) — fine for the reference, the kernel
    # never materialises this
    k = k_pool[layer, block_tables].reshape(s_, mb * bs, kh, d)
    v = v_pool[layer, block_tables].reshape(s_, mb * bs, kh, d)
    k = _repeat_kv_heads(k, h)
    v = _repeat_kv_heads(v, h)
    scores = jnp.einsum("sthd,skhd->shtk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if logit_softcap > 0.0:
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)
    kv_pos = jnp.arange(mb * bs, dtype=jnp.int32)            # [K]
    q_pos = q_start[:, None] + jnp.arange(t_, dtype=jnp.int32)  # [S, T]
    mask = kv_pos[None, None, :] < context_lens[:, None, None]
    mask &= kv_pos[None, None, :] <= q_pos[:, :, None]
    left, right = window
    if left >= 0:
        mask &= kv_pos[None, None, :] >= q_pos[:, :, None] - left
    if right >= 0:
        mask &= kv_pos[None, None, :] <= q_pos[:, :, None] + right
    mask = mask[:, None, :, :]                               # [S, 1, T, K]
    scores = jnp.where(mask, scores, NEG_INF)
    lse = jax.nn.logsumexp(scores, axis=-1)
    probs = jnp.where(mask, jnp.exp(scores - lse[..., None]), 0.0)
    out = jnp.einsum("shtk,skhd->sthd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _paged_fwd_kernel(tbl_ref, lens_ref, layer_ref, q_ref, k_ref, v_ref,
                      o_ref, m_scr, l_scr, acc_scr,
                      *, scale, block_size, head_dim, t_len, rows,
                      heads_per_step, num_kv_blocks, window, logit_softcap,
                      walk_window=False):
    si = pl.program_id(0)
    bi = pl.program_id(2)

    @pl.when(bi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = lens_ref[si, 0]
    q0 = lens_ref[si, 1]
    k_start = bi * block_size
    if walk_window:
        # the block axis covers the blocks the slot's windows reach and
        # no others: step bi is the bi-th block from the first one
        k_start += _first_window_block(q0, window[0], block_size) \
            * block_size

    @pl.when(k_start < ctx)
    def _compute():
        kv_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1)
        # row r holds q head (r // T) of the group at chunk token r % T
        q_pos = q0 + jax.lax.rem(jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 0), t_len)
        mask = (kv_pos < ctx) & (kv_pos <= q_pos)
        left, right = window
        if left >= 0:
            mask &= kv_pos >= q_pos - left
        if right >= 0:
            mask &= kv_pos <= q_pos + right
        for hi in range(heads_per_step):
            # a kv head is a lane slice of the page's [BS, hb*D] rows
            lanes = slice(hi * head_dim, (hi + 1) * head_dim)
            q = q_ref[0, hi]                                # [R, D]
            k = k_ref[:, lanes]                             # [BS, D]
            v = v_ref[:, lanes]                             # [BS, D]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [R, BS]
            if logit_softcap > 0.0:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[hi, :, 0]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
            p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            alpha = jnp.where(m_prev == NEG_INF, 0.0, alpha)
            l_scr[hi] = jnp.broadcast_to(
                (alpha * l_scr[hi, :, 0] + jnp.sum(p, axis=1))[:, None],
                l_scr.shape[1:])
            acc_scr[hi] = acc_scr[hi] * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[hi] = jnp.broadcast_to(m_new[:, None], m_scr.shape[1:])

    @pl.when(bi == num_kv_blocks - 1)
    def _finalize():
        for hi in range(heads_per_step):
            l = l_scr[hi, :, 0]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, hi] = (acc_scr[hi] / l_safe[:, None]).astype(
                o_ref.dtype)


def _first_window_block(q_start, left, block_size):
    """The logical block that holds the first position the window of a
    query at ``q_start`` reaches."""
    return jnp.maximum(q_start - left, 0) // block_size


def window_walk_blocks(left: int, t: int, block_size: int) -> int:
    """Blocks that ``t`` consecutive queries with a left window of
    ``left`` positions can touch: ``left + t`` positions in a row lie in
    at most one block more than they fill."""
    return (left + t + block_size - 2) // block_size + 1


_LANES = 128
# What one grid step may hold in VMEM.  The compiler's scoped default on
# the chips this targets is 16 MiB; the rest is left to its own
# temporaries.
_VMEM_BUDGET = 10 * 1024 * 1024


def heads_per_step(num_heads: int, kv_heads: int, head_dim: int,
                   block_size: int, t: int, dtype) -> int:
    """How many kv heads one grid step of the kernel takes for ``t``
    query tokens a slot, or ``ValueError`` where the kernel cannot tile
    the geometry (serve/scheduler.PagedDecoder asks at construction).

    It is the largest divisor ``hb`` of ``kv_heads`` whose page block
    ``[block_size, hb*head_dim]`` is a legal tile of the pool's rows
    (``hb*head_dim`` a multiple of 128 lanes, or the whole row) and
    whose blocks fit the VMEM budget: q and out blocks [rows, d] and the
    k/v pages [bs, d] double-buffered, the f32 m/l/acc scratch, and the
    [rows, bs] f32 score temporaries (one head's worth — heads run one
    after another inside a step)."""
    kh, d, bs = kv_heads, head_dim, block_size
    itemsize = jnp.dtype(dtype).itemsize
    if bs % min_block_size(dtype):
        raise ValueError(
            f"paged attention kernel: block_size {bs} is not a multiple "
            f"of {min_block_size(dtype)}, the TPU sublane tile of a "
            f"{jnp.dtype(dtype).name} pool")
    rows = (num_heads // kh) * t
    lanes_d = max(d, _LANES)
    per_head = (2 * 2 * rows * lanes_d * itemsize            # q, out
                + 2 * 2 * bs * lanes_d * itemsize            # k, v
                + rows * (2 * _LANES + lanes_d) * 4)         # m, l, acc
    temps = 3 * rows * max(bs, _LANES) * 4
    for hb in range(kh, 0, -1):
        if (kh % hb == 0 and (hb == kh or (hb * d) % _LANES == 0)
                and hb * per_head + temps <= _VMEM_BUDGET):
            return hb
    raise ValueError(
        f"paged attention kernel: no group of the {kh} kv heads of "
        f"head_dim {d} ({rows} q rows a head, block_size {bs}) is both a "
        f"{_LANES}-lane slice of the pool's rows (or the whole row) and "
        f"within the {_VMEM_BUDGET / 2**20:.0f} MiB VMEM budget (one "
        f"head's blocks take {(per_head + temps) / 2**20:.1f} MiB) — "
        f"lower serve.prefill_chunk or serve.block_size")


def query_tile(num_heads: int, kv_heads: int, head_dim: int,
               block_size: int, t: int, dtype) -> int:
    """Tokens of a slot one call of the kernel takes at a time: ``t``
    where a step's blocks for all of them fit (:func:`heads_per_step`),
    else the largest ``t / 2**k`` that does — a chunk of 512 tokens
    under 8 query heads a kv head is 4,096 rows a head, more than a
    step holds.  ``ValueError`` (the whole ``t``'s) where none does."""
    tq, first = t, None
    while True:
        try:
            heads_per_step(num_heads, kv_heads, head_dim, block_size, tq,
                           dtype)
            return tq
        except ValueError as e:
            first = first or e
            if tq % 2:
                raise first
            tq //= 2


def _paged_attention_pallas(q, k_pool, v_pool, block_tables, context_lens,
                            q_start, layer, scale, window, logit_softcap,
                            name="paged_attention"):
    s_, t_, h, d = q.shape
    bs, kh = k_pool.shape[2], k_pool.shape[3] // d
    mb = block_tables.shape[1]
    group = h // kh
    tq = query_tile(h, kh, d, bs, t_, k_pool.dtype)
    if tq < t_:
        # a chunk too tall for one step runs as tiles of tq tokens, each
        # a slot of its own: the slot's table, its own first position,
        # the context cut to its causal reach (the blocks past it are
        # skipped, not multiplied and masked)
        nt = t_ // tq
        starts = (q_start[:, None]
                  + tq * jnp.arange(nt, dtype=jnp.int32)).reshape(-1)
        out = _paged_attention_pallas(
            q.reshape(s_ * nt, tq, h, d), k_pool, v_pool,
            jnp.repeat(block_tables, nt, axis=0),
            jnp.minimum(jnp.repeat(context_lens, nt), starts + tq), starts,
            layer, scale, window, logit_softcap, name)
        return out.reshape(s_, t_, h, d)
    # a left window alone: the block axis is the blocks the slot's
    # windows reach, from the first one on — the table's entries before
    # it are never read (serve/kv_cache.WindowBlocks frees them), and a
    # long context costs a sliding layer no more steps than a short one
    walk_window = window[0] >= 0 and window[1] < 0
    nb = (min(mb, window_walk_blocks(window[0], t_, bs)) if walk_window
          else mb)
    # three scalar-prefetch operands: the block table, lens = [S, 2]
    # (context_len, q_start) and the layer index, so every BlockSpec
    # index map can address the page for (layer, slot, kv-block) in the
    # stacked pool before the body runs
    lens = jnp.stack([context_lens.astype(jnp.int32),
                      q_start.astype(jnp.int32)], axis=1)
    layer = layer.reshape(1)
    # stack each kv head's q group into the row dim: [S, KH, G*T, D]
    rows = group * t_
    qg = q.reshape(s_, t_, kh, group, d).transpose(0, 2, 3, 1, 4).reshape(
        s_, kh, rows, d)
    hb = heads_per_step(h, kh, d, bs, t_, k_pool.dtype)

    q_spec = pl.BlockSpec((1, hb, rows, d),
                          lambda s, g, b, tbl, lens, layer: (s, g, 0, 0))
    if walk_window:
        def page(s, g, b, tbl, lens, layer):
            first = _first_window_block(lens[s, 1], window[0], bs)
            return (layer[0], tbl[s, jnp.minimum(first + b, mb - 1)], 0, g)
    else:
        def page(s, g, b, tbl, lens, layer):
            return (layer[0], tbl[s, b], 0, g)
    kv_spec = pl.BlockSpec((None, None, bs, hb * d), page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_, kh // hb, nb),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hb, rows, _LANES), jnp.float32),
            pltpu.VMEM((hb, rows, _LANES), jnp.float32),
            pltpu.VMEM((hb, rows, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_fwd_kernel, scale=scale, block_size=bs, head_dim=d, t_len=t_,
        rows=rows, heads_per_step=hb, num_kv_blocks=nb, window=window,
        logit_softcap=logit_softcap, walk_window=walk_window)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name=name,
    )(block_tables.astype(jnp.int32), lens, layer, qg, k_pool, v_pool)
    return out.reshape(s_, kh, group, t_, d).transpose(0, 3, 1, 2, 4).reshape(
        s_, t_, h, d)


def _paged_attention_pallas_sharded(mesh, q, k_pool, v_pool, block_tables,
                                    context_lens, q_start, layer, *static):
    """The kernel per shard of ``mesh``: GSPMD cannot partition a Mosaic
    kernel.  Heads split over 'tp' where it divides the kv heads — a
    shard of the pool's rows is then a group of whole heads (the layout
    serve/kv_cache.make_pools gives the pool); slots, tables, lengths
    and the layer index are replicated."""
    tp = (1 if "tp" in mesh.manual_axes else int(mesh.shape.get("tp", 1)))
    kh = k_pool.shape[3] // q.shape[3]
    h_axis = "tp" if tp > 1 and kh % tp == 0 else None
    q_spec = P(None, None, h_axis, None)
    pool_spec = P(None, None, None, h_axis)
    return jax.shard_map(
        lambda *a: _paged_attention_pallas(*a, *static), mesh=mesh,
        in_specs=(q_spec, pool_spec, pool_spec, P(), P(), P(), P()),
        out_specs=q_spec, check_vma=False,
    )(q, k_pool, v_pool, block_tables, context_lens, q_start, layer)


# ---------------------------------------------------------------------------
# latent (MLA, absorbed form) paged attention: one shared row a token
# ---------------------------------------------------------------------------
#
# The pool is ONE stack [L, NB, BS, W]: a token's row is [c_kv | k_pe]
# (the latent of ``R`` lanes that is key AND value, then the rotated
# shared key of ``P`` lanes; W = R + P).  Every query head reads the same
# row: scores are ``q_lat . row[:R] + q_pe . row[R:]`` and the output is
# ``P row[:R]`` — multi-query attention whose value is the first R lanes
# of its key.  Query rows of a slot are tiled (``tq`` tokens x all heads
# a grid step, token-major, so the tiling is a reshape), which is what
# lets a prefill chunk of 512 tokens x 64 heads through the same kernel
# as a decode step.
#
# The grid is (slots, query tiles); the PAGE WALK is a loop inside the
# kernel.  The pool stays in HBM and a tile copies the pages it can see
# itself — from the first block its window reaches (block 0 without one)
# to its causal reach within the slot's length, a bound read at run time
# — ``pages`` of them a step into one of two VMEM buffers, the next
# group's copies in flight while this group is multiplied (and behind a
# tile's last group the first group of the tile after it: the grid runs
# in order).  So a tile early in a long table walks its own few pages
# and a slot that holds nothing walks none (its output is zeros); a step
# is one online-softmax update over ``pages * BS`` positions: one row
# max, one exp, one rescale of the [rows, R] accumulator.  Pages of the
# last group past the reach are masked like positions past the length,
# and their buffer rows are zeroed instead of fetched (a masked weight
# of 0 times a stale NaN in the value product would be NaN).
# ``latent_query_tile`` sizes ``tq`` and ``pages`` from the shapes of
# the call.
#
# Two more shapes of the same attention (a model that mixes a learned
# sparse selection with windowed latent layers, models/mla.py):
#
# - ``window`` >= 0: a query at position t sees ``[t - window, t]``; the
#   walk starts at the tile's first live block, so the table's entries
#   before the window are never read (serve/kv_cache.py frees them);
# - ``selection = (scores, thr, tie_hi)``: query (s, t) attends position
#   p iff ``scores[s, t, p] > thr[s, t]``, or ``== thr`` and ``p <=
#   tie_hi[s, t]`` (:func:`select_topk`: exactly the k best, ties to the
#   lower position).  The kernel walks the tile's live pages as before,
#   fetches the tile's scores of each page beside it and masks what is
#   not selected: it reads whole pages, not the selected rows alone.  For
#   prefill chunks that is settled by a count: each query has its own
#   2,048 rows (they are shared by its heads, not by its neighbours), so
#   a row gather issues one 1,280-byte copy a (query, selected position)
#   pair — 1.05G copies a 51 s window of the long-context cell against
#   the 28.6 s the masked walk took before it moved into the kernel, 27
#   ns a copy to break even, index compaction included (ISSUE 31; PERF.md
#   section 6).  A decode step's one query a slot is the gather's case
#   (ROADMAP S1).

@functools.partial(jax.jit, static_argnames=("scale", "window"))
def _latent_paged_attention_xla(q_lat, q_pe, pool, block_tables,
                                context_lens, q_start, layer, selection=None,
                                *, scale, window=-1):
    s_, t_, h, r = q_lat.shape
    bs = pool.shape[2]
    mb = block_tables.shape[1]
    rows = pool[layer, block_tables].reshape(s_, mb * bs, -1).astype(
        jnp.float32)
    scores = (jnp.einsum("sthr,skr->shtk", q_lat.astype(jnp.float32),
                         rows[..., :r])
              + jnp.einsum("sthp,skp->shtk", q_pe.astype(jnp.float32),
                           rows[..., r:r + q_pe.shape[-1]])) * scale
    kv_pos = jnp.arange(mb * bs, dtype=jnp.int32)
    q_pos = q_start[:, None] + jnp.arange(t_, dtype=jnp.int32)
    mask = kv_pos[None, None, :] < context_lens[:, None, None]
    mask &= kv_pos[None, None, :] <= q_pos[:, :, None]
    if window >= 0:
        mask &= kv_pos[None, None, :] >= q_pos[:, :, None] - window
    if selection is not None:
        sel_scores, thr, tie_hi = selection
        mask &= (sel_scores > thr[..., None]) | (
            (sel_scores == thr[..., None])
            & (kv_pos[None, None, :] <= tie_hi[..., None]))
    mask = mask[:, None, :, :]
    scores = jnp.where(mask, scores, NEG_INF)
    lse = jax.nn.logsumexp(scores, axis=-1)
    probs = jnp.where(mask, jnp.exp(scores - lse[..., None]), 0.0)
    out = jnp.einsum("shtk,skr->sthr", probs, rows[..., :r])
    return out.astype(q_lat.dtype)


def _latent_fwd_kernel(tbl_ref, lens_ref, layer_ref, ql_ref, qp_ref, *rest,
                       scale, block_size, latent, rope, heads, tq, pages,
                       window=-1, select=False):
    if select:
        (sc_hbm, thr_ref, tie_ref, pool_hbm, o_ref, m_scr, l_scr, acc_scr,
         kv_buf, kv_sem, slot_ref, sc_buf, sc_sem) = rest
    else:
        (pool_hbm, o_ref, m_scr, l_scr, acc_scr, kv_buf, kv_sem,
         slot_ref) = rest
    si, ti = pl.program_id(0), pl.program_id(1)
    ns, nt = pl.num_programs(0), pl.num_programs(1)
    bs, span = block_size, pages * block_size
    last_entry = tbl_ref.shape[1] - 1
    layer = layer_ref[0]

    def walk(s, t):
        """The pages tile ``t`` of slot ``s`` can see: from the block
        its window reaches (block 0 without one) to its causal reach
        within the slot's length, ``groups`` steps of ``pages``."""
        ctx = lens_ref[s, 0]
        q0 = lens_ref[s, 1] + t * tq        # the tile's first position
        reach = jnp.minimum(ctx, q0 + tq)
        first = jnp.maximum(q0 - window, 0) // bs if window >= 0 else 0
        groups = jnp.maximum((reach - first * bs + span - 1) // span, 0)
        return types.SimpleNamespace(s=s, t=t, ctx=ctx, q0=q0, reach=reach,
                                     first=first, groups=groups)

    def page_dmas(tile, g, j, slot):
        """The copies of page ``j`` of a tile's group ``g`` into buffer
        ``slot``: the pool page by its table entry and, under a
        selection, the tile's scores of the page's positions."""
        entry = jnp.minimum(tile.first + g * pages + j, last_entry)
        dmas = [pltpu.make_async_copy(
            pool_hbm.at[layer, tbl_ref[tile.s, entry]],
            kv_buf.at[slot, pl.ds(pl.multiple_of(j * bs, bs), bs)],
            kv_sem.at[slot])]
        if select:
            dmas.append(pltpu.make_async_copy(
                sc_hbm.at[tile.s, tile.t, :,
                          pl.ds(pl.multiple_of(entry * bs, bs), bs)],
                sc_buf.at[slot, j], sc_sem.at[slot]))
        return dmas

    def live(tile, g):
        """Pages of a tile's group ``g`` inside its reach (the rest of
        the group is dead: masked, and never fetched)."""
        left = tile.reach - (tile.first + g * pages) * bs
        return jnp.clip((left + bs - 1) // bs, 0, pages)

    # loops over the pages, not Python ones: a kernel's trace holds each
    # body once (per-page conditionals made tracing a program cost
    # seconds, and a warm set-up pays the trace)
    def start(tile, g, slot):
        def fetch(j, carry):
            for dma in page_dmas(tile, g, j, slot):
                dma.start()
            return carry

        # a dead page must still be finite: a masked weight of 0 times a
        # stale NaN in the value product is NaN
        def clear(j, carry):
            kv_buf[slot, pl.ds(pl.multiple_of(j * bs, bs), bs), :] = \
                jnp.zeros((bs, kv_buf.shape[2]), kv_buf.dtype)
            return carry

        n = live(tile, g)
        jax.lax.fori_loop(0, n, fetch, 0)
        jax.lax.fori_loop(n, pages, clear, 0)

    def wait(tile, g, slot):
        def arrived(j, carry):
            for dma in page_dmas(tile, g, j, slot):
                dma.wait()
            return carry

        jax.lax.fori_loop(0, live(tile, g), arrived, 0)

    def either(pick, a, b):
        return types.SimpleNamespace(**{
            k: jnp.where(pick, v, getattr(b, k)) for k, v in vars(a).items()})

    here = walk(si, ti)
    groups = here.groups
    # the grid runs in order, so the tile after this one is known: its
    # first group is fetched behind this tile's last, into the buffer
    # ``slot_ref`` hands on
    wrap = ti + 1 >= nt
    s_after = jnp.where(wrap, si + 1, si)
    after = walk(jnp.minimum(s_after, ns - 1), jnp.where(wrap, 0, ti + 1))
    more = (s_after < ns) & (after.groups > 0)
    boot = (si == 0) & (ti == 0)

    @pl.when(boot)
    def _first_tile():
        slot_ref[0] = 0

    base = slot_ref[0]
    none = groups == 0

    # nobody fetched the very first tile's first group; and a tile that
    # walks nothing hands the next tile's first group on from here
    @pl.when((boot & jnp.logical_not(none)) | (none & more))
    def _first_group():
        start(either(none, after, here), 0, base)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(g, carry):
        slot = jax.lax.rem(base + g, 2)
        last = g + 1 == groups

        @pl.when(jnp.logical_not(last) | more)
        def _next_group():
            start(either(last, after, here), jnp.where(last, 0, g + 1),
                  1 - slot)

        wait(here, g, slot)
        # what each token of the tile may attend, [tq, span]: every head
        # of a token shares it
        pos = (here.first + g * pages) * bs + jax.lax.broadcasted_iota(
            jnp.int32, (tq, span), 1)
        q_pos = here.q0 + jax.lax.broadcasted_iota(
            jnp.int32, (tq, span), 0)
        seen = (pos < here.ctx) & (pos <= q_pos)
        if window >= 0:
            seen &= pos >= q_pos - window
        if select:
            sc = jnp.concatenate(
                [sc_buf[slot, j] for j in range(pages)], axis=1)
            seen &= (sc > thr_ref[0, 0]) | (
                (sc == thr_ref[0, 0]) & (pos <= tie_ref[0, 0]))
        # token-major rows: row r holds head r % heads of token r // heads
        seen = seen.astype(jnp.float32)
        mask = jnp.concatenate(
            [jnp.broadcast_to(seen[i:i + 1], (heads, span))
             for i in range(tq)], axis=0) > 0.0
        c_kv = kv_buf[slot, :, :latent]                       # [span, R]
        k_pe = kv_buf[slot, :, latent:latent + rope]          # [span, P]
        s = (jax.lax.dot_general(
            ql_ref[0, 0], c_kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
             + jax.lax.dot_general(
            qp_ref[0, 0], k_pe, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)) * scale      # [rows, span]
        s = jnp.where(mask, s, NEG_INF)
        # one online-softmax update a group; m and l stay [rows, 1]
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row that has seen nothing yet (m = NEG_INF) is shifted by 0:
        # exp(NEG_INF) is 0, as exp(NEG_INF - m) is for every masked
        # score of a row that has; alpha of such a row scales zeros
        p = jnp.exp(s - jnp.where(m_new == NEG_INF, 0.0, m_new))
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(c_kv.dtype), c_kv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        return carry

    jax.lax.fori_loop(0, groups, step, 0)
    slot_ref[0] = jax.lax.rem(base + groups, 2)
    l = l_scr[...]
    o_ref[0, 0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype)


# What a step of the latent kernel may hold in VMEM and what the call
# asks of the compiler for it (its scoped default is 16 MiB of a v5e's
# 128): a group of pages and its float32 score tiles are the step's bulk.
_LATENT_VMEM_BUDGET = 40 * 1024 * 1024
_LATENT_VMEM_LIMIT = 64 * 1024 * 1024


def latent_vmem_bytes(rows: int, pages: int, latent: int, rope: int,
                      block_size: int, itemsize: int, select: bool) -> int:
    """VMEM one step of the latent kernel holds for ``rows`` query rows
    and ``pages`` pool pages: q and out [rows, R] and q_pe [rows, P]
    double-buffered, the f32 m/l/acc scratch, two groups of pages (and
    of their scores under a selection) and the [rows, pages * bs] float32
    score tiles."""
    lat, pe = round_up(latent, _LANES), round_up(rope, _LANES)
    span = pages * max(block_size, _LANES)
    return (2 * rows * (2 * lat + pe) * itemsize
            + rows * (2 * _LANES + lat) * 4
            + 2 * pages * block_size * round_up(latent + rope, _LANES)
            * itemsize
            + (2 * 8 * span * 4 if select else 0)
            + (5 if select else 4) * rows * span * 4)


def latent_query_tile(num_heads: int, latent: int, rope: int,
                      block_size: int, t: int, dtype,
                      select: bool = False,
                      max_blocks: Optional[int] = None,
                      window: int = -1) -> Tuple[int, int]:
    """``(tq, pages)``: the query tokens one grid step of the latent
    kernel takes of ``t`` a slot (all heads of each) and the pool pages
    one step of its page walk takes, or ``ValueError`` where nothing
    fits.  Both follow what the call can see.  ``tq`` is the largest
    divisor of ``t`` whose step fits the VMEM budget
    (:func:`latent_vmem_bytes`; rows = tile tokens x heads).  ``pages``
    is a power of two, at most 8 and at most a third of the longest
    walk a tile can make — the table's ``max_blocks``, or the blocks a
    ``window`` and the tile span: a walk's last group is multiplied
    whole, its dead pages masked, so a long group is wasted on a short
    walk (on a v5e 8 pages a step beat 4 by 13% over 33k-token tables
    and lost 20% to 2 over a window's 6 blocks; PERF.md section 6,
    PR 31)."""
    itemsize = jnp.dtype(dtype).itemsize
    if block_size % min_block_size(dtype):
        raise ValueError(
            f"latent paged attention kernel: block_size {block_size} is "
            f"not a multiple of {min_block_size(dtype)}, the TPU sublane "
            f"tile of a {jnp.dtype(dtype).name} pool")
    need = functools.partial(
        latent_vmem_bytes, latent=latent, rope=rope, block_size=block_size,
        itemsize=itemsize, select=select)
    for tq in range(t, 0, -1):
        rows = tq * num_heads
        # the mask unrolls over the tile's tokens: at most 8 of them
        # under a selection (its score tile has 8 sublanes), 32 without
        if t % tq or (rows % 8 and tq != t) or tq > (8 if select else 32):
            continue
        walk = max_blocks or 24
        if window >= 0:
            walk = min(walk, -(-(window + tq) // block_size) + 1)
        pages = 1
        while pages < 8 and pages * 2 <= walk // 3 \
                and need(rows, pages * 2) <= _LATENT_VMEM_BUDGET:
            pages *= 2
        if need(rows, pages) <= _LATENT_VMEM_BUDGET:
            return tq, pages
    raise ValueError(
        f"latent paged attention kernel: {num_heads} heads of a "
        f"{latent}+{rope} row do not fit the "
        f"{_LATENT_VMEM_BUDGET / 2**20:.0f} MiB VMEM budget even one token "
        f"and one page a step ({need(num_heads, 1) / 2**20:.1f} MiB)")


def _latent_paged_attention_pallas(q_lat, q_pe, pool, block_tables,
                                   context_lens, q_start, layer,
                                   selection=None, *, scale, window=-1,
                                   name="latent_paged_attention"):
    s_, t_, h, r = q_lat.shape
    pe = q_pe.shape[-1]
    bs, w = pool.shape[2], pool.shape[3]
    mb = block_tables.shape[1]
    select = selection is not None
    tq, pages = latent_query_tile(h, r, pe, bs, t_, pool.dtype, select, mb,
                                  window)
    nt, rows = t_ // tq, tq * h
    lens = jnp.stack([context_lens.astype(jnp.int32),
                      q_start.astype(jnp.int32)], axis=1)
    layer = layer.reshape(1)
    # token-major rows: tiling the slot's [T, H, .] queries is a reshape
    ql = q_lat.reshape(s_, nt, rows, r)
    qp = q_pe.reshape(s_, nt, rows, pe)

    q_map = lambda s, t, tbl, lens, layer: (s, t, 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, 1, rows, r), q_map),
                pl.BlockSpec((1, 1, rows, pe), q_map)]
    operands = [ql, qp]
    # m, l, acc; two groups of pages with a DMA semaphore each; the
    # buffer the tile's first group is in
    scratch = [pltpu.VMEM((rows, 1), jnp.float32),
               pltpu.VMEM((rows, 1), jnp.float32),
               pltpu.VMEM((rows, r), jnp.float32),
               pltpu.VMEM((2, pages * bs, w), pool.dtype),
               pltpu.SemaphoreType.DMA((2,)),
               pltpu.SMEM((1,), jnp.int32)]
    if select:
        sel_scores, thr, tie_hi = selection
        # the scores stay in HBM beside the pool: the walk copies the
        # tile's [tq, bs] of each page it fetches
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec((1, 1, tq, 1), q_map),
                     pl.BlockSpec((1, 1, tq, 1), q_map)]
        operands += [sel_scores.reshape(s_, nt, tq, mb * bs),
                     thr.reshape(s_, nt, tq, 1).astype(jnp.float32),
                     tie_hi.reshape(s_, nt, tq, 1).astype(jnp.int32)]
        scratch += [pltpu.VMEM((2, pages, tq, bs), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_, nt),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, rows, r), q_map),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(
        _latent_fwd_kernel, scale=scale, block_size=bs, latent=r, rope=pe,
        heads=h, tq=tq, pages=pages, window=window, select=select)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(ql.shape, q_lat.dtype),
        # in order: a tile fetches the first pages of the one after it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_LATENT_VMEM_LIMIT),
        interpret=_interpret(),
        name=name,
    )(block_tables.astype(jnp.int32), lens, layer, *operands, pool)
    return out.reshape(s_, t_, h, r)


def latent_paged_attention(
    q_lat: jax.Array,
    q_pe: jax.Array,
    pool: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    q_start: jax.Array,
    *,
    layer,
    scale: float,
    impl: str = "auto",
    window: int = -1,
    selection=None,
    name: str = "latent_paged_attention",
) -> jax.Array:
    """Causal absorbed-form MLA attention over layer ``layer`` of a
    latent paged pool.

    ``q_lat`` [S, T, H, R] (queries with ``W_kvb^K`` folded in), ``q_pe``
    [S, T, H, P] (rotated); ``pool`` [layers, num_blocks, block_size, W]
    with a token's row ``[c_kv (R) | rope(k_pe) (P) | padding]``.
    Tables, lengths, ``q_start`` and ``impl`` as
    :func:`paged_attention`.  ``window`` >= 0 bounds a query at t to
    ``[t - window, t]``; ``selection`` ``(scores [S, T, MB * BS], thr
    [S, T], tie_hi [S, T])`` to the positions :func:`select_topk` chose;
    ``name`` is the kernel's instruction name (a profile reads the
    variants apart by it).  Returns the latent outputs ``P c_kv``
    [S, T, H, R]; the caller applies ``W_kvb^V``."""
    if q_lat.ndim != 4 or q_pe.shape[:3] != q_lat.shape[:3]:
        raise ValueError(f"q_lat {q_lat.shape} / q_pe {q_pe.shape} must be "
                         f"[slots, t, heads, R] and [slots, t, heads, P]")
    s_ = q_lat.shape[0]
    if pool.ndim != 4 or pool.shape[3] < q_lat.shape[3] + q_pe.shape[3]:
        raise ValueError(
            f"pool {pool.shape} must be [layers, blocks, block_size, W] "
            f"with W >= {q_lat.shape[3]} + {q_pe.shape[3]}")
    if block_tables.shape[0] != s_ or context_lens.shape != (s_,):
        raise ValueError(
            f"block_tables {block_tables.shape} / context_lens "
            f"{context_lens.shape} do not match {s_} slots")
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"impl must be auto|pallas|xla, got {impl!r}")
    fn = (functools.partial(_latent_paged_attention_pallas, name=name)
          if impl == "pallas" else _latent_paged_attention_xla)
    fn = functools.partial(fn, scale=float(scale), window=int(window))
    args = (q_lat, q_pe, pool, block_tables.astype(jnp.int32),
            context_lens.astype(jnp.int32), q_start.astype(jnp.int32),
            jnp.asarray(layer, jnp.int32))
    if selection is not None:
        args += (tuple(selection),)
    mesh = ambient_mesh()
    if impl == "pallas" and needs_shard_map(mesh):
        # one shared row for every head: nothing to split, each shard
        # runs the whole call
        fn = jax.shard_map(fn, mesh=mesh, in_specs=(P(),) * len(args),
                           out_specs=P(), check_vma=False)
    return fn(*args)


# ---------------------------------------------------------------------------
# the learned selection in front of latent attention: indexer scores over
# a paged pool of index keys, and the exact k best of them
# ---------------------------------------------------------------------------
#
# A token banks ONE index key of ``dI`` lanes in the pool [L, NB, BS, dI]
# beside its latent row (same block, same offset).  A query's score of a
# cached position is ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
# over the indexer's ``nI`` heads; the [T, nI, ctx] products exist only
# in VMEM, a page at a time — what reaches HBM is I, one float32 a
# (query, position), NEG_INF where the position is not visible.

@jax.jit
def _indexer_scores_xla(q_idx, w, pool, block_tables, context_lens,
                        q_start, layer):
    s_, t_ = q_idx.shape[:2]
    bs, mb = pool.shape[2], block_tables.shape[1]
    keys = pool[layer, block_tables].reshape(s_, mb * bs, -1)
    prod = jnp.einsum("stjd,skd->stjk", q_idx.astype(jnp.float32),
                      keys.astype(jnp.float32))
    scores = jnp.sum(jnp.maximum(prod, 0.0) * w[..., None], axis=2)
    kv_pos = jnp.arange(mb * bs, dtype=jnp.int32)
    q_pos = q_start[:, None] + jnp.arange(t_, dtype=jnp.int32)
    mask = ((kv_pos[None, None, :] < context_lens[:, None, None])
            & (kv_pos[None, None, :] <= q_pos[:, :, None]))
    return jnp.where(mask, scores, NEG_INF)


def _indexer_kernel(tbl_ref, lens_ref, layer_ref, q_ref, w_ref, k_ref, o_ref,
                    *, block_size, heads, tq):
    si, ti, bi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ctx = lens_ref[si, 0]
    q0 = lens_ref[si, 1] + ti * tq
    k_start = bi * block_size
    live = (k_start < ctx) & (k_start <= q0 + tq - 1)

    @pl.when(live)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [tq * nI, BS]
        s = jnp.maximum(s, 0.0) * w_ref[0, 0]              # [rows, 1] weights
        # rows are token-major: a token's heads are `heads` adjacent rows
        tok = jnp.concatenate(
            [jnp.sum(s[i * heads:(i + 1) * heads], axis=0, keepdims=True)
             for i in range(tq)], axis=0)                  # [tq, BS]
        kv_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (tq, block_size), 1)
        q_pos = q0 + jax.lax.broadcasted_iota(
            jnp.int32, (tq, block_size), 0)
        o_ref[0, 0] = jnp.where((kv_pos < ctx) & (kv_pos <= q_pos), tok,
                                NEG_INF)

    @pl.when(jnp.logical_not(live))
    def _fill():
        o_ref[0, 0] = jnp.full((tq, block_size), NEG_INF, jnp.float32)


def index_query_tile(heads: int, dim: int, block_size: int, t: int,
                     dtype) -> int:
    """Query tokens one grid step of the indexer kernel takes of ``t`` a
    slot, or ``ValueError``: the largest divisor of ``t``, at most 32
    (the head sum unrolls over the tile's tokens), whose blocks — q
    [rows, dI] and the weights [rows, 1] (a lane-padded column)
    double-buffered, the key page, the [rows, bs] float32 products and
    the [tq, bs] output — fit the VMEM budget (rows = tokens x heads)."""
    itemsize = jnp.dtype(dtype).itemsize
    if block_size % min_block_size(dtype):
        raise ValueError(
            f"indexer kernel: block_size {block_size} is not a multiple "
            f"of {min_block_size(dtype)}, the TPU sublane tile of a "
            f"{jnp.dtype(dtype).name} pool")
    lanes = round_up(dim, _LANES)
    bsl = max(block_size, _LANES)

    def need(tq):
        rows = tq * heads
        return (2 * rows * lanes * itemsize + 2 * rows * _LANES * 4
                + 3 * rows * bsl * 4 + 2 * block_size * lanes * itemsize
                + 2 * max(tq, 8) * bsl * 4)
    for tq in range(min(t, 32), 0, -1):
        if t % tq == 0 and (tq % 8 == 0 or tq == t) \
                and need(tq) <= _VMEM_BUDGET:
            return tq
    raise ValueError(
        f"indexer kernel: {heads} heads of {dim} do not fit the "
        f"{_VMEM_BUDGET / 2**20:.0f} MiB VMEM budget in any tile of {t} "
        f"query tokens")


def _indexer_scores_pallas(q_idx, w, pool, block_tables, context_lens,
                           q_start, layer):
    s_, t_, nh, d = q_idx.shape
    bs, mb = pool.shape[2], block_tables.shape[1]
    tq = index_query_tile(nh, d, bs, t_, pool.dtype)
    nt, rows = t_ // tq, tq * nh
    lens = jnp.stack([context_lens, q_start], axis=1)

    def page(s, t, b, tbl, lens, layer):
        reach = jnp.minimum(lens[s, 0], lens[s, 1] + (t + 1) * tq)
        last = jnp.maximum(reach - 1, 0) // bs
        return (layer[0], tbl[s, jnp.minimum(b, last)], 0, 0)

    q_map = lambda s, t, b, tbl, lens, layer: (s, t, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_, nt, mb),
        in_specs=[pl.BlockSpec((1, 1, rows, d), q_map),
                  pl.BlockSpec((1, 1, rows, 1), q_map),
                  pl.BlockSpec((None, None, bs, d), page)],
        out_specs=pl.BlockSpec(
            (1, 1, tq, bs), lambda s, t, b, tbl, lens, layer: (s, t, 0, b)),
    )
    out = pl.pallas_call(
        functools.partial(_indexer_kernel, block_size=bs, heads=nh, tq=tq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_, nt, tq, mb * bs), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="indexer_scores",
    )(block_tables, lens, layer.reshape(1),
      q_idx.reshape(s_, nt, rows, d),
      w.astype(jnp.float32).reshape(s_, nt, rows, 1), pool)
    return out.reshape(s_, t_, mb * bs)


def indexer_scores(q_idx: jax.Array, w: jax.Array, pool: jax.Array,
                   block_tables: jax.Array, context_lens: jax.Array,
                   q_start: jax.Array, *, layer, impl: str = "auto"
                   ) -> jax.Array:
    """``I[s, t, p] = sum_j w[s, t, j] relu(q_idx[s, t, j] . key(s, p))``
    over layer ``layer`` of the paged index-key pool [layers, blocks,
    block_size, dI], float32 [S, T, MB * BS]; NEG_INF where position p
    is not banked or lies after query t.  ``q_idx`` [S, T, nI, dI]
    (rotated), ``w`` [S, T, nI]; tables, lengths, ``q_start`` and
    ``impl`` as :func:`paged_attention`."""
    if q_idx.ndim != 4 or w.shape != q_idx.shape[:3]:
        raise ValueError(f"q_idx {q_idx.shape} / w {w.shape} must be "
                         f"[slots, t, heads, dI] and [slots, t, heads]")
    if pool.ndim != 4 or pool.shape[3] != q_idx.shape[3]:
        raise ValueError(f"pool {pool.shape} must be [layers, blocks, "
                         f"block_size, {q_idx.shape[3]}]")
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"impl must be auto|pallas|xla, got {impl!r}")
    fn = _indexer_scores_pallas if impl == "pallas" else _indexer_scores_xla
    args = (q_idx, w, pool, block_tables.astype(jnp.int32),
            context_lens.astype(jnp.int32), q_start.astype(jnp.int32),
            jnp.asarray(layer, jnp.int32))
    mesh = ambient_mesh()
    if impl == "pallas" and needs_shard_map(mesh):
        fn = jax.shard_map(fn, mesh=mesh, in_specs=(P(),) * 7,
                           out_specs=P(), check_vma=False)
    return fn(*args)


def select_topk(scores: jax.Array, k: int):
    """The exact ``k`` best of ``scores`` [..., N] along the last axis,
    ties to the lower position, as a rule a kernel can apply to a tile:
    ``(thr [...], tie_hi [...])`` such that position p is chosen iff
    ``scores[p] > thr`` or (``scores[p] == thr`` and ``p <= tie_hi``).
    Exactly ``k`` positions satisfy it (rows of fewer than ``k``
    positions above NEG_INF choose them all, and some masked ones the
    caller's own mask drops).

    No sort: the k-th largest value is found bit by bit on the floats'
    order-preserving integer keys — 32 counting passes over the row —
    and the ties at it by one running count."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    sign = jnp.uint32(1 << 31)
    # unsigned keys in the floats' order: negatives flip whole, the rest
    # gain the top bit
    key = jnp.where(bits >= sign, ~bits, bits | sign)

    def refine(i, prefix):
        cand = prefix | (sign >> i.astype(jnp.uint32))
        enough = jnp.sum(key >= cand[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, prefix)

    kth = jax.lax.fori_loop(0, 32, refine,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    thr = jax.lax.bitcast_convert_type(
        jnp.where(kth >= sign, kth ^ sign, ~kth), jnp.float32)
    above = jnp.sum(scores > thr[..., None], axis=-1, dtype=jnp.int32)
    ties = scores == thr[..., None]
    seen = jnp.cumsum(ties.astype(jnp.int32), axis=-1)
    tie_hi = jnp.argmax(ties & (seen >= (k - above)[..., None]), axis=-1)
    return thr, tie_hi.astype(jnp.int32)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    q_start: jax.Array,
    *,
    layer,
    scale: Optional[float] = None,
    window: Tuple[int, int] = (-1, -1),
    logit_softcap: float = 0.0,
    impl: str = "auto",
    name: str = "paged_attention",
) -> jax.Array:
    """Causal attention of ``q [S, T, H, D]`` over layer ``layer`` of a
    paged KV pool.

    ``k_pool``/``v_pool``: [layers, num_blocks, block_size,
    kv_heads * head_dim] (the whole stack; ``layer`` is an int or a
    traced int32 scalar).  ``block_tables [S, MB]`` maps slot-s logical
    block j to a pool block; ``context_lens [S]`` is the total banked
    length per slot (chunk included); ``q_start [S]`` the global
    position of each slot's first query row.  Returns [S, T, H, D];
    slots with ``context_lens == 0`` return zeros.

    ``window`` ``(left, right)`` bounds a query at t to ``[t - left,
    t + right]`` (-1 = unbounded).  Under a left window alone the kernel
    walks only the blocks the slot's windows reach: the table's entries
    before them are never read.  ``name`` is the kernel's instruction
    name (a profile reads a model's sliding layers apart from its global
    ones by it).

    ``impl``: 'auto' (pallas on TPU, xla elsewhere) | 'pallas'
    (interpret mode off-TPU) | 'xla'.
    """
    if q.ndim != 4:
        raise ValueError(f"q must be [slots, t, heads, head_dim], got "
                         f"{q.shape}")
    if k_pool.ndim != 4 or k_pool.shape != v_pool.shape:
        raise ValueError(
            f"k_pool {k_pool.shape} / v_pool {v_pool.shape} must be one "
            f"[layers, blocks, block_size, kv_heads*head_dim] shape")
    s_, t_, h, d = q.shape
    if k_pool.shape[3] % d != 0:
        raise ValueError(
            f"pool rows of {k_pool.shape[3]} are not whole heads of "
            f"head_dim {d}")
    kh = k_pool.shape[3] // d
    if h % kh != 0:
        raise ValueError(
            f"num q heads ({h}) must be a multiple of kv heads ({kh})")
    if block_tables.shape[0] != s_ or context_lens.shape != (s_,):
        raise ValueError(
            f"block_tables {block_tables.shape} / context_lens "
            f"{context_lens.shape} do not match {s_} slots")
    if scale is None:
        scale = d ** -0.5
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"impl must be auto|pallas|xla, got {impl!r}")
    static = (float(scale), tuple(window), float(logit_softcap))
    fn = _paged_attention_xla
    if impl == "pallas":
        static += (name,)
        mesh = ambient_mesh()
        fn = (functools.partial(_paged_attention_pallas_sharded, mesh)
              if needs_shard_map(mesh) else _paged_attention_pallas)
    return fn(q, k_pool, v_pool, block_tables.astype(jnp.int32),
              context_lens.astype(jnp.int32), q_start.astype(jnp.int32),
              jnp.asarray(layer, jnp.int32), *static)
