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
"""

from __future__ import annotations

import functools
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
                      heads_per_step, num_kv_blocks, window, logit_softcap):
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


def _paged_attention_pallas(q, k_pool, v_pool, block_tables, context_lens,
                            q_start, layer, scale, window, logit_softcap):
    s_, t_, h, d = q.shape
    bs, kh = k_pool.shape[2], k_pool.shape[3] // d
    mb = block_tables.shape[1]
    group = h // kh
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
    kv_spec = pl.BlockSpec(
        (None, None, bs, hb * d),
        lambda s, g, b, tbl, lens, layer: (layer[0], tbl[s, b], 0, g))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_, kh // hb, mb),
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
        rows=rows, heads_per_step=hb, num_kv_blocks=mb, window=window,
        logit_softcap=logit_softcap)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="paged_attention",
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
# as a decode step; a page's index map stops at the last block the tile
# can see (its causal reach, the slot's length), so blocks past it are
# not fetched again.

@functools.partial(jax.jit, static_argnames=("scale",))
def _latent_paged_attention_xla(q_lat, q_pe, pool, block_tables,
                                context_lens, q_start, layer, scale):
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
    mask = mask[:, None, :, :]
    scores = jnp.where(mask, scores, NEG_INF)
    lse = jax.nn.logsumexp(scores, axis=-1)
    probs = jnp.where(mask, jnp.exp(scores - lse[..., None]), 0.0)
    out = jnp.einsum("shtk,skr->sthr", probs, rows[..., :r])
    return out.astype(q_lat.dtype)


def _latent_fwd_kernel(tbl_ref, lens_ref, layer_ref, ql_ref, qp_ref, kv_ref,
                       o_ref, m_scr, l_scr, acc_scr,
                       *, scale, block_size, latent, rope, heads, tq, rows,
                       num_kv_blocks):
    si = pl.program_id(0)
    ti = pl.program_id(1)
    bi = pl.program_id(2)

    @pl.when(bi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = lens_ref[si, 0]
    q0 = lens_ref[si, 1] + ti * tq          # this tile's first position
    k_start = bi * block_size

    @pl.when((k_start < ctx) & (k_start <= q0 + tq - 1))
    def _compute():
        kv_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1)
        # row r holds head r % heads of tile token r // heads
        q_pos = q0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 0) // heads
        mask = (kv_pos < ctx) & (kv_pos <= q_pos)
        c_kv = kv_ref[:, :latent]                             # [BS, R]
        k_pe = kv_ref[:, latent:latent + rope]                # [BS, P]
        s = (jax.lax.dot_general(
            ql_ref[0, 0], c_kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
             + jax.lax.dot_general(
            qp_ref[0, 0], k_pe, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)) * scale      # [rows, BS]
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        l_scr[...] = jnp.broadcast_to(
            (alpha * l_scr[:, 0] + jnp.sum(p, axis=1))[:, None], l_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(c_kv.dtype), c_kv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)

    @pl.when(bi == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[:, 0]
        o_ref[0, 0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)[:, None]
                       ).astype(o_ref.dtype)


def latent_query_tile(num_heads: int, latent: int, rope: int,
                      block_size: int, t: int, dtype) -> int:
    """Query tokens one grid step of the latent kernel takes of ``t`` a
    slot (all heads of each), or ``ValueError`` where no tile fits: the
    largest divisor of ``t`` whose blocks — q and out [rows, R] and
    q_pe [rows, P] double-buffered, the page, the f32 m/l/acc scratch
    and the [rows, bs] score temporaries — stay inside the VMEM budget
    (rows = tile tokens x heads)."""
    itemsize = jnp.dtype(dtype).itemsize
    if block_size % min_block_size(dtype):
        raise ValueError(
            f"latent paged attention kernel: block_size {block_size} is "
            f"not a multiple of {min_block_size(dtype)}, the TPU sublane "
            f"tile of a {jnp.dtype(dtype).name} pool")
    lat, pe = round_up(latent, _LANES), round_up(rope, _LANES)
    page = 2 * block_size * (lat + pe) * itemsize

    def need(rows):
        return (2 * rows * (2 * lat + pe) * itemsize
                + rows * (2 * _LANES + lat) * 4
                + 3 * rows * max(block_size, _LANES) * 4 + page)
    for tq in range(t, 0, -1):
        rows = tq * num_heads
        if t % tq == 0 and (rows % 8 == 0 or tq == t) \
                and need(rows) <= _VMEM_BUDGET:
            return tq
    raise ValueError(
        f"latent paged attention kernel: {num_heads} heads of a "
        f"{latent}+{rope} row do not fit the "
        f"{_VMEM_BUDGET / 2**20:.0f} MiB VMEM budget even one token a "
        f"step ({need(num_heads) / 2**20:.1f} MiB)")


def _latent_paged_attention_pallas(q_lat, q_pe, pool, block_tables,
                                   context_lens, q_start, layer, *, scale):
    s_, t_, h, r = q_lat.shape
    pe = q_pe.shape[-1]
    bs, w = pool.shape[2], pool.shape[3]
    mb = block_tables.shape[1]
    tq = latent_query_tile(h, r, pe, bs, t_, pool.dtype)
    nt, rows = t_ // tq, tq * h
    lens = jnp.stack([context_lens.astype(jnp.int32),
                      q_start.astype(jnp.int32)], axis=1)
    layer = layer.reshape(1)
    # token-major rows: tiling the slot's [T, H, .] queries is a reshape
    ql = q_lat.reshape(s_, nt, rows, r)
    qp = q_pe.reshape(s_, nt, rows, pe)

    def page(s, t, b, tbl, lens, layer):
        # the last block this tile reads: its causal reach within the
        # slot's length (the same index again = no new fetch)
        reach = jnp.minimum(lens[s, 0], lens[s, 1] + (t + 1) * tq)
        last = jnp.maximum(reach - 1, 0) // bs
        return (layer[0], tbl[s, jnp.minimum(b, last)], 0, 0)

    q_map = lambda s, t, b, tbl, lens, layer: (s, t, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_, nt, mb),
        in_specs=[pl.BlockSpec((1, 1, rows, r), q_map),
                  pl.BlockSpec((1, 1, rows, pe), q_map),
                  pl.BlockSpec((None, None, bs, w), page)],
        out_specs=pl.BlockSpec((1, 1, rows, r), q_map),
        scratch_shapes=[
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, r), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _latent_fwd_kernel, scale=scale, block_size=bs, latent=r, rope=pe,
        heads=h, tq=tq, rows=rows, num_kv_blocks=mb)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(ql.shape, q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="latent_paged_attention",
    )(block_tables.astype(jnp.int32), lens, layer, ql, qp, pool)
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
) -> jax.Array:
    """Causal absorbed-form MLA attention over layer ``layer`` of a
    latent paged pool.

    ``q_lat`` [S, T, H, R] (queries with ``W_kvb^K`` folded in), ``q_pe``
    [S, T, H, P] (rotated); ``pool`` [layers, num_blocks, block_size, W]
    with a token's row ``[c_kv (R) | rope(k_pe) (P) | padding]``.
    Tables, lengths, ``q_start`` and ``impl`` as
    :func:`paged_attention`.  Returns the latent outputs ``P c_kv``
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
    fn = functools.partial(
        _latent_paged_attention_pallas if impl == "pallas"
        else _latent_paged_attention_xla, scale=float(scale))
    mesh = ambient_mesh()
    if impl == "pallas" and needs_shard_map(mesh):
        # one shared row for every head: nothing to split, each shard
        # runs the whole call
        fn = jax.shard_map(fn, mesh=mesh, in_specs=(P(),) * 7,
                           out_specs=P(), check_vma=False)
    return fn(q_lat, q_pe, pool, block_tables.astype(jnp.int32),
              context_lens.astype(jnp.int32), q_start.astype(jnp.int32),
              jnp.asarray(layer, jnp.int32))


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
    fn = (_paged_attention_pallas if impl == "pallas"
          else _paged_attention_xla)
    mesh = ambient_mesh()
    if impl == "pallas" and needs_shard_map(mesh):
        fn = functools.partial(_paged_attention_pallas_sharded, mesh)
    return fn(q, k_pool, v_pool, block_tables.astype(jnp.int32),
              context_lens.astype(jnp.int32), q_start.astype(jnp.int32),
              jnp.asarray(layer, jnp.int32), float(scale), tuple(window),
              float(logit_softcap))
