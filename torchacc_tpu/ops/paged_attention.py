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
