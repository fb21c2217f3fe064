"""Pallas TPU flash attention: forward + backward, LSE, causal, GQA,
sliding window, segment-id varlen.

TPU-native replacement for the reference's CUDA flash-attention custom
calls (`torch_xla._XLAC._flash_attention_{forward,backward}` and the
position-ids variants — used at reference ops/flash_attn.py:36,56,185,206)
covering the same feature matrix documented at ops/flash_attn.py:386-432:
fixed-length + varlen (packed sequences via segment ids, the equivalent of
cu_seqlens/position_ids), causal, sliding window, GQA/MQA.  Returns the
per-row log-sum-exp exactly like the reference kernels' ``softmax_lse``
so context-parallel ring merging can combine partial results
(reference cp/utils.py:302-343).

Kernel layout (TPU tiling: last two block dims must be (8k, 128k)):
  q/k/v in BHSD; one program per (batch, q_head, q_block); kv blocks on
  the innermost sequential grid dim with VMEM carry (online softmax).
  LSE leaves the forward along the lanes, [b, h, 1, sq] ([b, h, sq] at
  the wrapper).  Segment ids broadcast to (b, sq, 128) for q and
  (b, 8, sk) for kv (sublane-broadcast), the standard trick.
  A per-q-row statistic of a [q, k] tile (the running max and sum, lse,
  delta) is used as it is stored, lane-broadcast [rows, 128], tiled
  across the lanes of the tile (``_across_lanes``), never as a 1-D
  vector.
Backward = two kernels (flash-attn standard): dq over q blocks looping
kv; dk/dv over kv blocks looping q; both recompute P from the saved LSE.
``flash_dq`` forms its tiles as [block_q, block_k] (lse / delta
lane-broadcast, [b, h, sq, 128]); ``flash_dkv`` forms them
as [block_k, block_q] — K·Qᵀ, the orientation dV = Pᵀ·dO and
dK = dSᵀ·Q contract — so it transposes no tile and takes lse / delta as
plain ``[b, h, 1, sq]`` lane vectors (segment ids with the roles of the
two broadcasts swapped).
Public API stays BSHD to match the model layer ([b, s, h, d]).

What the positional mask discards is not paid for (``_band`` is the one
rule; ``tile_plan`` counts it): a grid step outside the band runs no
body, and where the band is known when the kernel is built (no traced
q / k offsets) it names the neighbouring live step's blocks, so the
pipeline copies nothing for it; a tile on the causal diagonal is taken
in two pieces that leave out its masked upper-right quarter
(``_diagonal_pieces``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchacc_tpu.ops._common import (
    _B_PRIME,
    _K_PRIME,
    NEG_INF,
    interpret_mode as _interpret,
    mix32,
    round_up as _round_up,
)

_LANES = 128
_SUBLANES = 8


def _positions(q0, k0, nq, nk, q_axis):
    """(q_pos, k_pos) of one tile of ``nq`` queries from ``q0`` and
    ``nk`` keys from ``k0``, as 2-D int32 iotas (a 1-D iota does not
    lower on TPU, nor does a float one).  ``q_axis`` is the dimension the
    queries run along: 0 for a [q, k] tile (forward, dq), 1 for a [k, q]
    tile (dk/dv)."""
    shape = (nq, nk) if q_axis == 0 else (nk, nq)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return q_pos, k_pos


def _keep_mask_2d(seed, b_idx, h_idx, q0, k0, block_q, block_k,
                  dropout_p: float, q_axis: int = 0):
    """Dropout keep mask of one tile from GLOBAL coordinates.

    Same formula as ops._common.dropout_keep (the XLA path) expressed via
    2-D broadcasted iota so it lowers on TPU: the mask is a pure function
    of (seed, batch, head, absolute q, absolute k), hence bit-identical
    across the forward and both backward kernels (whichever way they
    orient the tile), across block-size choices, and across
    context-parallel ring steps."""
    base = mix32(jnp.uint32(seed).astype(jnp.uint32)
                 + jnp.uint32(b_idx) * jnp.uint32(_B_PRIME)
                 + jnp.uint32(h_idx))
    gq, gk = _positions(q0, k0, block_q, block_k, q_axis)
    gq, gk = gq.astype(jnp.uint32), gk.astype(jnp.uint32)
    bits = mix32(mix32(base ^ gq) ^ mix32(gk * jnp.uint32(_K_PRIME)))
    threshold = jnp.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    return bits >= threshold


def _block_sizes(sq: int, sk: int) -> Tuple[int, int]:
    """TPU-legal defaults: block_q lands in sublane positions (multiple of
    8), block_k lands in lane positions of the kv-segment block (multiple
    of 128); the wrapper pads sequences up to a block multiple.  1024x1024
    measured fastest on v5e at seq 2048 (docs/PERF.md) — fewer grid steps
    amortise the per-tile mask/softmax VPU overhead — and what such a
    coarse tile would waste under a causal mask the kernels skip: steps
    outside the band fetch nothing and a diagonal tile leaves out its
    masked quarter (module docstring; both need block_q == block_k,
    which these defaults give wherever sq and sk pick the same block).
    A block that divides the sequence is preferred over a larger one:
    padding fabricates segment ids, which disables the interior-tile
    mask-skip fast path and the diagonal split."""
    def pick(s: int, unit: int) -> int:
        for cand in (1024, 512):
            if s % cand == 0:
                return cand
        return min(1024, _round_up(s, unit))
    return pick(sq, 8), pick(sk, _LANES)


def _band_mask(q_start, k_start, block_q, block_k, causal, window,
               qk_shift=0, q_axis=0):
    """Positional (causal + sliding window) mask for one tile, or None.

    ``qk_shift = sk - sq`` bottom-right aligns the geometry for sq != sk
    (flash-attn semantics: the LAST query aligns with the LAST key), the
    same shift the ALiBi bias uses — mask and bias always agree."""
    left, right = window
    if not causal and left < 0 and right < 0:
        return None
    q_pos, k_pos = _positions(q_start + qk_shift, k_start, block_q, block_k,
                              q_axis)
    mask = jnp.ones(q_pos.shape, jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if left >= 0:
        mask &= k_pos >= q_pos - left
    if right >= 0:
        mask &= k_pos <= q_pos + right
    return mask


def _alibi_bias(slope, q_start, k_start, block_q, block_k, qk_shift,
                q_axis=0):
    """Additive ALiBi bias -slope * |q_pos + (sk - sq) - k_pos| for one
    tile — bottom-right aligned like the reference (alibi_slopes through
    every flash op, ops/flash_attn.py:411-413), so decode-style sq != sk
    keeps the most recent keys least penalised."""
    q_pos, k_pos = _positions(q_start + qk_shift, k_start, block_q, block_k,
                              q_axis)
    return -slope * jnp.abs(q_pos - k_pos).astype(jnp.float32)


def _across_lanes(col, n):
    """A per-row statistic kept lane-broadcast, [rows, 128], as [rows, n]:
    whole vregs side by side — no trip through a 1-D vector, whose rows
    would have to leave the sublanes and come back."""
    if n % _LANES:
        return jnp.broadcast_to(col[:, :1], (col.shape[0], n))
    return col if n == _LANES else jnp.tile(col, (1, n // _LANES))


def _band(block_q, block_k, causal, window):
    """The positional rule, once: a (q block, kv block) pair holds a
    visible (query, key) pair iff ``d_min <= d <= d_max`` for
    ``d = q_start + shift - k_start``, the distance between the blocks'
    first positions (None = unbounded on that side).  Causal: the block's
    last query reaches the block's first key; a left window: its first
    query still sees the block's last key.  ``_block_should_run`` asks it
    of one pair, ``_live_range`` solves it for the other block's index."""
    left, right = window
    d_min = d_max = None
    if causal:                       # tighter than any right window
        d_min = -(block_q - 1)
    elif right >= 0:
        d_min = -(block_q - 1) - right
    if left >= 0:
        d_max = block_k - 1 + left
    return d_min, d_max


def _block_should_run(q_start, k_start, block_q, block_k, causal, window,
                      qk_shift=0):
    """Whether the tile holds a visible pair: a traced bool in a kernel,
    a Python one for Python ints (``tile_plan``)."""
    d_min, d_max = _band(block_q, block_k, causal, window)
    d = q_start + qk_shift - k_start
    run = True
    if d_min is not None:
        run = run & (d >= d_min)
    if d_max is not None:
        run = run & (d <= d_max)
    return run


def _live_range(i, block_q, block_k, causal, window, shift, n,
                of_kv_block=False):
    """(lo, hi): the first and last of the ``n`` kv blocks that
    ``_block_should_run`` admits for q block ``i`` — or, ``of_kv_block``,
    the first and last of the ``n`` q blocks it admits for kv block
    ``i`` — clamped into [0, n - 1]; ``lo > hi`` where it admits none.
    ``_band`` solved for the other block's index; ``shift`` is a Python
    int, ``i`` a Python int or a traced grid index."""
    d_min, d_max = _band(block_q, block_k, causal, window)
    if of_kv_block:     # q_start = k_start - shift + d
        base, block = i * block_k - shift, block_q
        first = None if d_min is None else base + d_min
        last = None if d_max is None else base + d_max
    else:               # k_start = q_start + shift - d
        base, block = i * block_q + shift, block_k
        first = None if d_max is None else base - d_max
        last = None if d_min is None else base - d_min
    lo = 0 if first is None else -((-first) // block)       # ceil
    hi = n - 1 if last is None else last // block           # floor
    least, most = _min_max(i)
    return most(lo, 0), least(hi, n - 1)


def _min_max(i):
    """min and max for a Python int or a traced grid index."""
    return (min, max) if isinstance(i, int) else (jnp.minimum, jnp.maximum)


def _live_block(j, i, block_q, block_k, causal, window, shift, n,
                of_kv_block=False):
    """Block index for grid step ``j`` of row ``i``: ``j`` where the step
    is live, else the nearest live step's — a dead step then names the
    block its neighbour holds and the pipeline issues no copy for it.
    ``shift`` None (traced offsets: the band is not known here) leaves
    ``j`` as it is."""
    if shift is None:
        return j
    lo, hi = _live_range(i, block_q, block_k, causal, window, shift, n,
                         of_kv_block)
    least, most = _min_max(i)
    return least(most(least(most(j, lo), hi), 0), n - 1)


def _block_fully_inside(q_start, k_start, block_q, block_k, causal, window,
                        qk_shift=0):
    """True when no (q, k) pair in the tile is positionally masked — the
    kernels then skip the iota/compare/where mask work entirely (the
    softmax VPU path dominates interior tiles otherwise)."""
    left, right = window
    q_hi = q_start + qk_shift + block_q - 1
    q_lo = q_start + qk_shift
    k_hi = k_start + block_k - 1
    inside = True
    if causal:
        inside = jnp.logical_and(inside, k_hi <= q_lo)
    if left >= 0:
        inside = jnp.logical_and(inside, k_start >= q_hi - left)
    if right >= 0:
        inside = jnp.logical_and(inside, k_hi <= q_lo + right)
    return inside


def _splits_diagonal(block_q, block_k, causal, window, shift, has_seg):
    """Whether every tile the positional mask cuts is an exact diagonal
    tile — q_start + shift == k_start, square — so that its upper-right
    quarter is wholly masked and can be left out: a causal mask alone
    (no left window, no segment ids), the band known when the kernel is
    built (``shift`` a Python int) and a multiple of the square block,
    halves that still tile the lanes."""
    return (causal and window[0] < 0 and not has_seg
            and shift is not None and block_q == block_k
            and shift % block_q == 0 and (block_k // 2) % _LANES == 0)


def _diagonal_pieces(block_q, block_k, causal, window, shift, has_seg,
                     by_keys=False):
    """Where ``_splits_diagonal`` holds (else None), a diagonal tile as
    two (rows, keys) pieces, each (start, size), that cover everything on
    or below the diagonal: the first half of the rows against the first
    half of the keys and the second half against all keys — or,
    ``by_keys`` (the dk/dv kernel, which accumulates by key), the first
    half of the keys under all rows and the second half under the second
    half of the rows."""
    if not _splits_diagonal(block_q, block_k, causal, window, shift,
                            has_seg):
        return None
    hq, hk = block_q // 2, block_k // 2
    if by_keys:
        return (((0, block_q), (0, hk)), ((hq, hq), (hk, hk)))
    return (((0, hq), (0, hk)), ((hq, hq), (0, block_k)))


def _dispatch_masked(tile, has_seg, q_start, k_start, block_q, block_k,
                     causal, window, shift, diagonal=None):
    """Run ``tile(rows, keys, masked)`` for one grid step: skipped
    entirely outside the band, the whole tile mask-free where it is fully
    interior (positional masks only — any segment ids force the masked
    path), else masked: the whole tile, or the ``diagonal`` pieces where
    ``_splits_diagonal`` holds."""
    whole = ((0, block_q), (0, block_k))
    pieces = diagonal or (whole,)

    def masked():
        for rows, keys in pieces:
            tile(rows, keys, True)

    run = _block_should_run(q_start, k_start, block_q, block_k,
                            causal, window, shift)
    if not has_seg and (causal or window[0] >= 0 or window[1] >= 0):
        inside = _block_fully_inside(q_start, k_start, block_q, block_k,
                                     causal, window, shift)
        pl.when(jnp.logical_and(run, inside))(
            functools.partial(tile, *whole, False))
        pl.when(jnp.logical_and(run, jnp.logical_not(inside)))(masked)
    else:
        pl.when(run)(masked)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _shift_of(qk_shift, meta_ref, traced_offsets):
    """The tile's q-to-k alignment: the static part, plus — context-
    parallel ring chunks — the traced global q / k offsets out of
    ``meta`` = [seed, q_off, k_off, h_off, b_off] (see _make_meta)."""
    if traced_offsets:
        return qk_shift + meta_ref[1] - meta_ref[2]
    return qk_shift


def _fwd_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, alibi_ref, meta_ref,
                o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, scale, causal, window, block_q, block_k, num_kv_blocks,
                qk_shift=0, dropout_p=0.0, logit_softcap=0.0,
                traced_offsets=False, diagonal=None):
    bi = pl.program_id(0)
    hi = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # the h/b offsets of meta key the dropout hash
    shift = _shift_of(qk_shift, meta_ref, traced_offsets)

    def _tile(rows, keys, masked):
        # one online-softmax update of ``rows`` of the q block by ``keys``
        # of the kv block (each (start, size): the whole tile, or a piece
        # of a diagonal one).  dots take the inputs' native dtype (bf16
        # in training) and accumulate in f32 — an f32 input cast here
        # would knock the MXU off its native bf16 path (~8x slower on
        # v5e); softmax math stays in f32 throughout
        (r0, rn), (c0, cn) = rows, keys
        r, c = pl.ds(r0, rn), pl.ds(c0, cn)
        q = q_ref[0, 0, r, :]                              # [rn, d]
        k = k_ref[0, 0, c, :]                              # [cn, d]
        v = v_ref[0, 0, c, :]                              # [cn, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [rn, cn]
        if logit_softcap > 0.0:
            # Gemma2 score capping: c * tanh(s / c), after the scale and
            # before alibi/mask (matches the XLA reference)
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        if alibi_ref is not None:
            s = s + _alibi_bias(alibi_ref[0, 0, 0], q_start + r0,
                                k_start + c0, rn, cn, shift)

        mask = None
        if masked:
            mask = _band_mask(q_start + r0, k_start + c0, rn, cn, causal,
                              window, shift)
            if qseg_ref is not None:
                qs = qseg_ref[0, r, 0]                      # [rn]
                ks = kseg_ref[0, 0, c]                      # [cn]
                seg = qs[:, None] == ks[None, :]
                mask = seg if mask is None else mask & seg
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        # the running max and sum stay lane-broadcast, [rn, 128], as the
        # scratch holds them (_across_lanes)
        m_prev = m_scr[r, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _across_lanes(m_new, cn))
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev == NEG_INF, 0.0, alpha)
        l_new = alpha * l_scr[r, :] + jnp.sum(p, axis=1, keepdims=True)
        # dropout applies to the accumulated P@V only: l (and so the lse)
        # stays the UNdropped softmax normaliser — exactly flash-attn's
        # decomposition, and what the backward recomputation assumes
        p_v = p
        if dropout_p > 0.0:
            keep = _keep_mask_2d(
                meta_ref[0], meta_ref[4] + bi, meta_ref[3] + hi,
                meta_ref[1] + q_start + r0, meta_ref[2] + k_start + c0,
                rn, cn, dropout_p)
            p_v = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_p))
        acc_scr[r, :] = (
            acc_scr[r, :] * _across_lanes(alpha, acc_scr.shape[1])
            + jax.lax.dot_general(
                p_v.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        m_scr[r, :] = m_new
        l_scr[r, :] = l_new

    _dispatch_masked(_tile, qseg_ref is not None, q_start, k_start,
                     block_q, block_k, causal, window, shift, diagonal)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        m, l = m_scr[...], l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[...] / _across_lanes(
            l_safe, acc_scr.shape[1])).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
        # out along the lanes, [1, block_q]: one row of the lane-broadcast
        # column's transpose, once a q block
        lse_ref[0, 0, :, :] = lse.T[:1, :]


def _mk_kernel(core, has_seg, has_alibi, has_meta=False, **kw):
    """Adapter: unpack the optional (seg, alibi, meta) refs positionally
    so one core kernel serves all feature combinations."""
    def kernel(*refs):
        q_ref, k_ref, v_ref = refs[:3]
        i = 3
        qseg = kseg = alibi = meta = None
        if has_seg:
            qseg, kseg = refs[i], refs[i + 1]
            i += 2
        if has_alibi:
            alibi = refs[i]
            i += 1
        if has_meta:
            meta = refs[i]
            i += 1
        rest = refs[i:]
        core(q_ref, k_ref, v_ref, qseg, kseg, alibi, meta, *rest, **kw)
    return kernel


# ---------------------------------------------------------------------------
# the grids: operands, BlockSpecs, and what their pipelines copy
# ---------------------------------------------------------------------------

def _operands(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes, meta,
              q_axis=0):
    """(q, k, v, segment ids, alibi, meta) as the kernels take them, the
    optional ones left out (``_mk_kernel`` unpacks them).  Segment ids
    for a [q, k] tile (``q_axis`` 0): q ids down the sublanes,
    lane-broadcast (b, sq, 128), kv ids along the lanes,
    sublane-broadcast (b, 8, sk); for a [k, q] tile (1) the other way
    round.  Slopes as a TPU-legal (h, 8, 128) broadcast."""
    def down(ids):
        return jax.lax.broadcast_in_dim(ids, ids.shape + (_LANES,), (0, 1))

    def along(ids):
        b, s = ids.shape
        return jax.lax.broadcast_in_dim(ids, (b, _SUBLANES, s), (0, 2))

    args = [q, k, v]
    if q_segment_ids is not None:
        q_form, kv_form = (down, along) if q_axis == 0 else (along, down)
        args += [q_form(q_segment_ids), kv_form(kv_segment_ids)]
    if alibi_slopes is not None:
        h = alibi_slopes.shape[0]
        args.append(jax.lax.broadcast_in_dim(
            alibi_slopes.astype(jnp.float32), (h, _SUBLANES, _LANES), (0,)))
    if meta is not None:
        args.append(meta)
    return args


def _seg_specs(block_q, block_k, q_block, kv_block, q_axis):
    """BlockSpecs of ``_operands``' segment ids; ``q_block`` /
    ``kv_block`` map the grid indices to the sequence block each names."""
    def down(block, index):             # a column a block
        return pl.BlockSpec((1, block, _LANES),
                            lambda b_, *g: (b_, index(*g), 0))

    def along(block, index):            # a row a block
        return pl.BlockSpec((1, _SUBLANES, block),
                            lambda b_, *g: (b_, 0, index(*g)))

    q_form, kv_form = (down, along) if q_axis == 0 else (along, down)
    return [q_form(block_q, q_block), kv_form(block_k, kv_block)]


def _q_major_specs(d, group, block_q, block_k, nk, causal, window, shift,
                   has_seg, has_alibi, has_meta, bwd=False):
    """in_specs over the grid (b, hq, nq, nk): ``_operands`` for
    ``flash_fwd`` and, ``bwd``, ``flash_dq`` with do and the
    lane-broadcast lse / delta behind them.  A kv-side operand names
    ``_live_block`` of its step."""
    def kv_block(h, qi, ki):
        return _live_block(ki, qi, block_q, block_k, causal, window, shift,
                           nk)

    def q_side(last):
        return pl.BlockSpec((1, 1, block_q, last),
                            lambda b_, h, qi, ki: (b_, h, qi, 0))

    def kv_side():
        return pl.BlockSpec(
            (1, 1, block_k, d),
            lambda b_, h, qi, ki: (b_, h // group, kv_block(h, qi, ki), 0))

    specs = [q_side(d), kv_side(), kv_side()]
    if has_seg:
        specs += _seg_specs(block_q, block_k, lambda h, qi, ki: qi, kv_block,
                            q_axis=0)
    if has_alibi:
        specs.append(pl.BlockSpec((1, _SUBLANES, _LANES),
                                  lambda b_, h, qi, ki: (h, 0, 0)))
    if has_meta:
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if bwd:
        specs += [q_side(d), q_side(_LANES), q_side(_LANES)]
    return specs


def _kv_major_specs(d, group, block_q, block_k, nq, causal, window, shift,
                    has_seg, has_alibi, has_meta):
    """in_specs of ``flash_dkv`` over the grid (b, hk, nk, group, nq):
    ``_operands(q_axis=1)``, then do and lse / delta as [1, block_q] lane
    vectors.  A q-side operand names ``_live_block`` of its step."""
    def q_block(hkv, ki, g, qi):
        return _live_block(qi, ki, block_q, block_k, causal, window, shift,
                           nq, of_kv_block=True)

    def q_side(last_two, place):
        # a per-q-head operand's block, at the q block the step names
        return pl.BlockSpec(
            (1, 1) + last_two,
            lambda b_, hkv, ki, g, qi: (b_, hkv * group + g) + place(
                q_block(hkv, ki, g, qi)))

    def kv_side():
        return pl.BlockSpec((1, 1, block_k, d),
                            lambda b_, hkv, ki, g, qi: (b_, hkv, ki, 0))

    specs = [q_side((block_q, d), lambda i: (i, 0)), kv_side(), kv_side()]
    if has_seg:
        specs += _seg_specs(block_q, block_k, q_block,
                            lambda hkv, ki, g, qi: ki, q_axis=1)
    if has_alibi:
        specs.append(pl.BlockSpec(
            (1, _SUBLANES, _LANES),
            lambda b_, hkv, ki, g, qi: (hkv * group + g, 0, 0)))
    if has_meta:
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    specs += [q_side((block_q, d), lambda i: (i, 0)),
              q_side((1, block_q), lambda i: (0, i)),
              q_side((1, block_q), lambda i: (0, i))]
    return specs


def _copies_in_vain(in_specs, steps, live):
    """The dead ``steps`` at which a pipeline over ``in_specs`` copies a
    block that no live step reads before it is replaced.  ``steps`` are
    one (batch, head)'s grid indices in the order the grid runs them; an
    operand is copied when a step names another block than the step
    before it."""
    in_vain = set()
    for spec in in_specs:
        if spec.index_map is None:      # SMEM: not pipelined
            continue
        held = unread = None    # the block held; the dead step that fetched it
        for step in steps:
            block = tuple(int(i) for i in spec.index_map(0, *step))
            if block != held:
                in_vain.add(unread)
                held, unread = block, step
            if live(step):
                unread = None
        in_vain.add(unread)
    return in_vain - {None}


def tile_plan(sq, sk, block_q, block_k, causal=True, window=(-1, -1),
              shift=0, has_seg=False):
    """What the kernels' grids do for one (batch, head) over ``sq``
    queries and ``sk`` keys: ``steps`` (q block, kv block) pairs,
    ``live`` pairs ``_block_should_run`` admits, ``dead_fetching`` dead
    pairs at which one of the three kernels copies a block in vain — read
    off the index maps the ``pallas_call``s are given, walked over their
    grids (``_copies_in_vain``) — ``diagonal_split`` live tiles taken
    without their masked quarter.  ``shift`` is the static q-to-k
    alignment (sk - sq plus static offsets); None — traced offsets —
    leaves the band to run time: ``live`` and ``dead_fetching`` are then
    None."""
    nq, nk = -(-sq // block_q), -(-sk // block_k)
    plan = {"steps": nq * nk, "live": None, "dead_fetching": None,
            "diagonal_split": 0}
    if shift is None:
        return plan
    pairs = [(qi, ki) for qi in range(nq) for ki in range(nk)]
    runs = {(qi, ki) for qi, ki in pairs if _block_should_run(
        qi * block_q, ki * block_k, block_q, block_k, causal, window, shift)}
    band = (causal, window, shift, has_seg, False, False)
    q_major = [(0, qi, ki) for qi, ki in pairs]                 # (h, qi, ki)
    kv_major = [(0, ki, 0, qi) for ki in range(nk) for qi in range(nq)]
    in_vain = {step[1:] for bwd in (False, True) for step in _copies_in_vain(
        _q_major_specs(_LANES, 1, block_q, block_k, nk, *band, bwd=bwd),
        q_major, lambda step: step[1:] in runs)}
    in_vain |= {(qi, ki) for _, ki, _, qi in _copies_in_vain(
        _kv_major_specs(_LANES, 1, block_q, block_k, nq, *band),
        kv_major, lambda step: (step[3], step[1]) in runs)}
    split = _splits_diagonal(block_q, block_k, causal, window, shift, has_seg)
    plan.update(live=len(runs), dead_fetching=len(in_vain),
                diagonal_split=sum(
                    split and qi * block_q + shift == ki * block_k
                    for qi, ki in runs))
    return plan


@functools.cache
def _log_tile_plan(sq, sk, block_q, block_k, causal, window, shift, has_seg):
    """State a compiled geometry's plan once (trace time, INFO)."""
    from torchacc_tpu.utils.logger import logger
    plan = tile_plan(sq, sk, block_q, block_k, causal, window, shift, has_seg)
    logger.info(
        f"flash kernels sq={sq} sk={sk} blocks=({block_q}, {block_k}) "
        f"causal={causal} window={window} "
        + " ".join(f"{k}={v}" for k, v in plan.items()))


def _fwd(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes, meta, scale,
         causal, window, block_q, block_k, qk_shift=0, dropout_p=0.0,
         logit_softcap=0.0, traced_offsets=False):
    """q,k,v in BHSD.  Returns (o BHSD, lse [b,h,sq] f32).

    ``meta``: optional int32 [5] = (dropout seed, global q offset,
    global k offset, global head offset, global batch offset) — SMEM
    scalars, traced (no recompile per seed/offset); layout owned by
    _make_meta.  ``traced_offsets``: the q / k offsets in it are traced
    values (else they are already part of ``qk_shift``)."""
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    group = hq // hk
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    has_seg = q_segment_ids is not None
    has_alibi = alibi_slopes is not None
    has_meta = meta is not None
    # the alignment the grid can plan with; None where the ring's traced
    # offsets move it at run time
    shift = None if traced_offsets else qk_shift
    _log_tile_plan(sq, sk, block_q, block_k, causal, tuple(window), shift,
                   has_seg)
    diagonal = _diagonal_pieces(block_q, block_k, causal, window, shift,
                                has_seg)

    kernel = _mk_kernel(
        _fwd_kernel, has_seg, has_alibi, has_meta,
        scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_kv_blocks=nk,
        qk_shift=qk_shift, dropout_p=dropout_p,
        logit_softcap=logit_softcap, traced_offsets=traced_offsets,
        diagonal=diagonal)

    band = (causal, window, shift, has_seg, has_alibi, has_meta)
    args = _operands(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes,
                     meta)

    fwd_call = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=_q_major_specs(d, group, block_q, block_k, nk, *band),
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda b_, h, qi, ki: (b_, h, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, hq, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
        name="flash_fwd",
    )
    # scoped apart from the rope/transposes around it (obs/tracing.py
    # DEVICE_SCOPES): the kernel's device time reads under its own name
    with jax.named_scope("flash_fwd"):
        o, lse = fwd_call(*args)
    return o, lse[:, :, 0, :]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _recompute_p(q, k, lse, seg, slope, meta_ref, q0, k0, b_idx, h_idx, *,
                 scale, causal, window, shift, dropout_p=0.0,
                 logit_softcap=0.0, masked=True, q_axis=0):
    """Rebuild (p, p_tilde, dcap) for one tile from the saved lse.

    ``q`` [nq, d] from position ``q0`` and ``k`` [nk, d] from ``k0``
    give a [q, k] tile (``q_axis`` 0) or a [k, q] tile (1); ``lse`` and
    the ``seg`` = (q ids, kv ids) pair (or None) come shaped to
    broadcast against it.  ``p`` is the exact softmax tile; ``p_tilde``
    is the dropout-scaled tile actually used in the forward P@V (equal
    to ``p`` when dropout is off); ``dcap`` is the softcap derivative
    factor 1 - tanh^2 (1.0 when capping is off) the caller must chain
    into dS.  The VJP through dropped softmax is
        dS = P̃ ∘ (dO Vᵀ) − P ∘ delta
    with delta = rowsum(dO ∘ O) — note P̃ multiplies the dO Vᵀ term and
    the plain P multiplies delta."""
    nq, nk = q.shape[0], k.shape[0]
    a, b = (q, k) if q_axis == 0 else (k, q)
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    dcap = 1.0
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
        # d(c*tanh(x/c))/dx = 1 - tanh^2 = 1 - (s_capped / c)^2, taken
        # BEFORE the alibi bias lands on s
        dcap = 1.0 - (s / logit_softcap) ** 2
    if slope is not None:
        s = s + _alibi_bias(slope, q0, k0, nq, nk, shift, q_axis)
    mask = None
    if masked:
        mask = _band_mask(q0, k0, nq, nk, causal, window, shift, q_axis)
        if seg is not None:
            same = seg[0] == seg[1]
            mask = same if mask is None else mask & same
    p = jnp.exp(s - lse)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    p_tilde = p
    if dropout_p > 0.0:
        keep = _keep_mask_2d(
            meta_ref[0], meta_ref[4] + b_idx, meta_ref[3] + h_idx,
            meta_ref[1] + q0, meta_ref[2] + k0, nq, nk, dropout_p, q_axis)
        p_tilde = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_p))
    return p, p_tilde, dcap


def _bwd_dq_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, alibi_ref,
                   meta_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
                   *, scale, causal, window, block_q, block_k,
                   num_kv_blocks, qk_shift=0, dropout_p=0.0,
                   logit_softcap=0.0, traced_offsets=False, diagonal=None):
    bi = pl.program_id(0)
    hi = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    shift = _shift_of(qk_shift, meta_ref, traced_offsets)

    def _tile(rows, keys, masked):
        # [rn, cn] tiles: lse / delta come lane-broadcast, a column a row
        (r0, rn), (c0, cn) = rows, keys
        r, c = pl.ds(r0, rn), pl.ds(c0, cn)
        q = q_ref[0, 0, r, :]
        k = k_ref[0, 0, c, :]
        v = v_ref[0, 0, c, :]
        do = do_ref[0, 0, r, :]
        lse = _across_lanes(lse_ref[0, 0, r, :], cn)
        delta = _across_lanes(delta_ref[0, 0, r, :], cn)
        seg = None
        if qseg_ref is not None:
            seg = (qseg_ref[0, r, 0][:, None], kseg_ref[0, 0, c][None, :])
        slope = None if alibi_ref is None else alibi_ref[0, 0, 0]
        p, p_tilde, dcap = _recompute_p(
            q, k, lse, seg, slope, meta_ref, q_start + r0, k_start + c0,
            bi, hi, scale=scale, causal=causal, window=window, shift=shift,
            dropout_p=dropout_p, logit_softcap=logit_softcap, masked=masked)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p_tilde * dp - p * delta) * dcap * scale
        dq_scr[r, :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _dispatch_masked(_tile, qseg_ref is not None, q_start, k_start,
                     block_q, block_k, causal, window, shift, diagonal)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, alibi_ref,
                    meta_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_scr, dv_scr,
                    *, scale, causal, window, block_q, block_k,
                    num_q_blocks, group, qk_shift=0, dropout_p=0.0,
                    logit_softcap=0.0, traced_offsets=False, diagonal=None):
    # grid (b, hk, nk, group, nq): the scratch accumulates over the whole
    # (group, q-block) inner sweep, so GQA/MQA grads never materialise
    # per-q-head dk/dv in HBM.
    bi = pl.program_id(0)
    ki = pl.program_id(2)
    g = pl.program_id(3)
    qi = pl.program_id(4)
    # global q-head index: the dropout mask is keyed by q head
    h_idx = pl.program_id(1) * group + g

    @pl.when(jnp.logical_and(g == 0, qi == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    shift = _shift_of(qk_shift, meta_ref, traced_offsets)

    def _tile(rows, keys, masked):
        # [cn, rn] tiles, keys down the sublanes: the orientation both
        # accumulations contract (dV = Pᵀ dO, dK = dSᵀ Q), so no tile is
        # transposed; lse / delta are [1, rn] lane vectors
        (r0, rn), (c0, cn) = rows, keys
        r, c = pl.ds(r0, rn), pl.ds(c0, cn)
        q = q_ref[0, 0, r, :]
        k = k_ref[0, 0, c, :]
        v = v_ref[0, 0, c, :]
        do = do_ref[0, 0, r, :]
        lse = lse_ref[0, 0, :, r]
        delta = delta_ref[0, 0, :, r]
        seg = None
        if qseg_ref is not None:
            seg = (qseg_ref[0, 0, r][None, :], kseg_ref[0, c, 0][:, None])
        slope = None if alibi_ref is None else alibi_ref[0, 0, 0]
        p, p_tilde, dcap = _recompute_p(
            q, k, lse, seg, slope, meta_ref, q_start + r0, k_start + c0,
            bi, h_idx, scale=scale, causal=causal, window=window,
            shift=shift, dropout_p=dropout_p, logit_softcap=logit_softcap,
            masked=masked, q_axis=1)
        dv_scr[c, :] += jax.lax.dot_general(
            p_tilde.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [cn, d]
        dp = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p_tilde * dp - p * delta) * dcap * scale          # [cn, rn]
        dk_scr[c, :] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [cn, d]

    _dispatch_masked(_tile, qseg_ref is not None, q_start, k_start,
                     block_q, block_k, causal, window, shift, diagonal)

    @pl.when(jnp.logical_and(g == group - 1, qi == num_q_blocks - 1))
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(res, do, *, scale, causal, window, block_q, block_k, qk_shift=0,
         dropout_p=0.0, logit_softcap=0.0, traced_offsets=False):
    (q, k, v, o, lse, q_segment_ids, kv_segment_ids, alibi_slopes,
     meta) = res
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    group = hq // hk
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    has_seg = q_segment_ids is not None
    has_alibi = alibi_slopes is not None
    has_meta = meta is not None
    shift = None if traced_offsets else qk_shift    # as in _fwd
    split_by = functools.partial(_diagonal_pieces, block_q, block_k, causal,
                                 window, shift, has_seg)

    # delta = rowsum(do * o); dq reads lse / delta lane-broadcast (a
    # column a q row), dk/dv as the [b, h, 1, sq] lane vectors they are
    delta = jnp.einsum("bhqd,bhqd->bhq", do.astype(jnp.float32),
                       o.astype(jnp.float32))
    lse4 = jnp.broadcast_to(lse[..., None], (b, hq, sq, _LANES))
    delta4 = jnp.broadcast_to(delta[..., None], (b, hq, sq, _LANES))

    common = dict(scale=scale, causal=causal, window=window,
                  block_q=block_q, block_k=block_k, qk_shift=qk_shift,
                  dropout_p=dropout_p, logit_softcap=logit_softcap,
                  traced_offsets=traced_offsets)

    band = (causal, window, shift, has_seg, has_alibi, has_meta)

    # ---- dq: grid (b, hq, nq, nk) ----
    args = _operands(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes,
                     meta) + [do, lse4, delta4]
    dq_call = pl.pallas_call(
        _mk_kernel(_bwd_dq_kernel, has_seg, has_alibi, has_meta,
                   num_kv_blocks=nk, diagonal=split_by(), **common),
        grid=(b, hq, nq, nk),
        in_specs=_q_major_specs(d, group, block_q, block_k, nk, *band,
                                bwd=True),
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h, qi, ki: (b_, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
        name="flash_dq",
    )
    with jax.named_scope("flash_dq"):
        dq = dq_call(*args)

    # ---- dk/dv: grid (b, hk, nk, group, nq) — the (group, q-block) inner
    # sweep accumulates in VMEM scratch, writing dk/dv once per kv head ----
    args = _operands(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes,
                     meta, q_axis=1) + [do, lse[:, :, None, :],
                                        delta[:, :, None, :]]
    dkv_call = pl.pallas_call(
        _mk_kernel(_bwd_dkv_kernel, has_seg, has_alibi, has_meta,
                   num_q_blocks=nq, group=group,
                   diagonal=split_by(by_keys=True), **common),
        grid=(b, hk, nk, group, nq),
        in_specs=_kv_major_specs(d, group, block_q, block_k, nq, *band),
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, hkv, ki, g, qi: (b_, hkv, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, hkv, ki, g, qi: (b_, hkv, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="flash_dkv",
    )
    with jax.named_scope("flash_dkv"):
        dk, dv = dkv_call(*args)
    return (dq, dk, dv, None, None, None, None)


# ---------------------------------------------------------------------------
# public API (BSHD, matching the model layer / reference flash-attn layout)
# ---------------------------------------------------------------------------

def _check_blocks(block_q, block_k, sq):
    """On TPU lse travels along the lanes in q blocks and the kv segment
    ids in kv blocks: both tile by 128 (a q block may instead be the
    whole padded q length, as short sequences make it)."""
    if _interpret():
        return
    if (block_q % 8 or block_k % _LANES
            or (block_q % _LANES and block_q != _round_up(sq, block_q))):
        raise ValueError(
            f"on TPU block_q must be a multiple of 128 (or a multiple of 8 "
            f"that covers the whole q length) and block_k a multiple of "
            f"128; got ({block_q}, {block_k}) for {sq} queries")


def _pad_seq(x, block, axis, value=0):
    s = x.shape[axis]
    rem = s % block
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, block - rem)
    return jnp.pad(x, pad, constant_values=value)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14, 15))
def _flash(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes, meta,
           scale, causal, window, block_q, block_k, qk_shift, dropout_p,
           logit_softcap, traced_offsets):
    o, _ = _fwd(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes, meta,
                scale, causal, window, block_q, block_k, qk_shift, dropout_p,
                logit_softcap, traced_offsets)
    return o


def _flash_fwd(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes, meta,
               scale, causal, window, block_q, block_k, qk_shift, dropout_p,
               logit_softcap, traced_offsets):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _fwd(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes, meta,
                  scale, causal, window, block_q, block_k, qk_shift,
                  dropout_p, logit_softcap, traced_offsets)
    # Named so the selective-remat policies (utils/remat.py 'save_attn*')
    # can save the kernel's residuals and skip re-running the fwd kernel
    # in the backward pass; identity outside jax.checkpoint.  The SAME
    # named value must be both the primal output and the residual —
    # naming only a residual copy leaves the primal path unsaved, and
    # its recompute re-runs the forward kernel anyway.
    o = checkpoint_name(o, "attn_ctx")
    return o, (q, k, v, o, checkpoint_name(lse, "attn_lse"),
               q_segment_ids, kv_segment_ids, alibi_slopes, meta)


def _flash_bwd(scale, causal, window, block_q, block_k, qk_shift, dropout_p,
               logit_softcap, traced_offsets, res, g):
    return _bwd(res, g, scale=scale, causal=causal, window=window,
                block_q=block_q, block_k=block_k, qk_shift=qk_shift,
                dropout_p=dropout_p, logit_softcap=logit_softcap,
                traced_offsets=traced_offsets)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _make_meta(dropout_p, dropout_seed, q_offset, k_offset,
               h_offset=0, b_offset=0):
    """int32 [5] (seed, q_off, k_off, h_off, b_off) — or None when every
    feature that needs it is off, keeping the plain kernel signature
    unchanged.  h/b offsets are the GLOBAL head/batch indices of local
    row 0: under tensor/sequence/data parallelism they decorrelate the
    dropout hash across shards (and make CP bit-match single-device)."""
    static_off = all(isinstance(x, int) and x == 0
                     for x in (q_offset, k_offset, h_offset, b_offset))
    if dropout_p == 0.0 and static_off:
        return None
    seed = 0 if dropout_seed is None else dropout_seed
    return jnp.stack([
        jnp.asarray(x, jnp.int32).reshape(())
        for x in (seed, q_offset, k_offset, h_offset, b_offset)
    ])


def _alignment(sq, sk, q_offset, k_offset):
    """(qk_shift, traced_offsets): the q-to-k alignment the kernels are
    built with — bottom-right (sk - sq) plus the q / k offsets where they
    are Python ints, so the grids can plan with it — and whether the
    offsets are traced values the kernels must add from ``meta`` at run
    time (the context-parallel ring)."""
    if isinstance(q_offset, int) and isinstance(k_offset, int):
        return sk - sq + q_offset - k_offset, False
    return sk - sq, True


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Tuple[int, int] = (-1, -1),
    scale: Optional[float] = None,
    q_segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    alibi_slopes: Optional[jax.Array] = None,
    dropout_p: float = 0.0,
    dropout_seed=None,
    q_offset=0,
    k_offset=0,
    h_offset=0,
    b_offset=0,
    return_lse: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    logit_softcap: float = 0.0,
):
    """[b, s, h, d] flash attention (see module docstring).

    ``alibi_slopes``: [num_q_heads] f32 per-head ALiBi slopes (additive
    -slope*|i-j| bias, reference ops/flash_attn.py:411-413).
    ``dropout_p``/``dropout_seed``: attention dropout on the post-softmax
    probabilities (reference ops/flash_attn.py:418-423) via the stateless
    coordinate hash in ops/_common.py — same seed, same mask, on every
    backend.  ``q_offset``/``k_offset``: GLOBAL positions of this q/kv
    chunk (traced ints allowed; used by the context-parallel ring so
    causality, windows, ALiBi and dropout see global geometry).
    ``h_offset``/``b_offset``: global head/batch index of local row 0
    (decorrelates the dropout hash across tp/dp shards inside shard_map).
    With ``return_lse`` returns (out, lse[b, h, s]); that path is
    forward-only (used by the context-parallel ring, which defines its
    own VJP around the merged result).
    ``block_q``/``block_k``: tile sizes, ``_block_sizes``' by default.
    On TPU an explicit ``block_q`` must be a multiple of 128 — lse leaves
    the kernel along the lanes, a q block at a time — unless it covers
    the whole (padded) q length, where any multiple of 8 does; ``block_k``
    a multiple of 128.  Anything else raises ValueError.
    """
    b, sq, hq, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if hq % hk != 0:
        raise ValueError(
            f"num q heads ({hq}) must be a multiple of kv heads ({hk})")
    if (q_segment_ids is None) != (kv_segment_ids is None):
        raise ValueError(
            "q_segment_ids and kv_segment_ids must be provided together")
    if alibi_slopes is not None:
        if alibi_slopes.shape != (hq,):
            raise ValueError(
                f"alibi_slopes must have shape ({hq},) — one slope per q "
                f"head — got {alibi_slopes.shape}")
        # slopes are hyperparameters, not weights: stop_gradient keeps the
        # pallas and xla backends' gradients identical
        alibi_slopes = jax.lax.stop_gradient(alibi_slopes)
    if scale is None:
        scale = d ** -0.5
    bq0, bk0 = _block_sizes(sq, sk)
    block_q = block_q or bq0
    block_k = block_k or bk0
    _check_blocks(block_q, block_k, sq)

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q or pad_k or q_segment_ids is not None:
        # Padded positions get distinct negative segment ids so they match
        # nothing (padding-safe); real rows keep user segment ids.
        if q_segment_ids is None:
            q_segment_ids = jnp.zeros((b, sq), jnp.int32)
            kv_segment_ids = jnp.zeros((b, sk), jnp.int32)
        q_segment_ids = _pad_seq(q_segment_ids, block_q, 1, value=-1)
        kv_segment_ids = _pad_seq(kv_segment_ids, block_k, 1, value=-2)
    q = _pad_seq(q, block_q, 1).swapaxes(1, 2)   # -> BHSD
    k = _pad_seq(k, block_k, 1).swapaxes(1, 2)
    v = _pad_seq(v, block_k, 1).swapaxes(1, 2)
    meta = _make_meta(dropout_p, dropout_seed, q_offset, k_offset,
                      h_offset, b_offset)

    qk_shift, traced_offsets = _alignment(sq, sk, q_offset, k_offset)

    if return_lse:
        o, lse = _fwd(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes,
                      meta, scale, causal, window, block_q, block_k,
                      qk_shift=qk_shift, dropout_p=dropout_p,
                      logit_softcap=logit_softcap,
                      traced_offsets=traced_offsets)
        return o.swapaxes(1, 2)[:, :sq], lse[:, :, :sq]
    o = _flash(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes, meta,
               scale, causal, window, block_q, block_k, qk_shift, dropout_p,
               float(logit_softcap), traced_offsets)
    return o.swapaxes(1, 2)[:, :sq]


def flash_attention_bwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    o: jax.Array,
    lse: jax.Array,
    do: jax.Array,
    *,
    causal: bool = True,
    window: Tuple[int, int] = (-1, -1),
    scale: Optional[float] = None,
    q_segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    alibi_slopes: Optional[jax.Array] = None,
    dropout_p: float = 0.0,
    dropout_seed=None,
    q_offset=0,
    k_offset=0,
    h_offset=0,
    b_offset=0,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    logit_softcap: float = 0.0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Standalone flash backward: (dq, dk, dv) from saved (o, lse).

    BSHD in/out; lse is [b, h, sq].  Exposed for context-parallel ring
    attention, whose custom VJP evaluates each ring step's backward with
    the GLOBAL lse/o (the exact decomposition the reference implements at
    ring_attn.py:130-271 with reverse kv rotation).  Dropout/offset
    arguments follow :func:`flash_attention` — pass the SAME values the
    forward used so the regenerated dropout mask matches exactly; the
    block sizes are held to the same rule (on TPU multiples of 128, or a
    ``block_q`` that covers the whole q length; else ValueError).
    """
    b, sq, hq, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if scale is None:
        scale = d ** -0.5
    bq0, bk0 = _block_sizes(sq, sk)
    block_q = block_q or bq0
    block_k = block_k or bk0
    _check_blocks(block_q, block_k, sq)

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q or pad_k or q_segment_ids is not None:
        if q_segment_ids is None:
            q_segment_ids = jnp.zeros((b, sq), jnp.int32)
            kv_segment_ids = jnp.zeros((b, sk), jnp.int32)
        q_segment_ids = _pad_seq(q_segment_ids, block_q, 1, value=-1)
        kv_segment_ids = _pad_seq(kv_segment_ids, block_k, 1, value=-2)
    qT = _pad_seq(q, block_q, 1).swapaxes(1, 2)
    kT = _pad_seq(k, block_k, 1).swapaxes(1, 2)
    vT = _pad_seq(v, block_k, 1).swapaxes(1, 2)
    oT = _pad_seq(o, block_q, 1).swapaxes(1, 2)
    doT = _pad_seq(do, block_q, 1).swapaxes(1, 2)
    lseP = _pad_seq(lse, block_q, 2)

    meta = _make_meta(dropout_p, dropout_seed, q_offset, k_offset,
                      h_offset, b_offset)
    res = (qT, kT, vT, oT, lseP, q_segment_ids, kv_segment_ids,
           alibi_slopes, meta)
    qk_shift, traced_offsets = _alignment(sq, sk, q_offset, k_offset)
    dq, dk, dv, _, _, _, _ = _bwd(res, doT, scale=scale, causal=causal,
                                  window=window, block_q=block_q,
                                  block_k=block_k, qk_shift=qk_shift,
                                  dropout_p=dropout_p,
                                  logit_softcap=logit_softcap,
                                  traced_offsets=traced_offsets)
    return (dq.swapaxes(1, 2)[:, :sq], dk.swapaxes(1, 2)[:, :sk],
            dv.swapaxes(1, 2)[:, :sk])


def segment_ids_from_positions(positions: jax.Array) -> jax.Array:
    """Packed-sequence segment ids from position_ids (reference
    ``FlashAttnVarlenPositionIdsXla`` ops/flash_attn.py:173-216 derives
    cu_seqlens from positions resetting to 0)."""
    starts = (positions == 0).astype(jnp.int32)
    return jnp.cumsum(starts, axis=-1) - 1
