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
  LSE travels as [b, h, sq, 128] lane-broadcast and is sliced to
  [b, h, sq] at the wrapper.  Segment ids broadcast to (b, sq, 128) for
  q and (b, 8, sk) for kv (sublane-broadcast), the standard trick.
Backward = two kernels (flash-attn standard): dq over q blocks looping
kv; dk/dv over kv blocks looping q; both recompute P from the saved LSE.
Public API stays BSHD to match the model layer ([b, s, h, d]).
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


def _keep_mask_2d(seed, b_idx, h_idx, q0, k0, block_q, block_k,
                  dropout_p: float):
    """[block_q, block_k] dropout keep mask from GLOBAL coordinates.

    Same formula as ops._common.dropout_keep (the XLA path) expressed via
    2-D broadcasted iota so it lowers on TPU: the mask is a pure function
    of (seed, batch, head, absolute q, absolute k), hence bit-identical
    across the forward and both backward kernels, across block-size
    choices, and across context-parallel ring steps."""
    base = mix32(jnp.uint32(seed).astype(jnp.uint32)
                 + jnp.uint32(b_idx) * jnp.uint32(_B_PRIME)
                 + jnp.uint32(h_idx))
    gq = (q0 + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)).astype(jnp.uint32)
    gk = (k0 + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)).astype(jnp.uint32)
    bits = mix32(mix32(base ^ gq) ^ mix32(gk * jnp.uint32(_K_PRIME)))
    threshold = jnp.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    return bits >= threshold


def _block_sizes(sq: int, sk: int) -> Tuple[int, int]:
    """TPU-legal defaults: block_q lands in sublane positions (multiple of
    8), block_k lands in lane positions of the kv-segment block (multiple
    of 128); the wrapper pads sequences up to a block multiple.  1024x1024
    measured fastest on v5e at seq 2048 (docs/PERF.md) — fewer grid steps
    amortise the per-tile mask/softmax VPU overhead.  A block that divides
    the sequence is preferred over a larger one: padding fabricates
    segment ids, which disables the interior-tile mask-skip fast path."""
    def pick(s: int, unit: int) -> int:
        for cand in (1024, 512):
            if s % cand == 0:
                return cand
        return min(1024, _round_up(s, unit))
    return pick(sq, 8), pick(sk, _LANES)


def _band_mask(q_start, k_start, block_q, block_k, causal, window,
               qk_shift=0):
    """Positional (causal + sliding window) mask for one tile, or None.

    ``qk_shift = sk - sq`` bottom-right aligns the geometry for sq != sk
    (flash-attn semantics: the LAST query aligns with the LAST key), the
    same shift the ALiBi bias uses — mask and bias always agree."""
    left, right = window
    if not causal and left < 0 and right < 0:
        return None
    q_pos = q_start + qk_shift + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if left >= 0:
        mask &= k_pos >= q_pos - left
    if right >= 0:
        mask &= k_pos <= q_pos + right
    return mask


def _alibi_bias(slope, q_start, k_start, block_q, block_k, qk_shift):
    """Additive ALiBi bias -slope * |q_pos + (sk - sq) - k_pos| for one
    tile — bottom-right aligned like the reference (alibi_slopes through
    every flash op, ops/flash_attn.py:411-413), so decode-style sq != sk
    keeps the most recent keys least penalised."""
    q_pos = q_start + qk_shift + jax.lax.broadcasted_iota(
        jnp.float32, (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.float32,
                                               (block_q, block_k), 1)
    return -slope * jnp.abs(q_pos - k_pos)


def _block_should_run(q_start, k_start, block_q, block_k, causal, window,
                      qk_shift=0):
    left, right = window
    q_hi = q_start + qk_shift + block_q - 1
    q_lo = q_start + qk_shift
    run = True
    if causal:
        run = jnp.logical_and(run, k_start <= q_hi)
    if left >= 0:
        run = jnp.logical_and(run, k_start + block_k - 1 >= q_lo - left)
    if right >= 0:
        run = jnp.logical_and(run, k_start <= q_hi + right)
    return run


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


def _dispatch_masked(compute, has_seg, q_start, k_start, block_q, block_k,
                     causal, window, shift):
    """Run ``compute(masked)`` for one tile: skipped entirely outside the
    band, mask-free on fully-interior tiles (positional masks only — any
    segment ids force the masked path), masked otherwise."""
    run = _block_should_run(q_start, k_start, block_q, block_k,
                            causal, window, shift)
    if not has_seg and (causal or window[0] >= 0 or window[1] >= 0):
        inside = _block_fully_inside(q_start, k_start, block_q, block_k,
                                     causal, window, shift)
        pl.when(jnp.logical_and(run, inside))(
            functools.partial(compute, False))
        pl.when(jnp.logical_and(run, jnp.logical_not(inside)))(
            functools.partial(compute, True))
    else:
        pl.when(run)(functools.partial(compute, True))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, alibi_ref, meta_ref,
                o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, scale, causal, window, block_q, block_k, num_kv_blocks,
                qk_shift=0, dropout_p=0.0, logit_softcap=0.0):
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
    # meta = [seed, q_off, k_off, h_off, b_off] (see _make_meta): the
    # dynamic global q/k offsets (context-parallel ring chunks) fold
    # into the positional shift; h/b offsets key the dropout hash
    shift = qk_shift
    if meta_ref is not None:
        shift = shift + meta_ref[1] - meta_ref[2]

    def _compute(masked):
        # dots take the inputs' native dtype (bf16 in training) and
        # accumulate in f32 — an f32 input cast here would knock the MXU
        # off its native bf16 path (~8x slower on v5e); softmax math
        # stays in f32 throughout
        q = q_ref[0, 0, :, :]                              # [bq, d]
        k = k_ref[0, 0, :, :]                              # [bk, d]
        v = v_ref[0, 0, :, :]                              # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [bq, bk]
        if logit_softcap > 0.0:
            # Gemma2 score capping: c * tanh(s / c), after the scale and
            # before alibi/mask (matches the XLA reference)
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        if alibi_ref is not None:
            s = s + _alibi_bias(alibi_ref[0, 0, 0], q_start, k_start,
                                block_q, block_k, shift)

        mask = None
        if masked:
            mask = _band_mask(q_start, k_start, block_q, block_k, causal,
                              window, shift)
            if qseg_ref is not None:
                qs = qseg_ref[0, :, 0]                      # [bq]
                ks = kseg_ref[0, 0, :]                      # [bk]
                seg = qs[:, None] == ks[None, :]
                mask = seg if mask is None else mask & seg
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0]                                # [bq]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev == NEG_INF, 0.0, alpha)
        # dropout applies to the accumulated P@V only: l (and so the lse)
        # stays the UNdropped softmax normaliser — exactly flash-attn's
        # decomposition, and what the backward recomputation assumes
        l_new = alpha * l_scr[:, 0] + jnp.sum(p, axis=1)
        p_v = p
        if dropout_p > 0.0:
            keep = _keep_mask_2d(
                meta_ref[0], meta_ref[4] + bi, meta_ref[3] + hi,
                meta_ref[1] + q_start, meta_ref[2] + k_start,
                block_q, block_k, dropout_p)
            p_v = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_p))
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p_v.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    _dispatch_masked(_compute, qseg_ref is not None, q_start, k_start,
                     block_q, block_k, causal, window, shift)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        m = m_scr[:, 0]
        l = l_scr[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
        lse_ref[0, 0, :, :] = jnp.broadcast_to(lse[:, None], lse_ref.shape[2:])


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


def _alibi_operand(alibi_slopes):
    """[h] slopes -> TPU-legal (h, 8, 128) broadcast for per-head blocks."""
    h = alibi_slopes.shape[0]
    return jax.lax.broadcast_in_dim(
        alibi_slopes.astype(jnp.float32), (h, _SUBLANES, _LANES), (0,))


def _fwd(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes, meta, scale,
         causal, window, block_q, block_k, qk_shift=0, dropout_p=0.0,
         logit_softcap=0.0):
    """q,k,v in BHSD.  Returns (o BHSD, lse [b,h,sq] f32).

    ``meta``: optional int32 [5] = (dropout seed, global q offset,
    global k offset, global head offset, global batch offset) — SMEM
    scalars, traced (no recompile per seed/offset); layout owned by
    _make_meta."""
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    group = hq // hk
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    has_seg = q_segment_ids is not None
    has_alibi = alibi_slopes is not None
    has_meta = meta is not None

    kernel = _mk_kernel(
        _fwd_kernel, has_seg, has_alibi, has_meta,
        scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_kv_blocks=nk,
        qk_shift=qk_shift, dropout_p=dropout_p,
        logit_softcap=logit_softcap)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b_, h, qi, ki: (b_, h // group, ki, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b_, h, qi, ki: (b_, h // group, ki, 0)),
    ]
    args = [q, k, v]
    if has_seg:
        qseg = jax.lax.broadcast_in_dim(
            q_segment_ids, (b, sq, _LANES), (0, 1))
        kseg = jax.lax.broadcast_in_dim(
            kv_segment_ids, (b, _SUBLANES, sk), (0, 2))
        in_specs += [
            pl.BlockSpec((1, block_q, _LANES),
                         lambda b_, h, qi, ki: (b_, qi, 0)),
            pl.BlockSpec((1, _SUBLANES, block_k),
                         lambda b_, h, qi, ki: (b_, 0, ki)),
        ]
        args += [qseg, kseg]
    if has_alibi:
        in_specs.append(pl.BlockSpec((1, _SUBLANES, _LANES),
                                     lambda b_, h, qi, ki: (h, 0, 0)))
        args.append(_alibi_operand(alibi_slopes))
    if has_meta:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(meta)

    fwd_call = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda b_, h, qi, ki: (b_, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, _LANES), jnp.float32),
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
        o, lse4 = fwd_call(*args)
    return o, lse4[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _recompute_p(q_ref, k_ref, qseg_ref, kseg_ref, alibi_ref, meta_ref, lse,
                 q_start, k_start, b_idx, h_idx, *, scale, causal, window,
                 block_q, block_k, qk_shift=0, dropout_p=0.0,
                 logit_softcap=0.0, masked=True):
    """Rebuild (p, p_tilde, q, k) for one tile from the saved lse.

    Returns (p, p_tilde, q, k, dcap): ``p`` is the exact softmax tile;
    ``p_tilde`` is the dropout-scaled tile actually used in the forward
    P@V (equal to ``p`` when dropout is off); ``dcap`` is the softcap
    derivative factor 1 - tanh^2 (1.0 when capping is off) the caller
    must chain into dS.  The VJP through dropped softmax is
        dS = P̃ ∘ (dO Vᵀ) − P ∘ delta
    with delta = rowsum(dO ∘ O) — note P̃ multiplies the dO Vᵀ term and
    the plain P multiplies delta."""
    shift = qk_shift
    if meta_ref is not None:
        shift = shift + meta_ref[1] - meta_ref[2]
    q = q_ref[0, 0, :, :]
    k = k_ref[0, 0, :, :]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    dcap = 1.0
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
        # d(c*tanh(x/c))/dx = 1 - tanh^2 = 1 - (s_capped / c)^2, taken
        # BEFORE the alibi bias lands on s
        dcap = 1.0 - (s / logit_softcap) ** 2
    if alibi_ref is not None:
        s = s + _alibi_bias(alibi_ref[0, 0, 0], q_start, k_start,
                            block_q, block_k, shift)
    mask = None
    if masked:
        mask = _band_mask(q_start, k_start, block_q, block_k, causal,
                          window, shift)
        if qseg_ref is not None:
            seg = qseg_ref[0, :, 0][:, None] == kseg_ref[0, 0, :][None, :]
            mask = seg if mask is None else mask & seg
    p = jnp.exp(s - lse[:, None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    p_tilde = p
    if dropout_p > 0.0:
        keep = _keep_mask_2d(
            meta_ref[0], meta_ref[4] + b_idx, meta_ref[3] + h_idx,
            meta_ref[1] + q_start, meta_ref[2] + k_start,
            block_q, block_k, dropout_p)
        p_tilde = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_p))
    return p, p_tilde, q, k, dcap


def _bwd_dq_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, alibi_ref,
                   meta_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
                   *, scale, causal, window, block_q, block_k,
                   num_kv_blocks, qk_shift=0, dropout_p=0.0,
                   logit_softcap=0.0):
    bi = pl.program_id(0)
    hi = pl.program_id(1)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    shift = qk_shift
    if meta_ref is not None:
        shift = shift + meta_ref[1] - meta_ref[2]

    def _compute(masked):
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        do = do_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        p, p_tilde, q, k, dcap = _recompute_p(
            q_ref, k_ref, qseg_ref, kseg_ref, alibi_ref, meta_ref,
            lse, q_start, k_start, bi, hi, scale=scale,
            causal=causal, window=window, block_q=block_q,
            block_k=block_k, qk_shift=qk_shift, dropout_p=dropout_p,
            logit_softcap=logit_softcap, masked=masked)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p_tilde * dp - p * delta[:, None]) * dcap * scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _dispatch_masked(_compute, qseg_ref is not None, q_start, k_start,
                     block_q, block_k, causal, window, shift)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scr[...].astype(dq_ref.dtype)





def _bwd_dkv_kernel(q_ref, k_ref, v_ref, qseg_ref, kseg_ref, alibi_ref,
                    meta_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_scr, dv_scr,
                    *, scale, causal, window, block_q, block_k,
                    num_q_blocks, group, qk_shift=0, dropout_p=0.0,
                    logit_softcap=0.0):
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
    shift = qk_shift
    if meta_ref is not None:
        shift = shift + meta_ref[1] - meta_ref[2]

    def _compute(masked):
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        do = do_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        p, p_tilde, q, k, dcap = _recompute_p(
            q_ref, k_ref, qseg_ref, kseg_ref, alibi_ref, meta_ref,
            lse, q_start, k_start, bi, h_idx, scale=scale,
            causal=causal, window=window, block_q=block_q,
            block_k=block_k, qk_shift=qk_shift, dropout_p=dropout_p,
            logit_softcap=logit_softcap, masked=masked)
        dv_scr[...] += jax.lax.dot_general(
            p_tilde.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [bk, d]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p_tilde * dp - p * delta[:, None]) * dcap * scale  # [bq, bk]
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [bk, d]

    _dispatch_masked(_compute, qseg_ref is not None, q_start, k_start,
                     block_q, block_k, causal, window, shift)

    @pl.when(jnp.logical_and(g == group - 1, qi == num_q_blocks - 1))
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[...].astype(dv_ref.dtype)





def _bwd(res, do, *, scale, causal, window, block_q, block_k, qk_shift=0,
         dropout_p=0.0, logit_softcap=0.0):
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

    # delta = rowsum(do * o); lane-broadcast alongside lse for the kernels
    delta = jnp.einsum("bhqd,bhqd->bhq", do.astype(jnp.float32),
                       o.astype(jnp.float32))
    lse4 = jnp.broadcast_to(lse[..., None], (b, hq, sq, _LANES))
    delta4 = jnp.broadcast_to(delta[..., None], (b, hq, sq, _LANES))

    common = dict(scale=scale, causal=causal, window=window,
                  block_q=block_q, block_k=block_k, qk_shift=qk_shift,
                  dropout_p=dropout_p, logit_softcap=logit_softcap)

    if has_seg:
        qseg = jax.lax.broadcast_in_dim(
            q_segment_ids, (b, sq, _LANES), (0, 1))
        kseg = jax.lax.broadcast_in_dim(
            kv_segment_ids, (b, _SUBLANES, sk), (0, 2))

    # ---- dq: grid (b, hq, nq, nk) ----
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b_, h, qi, ki: (b_, h // group, ki, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b_, h, qi, ki: (b_, h // group, ki, 0)),
    ]
    args = [q, k, v]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, block_q, _LANES),
                         lambda b_, h, qi, ki: (b_, qi, 0)),
            pl.BlockSpec((1, _SUBLANES, block_k),
                         lambda b_, h, qi, ki: (b_, 0, ki)),
        ]
        args += [qseg, kseg]
    if has_alibi:
        in_specs.append(pl.BlockSpec((1, _SUBLANES, _LANES),
                                     lambda b_, h, qi, ki: (h, 0, 0)))
        args.append(_alibi_operand(alibi_slopes))
    if has_meta:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(meta)
    in_specs += [
        pl.BlockSpec((1, 1, block_q, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
        pl.BlockSpec((1, 1, block_q, _LANES),
                     lambda b_, h, qi, ki: (b_, h, qi, 0)),
        pl.BlockSpec((1, 1, block_q, _LANES),
                     lambda b_, h, qi, ki: (b_, h, qi, 0)),
    ]
    args += [do, lse4, delta4]
    dq_call = pl.pallas_call(
        _mk_kernel(_bwd_dq_kernel, has_seg, has_alibi, has_meta,
                   num_kv_blocks=nk, **common),
        grid=(b, hq, nq, nk),
        in_specs=in_specs,
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
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda b_, hkv, ki, g, qi: (b_, hkv * group + g, qi, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b_, hkv, ki, g, qi: (b_, hkv, ki, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda b_, hkv, ki, g, qi: (b_, hkv, ki, 0)),
    ]
    args = [q, k, v]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, block_q, _LANES),
                         lambda b_, hkv, ki, g, qi: (b_, qi, 0)),
            pl.BlockSpec((1, _SUBLANES, block_k),
                         lambda b_, hkv, ki, g, qi: (b_, 0, ki)),
        ]
        args += [qseg, kseg]
    if has_alibi:
        in_specs.append(pl.BlockSpec(
            (1, _SUBLANES, _LANES),
            lambda b_, hkv, ki, g, qi: (hkv * group + g, 0, 0)))
        args.append(_alibi_operand(alibi_slopes))
    if has_meta:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(meta)
    in_specs += [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda b_, hkv, ki, g, qi: (b_, hkv * group + g, qi, 0)),
        pl.BlockSpec((1, 1, block_q, _LANES),
                     lambda b_, hkv, ki, g, qi: (b_, hkv * group + g, qi, 0)),
        pl.BlockSpec((1, 1, block_q, _LANES),
                     lambda b_, hkv, ki, g, qi: (b_, hkv * group + g, qi, 0)),
    ]
    args += [do, lse4, delta4]
    dkv_call = pl.pallas_call(
        _mk_kernel(_bwd_dkv_kernel, has_seg, has_alibi, has_meta,
                   num_q_blocks=nq, group=group, **common),
        grid=(b, hk, nk, group, nq),
        in_specs=in_specs,
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

def _pad_seq(x, block, axis, value=0):
    s = x.shape[axis]
    rem = s % block
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, block - rem)
    return jnp.pad(x, pad, constant_values=value)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14))
def _flash(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes, meta,
           scale, causal, window, block_q, block_k, qk_shift, dropout_p,
           logit_softcap):
    o, _ = _fwd(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes, meta,
                scale, causal, window, block_q, block_k, qk_shift, dropout_p,
                logit_softcap)
    return o


def _flash_fwd(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes, meta,
               scale, causal, window, block_q, block_k, qk_shift, dropout_p,
               logit_softcap):
    from jax.ad_checkpoint import checkpoint_name

    o, lse = _fwd(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes, meta,
                  scale, causal, window, block_q, block_k, qk_shift,
                  dropout_p, logit_softcap)
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
               logit_softcap, res, g):
    return _bwd(res, g, scale=scale, causal=causal, window=window,
                block_q=block_q, block_k=block_k, qk_shift=qk_shift,
                dropout_p=dropout_p, logit_softcap=logit_softcap)


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
    if not _interpret() and (block_q % 8 or block_k % _LANES):
        raise ValueError(
            f"on TPU block_q must be a multiple of 8 and block_k a multiple "
            f"of 128; got ({block_q}, {block_k})")

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

    if return_lse:
        o, lse = _fwd(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes,
                      meta, scale, causal, window, block_q, block_k,
                      qk_shift=sk - sq, dropout_p=dropout_p,
                      logit_softcap=logit_softcap)
        return o.swapaxes(1, 2)[:, :sq], lse[:, :, :sq]
    o = _flash(q, k, v, q_segment_ids, kv_segment_ids, alibi_slopes, meta,
               scale, causal, window, block_q, block_k, sk - sq, dropout_p,
               float(logit_softcap))
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
    forward used so the regenerated dropout mask matches exactly.
    """
    b, sq, hq, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if scale is None:
        scale = d ** -0.5
    bq0, bk0 = _block_sizes(sq, sk)
    block_q = block_q or bq0
    block_k = block_k or bk0

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
    dq, dk, dv, _, _, _, _ = _bwd(res, doT, scale=scale, causal=causal,
                                  window=window, block_q=block_q,
                                  block_k=block_k, qk_shift=sk - sq,
                                  dropout_p=dropout_p,
                                  logit_softcap=logit_softcap)
    return (dq.swapaxes(1, 2)[:, :sq], dk.swapaxes(1, 2)[:, :sk],
            dv.swapaxes(1, 2)[:, :sk])


def segment_ids_from_positions(positions: jax.Array) -> jax.Array:
    """Packed-sequence segment ids from position_ids (reference
    ``FlashAttnVarlenPositionIdsXla`` ops/flash_attn.py:173-216 derives
    cu_seqlens from positions resetting to 0)."""
    starts = (positions == 0).astype(jnp.int32)
    return jnp.cumsum(starts, axis=-1) - 1
