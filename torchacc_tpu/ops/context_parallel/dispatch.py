"""cp_attention: the context-parallel attention front door.

Composes Ulysses (inner, 'spu' axis — ICI all-to-all) with Ring (outer,
'sp' axis — ppermute ring) inside one shard_map region, the TPU-native
equivalent of the reference's 2D FlashSequence (context_parallel_2d.py:
75-126) with its intra/inter process groups (init_group.py:42-91).
Degenerates automatically: spu=1 -> pure ring, sp=1 -> pure ulysses,
both 1 -> plain (local) flash attention.

The full attention feature matrix passes through CP (the reference ring
accepts window_size/alibi_slopes/dropout_p, ring_attn.py:32-36): sliding
windows and ALiBi ride the ring via per-step GLOBAL chunk offsets, and
dropout's stateless coordinate hash is keyed by global (batch, head, q,
k) indices so a CP run is bit-identical to a single-device run with the
same seed.

Called from the model's attention layer when context parallelism is on;
the surrounding train step is an ordinary jit and the region's in/out
specs splice into the global sharding (dp/fsdp on batch, tp on heads).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from torchacc_tpu.ops.attention import (
    attention_reference,
    attention_reference_bwd,
)
from torchacc_tpu.ops._common import ambient_mesh
from torchacc_tpu.ops.attn import attention
from torchacc_tpu.ops.context_parallel.ring import (
    _ring_fwd_impl,
    ring_attention_bwd,
)
from torchacc_tpu.ops.context_parallel.ulysses import ulysses_attention
from torchacc_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_bwd,
)


def _axis_index(mesh, name: str):
    """axis_index, or 0 when the axis is absent / extent 1."""
    if name and int(mesh.shape.get(name, 1)) > 1:
        return jax.lax.axis_index(name)
    return jnp.int32(0)


def cp_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Tuple[int, int] = (-1, -1),
    scale: Optional[float] = None,
    logit_softcap: float = 0.0,
    q_segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    alibi_slopes: Optional[jax.Array] = None,
    dropout_p: float = 0.0,
    dropout_seed=None,
    mesh: Optional[Mesh] = None,
    ring_axis: str = "sp",
    a2a_axis: str = "spu",
    data_axes: Tuple[str, ...] = ("dp", "fsdp"),
    tp_axis: str = "tp",
    impl: str = "auto",
    check_vma: bool = False,
):
    """[b, s, h, d] attention with the sequence dim context-parallel over
    (ring_axis, a2a_axis).  Falls back to plain attention when both axes
    have extent 1 (or no mesh is active)."""
    mesh = mesh or ambient_mesh()
    ring_n = int(mesh.shape.get(ring_axis, 1)) if mesh is not None else 1
    ul_n = int(mesh.shape.get(a2a_axis, 1)) if mesh is not None else 1
    if ring_n * ul_n == 1:
        return attention(q, k, v, causal=causal, window=window,
                         scale=scale, logit_softcap=logit_softcap,
                         q_segment_ids=q_segment_ids,
                         kv_segment_ids=kv_segment_ids,
                         alibi_slopes=alibi_slopes, dropout_p=dropout_p,
                         dropout_seed=dropout_seed, impl=impl)
    # 'auto' matches plain attention's semantics (ops/attn.py): the Pallas
    # kernel on TPU, plain-XLA elsewhere — the interpret-mode kernel is
    # orders of magnitude slower and only worth running when a test
    # explicitly requests impl='pallas'.  Either way the two backends are
    # bit-identical per the parity tests in tests/test_flash_attention.py.
    if impl == "auto":
        from torchacc_tpu.ops._common import on_tpu
        inner_impl = "pallas" if on_tpu() else "xla"
    else:
        inner_impl = impl

    d = q.shape[-1]
    has_seg = q_segment_ids is not None
    has_alibi = alibi_slopes is not None
    has_seed = dropout_seed is not None
    seq_axes = (ring_axis, a2a_axis)
    qkv_spec = P(data_axes, seq_axes, tp_axis, None)
    seg_spec = P(data_axes, seq_axes)

    def _unpack(rest):
        rest = list(rest)
        qseg = rest.pop(0) if has_seg else None
        kseg = rest.pop(0) if has_seg else None
        slopes_tp = rest.pop(0) if has_alibi else None  # [h_tp] local slice
        seed = rest.pop(0) if has_seed else None
        return qseg, kseg, slopes_tp, seed

    def _offsets(q, slopes_tp):
        """Global offsets of this shard's rows (batch over the data axes,
        heads over tp — further split by the ulysses a2a) and the
        per-device slopes slice in the INNER (post-a2a) head layout."""
        b_loc = q.shape[0]
        b_pos = jnp.int32(0)
        for ax in data_axes:
            b_pos = b_pos * jnp.int32(int(mesh.shape.get(ax, 1))) \
                + _axis_index(mesh, ax)
        b_off = b_pos * b_loc
        h_tp_off = _axis_index(mesh, tp_axis) * q.shape[2]

        def inner_offsets(h_inner):
            # ulysses a2a gave this device head chunk [spu_idx*h_inner ..)
            spu_idx = _axis_index(mesh, a2a_axis)
            h_off = h_tp_off + spu_idx * h_inner
            slopes = slopes_tp
            if slopes is not None and ul_n > 1:
                slopes = jax.lax.dynamic_slice_in_dim(
                    slopes_tp, spu_idx * h_inner, h_inner)
            return h_off, slopes

        return b_off, inner_offsets

    if scale is None:
        scale = d ** -0.5

    def region_fwd(q, k, v, *rest):
        """Forward returning (out, o_inner, lse): the inner-layout
        attention output and merged lse are the residuals the backward
        consumes — no forward re-walk (the round-2 recompute debt)."""
        qseg, kseg, slopes_tp, seed = _unpack(rest)
        b_off, inner_offsets = _offsets(q, slopes_tp)

        def local_attn(q_, k_, v_, qs_, ks_):
            h_off, slopes = inner_offsets(q_.shape[2])
            if ring_n > 1:
                o, lse = _ring_fwd_impl(
                    q_, k_, v_, qs_, ks_, slopes, seed, h_off, b_off,
                    ring_axis, ring_n, causal, window, dropout_p,
                    inner_impl, scale, logit_softcap)
            else:
                fn = (attention_reference if inner_impl == "xla"
                      else flash_attention)
                o, lse = fn(q_, k_, v_, causal=causal, window=window,
                            scale=scale, q_segment_ids=qs_,
                            kv_segment_ids=ks_, alibi_slopes=slopes,
                            dropout_p=dropout_p, dropout_seed=seed,
                            h_offset=h_off, b_offset=b_off,
                            return_lse=True,
                            logit_softcap=logit_softcap)
            return o, (o, lse)

        out, (o_in, lse) = ulysses_attention(
            q, k, v, qseg, kseg, a2a_axis, ul_n, inner=local_attn,
            with_aux=True)
        return out, o_in, lse

    def region_bwd(q, k, v, o_in, lse, do, *rest):
        """Backward from saved (o_inner, lse): redo only the cheap a2a
        layout moves, then the explicit ring/flash backward, then the
        inverse a2a on the grads (the transpose of the forward's input
        a2a is the forward's output a2a and vice versa)."""
        qseg, kseg, slopes_tp, seed = _unpack(rest)
        b_off, inner_offsets = _offsets(q, slopes_tp)
        if ul_n > 1:
            a2a_in = lambda x: jax.lax.all_to_all(
                x, a2a_axis, split_axis=2, concat_axis=1, tiled=True)
            q_, k_, v_, do_ = a2a_in(q), a2a_in(k), a2a_in(v), a2a_in(do)
            qs_ = ks_ = None
            if qseg is not None:
                qs_ = jax.lax.all_gather(qseg, a2a_axis, axis=1, tiled=True)
                ks_ = jax.lax.all_gather(kseg, a2a_axis, axis=1, tiled=True)
        else:
            q_, k_, v_, do_, qs_, ks_ = q, k, v, do, qseg, kseg

        h_off, slopes = inner_offsets(q_.shape[2])
        if ring_n > 1:
            dq, dk, dv = ring_attention_bwd(
                q_, k_, v_, qs_, ks_, slopes, seed, h_off, b_off,
                o_in, lse, do_, axis_name=ring_axis, n=ring_n,
                causal=causal, window=window, dropout_p=dropout_p,
                impl=inner_impl, scale=scale,
                logit_softcap=logit_softcap)
        else:
            bwd = (attention_reference_bwd if inner_impl == "xla"
                   else flash_attention_bwd)
            dq, dk, dv = bwd(q_, k_, v_, o_in, lse, do_, causal=causal,
                             window=window, scale=scale,
                             q_segment_ids=qs_, kv_segment_ids=ks_,
                             alibi_slopes=slopes, dropout_p=dropout_p,
                             dropout_seed=seed, h_offset=h_off,
                             b_offset=b_off,
                             logit_softcap=logit_softcap)
        if ul_n > 1:
            a2a_out = lambda x: jax.lax.all_to_all(
                x, a2a_axis, split_axis=1, concat_axis=2, tiled=True)
            dq, dk, dv = a2a_out(dq), a2a_out(dk), a2a_out(dv)
        return dq, dk, dv

    in_specs = [qkv_spec, qkv_spec, qkv_spec]
    args = [q, k, v]
    if has_seg:
        in_specs += [seg_spec, seg_spec]
        args += [q_segment_ids, kv_segment_ids]
    if has_alibi:
        in_specs.append(P(tp_axis))
        args.append(alibi_slopes)
    if has_seed:
        in_specs.append(P())
        args.append(jnp.asarray(dropout_seed, jnp.int32))
    in_specs = tuple(in_specs)

    # The region is wrapped in a custom VJP whose backward opens a FRESH
    # shard_map.  Rationale: letting autodiff transpose ACROSS the
    # shard_map boundary mis-accumulates cotangents when this region is
    # nested inside another manual region (the pp pipeline) — verified
    # by pp×sp gradient divergence with the plain transpose path.  The
    # forward saves the inner-layout (o, lse) so the backward runs the
    # explicit ring/flash backward directly — no forward re-walk (the
    # reference backward consumes the saved softmax_lse + out the same
    # way, ring_attn.py:130-271).  The residuals carry the remat names
    # (attn_ctx/attn_lse) so the save_attn* policies keep them across a
    # jax.checkpoint boundary.
    # o/lse cross the boundary in the INNER layout: seq sharded over the
    # ring axis only (a2a gathered the ulysses part), heads over tp+spu.
    o_spec = P(data_axes, ring_axis, (tp_axis, a2a_axis), None)
    lse_spec = P(data_axes, (tp_axis, a2a_axis), ring_axis)

    fwd_mapped = jax.shard_map(
        region_fwd, mesh=mesh, in_specs=in_specs,
        out_specs=(qkv_spec, o_spec, lse_spec), check_vma=check_vma)

    @jax.custom_vjp
    def core(q, k, v, *rest):
        return fwd_mapped(q, k, v, *rest)[0]

    def core_fwd(q, k, v, *rest):
        from jax.ad_checkpoint import checkpoint_name

        out, o_in, lse = fwd_mapped(q, k, v, *rest)
        o_in = checkpoint_name(o_in, "attn_ctx")
        lse = checkpoint_name(lse, "attn_lse")
        return out, (q, k, v, o_in, lse) + tuple(rest)

    def core_bwd(res, do):
        q, k, v, o_in, lse = res[:5]
        rest = res[5:]
        dq, dk, dv = jax.shard_map(
            region_bwd, mesh=mesh,
            in_specs=in_specs[:3] + (o_spec, lse_spec, qkv_spec)
            + in_specs[3:],
            out_specs=(qkv_spec, qkv_spec, qkv_spec),
            check_vma=check_vma)(q, k, v, o_in, lse, do, *rest)
        return (dq, dk, dv) + tuple(None for _ in rest)

    core.defvjp(core_fwd, core_bwd)
    return core(*args)
