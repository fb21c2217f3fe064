"""Attention front door: impl dispatch (reference analogue: the SDPA swap
ops/scaled_dot_product_attention.py:7-20 + `flash_attention` dual-backend
dispatch in ops/context_parallel/utils.py:60-137).

``impl``:
  - 'auto'   : Pallas kernel on TPU, reference XLA attention elsewhere
  - 'pallas' : force the Pallas flash kernel (interpret mode off-TPU)
  - 'xla'    : force the plain-XLA reference attention

Under a device mesh the Pallas kernel runs per shard inside a
``shard_map`` (batch over the data axes, heads over 'tp'): GSPMD cannot
partition a Mosaic kernel, so plain ``jit`` with sharded operands does
not compile for more than one real chip.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from torchacc_tpu.config import DATA_AXES as _DATA_AXES
from torchacc_tpu.ops._common import ambient_mesh, needs_shard_map
from torchacc_tpu.ops._common import on_tpu as _on_tpu
from torchacc_tpu.ops.attention import attention_reference

# the mesh axes [b, s, h, d] activations are sharded over (the 'batch'
# and 'heads' rows of parallel/sharding.DEFAULT_RULES)
_HEAD_AXIS = "tp"


def _sharded_flash(mesh, q, k, v, *, q_segment_ids, kv_segment_ids,
                   alibi_slopes, dropout_seed, return_lse, **kw):
    """The flash kernel per shard of ``mesh``.  The region carries its
    own VJP whose backward opens a fresh ``shard_map`` around the
    explicit flash backward (the same construction, for the same
    reason, as ops/context_parallel/dispatch.py): nothing is transposed
    across the boundary, no collective is inserted, and the residuals
    keep the names the ``save_attn*`` remat policies look for."""
    from jax.ad_checkpoint import checkpoint_name

    from torchacc_tpu.ops.flash_attention import (
        flash_attention,
        flash_attention_bwd,
    )

    manual = set(mesh.manual_axes)

    def extent(a):
        return 1 if a in manual else int(mesh.shape.get(a, 1))

    # batch over the longest prefix of the data axes that divides it
    # (parallel/sharding._divisible's rule), heads over tp where tp
    # divides the kv heads; anything else stays replicated
    b_axes, n = [], 1
    for a in _DATA_AXES:
        if q.shape[0] % (n * extent(a)):
            break
        n *= extent(a)
        if extent(a) > 1:
            b_axes.append(a)
    b_axes = tuple(b_axes) or None
    tp = extent(_HEAD_AXIS)
    h_axis = _HEAD_AXIS if tp > 1 and k.shape[2] % tp == 0 else None

    qkv = P(b_axes, None, h_axis, None)
    lse_spec = P(b_axes, h_axis, None)
    has_seg = q_segment_ids is not None
    has_alibi = alibi_slopes is not None
    has_seed = dropout_seed is not None
    rest, rest_specs = [], []
    if has_seg:
        rest += [q_segment_ids, kv_segment_ids]
        rest_specs += [P(b_axes, None)] * 2
    if has_alibi:
        rest.append(alibi_slopes)
        rest_specs.append(P(h_axis))
    if has_seed:
        rest.append(jnp.asarray(dropout_seed, jnp.int32))
        rest_specs.append(P())

    def local_kw(q_loc, rest):
        rest = list(rest)
        out = dict(kw)
        if has_seg:
            out["q_segment_ids"] = rest.pop(0)
            out["kv_segment_ids"] = rest.pop(0)
        if has_alibi:
            out["alibi_slopes"] = rest.pop(0)
        if has_seed:
            # the dropout hash is keyed on GLOBAL (batch, head)
            # coordinates: offset this shard's row 0
            out["dropout_seed"] = rest.pop(0)
            b_pos = 0
            for a in b_axes or ():
                b_pos = b_pos * extent(a) + jax.lax.axis_index(a)
            out["b_offset"] = b_pos * q_loc.shape[0]
            if h_axis:
                out["h_offset"] = (jax.lax.axis_index(h_axis)
                                   * q_loc.shape[2])
        return out

    def region_fwd(q, k, v, *rest):
        return flash_attention(q, k, v, return_lse=True,
                               **local_kw(q, rest))

    def region_bwd(q, k, v, o, lse, do, *rest):
        return flash_attention_bwd(q, k, v, o, lse, do,
                                   **local_kw(q, rest))

    smap = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
    fwd_mapped = smap(region_fwd, in_specs=(qkv,) * 3 + tuple(rest_specs),
                      out_specs=(qkv, lse_spec))
    if return_lse:          # forward-only, like flash_attention's own
        return fwd_mapped(q, k, v, *rest)
    bwd_mapped = smap(
        region_bwd,
        in_specs=(qkv,) * 4 + (lse_spec, qkv) + tuple(rest_specs),
        out_specs=(qkv,) * 3)

    @jax.custom_vjp
    def core(q, k, v, *rest):
        return fwd_mapped(q, k, v, *rest)[0]

    def core_fwd(q, k, v, *rest):
        o, lse = fwd_mapped(q, k, v, *rest)
        # the SAME named value is primal output and residual (see
        # flash_attention._flash_fwd)
        o = checkpoint_name(o, "attn_ctx")
        return o, (q, k, v, o, checkpoint_name(lse, "attn_lse")) + rest

    def core_bwd(res, do):
        dq, dk, dv = bwd_mapped(*res[:5], do, *res[5:])
        return (dq, dk, dv) + tuple(None for _ in res[5:])

    core.defvjp(core_fwd, core_bwd)
    return core(q, k, v, *rest)


def attention(
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
    impl: str = "auto",
    return_lse: bool = False,
    logit_softcap: float = 0.0,
):
    """[b, s, h, d] attention with optional LSE output.

    ``dropout_p``/``dropout_seed``: post-softmax attention dropout; the
    stateless coordinate-hash mask (ops/_common.py) makes the pallas and
    xla backends bit-identical for the same seed.  ``logit_softcap``
    (Gemma2 score capping) is implemented by both backends."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "pallas":
        from torchacc_tpu.ops.flash_attention import flash_attention
        mesh = ambient_mesh()
        fn = (functools.partial(_sharded_flash, mesh)
              if needs_shard_map(mesh) else flash_attention)
        return fn(
            q, k, v, causal=causal, window=window, scale=scale,
            q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
            alibi_slopes=alibi_slopes, dropout_p=dropout_p,
            dropout_seed=dropout_seed, return_lse=return_lse,
            logit_softcap=logit_softcap)
    if impl != "xla":
        raise ValueError(f"impl must be auto|pallas|xla, got {impl!r}")
    return attention_reference(
        q, k, v, causal=causal, window=window, scale=scale,
        q_segment_ids=q_segment_ids, kv_segment_ids=kv_segment_ids,
        alibi_slopes=alibi_slopes, dropout_p=dropout_p,
        dropout_seed=dropout_seed, return_lse=return_lse,
        logit_softcap=logit_softcap)
