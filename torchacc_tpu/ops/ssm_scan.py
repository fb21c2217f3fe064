"""Selective state-space recurrence over a per-slot state pool.

The recurrence of one head (Mamba-2: a scalar decay a head)::

    S_t = exp(a_t) S_{t-1} + xdt_t (x) B_t        S [P, N], float32
    y_t = S_t C_t

with ``a_t = dt_t * A <= 0`` and ``xdt_t = dt_t * x_t``; the heads of one
group share ``B`` and ``C``.  A row that is padding carries ``a = 0`` and
``xdt = 0``: it leaves the state as it found it, so the state after a
chunk is the state at the row's LAST VALID position.

The state of every slot lives in one pool ``[L, slots + 1, Hm, P, N]``
(float32; the last slot is the null slot of a batched prefill's padded
rows), read and written where it lies through a layer index and a slot
index, as ``ops/paged_attention.py`` reads a page of the stacked KV pool:
the pool is donated to the serving programs and aliased through the
kernel, never copied.

- :func:`ssm_chunk_scan` — a prefill chunk from the slot's state (or
  from zero where ``fresh``: the first chunk of a request, which is how a
  slot's state is reset without a program of its own).  The chunked form
  of state-space duality: within a sub-chunk of ``chunk`` positions the
  output is a masked, decay-weighted ``(C B^T) xdt`` — matmuls —, across
  sub-chunks the state is carried.  ``impl='pallas'`` runs one kernel
  over (row, group, sub-chunk) with the state in VMEM from a row's first
  sub-chunk to its last; ``impl='xla'`` is its twin in ``jax.numpy``.
- :func:`ssm_step` — one token a slot (a decode step): a kernel over the
  slots that reads each slot's state once and writes it once, and its
  XLA twin.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchacc_tpu.ops._common import interpret_mode as _interpret

_NT = (((1,), (1,)), ((), ()))      # a @ b.T


def _chunked(a, chunk):
    """``a`` [R, T, Hm] -> the cumulative sums within sub-chunks
    [R, T // chunk, chunk, Hm]."""
    r, t, hm = a.shape
    return jnp.cumsum(a.reshape(r, t // chunk, chunk, hm), axis=2)


def _scan_kernel(layer, slots, fresh, xh_ref, xt_ref, b_ref, c_ref, csr_ref,
                 csc_ref, wrow_ref, last_ref, s_in_ref, y_ref, s_out_ref,
                 s_scr, *, heads: int, sub_chunks: int):
    """One (row, group, sub-chunk) step: the group's ``heads`` heads over
    ``Q`` positions.  ``csr``/``csc`` are the cumulative ``a`` of the
    sub-chunk with the positions on lanes / on sublanes, ``wrow`` the
    decay from each position to the sub-chunk's end, ``last`` the decay
    over the whole sub-chunk (replicated over the state's lanes)."""
    del layer, slots                 # the index maps read them
    r, c = pl.program_id(0), pl.program_id(2)

    @pl.when(c == 0)
    def _load():
        s_scr[...] = jnp.where(fresh[r] != 0, 0.0, s_in_ref[...])

    bc, cc = b_ref[...], c_ref[...]                       # [Q, N]
    op = bc.dtype
    q = bc.shape[0]
    cb = jax.lax.dot_general(cc, bc, _NT,
                             preferred_element_type=jnp.float32)  # [t, s]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
              <= jax.lax.broadcasted_iota(jnp.int32, (q, q), 0))
    for h in range(heads):
        csr = csr_ref[h:h + 1, :]                         # [1, Q]
        csc = csc_ref[:, h:h + 1]                         # [Q, 1]
        # exp(cs_t - cs_s) for s <= t (a <= 0: the difference is <= 0)
        decay = jnp.where(causal, jnp.exp(jnp.minimum(csc - csr, 0.0)), 0.0)
        y = jnp.dot((cb * decay).astype(op), xh_ref[h],
                    preferred_element_type=jnp.float32)   # [Q, P]
        s = s_scr[h]                                      # [P, N]
        y += jnp.exp(csc) * jax.lax.dot_general(
            cc, s.astype(op), _NT, preferred_element_type=jnp.float32)
        y_ref[h] = y
        xw = (xt_ref[h].astype(jnp.float32)
              * wrow_ref[h:h + 1, :]).astype(op)          # [P, Q]
        s_scr[h] = last_ref[h:h + 1, :] * s + jnp.dot(
            xw, bc, preferred_element_type=jnp.float32)

    @pl.when(c == sub_chunks - 1)
    def _store():
        s_out_ref[...] = s_scr[...]


def _chunk_scan_pallas(xdt, a, b, c, states, layer, slots, fresh, *, chunk):
    r, t, hm, p = xdt.shape
    g, n = b.shape[2], b.shape[3]
    hg, nc = hm // g, t // chunk
    cs = _chunked(a, chunk)                               # [R, nc, Q, Hm]
    last = cs[:, :, -1:, :]
    by_group = lambda v: v.reshape(r, nc, -1, g, hg)      # noqa: E731
    csc = jnp.transpose(by_group(cs), (0, 3, 1, 2, 4))    # [R, G, nc, Q, hg]
    csr = jnp.swapaxes(csc, -1, -2)                       # [R, G, nc, hg, Q]
    wrow = jnp.exp(jnp.swapaxes(jnp.transpose(
        by_group(last - cs), (0, 3, 1, 2, 4)), -1, -2))
    last = jnp.broadcast_to(
        jnp.exp(jnp.transpose(by_group(last), (0, 3, 1, 4, 2))),
        (r, g, nc, hg, n))
    xh = jnp.transpose(xdt, (0, 2, 1, 3))                 # [R, Hm, T, P]
    xt = jnp.transpose(xdt, (0, 2, 3, 1))                 # [R, Hm, P, T]
    bg = jnp.transpose(b, (0, 2, 1, 3))                   # [R, G, T, N]
    cg = jnp.transpose(c, (0, 2, 1, 3))
    per_step = lambda *blk: pl.BlockSpec(                 # noqa: E731
        (None, None, None) + blk, lambda ri, gi, ci, *_: (ri, gi, ci, 0, 0))
    state = pl.BlockSpec(
        (None, None, hg, p, n),
        lambda ri, gi, ci, layer, slots, fresh: (layer[0], slots[ri], gi,
                                                 0, 0))
    y, states = pl.pallas_call(
        functools.partial(_scan_kernel, heads=hg, sub_chunks=nc),
        out_shape=(jax.ShapeDtypeStruct((r, hm, t, p), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(r, g, nc),
            in_specs=[
                pl.BlockSpec((None, hg, chunk, p),
                             lambda ri, gi, ci, *_: (ri, gi, ci, 0)),
                pl.BlockSpec((None, hg, p, chunk),
                             lambda ri, gi, ci, *_: (ri, gi, 0, ci)),
                pl.BlockSpec((None, None, chunk, n),
                             lambda ri, gi, ci, *_: (ri, gi, ci, 0)),
                pl.BlockSpec((None, None, chunk, n),
                             lambda ri, gi, ci, *_: (ri, gi, ci, 0)),
                per_step(hg, chunk), per_step(chunk, hg),
                per_step(hg, chunk), per_step(hg, n), state],
            out_specs=(
                pl.BlockSpec((None, hg, chunk, p),
                             lambda ri, gi, ci, *_: (ri, gi, ci, 0)),
                state),
            scratch_shapes=[pltpu.VMEM((hg, p, n), jnp.float32)]),
        # the pool (operand 11 with the three scalars) is the second
        # output: updated where it lies
        input_output_aliases={11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=_interpret(),
        name="ssm_chunk_scan",
    )(layer.reshape(1), slots, fresh, xh, xt, bg, cg, csr, csc, wrow, last,
      states)
    return jnp.transpose(y, (0, 2, 1, 3)), states


def chunk_scan_reference(xdt, a, b, c, s0, *, chunk):
    """The chunked scan in ``jax.numpy``: ``(y [R, T, Hm, P] float32,
    the state after the last position [R, Hm, P, N])`` from the state
    ``s0`` — the kernel's arithmetic, sub-chunk by sub-chunk."""
    r, t, hm, p = xdt.shape
    g, n = b.shape[2], b.shape[3]
    hg, nc = hm // g, t // chunk
    op = xdt.dtype
    cs = _chunked(a, chunk)                               # [R, nc, Q, Hm]
    xq = xdt.reshape(r, nc, chunk, g, hg, p)
    bq = b.reshape(r, nc, chunk, g, n)
    cq = c.reshape(r, nc, chunk, g, n)
    csq = cs.reshape(r, nc, chunk, g, hg)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def sub_chunk(s, per):
        x_, b_, c_, cs_ = per        # [R, Q, G, hg, P] [R, Q, G, N] ...
        cb = jnp.einsum("rtgn,rsgn->rgts", c_, b_,
                        preferred_element_type=jnp.float32)
        diff = cs_[:, :, None] - cs_[:, None, :]          # [R, t, s, G, hg]
        decay = jnp.where(causal[None, :, :, None, None],
                          jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        m = (cb.transpose(0, 2, 3, 1)[..., None] * decay).astype(op)
        y = jnp.einsum("rtsgh,rsghp->rtghp", m, x_,
                       preferred_element_type=jnp.float32)
        sg = s.reshape(r, g, hg, p, n)
        y += jnp.exp(cs_)[..., None] * jnp.einsum(
            "rtgn,rghpn->rtghp", c_, sg.astype(op),
            preferred_element_type=jnp.float32)
        xw = (x_.astype(jnp.float32)
              * jnp.exp(cs_[:, -1:] - cs_)[..., None]).astype(op)
        sg = jnp.exp(cs_[:, -1])[..., None, None] * sg + jnp.einsum(
            "rsghp,rsgn->rghpn", xw, b_, preferred_element_type=jnp.float32)
        return sg.reshape(r, hm, p, n), y

    per = tuple(jnp.moveaxis(v, 1, 0) for v in (xq, bq, cq, csq))
    s, y = jax.lax.scan(sub_chunk, s0.astype(jnp.float32), per)
    return jnp.moveaxis(y, 0, 1).reshape(r, t, hm, p), s


def ssm_chunk_scan(xdt, a, b, c, states, layer, slots, fresh, *, chunk: int,
                   impl: str = "xla"):
    """A chunk of ``T`` positions of ``R`` rows through the recurrence.

    ``xdt`` [R, T, Hm, P] (``dt * x``, the compute dtype), ``a``
    [R, T, Hm] float32 (``dt * A``; 0 on padded rows, where ``xdt`` is 0
    too), ``b``/``c`` [R, T, G, N]; ``states`` the pool [L, slots + 1,
    Hm, P, N] float32; ``layer`` an int32 scalar, ``slots`` int32 [R] the
    rows' slots, ``fresh`` int32 [R] non-zero where a row starts from the
    zero state instead of its slot's.  ``T`` is a multiple of ``chunk``.
    Returns ``(y [R, T, Hm, P] float32 — without the skip term —, the
    pool with the rows' slots holding the state after their last valid
    position)``."""
    t = xdt.shape[1]
    if t % chunk:
        raise ValueError(f"ssm_chunk_scan: {t} positions are not whole "
                         f"sub-chunks of {chunk}")
    layer = jnp.asarray(layer, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    fresh = jnp.asarray(fresh, jnp.int32)
    if impl == "pallas":
        return _chunk_scan_pallas(xdt, a, b, c, states, layer, slots, fresh,
                                  chunk=chunk)
    s0 = jnp.where(fresh[:, None, None, None] != 0, 0.0,
                   states[layer, slots])
    y, s = chunk_scan_reference(xdt, a, b, c, s0, chunk=chunk)
    return y, states.at[layer, slots].set(s)


def _step_kernel(layer, xt_ref, decay_ref, dtb_ref, c_ref, s_in_ref, y_ref,
                 s_out_ref, *, heads: int):
    """One slot: every head's state [P, N] through one token.  Per head
    the decay and ``dt * B`` are rows over the state's lanes, the input a
    column over its sublanes (``xt`` holds the heads on lanes), and the
    readout's sum over lanes is a column again, gathered into ``y``
    [P, heads] one lane a head."""
    del layer
    p = xt_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (p, heads), 1)
    y = jnp.zeros((p, heads), jnp.float32)
    for h in range(heads):
        new = (decay_ref[h:h + 1, :] * s_in_ref[h]
               + xt_ref[:, h:h + 1] * dtb_ref[h:h + 1, :])    # [P, N]
        s_out_ref[h] = new
        y = jnp.where(lane == h, jnp.sum(new * c_ref[h:h + 1, :], axis=-1,
                                         keepdims=True), y)
    y_ref[...] = y


def _step_pallas(x, dt, a, bh, ch, states, layer):
    s_, hm, p = x.shape
    n = bh.shape[-1]
    xt = jnp.swapaxes(x.astype(jnp.float32), 1, 2)            # [S, P, Hm]
    decay = jnp.broadcast_to(jnp.exp(a)[..., None], (s_, hm, n))
    per_slot = lambda *blk: pl.BlockSpec(                     # noqa: E731
        (None,) + blk, lambda si, layer: (si, 0, 0))
    state = pl.BlockSpec((None, None, hm, p, n),
                         lambda si, layer: (layer[0], si, 0, 0, 0))
    y, states = pl.pallas_call(
        functools.partial(_step_kernel, heads=hm),
        out_shape=(jax.ShapeDtypeStruct((s_, p, hm), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(s_,),
            in_specs=[per_slot(p, hm), per_slot(hm, n), per_slot(hm, n),
                      per_slot(hm, n), state],
            out_specs=(per_slot(p, hm), state)),
        # the pool (operand 5 with the layer index) is the second output
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a slot's state in and out, double-buffered: 4 x 2 MiB at the
            # published sizes, beside the rows
            vmem_limit_bytes=max(32 * 2**20, 6 * 4 * hm * p * n)),
        interpret=_interpret(),
        name="ssm_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), xt, decay,
      dt[..., None] * bh, ch, states)
    return jnp.swapaxes(y, 1, 2), states


def ssm_step(x, dt, a, b, c, states, layer, *, impl: str = "xla"):
    """One token a slot: slot ``i`` of the pool's layer ``layer`` goes
    through ``S <- exp(a) S + (dt x) (x) B`` and gives ``y = S C``, in
    place.  ``impl='pallas'``: one kernel over the slots, each slot's
    state read once and written once; ``impl='xla'``: its twin in
    ``jax.numpy`` (XLA makes two fusions of it — the output's reads the
    state, the update's reads and writes it: three passes over the state
    where the kernel makes two).

    ``x`` [S, Hm, P], ``dt``/``a`` [S, Hm] float32 (both 0 for a slot
    that is not decoding: its state stays), ``b``/``c`` [S, G, N];
    ``states`` [L, >= S, Hm, P, N] float32.  Returns ``(y [S, Hm, P]
    float32 — without the skip term —, the pool)``."""
    s_, hm, _ = x.shape
    hg = hm // b.shape[1]
    bh = jnp.repeat(b.astype(jnp.float32), hg, axis=1)    # [S, Hm, N]
    ch = jnp.repeat(c.astype(jnp.float32), hg, axis=1)
    if impl == "pallas":
        return _step_pallas(x, dt, a, bh, ch, states, layer)
    state = states[layer, :s_]
    new = (jnp.exp(a)[..., None, None] * state
           + (dt[..., None] * x.astype(jnp.float32))[..., None]
           * bh[:, :, None, :])
    y = jnp.sum(new * ch[:, :, None, :], axis=-1)
    return y, states.at[layer, :s_].set(new)
