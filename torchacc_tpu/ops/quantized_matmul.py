"""Quantized matmuls: int8 / fp8 ``dot_general`` with delayed scaling.

The MFU gap at the 8B geometry is communication and precision
(ROADMAP #4); this is the precision half.  Low-precision matmul formats
with per-tensor *delayed* scaling are the standard lever (Micikevicius
et al., "FP8 Formats for Deep Learning", 2022; NVIDIA Transformer
Engine): activations are quantized with a scale derived from an
**amax history** of previous steps — so the scale is a constant within
the step (no extra pass over the activation before the matmul) — while
weights use just-in-time **per-channel** scales (the weights are in
hand exactly when needed, and per-channel absorbs the large
inter-channel spread of trained weight matrices).

Two executable paths, selected like ``ops/flash_attention.py``:

- ``impl='pallas'`` — a fused quantize → matmul → dequantize Pallas TPU
  kernel: the int8 tiles are produced in VMEM and fed straight to the
  MXU's int8 path with an int32 accumulator (fp8 accumulates f32), so
  the quantized operands never round-trip through HBM.  Interpret mode
  off-TPU.
- ``impl='xla'`` — ``lax.dot_general(preferred_element_type=...)`` on
  explicitly quantized operands; XLA fuses the casts.  This is the CPU
  path and the semantics anchor: for int8 both paths accumulate in
  exact int32 arithmetic, so the kernel and the XLA dot agree
  **bitwise**; fp8 accumulates in f32 on both, in different orders, so
  they agree to about one f32 ulp (tests/test_quant.py).

Numerics are anchored to :func:`quantized_matmul_reference` (an f32
dequantize-then-matmul mirror) the same way ``ops/paged_attention.py``
anchors to ``attention_reference``; see tests/test_quant.py for the
measured tolerances.

Gradients: the forward matmul is quantized, the backward runs in the
compute dtype (bf16/f32) on the **saved unquantized operands** with the
scales treated as constants — the straight-through estimator every
production recipe uses (a rounded forward has zero almost-everywhere
derivative).  ``dL/dw`` deliberately ignores the path through the
just-in-time weight scale.

Delayed-scaling state (the amax history) lives in the ``'quant'`` flax
collection of :class:`QuantDenseGeneral` (one history per matmul site),
is carried through the train step alongside the AMP scaler
(``TrainState.quant``) and persists through checkpoints so elastic
resume stays exact — see docs/performance.md "Quantized matmuls".
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchacc_tpu.ops._common import ambient_mesh, needs_shard_map
from torchacc_tpu.ops._common import interpret_mode as _interpret
from torchacc_tpu.ops._common import on_tpu as _on_tpu
from torchacc_tpu.ops._common import round_up as _round_up

#: quantization formats: dtype + largest representable magnitude.
#: int8 uses the symmetric [-127, 127] range (-128 unused, the standard
#: symmetric-quantization choice); fp8 is e4m3 (max finite 448) — the
#: forward-pass format of the fp8 recipes (e5m2 is a gradient format;
#: gradients here stay in the compute dtype, so it is not needed).
_FORMATS = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, 448.0),
}


def quant_formats() -> Tuple[str, ...]:
    return tuple(_FORMATS)


def _fmt(fmt: str) -> Tuple[Any, float]:
    if fmt not in _FORMATS:
        raise ValueError(f"quant format must be one of {tuple(_FORMATS)}, "
                         f"got {fmt!r}")
    return _FORMATS[fmt]


# ---------------------------------------------------------------------------
# scales + (de)quantize
# ---------------------------------------------------------------------------

def compute_scale(amax: jax.Array, fmt: str) -> jax.Array:
    """``scale = amax / qmax`` in f32, guarded so an all-zero tensor
    (amax 0) quantizes through scale 1 instead of dividing by zero."""
    _, qmax = _fmt(fmt)
    amax = jnp.asarray(amax, jnp.float32)
    return jnp.where(amax > 0.0, amax / qmax, 1.0)


def quantize(x: jax.Array, scale: jax.Array, fmt: str) -> jax.Array:
    """Quantize ``x / scale`` into the format's dtype (saturating).

    int8 rounds half-to-even (``jnp.round``) and clips to ±127; fp8
    clips to ±448 before the cast (an e4m3 overflow would produce NaN,
    not saturate)."""
    dt, qmax = _fmt(fmt)
    s = jnp.asarray(scale, jnp.float32)
    y = x.astype(jnp.float32) / s
    y = jnp.clip(y, -qmax, qmax)
    if fmt == "int8":
        y = jnp.round(y)
    return y.astype(dt)


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)


def per_channel_scale(w2d: jax.Array, fmt: str) -> jax.Array:
    """Just-in-time per-output-channel scale ``[N]`` for a ``[K, N]``
    weight (amax over the contracting dim)."""
    return compute_scale(jnp.max(jnp.abs(w2d.astype(jnp.float32)), axis=0),
                         fmt)


# ---------------------------------------------------------------------------
# delayed scaling (amax history)
# ---------------------------------------------------------------------------

def amax_history_init(length: int) -> jax.Array:
    """Fresh rolling amax history (f32 zeros; a zero history means "no
    observation yet" and :func:`delayed_scale` falls back to the current
    amax — the just-in-time first step every delayed-scaling recipe
    uses)."""
    return jnp.zeros((int(length),), jnp.float32)


def delayed_scale(history: jax.Array, amax_now: jax.Array,
                  fmt: str) -> jax.Array:
    """Per-tensor scale from the amax HISTORY (max over the window), so
    quantization within the step needs no extra pass over the tensor;
    falls back to ``amax_now`` while the history is still all zeros
    (step 0 / a freshly initialised site)."""
    amax_h = jnp.max(history)
    return compute_scale(jnp.where(amax_h > 0.0, amax_h, amax_now), fmt)


def update_amax_history(history: jax.Array,
                        amax_now: jax.Array) -> jax.Array:
    """Roll the window and record the current step's amax at slot 0."""
    return jnp.roll(history, 1).at[0].set(
        jnp.asarray(amax_now, jnp.float32))


# ---------------------------------------------------------------------------
# XLA path
# ---------------------------------------------------------------------------

def _qmm2d_xla(x2d: jax.Array, w2d: jax.Array, sx: jax.Array,
               sw: jax.Array, fmt: str) -> jax.Array:
    """[M, K] @ [K, N] on quantized operands.  int8 accumulates exact
    int32 (bitwise comparable to the Pallas kernel); fp8 accumulates
    f32.  Dequantization folds the two scales into one [N] row."""
    qx = quantize(x2d, sx, fmt)
    qw = quantize(w2d, sw[None, :], fmt)
    if fmt == "int8":
        acc = jax.lax.dot_general(
            qx, qw, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        acc = acc.astype(jnp.float32)
    else:
        acc = jax.lax.dot_general(
            qx, qw, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return acc * (jnp.asarray(sx, jnp.float32) * sw)[None, :]


# ---------------------------------------------------------------------------
# Pallas kernel (fused quantize -> matmul -> dequantize)
# ---------------------------------------------------------------------------

def _qmm_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref,
                *, n_k: int, fmt: str):
    """One (m, n) output tile; grid dim 2 sweeps K with an accumulator
    scratch (int32 for int8 — exact, matching the XLA path bitwise;
    f32 for fp8).  Quantization happens on the VMEM tiles, so the int8
    operands are born next to the MXU."""
    ki = pl.program_id(2)
    dt, qmax = _FORMATS[fmt]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sx = sx_ref[0, 0]
    sw = sw_ref[0, :]
    xq = x_ref[...].astype(jnp.float32) / sx
    xq = jnp.clip(xq, -qmax, qmax)
    wq = w_ref[...].astype(jnp.float32) / sw[None, :]
    wq = jnp.clip(wq, -qmax, qmax)
    if fmt == "int8":
        xq = jnp.round(xq).astype(jnp.int8)
        wq = jnp.round(wq).astype(jnp.int8)
        acc_ref[...] += jax.lax.dot_general(
            xq, wq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    else:
        xq = xq.astype(dt)
        wq = wq.astype(dt)
        acc_ref[...] += jax.lax.dot_general(
            xq, wq, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * (sx * sw)[None, :]).astype(o_ref.dtype)


def _qmm2d_pallas(x2d: jax.Array, w2d: jax.Array, sx: jax.Array,
                  sw: jax.Array, fmt: str) -> jax.Array:
    m, k = x2d.shape
    _, n = w2d.shape
    # int8 tiles want (32, 128); generous blocks amortise the per-tile
    # quantize VPU work.  Pad with zeros — zero quantizes to zero and
    # contributes nothing to the dot, so padding is exact.
    bm = min(512, _round_up(m, 32))
    bk = min(512, _round_up(k, 128))
    bn = min(512, _round_up(n, 128))
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    xp = (x2d if (mp, kp) == (m, k)
          else jnp.pad(x2d, ((0, mp - m), (0, kp - k))))
    wp = (w2d if (kp, np_) == (k, n)
          else jnp.pad(w2d, ((0, kp - k), (0, np_ - n))))
    # padded channels get scale 1.0 (their amax is 0) — harmless, sliced
    # away below
    swp = (sw if np_ == n
           else jnp.pad(sw, (0, np_ - n), constant_values=1.0))
    acc_dt = jnp.int32 if fmt == "int8" else jnp.float32
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=kp // bk, fmt=fmt),
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((1, 1), lambda i, j, ki: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, ki: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(xp, wp, jnp.reshape(jnp.asarray(sx, jnp.float32), (1, 1)),
      swp.astype(jnp.float32)[None, :])
    return out[:m, :n]


# ---------------------------------------------------------------------------
# custom-VJP core
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _qmm2d(x2d, w2d, sx, sw, fmt, impl):
    y, _ = _qmm2d_fwd(x2d, w2d, sx, sw, fmt, impl)
    return y


def _qmm2d_fwd(x2d, w2d, sx, sw, fmt, impl):
    fn = _qmm2d_pallas if impl == "pallas" else _qmm2d_xla
    y = fn(x2d, w2d, sx, sw, fmt).astype(x2d.dtype)
    return y, (x2d, w2d)


def _qmm2d_bwd(fmt, impl, res, g):
    # straight-through: backward in the compute dtype on the saved
    # unquantized operands; scales are constants (zero cotangent)
    x2d, w2d = res
    g = g.astype(x2d.dtype)
    dx = jax.lax.dot_general(g, w2d.astype(g.dtype),
                             (((1,), (1,)), ((), ())))
    dw = jax.lax.dot_general(x2d.astype(g.dtype), g,
                             (((0,), (0,)), ((), ())))
    return (dx.astype(x2d.dtype), dw.astype(w2d.dtype),
            jnp.zeros((), jnp.float32),
            jnp.zeros((w2d.shape[1],), jnp.float32))


_qmm2d.defvjp(_qmm2d_fwd, _qmm2d_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def quantized_dot(
    x: jax.Array,
    kernel: jax.Array,
    contract_ndim: int = 1,
    *,
    fmt: str = "int8",
    x_scale: Optional[jax.Array] = None,
    impl: str = "auto",
) -> jax.Array:
    """Quantized ``x @ kernel`` contracting ``x``'s trailing
    ``contract_ndim`` dims with ``kernel``'s leading ones (the
    ``nn.DenseGeneral`` trailing-axis convention: kernel shape is
    ``[*contract_dims, *feature_dims]``).

    ``x_scale``: per-tensor activation scale (from
    :func:`delayed_scale`); None derives it just-in-time from
    ``max|x|``.  Weights always use just-in-time per-channel scales.
    ``impl``: 'auto' (pallas on TPU, xla elsewhere) | 'pallas'
    (interpret mode off-TPU) | 'xla'.  Returns ``x.dtype``.
    """
    _fmt(fmt)  # validate
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"impl must be auto|pallas|xla, got {impl!r}")
    if impl == "pallas" and needs_shard_map(ambient_mesh()):
        # GSPMD cannot partition a Mosaic kernel, and this one has no
        # shard_map region yet (fsdp/tp-sharded weights need their
        # gathers and reductions placed by hand)
        from torchacc_tpu.config import ConfigError
        raise ConfigError(
            "the quantized-matmul Pallas kernel does not run under a "
            "mesh of more than one device; set compute.quant_impl='xla' "
            "(ops.quantized_dot(impl='xla')) for multi-chip runs")
    cd = int(contract_ndim)
    if cd < 1 or cd > min(x.ndim, kernel.ndim - 1):
        raise ValueError(
            f"contract_ndim {cd} invalid for x{x.shape} @ k{kernel.shape}")
    if x.shape[x.ndim - cd:] != kernel.shape[:cd]:
        raise ValueError(
            f"contracting dims mismatch: x{x.shape} vs kernel"
            f"{kernel.shape} over the trailing/leading {cd} dim(s)")
    batch_shape = x.shape[:x.ndim - cd]
    feat_shape = kernel.shape[cd:]
    k_sz = 1
    for d in kernel.shape[:cd]:
        k_sz *= d
    n_sz = 1
    for d in feat_shape:
        n_sz *= d
    m_sz = x.size // k_sz if x.size else 0
    x2d = x.reshape(m_sz, k_sz)
    w2d = kernel.reshape(k_sz, n_sz)
    if x_scale is None:
        x_scale = compute_scale(jnp.max(jnp.abs(x2d.astype(jnp.float32))),
                                fmt)
    sw = per_channel_scale(w2d, fmt)
    y = _qmm2d(x2d, w2d, jnp.asarray(x_scale, jnp.float32), sw, fmt, impl)
    return y.reshape(batch_shape + feat_shape)


def quantized_matmul_reference(
    x: jax.Array,
    kernel: jax.Array,
    contract_ndim: int = 1,
    *,
    fmt: str = "int8",
    x_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """f32 numerics anchor: dequantize(quantize(·)) on both operands,
    then a plain f32 matmul.  The kernel/XLA paths differ from this only
    by accumulation order (int8: exact int32 accumulation vs f32 sums;
    fp8: f32 both) — tests/test_quant.py pins the measured tolerance."""
    cd = int(contract_ndim)
    batch_shape = x.shape[:x.ndim - cd]
    feat_shape = kernel.shape[cd:]
    k_sz = 1
    for d in kernel.shape[:cd]:
        k_sz *= d
    x2d = x.reshape(-1, k_sz).astype(jnp.float32)
    w2d = kernel.reshape(k_sz, -1).astype(jnp.float32)
    if x_scale is None:
        x_scale = compute_scale(jnp.max(jnp.abs(x2d)), fmt)
    sw = per_channel_scale(w2d, fmt)
    xd = dequantize(quantize(x2d, x_scale, fmt), x_scale)
    wd = dequantize(quantize(w2d, sw[None, :], fmt), sw[None, :])
    return (xd @ wd).reshape(batch_shape + feat_shape)


# ---------------------------------------------------------------------------
# flax module: a drop-in Dense/DenseGeneral with delayed scaling
# ---------------------------------------------------------------------------

import flax.linen as nn  # noqa: E402  (kept below the pure-op API)


class QuantDenseGeneral(nn.Module):
    """``nn.DenseGeneral`` with a quantized forward matmul.

    Parameter names, shapes and initialisation match ``nn.DenseGeneral``
    / ``nn.Dense`` exactly (``kernel`` ``[*in_dims, *features]``,
    optional ``bias``), so swapping a site between the plain and
    quantized module keeps checkpoints and the init RNG stream
    bit-identical — ``compute.quant`` flips execution, never layout.

    The delayed-scaling amax history rides the ``'quant'`` collection
    (``amax_history [history_len]`` f32 per site; stacked ``[L, ...]``
    under ``nn.scan``): reads use the max over the window (falling back
    to the current amax while the history is empty), and the history is
    updated only when the collection is mutable — train steps thread it
    through ``TrainState.quant``; eval/restored inference reads the
    trained scales without mutating.

    Only trailing contraction axes are supported (every site in
    ``models/transformer.py`` contracts trailing dims).
    """

    features: Union[int, Sequence[int]]
    axis: Union[int, Sequence[int]] = -1
    use_bias: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    kernel_init: Any = nn.initializers.lecun_normal()
    bias_init: Any = nn.initializers.zeros
    quant: str = "int8"
    quant_impl: str = "auto"
    amax_history_len: int = 16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        feats = (tuple(self.features) if isinstance(self.features,
                                                    (tuple, list))
                 else (int(self.features),))
        axes = (tuple(self.axis) if isinstance(self.axis, (tuple, list))
                else (int(self.axis),))
        axes = tuple(a % x.ndim for a in axes)
        if axes != tuple(range(x.ndim - len(axes), x.ndim)):
            raise ValueError(
                f"QuantDenseGeneral supports trailing contraction axes "
                f"only, got axis={self.axis} for rank-{x.ndim} input")
        in_dims = tuple(x.shape[a] for a in axes)
        kernel = self.param("kernel", self.kernel_init,
                            in_dims + feats, self.param_dtype)
        bias = (self.param("bias", self.bias_init, feats,
                           self.param_dtype)
                if self.use_bias else None)
        hist = self.variable(
            "quant", "amax_history",
            lambda: amax_history_init(self.amax_history_len))
        xc = x.astype(self.dtype)
        wc = kernel.astype(self.dtype)
        if self.is_initializing():
            # init traces only shapes; keep it on the plain matmul so
            # abstract init never touches the quant kernels
            y = jax.lax.dot_general(
                xc, wc,
                ((axes, tuple(range(len(axes)))), ((), ())))
        else:
            amax_now = jnp.max(jnp.abs(xc.astype(jnp.float32)))
            sx = delayed_scale(hist.value, amax_now, self.quant)
            if self.is_mutable_collection("quant"):
                hist.value = update_amax_history(hist.value, amax_now)
            y = quantized_dot(xc, wc, len(axes), fmt=self.quant,
                              x_scale=sx, impl=self.quant_impl)
        if bias is not None:
            y = y + bias.astype(self.dtype)
        return y
