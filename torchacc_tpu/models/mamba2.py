"""The Mamba-2 mixer on a raw parameter tree.

One layer of kind 'mamba' of a ``mixer_pattern`` model
(models/transformer.ModelConfig) computes, on the normed residual ``u``::

    [z | xBC | dt] = u W_in               d_inner | d_inner + 2 G N | Hm
    xBC   = silu(causal depthwise conv(xBC, width K) + bias)
    x, B, C = split(xBC)                  x [Hm, P], B and C [G, N]
    delta = softplus(dt + dt_bias)        A = -exp(A_log), one a head
    S_t   = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t       (float32)
    y_t   = S_t C_t + D x_t               head h reads group h // (Hm / G)
    out   = RMSNorm_grouped(y * silu(z)) W_out    (G groups, gate first)

with ``d_inner = Hm * P`` (``ssm_heads * ssm_head_dim``).  What the layer
carries from one call to the next is ``(conv, ssm)``: the last ``K - 1``
inputs of the convolution (kept as ONE row of ``(K - 1) * channels``
values a slot: a pool whose last-but-one dimension is 3 would be padded
to a tile's 16 rows, and XLA copied it whole with its dimensions swapped
— 1.65 GiB at the cell's size; sandbox compile, PR 42) and the state
``S``.  Three forms, one
arithmetic:

- :func:`mixer_chunk` — ``T`` positions of ``R`` rows from the state of
  the rows' slots in the serving pools (``serve/kv_cache.make_pools``),
  written back in place; rows padded past ``n_valid`` leave the state at
  the last valid position (``ops/ssm_scan.py``);
- :func:`mixer_step` — one token of every slot (a decode step);
- :func:`mixer_sequence` — a whole sequence from the zero state, no pool
  (``models/generate`` and the tests): :func:`mixer_chunk` over one-slot
  pools of its own.

The tree of one layer: ``in_proj/kernel [H, 2 d_inner + 2 G N + Hm]``,
``conv/kernel [K, d_inner + 2 G N]``, ``conv/bias``, ``dt_bias``,
``A_log``, ``D`` [Hm], ``norm/scale [d_inner]``, ``out_proj/kernel
[d_inner, H]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from torchacc_tpu.ops.ssm_scan import ssm_chunk_scan, ssm_step


def d_inner(cfg) -> int:
    return cfg.ssm_heads * cfg.ssm_head_dim


def conv_width(cfg) -> int:
    """Channels of the convolution: ``x``, ``B`` and ``C`` side by side."""
    return d_inner(cfg) + 2 * cfg.ssm_groups * cfg.ssm_state


def param_count(cfg) -> int:
    """Parameters of one layer's mixer (its pre-norm not counted)."""
    di, cw, hm = d_inner(cfg), conv_width(cfg), cfg.ssm_heads
    return (cfg.hidden_size * (di + cw + hm) + (cfg.ssm_conv + 1) * cw
            + 3 * hm + di + di * cfg.hidden_size)


def state_bytes(cfg) -> int:
    """Bytes one slot's state takes in one layer: the float32 ``S`` and
    the convolution's ``K - 1`` rows in the compute dtype."""
    return (4 * d_inner(cfg) * cfg.ssm_state + (cfg.ssm_conv - 1)
            * conv_width(cfg) * jnp.dtype(cfg.dtype).itemsize)


def _project_in(cfg, p, u):
    """``u`` [R, T, H] -> ``(z [R, T, d_inner], xBC [R, T, conv width],
    dt [R, T, Hm])`` in the compute dtype."""
    zxbcdt = jnp.einsum("rth,hf->rtf", u.astype(cfg.dtype),
                        p["in_proj"]["kernel"].astype(cfg.dtype))
    di = d_inner(cfg)
    return (zxbcdt[..., :di], zxbcdt[..., di:di + conv_width(cfg)],
            zxbcdt[..., di + conv_width(cfg):])


def _conv(cfg, p, window):
    """``silu(sum_j w[j] * window[..., t + j, :] + bias)`` over the
    ``T = window rows - (K - 1)`` positions whose ``K`` inputs the window
    holds, in float32, then the compute dtype."""
    k = cfg.ssm_conv
    t = window.shape[-2] - (k - 1)
    w = p["conv"]["kernel"].astype(jnp.float32)
    acc = p["conv"]["bias"].astype(jnp.float32)
    for j in range(k):
        acc = acc + w[j] * jax.lax.slice_in_dim(
            window, j, j + t, axis=-2).astype(jnp.float32)
    return jax.nn.silu(acc).astype(cfg.dtype)


def _scan_inputs(cfg, p, xbc, dt, valid):
    """The convolution's output and the raw ``dt`` -> what the recurrence
    reads: ``(x [.., Hm, P], delta [.., Hm] float32 — 0 where not valid
    —, a = delta * A, B, C [.., G, N])``."""
    di, g, n = d_inner(cfg), cfg.ssm_groups, cfg.ssm_state
    lead = xbc.shape[:-1]
    x = xbc[..., :di].reshape(lead + (cfg.ssm_heads, cfg.ssm_head_dim))
    b = xbc[..., di:di + g * n].reshape(lead + (g, n))
    c = xbc[..., di + g * n:].reshape(lead + (g, n))
    delta = jax.nn.softplus(dt.astype(jnp.float32)
                            + p["dt_bias"].astype(jnp.float32))
    delta = jnp.where(valid[..., None], delta, 0.0)
    a = -jnp.exp(p["A_log"].astype(jnp.float32)) * delta
    return x, delta, a, b, c


def _gate_out(cfg, p, y, x, z):
    """``y`` (float32, without the skip term), the recurrence's input
    ``x`` and the gate ``z`` -> the mixer's output: skip, gate, grouped
    RMSNorm, ``W_out``."""
    lead = y.shape[:-2]
    y = y + p["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(lead + (-1,)) * jax.nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(lead + (cfg.ssm_groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + cfg.norm_eps)
    y = grouped.reshape(y.shape) * p["norm"]["scale"].astype(jnp.float32)
    return jnp.einsum("...f,fh->...h", y.astype(cfg.dtype),
                      p["out_proj"]["kernel"].astype(cfg.dtype))


def mixer_chunk(cfg, p, u, conv_pool, ssm_pool, layer, slots, fresh,
                n_valid, impl="xla"):
    """``T`` positions of ``R`` rows: ``u`` [R, T, H] the normed
    residual, ``slots`` [R] the rows' slots in the pools' layer
    ``layer``, ``fresh`` [R] true where a row starts a request (from the
    zero state: nothing a slot's last tenant left is read), ``n_valid``
    [R] the rows' real positions (the rest is padding).  Returns
    ``(output [R, T, H], conv_pool, ssm_pool)``, the slots holding the
    state at each row's last valid position."""
    r, t, _ = u.shape
    k = cfg.ssm_conv
    valid = jnp.arange(t)[None, :] < n_valid[:, None]
    z, xbc, dt = _project_in(cfg, p, u)
    with jax.named_scope("ssm_conv"):
        before = jnp.where(fresh[:, None, None], 0, conv_pool[
            layer, slots].reshape(r, k - 1, -1))             # [R, K-1, C]
        window = jnp.concatenate([before.astype(cfg.dtype), xbc], axis=1)
        xbc = _conv(cfg, p, window)
        # the K - 1 inputs before position n_valid: the next chunk's
        rows = n_valid[:, None] + jnp.arange(k - 1)[None, :]
        conv_pool = conv_pool.at[layer, slots].set(jnp.take_along_axis(
            window, rows[:, :, None], axis=1).reshape(r, -1).astype(
                conv_pool.dtype))
    with jax.named_scope("ssm_scan"):
        x, delta, a, b, c = _scan_inputs(cfg, p, xbc, dt, valid)
        xdt = (x.astype(jnp.float32) * delta[..., None]).astype(cfg.dtype)
        y, ssm_pool = ssm_chunk_scan(
            xdt, a, b, c, ssm_pool, layer, slots, fresh,
            chunk=min(cfg.ssm_chunk, t), impl=impl)
    return _gate_out(cfg, p, y, x, z), conv_pool, ssm_pool


def mixer_step(cfg, p, u, conv_pool, ssm_pool, layer, active, impl="xla"):
    """One token of every slot: ``u`` [S, 1, H], slot ``i`` at index
    ``i`` of the pools' layer ``layer``; a slot that is not ``active``
    leaves its state alone."""
    s_ = u.shape[0]
    z, xbc, dt = _project_in(cfg, p, u)
    with jax.named_scope("ssm_conv"):
        before = conv_pool[layer, :s_]                       # [S, (K-1) C]
        window = jnp.concatenate(
            [before.reshape(s_, cfg.ssm_conv - 1, -1).astype(cfg.dtype),
             xbc], axis=1)
        xbc = _conv(cfg, p, window)[:, 0]
        conv_pool = conv_pool.at[layer, :s_].set(jnp.where(
            active[:, None], window[:, 1:].reshape(s_, -1).astype(
                conv_pool.dtype), before))
    with jax.named_scope("ssm_step"):
        x, delta, a, b, c = _scan_inputs(cfg, p, xbc, dt[:, 0], active)
        y, ssm_pool = ssm_step(x, delta, a, b, c, ssm_pool, layer,
                               impl=impl)
    return _gate_out(cfg, p, y, x, z[:, 0])[:, None], conv_pool, ssm_pool


def mixer_sequence(cfg, p, u, n_valid=None, state=None, impl="xla"):
    """A whole sequence: ``u`` [B, T, H] -> ``(output [B, T, H], (conv,
    ssm))`` from ``state`` (None = zero), the returned state that at
    each row's last valid position (``n_valid`` [B]; None = every
    position).  ``T`` is padded to whole sub-chunks here."""
    b, t, _ = u.shape
    q = cfg.ssm_chunk
    pad = -t % q
    if n_valid is None:
        n_valid = jnp.full((b,), t, jnp.int32)
    if state is None:
        state = (jnp.zeros((b, (cfg.ssm_conv - 1) * conv_width(cfg)),
                           cfg.dtype),
                 jnp.zeros((b, cfg.ssm_heads, cfg.ssm_head_dim,
                            cfg.ssm_state), jnp.float32))
        fresh = jnp.ones((b,), bool)
    else:
        fresh = jnp.zeros((b,), bool)
    out, conv, ssm = mixer_chunk(
        cfg, p, jnp.pad(u, ((0, 0), (0, pad), (0, 0))), state[0][None],
        state[1][None], 0, jnp.arange(b), fresh, n_valid, impl)
    return out[:, :t], (conv[0], ssm[0])
