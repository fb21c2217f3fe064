"""Plain reference for the dense decoder family (Mistral, OLMo-2).

Written from the published descriptions, in ``jax.numpy`` and float32
with ``jax.default_matmul_precision("highest")``; no kernels, no cache,
no batching, nothing imported from the program and nothing the program
made.  Weights come from ``chipbench.weights`` (made by the benchmark
from ``--seed``) in the canonical layout described there.

The block, per the two sources:

- Mistral (``model_type: mistral``): pre-norm.  ``h = x + attn(norm1(x))``,
  ``y = h + mlp(norm2(h))``; grouped-query attention, rotary positions
  in the half-split ("rotate_half") layout, SwiGLU, RMSNorm, untied head.
- OLMo-2 (``model_type: olmo2``): post-norm.  ``h = x + norm1(attn(x))``,
  ``y = h + norm2(mlp(h))``; RMSNorm over the flat q and k projections
  before the head split and the rotary embedding.

Departures from a textbook forward, all of them about memory and none
about the arithmetic: attention runs one key/value head (with its group
of query heads) at a time and the head + cross-entropy one block of
positions at a time, each under ``jax.checkpoint``, so that a 4096-token
row at published widths fits beside float32 AdamW state on a 16 GB chip.

``dot`` is the one seam: every matrix product goes through it, so the
control ("the reference in the nearest precision below") is this same
code with ``dot`` swapped (see ``lower_precision_dot``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CE_BLOCK = 1024          # positions per head + cross-entropy block


def _f32_dot(a, b):
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def _quantize(x, dtype):
    """Per-tensor scaled cast to a narrow float type and back."""
    amax = jnp.max(jnp.abs(x)) + 1e-30
    top = float(jnp.finfo(dtype).max)
    scale = top / amax
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _int8(x, axis):
    """Symmetric int8 along ``axis`` (one scale per row or column)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True) + 1e-30
    scale = 127.0 / amax
    return jnp.clip(jnp.round(x * scale), -127, 127) / scale


@jax.custom_vjp
def _fp8_dot(a, b):
    return _f32_dot(_quantize(a, jnp.float8_e4m3fn),
                    _quantize(b, jnp.float8_e4m3fn))


def _fp8_fwd(a, b):
    qa = _quantize(a, jnp.float8_e4m3fn)
    qb = _quantize(b, jnp.float8_e4m3fn)
    return _f32_dot(qa, qb), (qa, qb)


def _fp8_bwd(res, g):
    qa, qb = res
    qg = _quantize(g, jnp.float8_e5m2)
    return _f32_dot(qg, qb.T), _f32_dot(qa.T, qg)


_fp8_dot.defvjp(_fp8_fwd, _fp8_bwd)


def _int8_dot(a, b):
    # weight-only would leave the activations exact; a PR tempted by
    # int8 quantizes both sides (the MXU's int8 rate needs both)
    return _f32_dot(_int8(a, -1), _int8(b, 0))


def lower_precision_dot(name: str):
    """The ``dot`` of a control: 'float32' is the reference itself."""
    return {"float32": _f32_dot, "fp8": _fp8_dot, "int8": _int8_dot}[name]


def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w.astype(jnp.float32)


def rope(x, positions, theta):
    """Rotary embedding, half-split layout.  x: [S, heads, D]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _group_attention(q, k, v, dot):
    """Causal softmax attention of one kv head.  q: [S, G, D]; k, v: [S, D]."""
    s, g, d = q.shape
    scores = dot(q.reshape(s * g, d), k.T).reshape(s, g, s) / jnp.sqrt(
        jnp.float32(d))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[:, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return dot(p.reshape(s * g, s), v).reshape(s, g, d)


def attention(x, lw, sizes, positions, dot):
    s = x.shape[0]
    nh, kh, d = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
    q, k, v = dot(x, lw["wq"]), dot(x, lw["wk"]), dot(x, lw["wv"])
    if sizes["qk_norm"]:
        q = rmsnorm(q, lw["q_norm"], sizes["eps"])
        k = rmsnorm(k, lw["k_norm"], sizes["eps"])
    q = rope(q.reshape(s, nh, d), positions, sizes["theta"])
    k = rope(k.reshape(s, kh, d), positions, sizes["theta"])
    v = v.reshape(s, kh, d)
    g = nh // kh
    # one kv head (with its group of q heads) at a time
    one = jax.checkpoint(lambda qkv: _group_attention(*qkv, dot))
    outs = jax.lax.map(one, (q.reshape(s, kh, g, d).transpose(1, 0, 2, 3),
                             k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return dot(outs.transpose(1, 0, 2, 3).reshape(s, nh * d), lw["wo"])


def mlp(x, lw, dot):
    return dot(jax.nn.silu(dot(x, lw["w_gate"])) * dot(x, lw["w_up"]),
               lw["w_down"])


def block(x, lw, sizes, positions, dot):
    eps = sizes["eps"]
    if sizes["post_norm"]:
        h = x + rmsnorm(attention(x, lw, sizes, positions, dot),
                        lw["ln1"], eps)
        return h + rmsnorm(mlp(h, lw, dot), lw["ln2"], eps)
    h = x + attention(rmsnorm(x, lw["ln1"], eps), lw, sizes, positions, dot)
    return h + mlp(rmsnorm(h, lw["ln2"], eps), lw, dot)


def sizes_of(published: dict) -> dict:
    """The sizes the block needs, from the source's config keys."""
    heads = published["num_attention_heads"]
    return dict(
        heads=heads,
        kv_heads=published.get("num_key_value_heads") or heads,
        head_dim=published.get("head_dim")
        or published["hidden_size"] // heads,
        eps=float(published["rms_norm_eps"]),
        theta=float(published["rope_theta"]),
        post_norm=published["model_type"] == "olmo2",
        qk_norm=published["model_type"] == "olmo2")


def embed(weights, ids):
    return weights["embed"][ids].astype(jnp.float32)


def hidden(weights, sizes, ids, dot=_f32_dot):
    """Final-norm hidden states of one row.  ids: [S] -> [S, H]."""
    positions = jnp.arange(ids.shape[0])
    layer = jax.checkpoint(
        lambda x, lw: (block(x, lw, sizes, positions, dot), None))
    x, _ = jax.lax.scan(layer, embed(weights, ids), weights["layers"])
    return rmsnorm(x, weights["final_norm"], sizes["eps"])


class _Static(dict):
    """A dict that can key a cache or be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def logits_at(weights, sizes, ids, positions, dot=_f32_dot):
    """Float32 logits of one row at ``positions``.  -> [len(positions), V]"""
    h = hidden(weights, sizes, ids, dot)
    return dot(h[positions], weights["head"])


def _ce_block(h, head, labels, dot):
    logits = dot(h, head)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(labels >= 0, lse - picked, 0.0))


def row_loss_sum(weights, sizes, ids, dot=_f32_dot):
    """Summed next-token cross-entropy of one row (S - 1 targets), the
    head and the softmax one block of positions at a time."""
    h = hidden(weights, sizes, ids, dot)
    labels = jnp.concatenate([ids[1:], jnp.full((1,), -1, ids.dtype)])
    n = -(-h.shape[0] // CE_BLOCK)
    pad = n * CE_BLOCK - h.shape[0]
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(n, CE_BLOCK, -1)
    labels = jnp.pad(labels, (0, pad), constant_values=-1).reshape(
        n, CE_BLOCK)
    one = jax.checkpoint(
        lambda hl: _ce_block(hl[0], weights["head"], hl[1], dot))
    return jnp.sum(jax.lax.map(one, (h, labels)))


@functools.lru_cache(maxsize=None)
def _row_grad(sizes, dot):
    """(weights, ids, acc) -> (row loss sum, acc + its gradient); the
    accumulator is donated, so a batch needs one gradient tree, not two."""
    def fn(w, ids, acc):
        val, g = jax.value_and_grad(
            lambda w_: row_loss_sum(w_, dict(sizes), ids, dot))(w)
        return val, jax.tree.map(jnp.add, acc, g)
    return jax.jit(fn, donate_argnums=2)


_scale = jax.jit(lambda t, c: jax.tree.map(lambda x: x / c, t),
                 donate_argnums=0)


def loss_and_grads(weights, sizes, batch, dot=_f32_dot):
    """Mean next-token loss over a batch and its gradient, row by row."""
    fn = _row_grad(_Static(sizes), dot)
    total = 0.0
    grads = jax.tree.map(jnp.zeros_like, weights)
    for row in batch:
        val, grads = fn(weights, jnp.asarray(row), grads)
        total = total + val
    count = float(batch.shape[0] * (batch.shape[1] - 1))
    return total / count, _scale(grads, count)


def adamw_step(weights, grads, m, v, step, opt):
    """One AdamW step as optax.adamw computes it.  ``step`` counts from 1."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd = opt["learning_rate"], opt["weight_decay"]

    def one(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** step)
        vhat = v / (1 - b2 ** step)
        return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p), m, v

    out = jax.tree.map(one, weights, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


_adamw = jax.jit(adamw_step, static_argnames=("step", "opt"),
                 donate_argnums=(0, 1, 2, 3))
_subtract = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b),
                    donate_argnums=0)
_norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
    x.astype(jnp.float32)))) for k, x in t.items()})


def leaf_norms(tree):
    """Float32 l2 norm of every leaf, as a flat {name: float} dict."""
    flat = {}

    def walk(prefix, node):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(f"{prefix}.{k}" if prefix else k, v)
        else:
            flat[prefix] = node
    walk("", tree)
    return {k: float(x) for k, x in _norms(flat).items()}


def train_readings(make_weights, sizes, batches, opt, dot=_f32_dot):
    """Follow ``len(batches)`` AdamW steps from ``make_weights()``.

    ``make_weights`` returns a fresh float32 tree each time it is called
    (the benchmark's seeded maker), so the start need not be kept beside
    the state.  Returns the loss of each step, the per-leaf norm of the
    first gradient and the per-leaf norm of the parameters' change
    after the last step."""
    p = make_weights()
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        loss, g = loss_and_grads(p, sizes, batch, dot)
        losses.append(float(loss))
        if i == 0:
            grad_norms = leaf_norms(g)
        p, m, v = _adamw(p, g, m, v, step=i + 1, opt=_Static(opt))
        del g
    del m, v
    return dict(losses=losses, grad_norms=grad_norms,
                delta_norms=leaf_norms(_subtract(p, make_weights())))
