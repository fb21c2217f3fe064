"""Shared op-layer helpers: platform detection and constants."""

from __future__ import annotations

import functools

import jax

NEG_INF = -1e30


def scoped(name: str):
    """Decorator: trace the function's ops under ``jax.named_scope(name)``
    (a registered device scope, obs/tracing.py ``DEVICE_SCOPES``) — its
    forward, its autodiff backward and any rematerialisation inherit it."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapped
    return deco


def on_tpu() -> bool:
    """True when the default backend is a TPU.  A backend that fails to
    initialise raises here — it never turns into an interpret-mode run."""
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Pallas kernels run in interpreter mode off-TPU (CPU tests)."""
    return not on_tpu()


def ambient_mesh():
    """The mesh ``jax.sharding.set_mesh`` made current, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh is not None and mesh.shape else None


def needs_shard_map(mesh) -> bool:
    """A Mosaic kernel cannot be partitioned by GSPMD: under a mesh of
    more than one device it lowers only where every mesh axis is
    manual, i.e. inside a ``shard_map`` over the whole mesh."""
    return (mesh is not None and mesh.size > 1
            and set(mesh.manual_axes) != set(mesh.axis_names))


def round_up(x: int, m: int) -> int:
    """Smallest multiple of ``m`` >= ``x`` (kernel tile padding)."""
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# Counter-based dropout hash (attention dropout)
#
# The reference threads dropout_p through every flash op via cuRAND states
# (ops/flash_attn.py:418-423).  TPU-native equivalent: a stateless
# murmur3-finalizer hash of the ABSOLUTE coordinates (seed, batch, head,
# global q position, global k position) -> uint32, thresholded at
# dropout_p * 2^32.  Because the mask is a pure function of absolute
# coordinates it is bit-identical between the forward and both backward
# kernels regardless of block sizes, identical between the Pallas and XLA
# paths (exact-match testable), and consistent across context-parallel
# ring steps when global offsets are passed.  Plain uint32 ops only, so
# it runs on the MXU-adjacent VPU and in interpreter mode alike.
# ---------------------------------------------------------------------------

def mix32(x):
    """murmur3 finalizer: uint32 -> well-mixed uint32."""
    import jax.numpy as jnp
    x = jnp.asarray(x).astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


_B_PRIME = 0x85EBCA6B
_K_PRIME = 0x9E3779B9  # golden-ratio odd constant


def dropout_keep(seed, b_idx, h_idx, q_pos, k_pos, dropout_p: float):
    """Boolean keep mask: True = keep.  ``q_pos`` [.., bq] and ``k_pos``
    [.., bk] are GLOBAL int32 positions; broadcasting forms [.., bq, bk].
    P(keep) = 1 - dropout_p (2^-32 granularity)."""
    import jax.numpy as jnp
    base = mix32(jnp.uint32(seed)
                 + jnp.uint32(b_idx) * jnp.uint32(_B_PRIME)
                 + jnp.uint32(h_idx))
    row = mix32(base ^ q_pos.astype(jnp.uint32))
    col = mix32(k_pos.astype(jnp.uint32) * jnp.uint32(_K_PRIME))
    bits = mix32(row[..., :, None] ^ col[..., None, :])
    threshold = jnp.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    return bits >= threshold


def batch_axes(mesh, batch: int) -> tuple:
    """The data axes a ``[batch, ...]`` activation is sharded over under
    ``mesh``: the longest prefix of ``config.DATA_AXES`` (``dp``,
    ``fsdp``, ``ep``) whose extent divides ``batch``
    (``parallel/sharding._divisible``'s rule, which
    ``ops/attn.py::_sharded_flash`` follows too), the axes of extent 1
    left out.  A manual axis counts as extent 1: inside its region the
    arrays are already per shard."""
    from torchacc_tpu.config import DATA_AXES
    axes, n = [], 1
    for a in DATA_AXES:
        extent = (1 if a in mesh.manual_axes
                  else int(mesh.shape.get(a, 1)))
        if batch % (n * extent):
            break
        n *= extent
        if extent > 1:
            axes.append(a)
    return tuple(axes)
