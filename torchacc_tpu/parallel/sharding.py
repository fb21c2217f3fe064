"""Logical-axis sharding rules: the GSPMD heart of the framework.

The reference expresses parallelism as nested module wrappers (torch FSDP /
GSPMD ``mark_sharding`` tp.py:1-5, ``SpmdFullyShardedDataParallel``
spmd_fsdp.py:37-41 with a global ``xs.Mesh((fsdp, tensor))``).  The
TPU-native design inverts this: models annotate parameters and activations
with *logical* axis names, and a single rule table maps logical axes to
mesh axes.  DP, FSDP, TP, SP and EP are then nothing but rows in this
table — composition is automatic and XLA inserts all collectives
(all-gather for FSDP unshard, reduce-scatter for grad sharding, psum for
DP, all-to-all for EP) from the shardings.

Default rule table (maxtext/t5x idiom, equivalent to the reference's
fsdp+tensor 2D mesh spmd_fsdp.py:75-84 extended with sp/ep/pp):

=============  ===============  =====================================
logical axis   mesh axes        role
=============  ===============  =====================================
``batch``      ('dp','fsdp',   batch split across all data axes
               'ep')
``seq``        'sp'             activation sequence dim (context par.)
``embed``      ('fsdp','ep')    param hidden dim — ZeRO-3 shard
``mlp``        'tp'             ffn hidden — megatron column/row
``heads``      'tp'             attention heads — megatron
``kv``         None             head_dim stays replicated
``vocab``      'tp'             embedding/logits vocab dim
``expert``     'ep'             MoE expert dim
``stage``      'pp'             stacked pipeline stages
=============  ===============  =====================================
"""

from __future__ import annotations

import math
from typing import Any, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from torchacc_tpu.config import DATA_AXES, Config

# A rule maps a logical axis name to a mesh axis, a tuple of mesh axes, or
# None (replicated).
LogicalRules = Sequence[Tuple[str, Union[str, Tuple[str, ...], None]]]

DEFAULT_RULES: LogicalRules = (
    ("batch", DATA_AXES),
    ("seq", ("sp", "spu")),
    # ZeRO-3: the hidden dim of every parameter (and of its optimizer
    # state) over fsdp, and over ep where a leaf's expert dim has not
    # taken it: the chips that hold the experts hold the rest of the
    # state in shares too (spec_for gives a mesh axis to one dim only,
    # and an expert leaf's 'expert' dim comes first)
    ("embed", ("fsdp", "ep")),
    ("mlp", "tp"),
    ("heads", "tp"),
    ("kv", None),
    ("vocab", "tp"),
    ("expert", "ep"),
    ("expert_mlp", "tp"),
    ("stage", "pp"),
    ("norm", None),
    # scan-over-layers stacking dim; pp.py re-maps it to 'pp' for pipelining
    ("layers", None),
)


def make_rules(config: Optional[Config] = None) -> LogicalRules:
    """Rule table for a config; ``fsdp.shard_axis_rules`` prepends overrides
    (reference: ``FSDPConfig.shard_output_callable``-style customisation,
    torchacc/config.py:224-270)."""
    rules: List[Tuple[str, Any]] = []
    if config is not None and config.dist.fsdp.shard_axis_rules:
        rules.extend(config.dist.fsdp.shard_axis_rules)
    if config is not None and config.dist.pp.size > 1:
        # pipeline stages: the scan-over-layers stacking dim becomes the
        # stage dim, sharded so each pp rank stores only its own layers
        rules.append(("layers", "pp"))
    rules.extend(DEFAULT_RULES)
    return tuple(rules)


def spec_for(logical_axes: Sequence[Optional[str]], rules: LogicalRules) -> PartitionSpec:
    """Map a tuple of logical axis names (one per tensor dim, None for
    unannotated dims) to a PartitionSpec, first-match-wins."""
    table = dict()
    for name, target in rules:
        table.setdefault(name, target)
    used: set = set()
    out: List[Any] = []
    for ax in logical_axes:
        if ax is not None and ax not in table:
            raise ValueError(
                f"unknown logical axis {ax!r}; known axes: {sorted(table)} "
                "(add a rule via fsdp.shard_axis_rules to extend)")
        tgt = table.get(ax) if ax is not None else None
        # A mesh axis may appear at most once in a spec.
        if tgt is None:
            out.append(None)
        elif isinstance(tgt, tuple):
            kept = tuple(t for t in tgt if t not in used)
            used.update(kept)
            out.append(kept if kept else None)
        else:
            if tgt in used:
                out.append(None)
            else:
                used.add(tgt)
                out.append(tgt)
    return PartitionSpec(*out)


def _prune_tiny(spec: PartitionSpec, shape: Tuple[int, ...],
                min_size: int) -> PartitionSpec:
    """Keep small params replicated (reference: torch-FSDP leaves modules
    below ``min_num_params`` unwrapped — fsdp.py auto-wrap policy)."""
    if math.prod(shape) >= min_size:
        return spec
    return PartitionSpec(*([None] * len(shape)))


def _divisible(spec: PartitionSpec, shape: Tuple[int, ...], mesh: Mesh) -> PartitionSpec:
    """Drop sharding on dims the mesh does not divide evenly — GSPMD would
    pad, which silently wastes memory and flops."""
    out = []
    for dim, tgt in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if tgt is None:
            out.append(None)
            continue
        axes = tgt if isinstance(tgt, tuple) else (tgt,)
        # mesh.shape may be an AbstractMesh mapping; .get works for both
        # Longest divisible prefix: batch=6 on ('dp','fsdp')=(2,2) still
        # shards over dp rather than falling all the way to replicated.
        while axes:
            extent = math.prod(mesh.shape.get(a, 1) for a in axes)
            if dim % extent == 0:
                break
            axes = axes[:-1]
        if not axes:
            out.append(None)
        elif isinstance(tgt, tuple):
            out.append(tuple(axes))
        else:
            out.append(axes[0])
    return PartitionSpec(*out)


def tree_shardings(
    mesh: Mesh,
    abstract_tree: Any,
    logical_axes_tree: Any,
    rules: LogicalRules,
    min_weight_size: int = 0,
) -> Any:
    """NamedSharding pytree for a pytree of abstract arrays + a matching
    pytree of logical-axis tuples."""
    def one(leaf, axes):
        if leaf is None:  # optax EmptyState / None optimizer slots
            return None
        spec = spec_for(axes, rules) if axes is not None else PartitionSpec()
        spec = _prune_tiny(spec, leaf.shape, min_weight_size)
        spec = _divisible(spec, leaf.shape, mesh)
        return NamedSharding(mesh, spec)
    return jax.tree.map(one, abstract_tree, logical_axes_tree,
                        is_leaf=lambda x: x is None)


def batch_spec(config: Optional[Config] = None) -> PartitionSpec:
    """Input batch sharding: leading dim over the data axes, sequence dim
    over 'sp' (reference: per-rank dataloader shards batch implicitly;
    sequence split enters the CP region via split_forward_gather_backward
    cp/utils.py:219-259)."""
    rules = make_rules(config)
    return spec_for(("batch", "seq"), rules)


def constraint(x: jax.Array, logical_axes: Sequence[Optional[str]],
               rules: LogicalRules, mesh: Optional[Mesh] = None) -> jax.Array:
    """``with_sharding_constraint`` by logical axis names — the equivalent of
    the reference's ``xs.mark_sharding`` (tp.py:1-5) applied to activations."""
    spec = spec_for(logical_axes, rules)
    if mesh is not None:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def micro_split_spec(data_axes: Sequence[str], mesh,
                     num_micro: int, micro_rows: int,
                     ndim: int) -> Optional[PartitionSpec]:
    """Reshape-NATURAL sharding of a ``[B, ...] -> [M, mb, ...]``
    micro-batch split, or None when no per-dim factorisation exists.

    A batch dim sharded contiguously over ``data_axes`` propagates
    through the split reshape to ``P(m_axes, mb_axes)`` exactly when a
    leading run of the axes tiles the micro dim completely (their
    product divides ``M``) and — if any axes remain — that run covers
    ``M`` exactly while the remainder divides ``mb``.  Pipeline
    schedules pin micro ROWS to the data axes (``P(None, data_axes)``,
    parallel/pp.py) so the per-tick dynamic index over M stays local;
    going from the batch layout to that pin *through the reshape* in
    one hop is exactly what GSPMD cannot do ("Involuntary full
    rematerialization", replicate-then-repartition).  Constraining the
    reshape's output to this natural spec first makes the reshape
    itself movement-free; the natural->pin hop then lowers as ordinary
    per-dim reshards (all-gather + dynamic-slice).  The mirror is used
    on the way out, around the loss-reduction/gradient reshape back to
    ``[B, ...]``.
    """
    extents = [int(mesh.shape[a]) for a in data_axes]
    m_axes: List[str] = []
    prod = 1
    i = 0
    while i < len(data_axes) and num_micro % (prod * extents[i]) == 0:
        prod *= extents[i]
        m_axes.append(data_axes[i])
        i += 1
    mb_axes = list(data_axes[i:])
    if mb_axes:
        rest = math.prod(extents[i:])
        if prod != num_micro or micro_rows % rest != 0:
            return None
    return PartitionSpec(tuple(m_axes) if m_axes else None,
                         tuple(mb_axes) if mb_axes else None,
                         *([None] * max(ndim - 2, 0)))


def fsdp_gather_params(tree: Any, specs: Any = None) -> Any:
    """Constrain every array leaf of a (one layer's) param tree to its
    UNSHARDED-over-fsdp layout — the decomposed FSDP boundary
    (``perf.overlap_fsdp``).

    Under GSPMD a with_sharding_constraint to ``P()`` on an
    fsdp-sharded weight lowers to exactly the all-gather the consuming
    matmul would otherwise trigger — but as a *standalone* op whose
    only operand is the stacked param slice.  The overlap loop
    (models/transformer.py) applies this at the top of each layer's
    block fn — inside the remat region, so residuals stay the
    fsdp-sharded slices and backward re-gathers (ZeRO-3 memory) —
    and since the gather has no data dependence on any other layer's
    compute, XLA's (latency-hiding) scheduler can overlap layer i+1's
    gather with layer i's compute; the backward mirror is each layer's
    weight cotangent resharding back into the fsdp-sharded stack
    independently of older layers' backward compute.  The gathered
    VALUES are bit-identical to
    what the non-overlapped path consumes, so the FORWARD (and the
    first step's loss) is bitwise-identical with overlap on/off
    (tests/test_quant.py pins this); the backward's weight-grad
    collective lowers as all-reduce instead of reduce-scatter, whose
    different summation order perturbs gradients at the reduction-order
    level (~1e-7 relative) — trajectories agree to that tolerance.

    ``specs`` (optional, per-leaf PartitionSpecs matching ``tree`` —
    :func:`fsdp_gather_specs` builds them from the param axes rules)
    keeps NON-fsdp sharding in place: on a tensor-parallel mesh the
    megatron 'tp' dims of each weight stay sharded and only the
    fsdp/ZeRO-3 dim is gathered — without specs every leaf is
    constrained fully replicated, which would also undo TP.

    No-op without a live mesh (plain single-device apply) so model code
    can call it unconditionally — same contract as
    :func:`activation_constraint`.
    """
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or not mesh.shape:
            return tree
    except Exception:
        return tree

    def one(a, spec=None):
        if not hasattr(a, "ndim"):
            return a
        if spec is None:
            spec = PartitionSpec(*([None] * a.ndim))
        return jax.lax.with_sharding_constraint(
            a, _known_divisible(spec, a, mesh))
    if specs is None:
        return jax.tree.map(one, tree)
    return jax.tree.map(one, tree, specs)


def fsdp_gather_specs(tree: Any, rules: LogicalRules) -> Any:
    """Per-leaf PartitionSpecs for :func:`fsdp_gather_params`: each
    param leaf's logical axes (models/axes.py path rules) mapped
    through ``rules`` with its ZeRO-3 dim — the ``embed`` one, over
    ``fsdp`` and ``ep`` — left whole: "this weight's layout, minus its
    ZeRO-3 shard".  Constraining to these gathers ONLY that shard;
    tp dims keep their megatron layout and an expert leaf's ``expert``
    dim stays on ``ep``.  ``tree`` must be the per-layer (sliced) param
    tree so the leaf ranks match the axes rules."""
    from torchacc_tpu.models.axes import param_axes
    axes_tree = param_axes(tree)

    def one(leaf, axes):
        if axes is None or not hasattr(leaf, "ndim"):
            return None
        spec = spec_for(axes, rules)
        parts = tuple(spec) + (None,) * (leaf.ndim - len(spec))
        return PartitionSpec(*(None if ax == "embed" else part
                               for ax, part in zip(axes, parts)))
    return jax.tree.map(one, tree, axes_tree,
                        is_leaf=lambda x: x is None)


def _known_divisible(spec: PartitionSpec, x: jax.Array,
                     mesh) -> PartitionSpec:
    """Drop axes the live mesh doesn't know, then longest-divisible
    prefix — the same cleanup :func:`activation_constraint` applies, so
    a constraint can never ask GSPMD to pad."""
    known = []
    for tgt in tuple(spec) + (None,) * (x.ndim - len(spec)):
        axes = tgt if isinstance(tgt, tuple) else ((tgt,) if tgt else ())
        axes = tuple(a for a in axes if a in mesh.shape)
        if not axes:
            known.append(None)
        elif isinstance(tgt, tuple):
            known.append(axes)
        else:
            known.append(axes[0])
    return _divisible(PartitionSpec(*known), x.shape, mesh)


def activation_constraint(x: jax.Array,
                          logical_axes: Sequence[Optional[str]],
                          rules: LogicalRules = DEFAULT_RULES) -> jax.Array:
    """Best-effort activation sharding hint (megatron-style TP activation
    layout — the reference's ``xs.mark_sharding`` on activations, tp.py:1-5).

    No-op when no mesh is active (plain single-device apply), so model
    code can call it unconditionally.
    """
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or not mesh.shape:
            return x
    except Exception:
        return x
    spec = spec_for(logical_axes, rules)
    # drop axes the mesh doesn't know, then longest-divisible-prefix
    known = []
    for tgt in tuple(spec) + (None,) * (x.ndim - len(spec)):
        axes = tgt if isinstance(tgt, tuple) else ((tgt,) if tgt else ())
        axes = tuple(a for a in axes if a in mesh.shape)
        if not axes:
            known.append(None)
        elif isinstance(tgt, tuple):
            known.append(axes)
        else:
            known.append(axes[0])
    cleaned = _divisible(PartitionSpec(*known), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, cleaned)
