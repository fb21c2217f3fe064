"""Device-mesh construction from the parallelism config.

TPU-native equivalent of the reference's rank-topology machinery
(``ProcessTopology``/``Mesh`` torchacc/dist/mesh.py:13-418, which maps
n-D strategy coordinates to global ranks and builds per-axis NCCL process
groups).  Under JAX there are no process groups: a single
:class:`jax.sharding.Mesh` with named axes *is* the topology, and XLA
derives every collective's replica groups from shardings over it.

Axis ordering follows ``DistConfig.topology`` (slowest network first),
mirroring the reference's inter-/intra-node ordering
(torchacc/config.py:291-303): ``jax.experimental.mesh_utils`` assigns
later (fastest-varying) mesh axes to physically adjacent devices, so axes
late in the topology tuple ride ICI and early axes span DCN.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from torchacc_tpu.config import DistConfig


def build_mesh(
    dist: DistConfig,
    devices: Optional[Sequence[Any]] = None,
) -> Mesh:
    """Build a named device mesh for the configured parallelism.

    Axes of size 1 are kept in the mesh (shape-1 axes are free) so that
    sharding rules can always reference every axis name.
    """
    if devices is None:
        devices = jax.devices()
    world = len(devices)
    sizes = dist.axis_sizes(world)
    axis_names = tuple(dist.topology)
    shape = tuple(sizes[a] for a in axis_names)

    # mesh_utils places axes on the physical ICI/DCN topology.  Virtual
    # CPU devices have none, so there a row-major reshape (fastest-
    # varying axes on adjacent device ids) is the mesh; on real chips a
    # placement failure is an error — a reshape would hide a wrong layout.
    on_chips = devices[0].platform == "tpu"
    if dist.num_slices > 1 and on_chips:
        # Multi-slice (DCN-connected) topology: split the leading axes
        # across slices, the rest within a slice over ICI.  Mirrors the
        # reference's node-boundary-aware axis placement.
        per_slice = world // dist.num_slices
        dcn_shape, ici_shape = _split_shape_for_dcn(shape, dist.num_slices, per_slice)
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices)
        return Mesh(dev_array.reshape(shape), axis_names)
    if on_chips:
        dev_array = mesh_utils.create_device_mesh(
            shape, devices=np.asarray(devices))
    else:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, axis_names)


def _split_shape_for_dcn(
    shape: Tuple[int, ...], num_slices: int, per_slice: int
) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Factor the mesh shape into a DCN part (leading axes, product ==
    num_slices) and an ICI part (product == per_slice)."""
    dcn = []
    remaining = num_slices
    for s in shape:
        if remaining > 1:
            if remaining % s == 0:
                dcn.append(s)
                remaining //= s
            elif s % remaining == 0:
                raise ValueError(
                    f"axis of size {s} straddles the slice boundary "
                    f"(num_slices={num_slices}); reorder dist.topology so "
                    "DCN-spanning axes come first and divide num_slices")
            else:
                dcn.append(1)
        else:
            dcn.append(1)
    if remaining != 1:
        raise ValueError(
            f"cannot place num_slices={num_slices} on leading mesh axes {shape}")
    ici = tuple(s // d for s, d in zip(shape, dcn))
    return tuple(dcn), ici


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1)


def describe_mesh(mesh: Mesh) -> Dict[str, int]:
    return dict(mesh.shape)
