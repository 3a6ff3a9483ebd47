"""Multi-axis device meshes: dp / fsdp / tp / sp / ep / pp over ICI + DCN.

Reference contrast (SURVEY.md §2.6): the reference is DP-only — its notion of
topology is "local comm within a node, cross comm across" (mpi_context.cc
local/cross communicators, HOROVOD_HIERARCHICAL_ALLREDUCE). The TPU-native
generalisation is an N-dimensional named mesh: contiguous inner axes ride
ICI within a slice, the outermost axis rides DCN across slices
(``create_hybrid_device_mesh``). Every parallelism style is then just an
axis name to shard over — process sets and hierarchical ops fall out as
sub-axes instead of extra communicators.

Canonical axis names (used by models/ sharding rules):
  dp    — data parallel (gradient psum)
  fsdp  — parameter-sharded data parallel (ZeRO-3-style; reducescatter+allgather)
  sp    — sequence/context parallel (ring attention / Ulysses)
  tp    — tensor parallel (megatron-style partials psum)
  ep    — expert parallel (MoE all_to_all)
  pp    — pipeline parallel (ppermute microbatch handoff)
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_ORDER = ("pp", "dp", "fsdp", "ep", "sp", "tp")


def create_mesh(axis_sizes: Dict[str, int],
                devices: Optional[Sequence[jax.Device]] = None,
                allow_split_physical_axes: bool = True) -> Mesh:
    """Build a named mesh. Axes with size 1 are kept (harmless, lets model
    code reference them unconditionally). Axis product must equal device
    count. The innermost axes (tp, sp) get the most-contiguous placement so
    their collectives ride the shortest ICI paths.
    """
    devices = list(devices) if devices is not None else jax.devices()
    names = [a for a in AXIS_ORDER if a in axis_sizes]
    names += [a for a in axis_sizes if a not in names]  # user extras last
    sizes = [int(axis_sizes[a]) for a in names]
    total = int(np.prod(sizes))
    if total != len(devices):
        raise ValueError(
            f"mesh axes {dict(zip(names, sizes))} require {total} devices, "
            f"have {len(devices)}")
    from jax.experimental import mesh_utils
    arr = mesh_utils.create_device_mesh(
        sizes, devices=devices,
        allow_split_physical_axes=allow_split_physical_axes)
    return Mesh(arr, tuple(names))


def create_hybrid_mesh(ici_axes: Dict[str, int], dcn_axes: Dict[str, int],
                       devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Multi-slice mesh: ``dcn_axes`` (usually {'dp': n_slices}) across
    slices over DCN, ``ici_axes`` within each slice over ICI — the
    generalisation of the reference's hierarchical allreduce topology."""
    devices = list(devices) if devices is not None else jax.devices()

    def ordered(d):
        out = [a for a in AXIS_ORDER if a in d]
        return out + [a for a in d if a not in out]  # user extras last

    # DCN-bearing axes are OUTERMOST regardless of canonical-vs-extra
    # naming: the hierarchical collective paths (`_hierarchical_axes`)
    # treat axis[-1] as the ICI-contiguous axis, so a user DCN axis
    # ordered innermost would silently put the bandwidth-heavy
    # reduce-scatter phase on DCN (ADVICE r2 — a performance inversion,
    # not a numerics bug). Axes with BOTH extents sort with the DCN group.
    names = ordered(dcn_axes) + [a for a in ordered(ici_axes)
                                 if a not in dcn_axes]
    ici = [int(ici_axes.get(a, 1)) for a in names]
    dcn = [int(dcn_axes.get(a, 1)) for a in names]
    from jax.experimental import mesh_utils
    arr = mesh_utils.create_hybrid_device_mesh(
        ici, dcn, devices=devices)
    return Mesh(arr, tuple(names))


def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape.get(name, 1)
