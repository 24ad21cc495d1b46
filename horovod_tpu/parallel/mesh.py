"""Device-mesh management: the TPU-native substrate for all collectives.

Where the reference builds NCCL communicators per process set
(horovod/common/mpi/mpi_context.cc, ops/nccl_operations.cc; SURVEY.md §2.8),
the TPU build names an axis of a ``jax.sharding.Mesh`` and lets XLA lower
``psum``/``all_gather``/... onto ICI rings.  The global mesh has a single
data-parallel axis ``"hvd"`` by default; richer layouts (dp × tp × sp × ep)
are built with :func:`build_mesh` and consumed by ``horovod_tpu.parallel``'s
sharded-training helpers — which is how TP/SP/EP become cheap extensions of
the same substrate (SURVEY.md §2.7).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

HVD_AXIS = "hvd"

_global_mesh = None

# jax process index -> hvd rank, learned at hvd.init() under jax.distributed.
# On a TPU a process's index follows its chips' place in the topology, not
# the process_id it gave the coordinator, so the two need not coincide (four
# one-chip workers on a v5e 2x2: ranks 0,1,2,3 are processes 0,2,3,1).
# Empty means identity.
_process_ranks: dict = {}


def build_global_mesh(axis_name: str = HVD_AXIS, devices=None):
    """Build (and remember) the 1-D global mesh over all visible devices."""
    import jax
    from jax.sharding import Mesh

    global _global_mesh
    if devices is None:
        devices = jax.devices()
    _global_mesh = Mesh(np.asarray(devices), (axis_name,))
    return _global_mesh


def build_mesh(axis_sizes: dict, devices=None):
    """Build an N-D mesh from ``{"dp": 2, "tp": 2, "sp": 2}``-style specs.

    Axis order follows insertion order; place the fastest-communicating axis
    last so it maps to the innermost ICI ring.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    names = tuple(axis_sizes)
    sizes = tuple(int(axis_sizes[n]) for n in names)
    n_needed = int(np.prod(sizes))
    if n_needed > len(devices):
        raise ValueError(f"mesh needs {n_needed} devices, have {len(devices)}")
    arr = np.asarray(devices[:n_needed]).reshape(sizes)
    return Mesh(arr, names)


def global_mesh():
    """The mesh built at hvd.init() (or None before init)."""
    return _global_mesh


def set_global_mesh(mesh) -> None:
    global _global_mesh
    _global_mesh = mesh


def reset() -> None:
    global _global_mesh
    _global_mesh = None
    _process_ranks.clear()


def set_process_ranks(mapping: dict) -> None:
    _process_ranks.clear()
    _process_ranks.update(mapping)


def rank_of_process(process_index: int) -> int:
    """The hvd rank of the jax process that owns a device."""
    return _process_ranks.get(process_index, process_index)


def mesh_axis_name() -> str:
    if _global_mesh is not None:
        return _global_mesh.axis_names[0]
    return HVD_AXIS


def sub_mesh(ranks: Sequence[int], axis_name: Optional[str] = None):
    """Mesh over the devices owned by the given process ranks.

    TPU analog of a process-set communicator: collectives over this mesh
    stay within the subset's ICI domain.
    """
    import jax
    from jax.sharding import Mesh

    axis_name = axis_name or mesh_axis_name()
    devices = [d for d in jax.devices()
               if rank_of_process(d.process_index) in ranks]
    if not devices:
        # Single-process simulation: treat local device i as "rank i"'s device.
        all_devices = jax.devices()
        devices = [all_devices[r] for r in ranks if r < len(all_devices)]
    if not devices:
        raise ValueError(f"no devices for ranks {ranks}")
    return Mesh(np.asarray(devices), (axis_name,))
