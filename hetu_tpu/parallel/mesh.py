"""Device mesh model and DS -> jax.sharding lowering.

The reference binds a ``DistributedStates`` to an ordered ``DeviceGroup``
and derives NCCL groups from the DS order (``distributed_states.cc:399``
``get_devices_by_dim``).  On TPU the analogue is a ``jax.sharding.Mesh``:
we build a mesh whose *flat device order matches the DS placement order* and
whose axes are the DS order dims, then lower the DS to a
``NamedSharding(mesh, PartitionSpec(...))``.  XLA/GSPMD then derives the
collective groups the same way ``get_devices_by_dim`` does — by striding the
flat device list along each axis.

Two usage styles:

* **Standard 3D/4D training** — build one global mesh with named axes
  (``dp``/``cp``/``tp``/``pp``...) via :func:`create_mesh` and annotate with
  `PartitionSpec` by axis name (the idiomatic jax path, used by the nn
  parallel layers).
* **DS-driven** — arbitrary ``DistributedStates`` lowered by
  :func:`ds_to_named_sharding` (used by resharding, checkpoint, hot switch).
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec
P = PartitionSpec

from .dstates import DUPLICATE, PARTIAL, DistributedStates

# Canonical axis names for the standard training mesh.
AXIS_DP = "dp"      # data parallel
AXIS_CP = "cp"      # context (sequence) parallel — ring attention
AXIS_TP = "tp"      # tensor/model parallel
AXIS_PP = "pp"      # pipeline parallel (stage axis, used by shard_map PP)
AXIS_EP = "ep"      # expert parallel


def create_mesh(shape: Dict[str, int],
                devices: Optional[Sequence[jax.Device]] = None,
                allow_split_physical_axes: bool = True) -> Mesh:
    """Create a Mesh with named axes from a ``{axis: size}`` dict.

    Axis order in ``shape`` is significant: later axes are
    innermost/fastest-varying (ride ICI first), mirroring the DS ``order``
    semantics.  Standard layout: ``{"pp": ..., "dp": ..., "cp": ...,
    "tp": ...}`` keeps TP on the innermost (highest-bandwidth) axis.
    """
    names = tuple(shape.keys())
    sizes = tuple(int(shape[n]) for n in names)
    n = int(np.prod(sizes)) if sizes else 1
    if devices is None:
        devices = jax.devices()
        if devices[0].platform == "tpu" and len(devices) == n:
            # topology-aware assignment over a whole TPU slice; virtual
            # CPU devices and sub-slices take enumeration order below
            from jax.experimental import mesh_utils
            dev_array = mesh_utils.create_device_mesh(
                sizes, devices,
                allow_split_physical_axes=allow_split_physical_axes)
            return Mesh(dev_array, names)
    if len(devices) < n:
        raise ValueError(f"need {n} devices for mesh {shape}, got {len(devices)}")
    dev_array = np.asarray(devices[:n]).reshape(sizes)
    return Mesh(dev_array, names)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    dev = device or jax.devices()[0]
    return Mesh(np.asarray([dev]).reshape((1,)), (AXIS_DP,))


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1) if axis in mesh.axis_names else 1


# ---------------------------------------------------------------------------
# DS -> NamedSharding lowering
# ---------------------------------------------------------------------------

def _axis_name_for(dim: int) -> str:
    if dim == DUPLICATE:
        return "_dup"
    if dim == PARTIAL:
        return "_partial"
    return f"_s{dim}"


def ds_to_mesh_and_spec(ds: DistributedStates,
                        devices: Sequence[jax.Device],
                        ) -> Tuple[Mesh, PartitionSpec]:
    """Lower a DS (+ its ordered placement devices) to (Mesh, PartitionSpec).

    The mesh axes are the DS ``order`` dims, outermost first, so that the
    flat device order of the mesh equals the DS device numbering — the exact
    invariant ``map_device_to_state_index`` (``distributed_states.cc:371``)
    encodes.  Duplicate/partial dims become unassigned mesh axes
    (replication); a *partial* tensor is represented as replicated storage
    whose values are partial sums — reduction placement is decided at graph
    level via ``deduce_comm_kind``.
    """
    if len(devices) != ds.device_num:
        raise ValueError(
            f"DS over {ds.device_num} devices, got {len(devices)}")
    order = ds.order
    if not order:
        mesh = Mesh(np.asarray(devices).reshape((1,)), ("_dup",))
        return mesh, P()
    sizes = tuple(ds.get_dim(o) for o in order)
    names = tuple(_axis_name_for(o) for o in order)
    dev_array = np.asarray(devices).reshape(sizes)
    mesh = Mesh(dev_array, names)
    ndim = max((o for o in order if o >= 0), default=-1) + 1
    spec = [None] * ndim
    for o in order:
        if o >= 0:
            spec[o] = _axis_name_for(o)
    return mesh, P(*spec)


def ds_to_named_sharding(ds: DistributedStates,
                         devices: Sequence[jax.Device]) -> NamedSharding:
    mesh, spec = ds_to_mesh_and_spec(ds, devices)
    return NamedSharding(mesh, spec)


def ds_from_partition_spec(mesh: Mesh, spec: PartitionSpec,
                           partial_axes: Sequence[str] = (),
                           zero: bool = False) -> DistributedStates:
    """Inverse lowering: a (mesh, pspec) pair back to a DistributedStates.

    Used to reason about GSPMD-produced shardings in DS terms (tests,
    checkpoint resharding).  ``partial_axes`` marks mesh axes over which the
    array holds partial sums (unreduced), which GSPMD cannot express but DS
    can (dim -2).
    """
    device_num = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    states: Dict[int, int] = {}
    dim_of_axis: Dict[str, int] = {}
    spec_tuple = tuple(spec) if spec is not None else ()
    for d, entry in enumerate(spec_tuple):
        if entry is None:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
            dim_of_axis[a] = d
        if n > 1:
            states[d] = states.get(d, 1) * n
    partial = 1
    for a in partial_axes:
        partial *= mesh.shape[a]
        dim_of_axis[a] = PARTIAL
    if partial > 1:
        states[PARTIAL] = partial
    dup = device_num // int(np.prod(list(states.values()))) if states else device_num
    if dup > 1:
        states[DUPLICATE] = dup
    # Order: mesh axis order, outermost first; replicated axes -> DUPLICATE.
    order: List[int] = []
    for a in mesh.axis_names:
        d = dim_of_axis.get(a, DUPLICATE)
        if d not in order:
            order.append(d)
    order = [o for o in order if states.get(o, 1) > 1]
    return DistributedStates(device_num, states, order, zero=zero)


# ---------------------------------------------------------------------------
# Test/simulation support
# ---------------------------------------------------------------------------

def force_virtual_cpu_devices(n: int = 8) -> None:
    """Request ``n`` virtual CPU devices (must run before jax backend init).

    This is the multi-device simulation story the reference lacks
    (SURVEY.md §4 takeaway): DP/TP/PP/CP tests run on
    ``--xla_force_host_platform_device_count`` fake devices.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
