"""Production meshes. Functions only — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import)."""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, **kwargs):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules
    place arrays with ``NamedSharding`` and leave propagation to GSPMD."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names), **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods,
    (pod=2, data=16, model=16) — the pod axis is pure data parallelism
    across the inter-pod (DCN-ish) boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_container_mesh(total_chips: int, n_containers: int):
    """The paper's factorisation as ONE joint mesh: n containers ×
    (chips/n) model shards. The "data" axis is the container axis (weights
    replicated across it) — the logical view for dry-runs/rooflines."""
    assert total_chips % n_containers == 0
    return make_mesh(
        (n_containers, total_chips // n_containers), ("data", "model"))


def make_container_meshes(total_chips: int, n_containers: int,
                          devices=None):
    """The paper's factorisation as n PHYSICAL meshes: one
    ``(data=1, model=chips/n)`` mesh per container, each over a disjoint
    contiguous slice of the pod's device list. Engines committed to these
    meshes occupy pairwise-disjoint device sets (serving/engine.py), so a
    concurrent pool overlaps real parallel hardware. On CPU CI, export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to fake a pod."""
    from repro.core.containers import ContainerSpec, container_meshes
    # divisibility is enforced by partition_indices inside container_meshes
    spec = ContainerSpec(n_containers, total_chips // n_containers,
                         total_chips)
    return container_meshes(spec, devices)


def mesh_axis_size(mesh, name: str) -> int:
    """Axis size by name (1 if absent). Works for Mesh and AbstractMesh."""
    return dict(mesh.shape).get(name, 1)
