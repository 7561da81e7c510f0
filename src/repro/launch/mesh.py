"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init and then builds the mesh; smoke tests build 1-device meshes.

Topology intent (TPU v5e):
  * single pod:   (16, 16)    ("data", "model") — 256 chips, ICI everywhere;
  * multi-pod:    (2, 16, 16) ("pod", "data", "model") — the "pod" axis is
    pure data parallelism across the DCN (slow) hop; "model" stays inside
    an ICI domain so TP collectives never cross pods.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(*, data: int = 1, model: int = 1):
    """Small mesh over however many devices this host has (tests)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"asked for {data}x{model} devices, have {n}")
    return _mesh((data, model), ("data", "model"))


def mesh_dp_size(mesh) -> int:
    out = 1
    for a in mesh.axis_names:
        if a in ("pod", "data"):
            out *= mesh.shape[a]
    return out


def mesh_tp_size(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1


def mesh_device_count(mesh) -> int:
    """Total devices in the mesh (the replica axis must divide this)."""
    out = 1
    for a in mesh.axis_names:
        out *= mesh.shape[a]
    return out


def replica_sharding(mesh):
    """NamedSharding placing dim 0 (the replica axis) over EVERY mesh
    axis jointly, remaining dims replicated — how the experiment layer
    (``launch/experiment.py``) shards a stacked ``Replicas`` pytree
    whose leaves have arbitrary trailing ranks."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as PS
    return NamedSharding(mesh, PS(tuple(mesh.axis_names)))


def put_chunk(tree, mesh, rows: int):
    """Shard one chunk's replica-leading pytree over ``mesh``
    (``launch/chunked.py`` calls this per chunk; every chunk — the
    remainder included — must divide over the mesh devices)."""
    n_dev = mesh_device_count(mesh)
    if rows % n_dev:
        raise ValueError(f"chunk of {rows} replicas must divide over "
                         f"{n_dev} devices")
    return jax.device_put(tree, replica_sharding(mesh))
