"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis is pure
data parallelism across the DCN/ICI-superlink boundary — gradients reduce
hierarchically (model → data → pod), which XLA emits as a two-stage
all-reduce.

Functions, not module constants: importing this module must never touch
jax device state (dryrun.py sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for smoke tests / examples on this container."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_search_mesh(n: int | None = None):
    """1-D ``("search",)`` mesh for the sharded ANNS datapath.

    ``n`` shards over the first n devices (default: all available).  On a
    CPU container, fake devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before the
    first jax call).
    """
    avail = len(jax.devices())
    n = avail if n is None else n
    if n > avail:
        raise ValueError(
            f"make_search_mesh({n}) needs {n} devices but only {avail} are "
            f"visible; set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} before the first jax call (host-platform meshes)")
    # Auto axes: the datapath places its arrays with NamedShardings and
    # partitions the search with shard_map, not with explicit sharding types
    return jax.make_mesh((n,), ("search",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh) -> tuple[str, ...]:
    """Axes used for batch/data parallelism (pod folds into data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
