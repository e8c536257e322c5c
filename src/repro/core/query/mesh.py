"""Device meshes for sharded serving, and the divisibility-safe specs on them.

A function, not a module-level constant: importing this module never touches
jax device state, so a caller may still force host devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax starts.

The serving runtime shards prefused partials over ``"model"`` and request
batches over the data-parallel axes (``"pod"`` and ``"data"``, where
present).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType, PartitionSpec as P


def make_serving_mesh(shape, axes=("data", "model")):
    """A (data, model) serving mesh over the first ``prod(shape)`` devices.

    The axes are ``Auto``: the sharded serving runtime places its arrays
    with explicit ``NamedSharding``s and runs ``shard_map`` programs, and
    slices their outputs eagerly, which ``Explicit`` axes (the
    ``jax.make_mesh`` default) reject.  The runtime shards prefused
    partials over ``"model"`` and request batches over ``"data"``; on CPU,
    force devices first with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    shape, axes = tuple(int(s) for s in shape), tuple(axes)
    n = math.prod(shape)
    devices = jax.devices()
    if n > len(devices):
        raise ValueError(f"mesh shape {shape} needs {n} devices, "
                         f"have {len(devices)}")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices[:n])


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod folds into DP)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def dp_size(mesh) -> int:
    out = 1
    for a in dp_axes(mesh):
        out *= mesh.shape[a]
    return out


def _div(mesh, dim: int, axis) -> bool:
    if axis is None:
        return True
    axes = (axis,) if isinstance(axis, str) else axis
    total = 1
    for a in axes:
        if a not in mesh.axis_names:
            return False
        total *= mesh.shape[a]
    return dim % total == 0


def safe_spec(mesh, shape, *axes):
    """PartitionSpec with divisibility fallback per dim."""
    return P(*[a if _div(mesh, d, a) else None
               for d, a in zip(shape, axes)])
