"""Production mesh construction.

A function, not a module-level constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model); the pod axis
is data-parallel across the (slower) inter-pod links — gradient all-reduce
is hierarchical: reduce-scatter inside pods, all-reduce across pods.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    mp = min(model_parallel, n)
    return make_serving_mesh((n // mp, mp))


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
