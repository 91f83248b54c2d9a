"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes (data, model) — v5e pod.
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model); the `pod`
axis crosses the DCN and carries only data parallelism (gradient
all-reduce), never tensor parallelism.

Functions, not module constants: importing this module must never touch jax
device state (the dry-run pins the device count via XLA_FLAGS first).
"""
from __future__ import annotations

import jax


def auto_mesh(shape, axes, devices=None):
    """jax.make_mesh with every axis Auto (jax.make_mesh defaults to
    Explicit): shardings are propagated by the compiler, and shard_map /
    NamedSharding / with_sharding_constraint state them where it matters."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    model = min(model, n)
    data = n // model
    return auto_mesh((data, model), ("data", "model"))


def mesh_chips(mesh) -> int:
    return int(mesh.devices.size)
