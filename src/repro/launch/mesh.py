"""Mesh builders.

Functions, not module-level constants — importing this module never touches
jax device state. The dry-run entrypoint sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before importing jax.

Every mesh in the repository comes from `make_mesh`: `jax.make_mesh` gives
`Explicit` axes by default, under which `with_sharding_constraint` and an
eager `shard_map` refuse the mesh. The engines here place arrays with
`NamedSharding` and let the partitioner propagate (`Auto` axes).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], names: Sequence[str], *,
              devices=None):
    """A mesh with `Auto` axis types over `devices` (default: all)."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the locally available devices (tests/examples)."""
    return make_mesh((data, model), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (everything except 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
