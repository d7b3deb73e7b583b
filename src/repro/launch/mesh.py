"""Production mesh construction (single-pod 16x16 and multi-pod 2x16x16).

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run pins the fake device count before any
jax initialization).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis Auto. The model code shards through
    ``with_sharding_constraint`` hints, which only Auto axes accept;
    ``jax.make_mesh`` makes Explicit axes unless told otherwise."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests / small runs."""
    return _make_mesh(tuple(shape), tuple(axes))


def make_search_mesh(num_devices=None):
    """1-D ``data`` mesh over (up to) every local device — the layout the
    sharded search backends (repro.shard) row-partition a DB across when
    no model parallelism is in play. ``ShardPlan.from_mesh`` derives the
    shard count from it."""
    n_avail = len(jax.devices())
    n = n_avail if num_devices is None else min(num_devices, n_avail)
    return _make_mesh((n,), ("data",))
