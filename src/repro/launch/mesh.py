"""Production mesh — (2 pods x) 16 x 16 TPU v5e chips.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state; the dry-run sets XLA_FLAGS before first init.
"""

from __future__ import annotations

from typing import Tuple

import jax


def make_mesh(axis_shapes, axis_names, **kwargs):
    """``jax.make_mesh`` with every axis Auto: ``shard_map`` bodies and the
    sharding annotations of this repo are written for Auto axes, and jax
    defaults new meshes to Explicit ones."""
    kwargs.setdefault(
        "axis_types", (jax.sharding.AxisType.Auto,) * len(axis_names))
    return jax.make_mesh(axis_shapes, axis_names, **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes_of(mesh) -> Tuple[str, ...]:
    names = mesh.axis_names
    return tuple(a for a in names if a in ("pod", "data"))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over host devices (tests / CPU examples)."""
    return make_mesh((data, model), ("data", "model"))
