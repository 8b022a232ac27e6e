"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count is locked at first jax init, and only the
dry-run is allowed to fake 512 host devices).

Every mesh is built with ``AxisType.Auto`` axes: the engines and the
composer's sub-meshes (plain ``Mesh(...)``) rely on sharding propagation,
while ``jax.make_mesh`` defaults to ``Explicit`` axes in JAX 0.9.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh

# spec fitting lives with the sharding rules now (the serving engine fits
# specs per composed sub-mesh at runtime); re-exported here for launch code.
from repro.distribution.partitioning import fit_spec, sanitize_spec  # noqa: F401


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 dual-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_host_mesh(shape, axes)


def make_host_mesh(shape: Tuple[int, ...] = (1, 1),
                   axes: Tuple[str, ...] = ("data", "model")) -> Mesh:
    """Mesh of ``shape`` over the first devices JAX sees, every axis
    ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:math.prod(shape)])
