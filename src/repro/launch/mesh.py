"""Production mesh construction (assignment-specified).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state. Single pod: 16x16 = 256 chips ("data", "model");
multi-pod: 2x16x16 = 512 chips ("pod", "data", "model").
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """Named mesh with every axis ``Auto`` (sharding propagated by XLA)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / local training)."""
    return make_mesh((data, model), ("data", "model"))
