"""Named device meshes for the sharded jobs (``launch/gram.py``,
``launch/cluster.py``) and their tests.

Functions, not module-level constants, so importing this module never
touches jax device state. ``make_host_mesh`` spans the devices present;
``make_production_mesh`` is the 16x16 ("data", "model") mesh, or 2x16x16
("pod", "data", "model") with ``multi_pod``, that the jobs' ``--dryrun``
compiles against.
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
    """Small mesh over whatever devices exist (tests, local jobs)."""
    return make_mesh((data, model), ("data", "model"))
