"""Mesh builders.

Functions (not module-level constants) so importing this module never
touches jax device state.  Every mesh is built with ``Auto`` axis types:
the model code places its arrays through sharding annotations and lets
the partitioner propagate them, which explicitly typed axes refuse
(e.g. the embedding gather raises ``ShardingTypeError``).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh"]


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods = 512 chips.

    Axes: ``pod`` (cross-pod data parallelism over DCN), ``data``
    (in-pod data parallelism), ``model`` (tensor parallelism over ICI).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist locally, as a 1D data mesh (smoke tests)."""
    return make_mesh((len(jax.devices()),), ("data",))
