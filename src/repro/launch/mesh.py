"""Production meshes. Functions only — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).

Meshes are built with ``Auto`` axes: the program shards by constraint and
GSPMD propagation (``repro.sharding``), which ``Explicit`` axes — the
``jax.make_mesh`` default — refuse."""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods = 512
    chips as (pod=2, data=16, model=16); the pod axis carries pure data
    parallelism over DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small host-device meshes, e.g. (2, 4))."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(shape)
    )
