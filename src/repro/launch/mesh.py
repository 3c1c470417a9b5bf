"""Production mesh factory.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.  Shapes: one v5e pod = 256 chips as
(data=16, model=16); two pods = 512 chips with a leading DCN-attached
``pod`` axis carrying only data parallelism.

Every axis is ``AxisType.Auto``: ``jax.make_mesh`` defaults to
``Explicit`` axes, under which ``with_sharding_constraint`` rejects the
partition specs the sharding rules and hints produce.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/examples (e.g. (2, 2) on 4 CPU devices)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))
