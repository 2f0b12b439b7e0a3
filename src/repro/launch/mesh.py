"""Production mesh construction (assignment MULTI-POD DRY-RUN §1).

A function, not a module-level constant, so importing this module never
touches jax device state. Every mesh gets ``Auto`` axes: the model code
places arrays with ``with_sharding_constraint`` under GSPMD, which
``jax.make_mesh``'s default ``Explicit`` axes refuse.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary meshes for tests (e.g. (2, 4) on 8 emulated devices)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
