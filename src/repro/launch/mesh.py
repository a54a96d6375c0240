"""Production mesh construction (assignment contract).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax


def _auto(n):
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/examples (e.g. (1, 2) on CPU)."""
    return jax.make_mesh(tuple(shape), tuple(axes), **_auto(len(axes)))
