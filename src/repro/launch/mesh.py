"""Mesh construction.

FUNCTIONS (not module-level state) so importing this module never touches
jax device initialization.
"""

from __future__ import annotations

import jax


def _mesh_kwargs(axes):
    return {"axis_types": (jax.sharding.AxisType.Auto,) * len(axes)}


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes), **_mesh_kwargs(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) devices exist — used by
    tests and CPU examples."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(1, n // data))
    return make_mesh((data, model), ("data", "model"))
