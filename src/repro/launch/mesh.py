"""Production mesh builders.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(data: int, model: int, pods: int = 1):
    """Arbitrary (pod ×) data × model mesh for tests / reduced runs."""
    if pods > 1:
        return _make_mesh((pods, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))
