"""Production meshes.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state. Single pod = 16x16
(data, model) = 256 chips; multi-pod adds the pod axis: (2, 16, 16) = 512.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    return _mesh(tuple(shape), tuple(axes))
