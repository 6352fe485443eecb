"""Production mesh definitions (TPU v5e pods; 256 chips per pod).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state. Single pod: (data=16, model=16). Multi-pod adds a
leading 'pod' axis (pure DP across the DCN).

Every axis is ``Auto``: the model places activations with sharding
constraints (``distributed.hints``) and leaves the rest to the compiler's
propagation. jax's default ``Explicit`` axes would instead demand an
out-sharding at every gather and reshape.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(shape=(2, 4), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh over host CPU devices for tests."""
    return _auto_mesh(shape, axes)
