"""Device meshes.  IMPORTANT: functions, not module-level constants —
importing this module must never touch jax device state.

Axes are ``AxisType.Auto``: the model code places activations with
``with_sharding_constraint`` (``dist.sharding.shard``), which only
accepts Auto axes."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod DCN axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh():
    """Single-process smoke mesh over whatever devices exist."""
    n = len(jax.devices())
    return _mesh((n, 1), ("data", "model"))
