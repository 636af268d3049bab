"""Production meshes.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is pure data parallelism across ICI-disjoint pods (DCN), so only
gradient all-reduces cross it.

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes. ``jax.make_mesh`` defaults to
    ``Explicit`` axes, which ``with_sharding_constraint`` — and so
    ``repro.dist.ctx.annotate`` — refuses; every mesh the launchers hand to
    model code is made here."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 2, data: int = 2):
    """Small mesh over forced host devices (tests)."""
    return make_mesh((data, model), ("data", "model"))
