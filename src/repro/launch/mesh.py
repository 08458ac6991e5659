"""Mesh construction over the local devices.

Functions, not module-level constants: importing this module never
touches jax device state, so a process picks its platform and device
count before the first mesh is built.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mk(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(n_model: int = 1) -> Mesh:
    """Mesh over whatever devices exist locally (tests/examples)."""
    n = jax.device_count()
    if n % n_model:
        raise ValueError(f"{n} devices not divisible by model={n_model}")
    return _mk((n // n_model, n_model), ("data", "model"))


def dp_axis_names(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)
