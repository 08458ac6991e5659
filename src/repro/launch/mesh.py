"""Mesh construction for the production pods and local runs.

Functions, not module-level constants: importing this module never
touches jax device state (required so smoke tests see 1 CPU device while
the dry-run sees 512 host-platform placeholders).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mk(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 dual-pod (512 chips) mesh.

    Axes: ``data`` (+ ``pod``) carry data parallelism; ``model`` carries
    tensor/expert parallelism.  The dry-run requires
    XLA_FLAGS=--xla_force_host_platform_device_count=512 *before* any jax
    import (see ``dryrun.py``).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_local_mesh(n_model: int = 1) -> Mesh:
    """Mesh over whatever devices exist locally (tests/examples)."""
    n = jax.device_count()
    if n % n_model:
        raise ValueError(f"{n} devices not divisible by model={n_model}")
    return _mk((n // n_model, n_model), ("data", "model"))


def dp_axis_names(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def dp_size(mesh: Mesh) -> int:
    out = 1
    for a in dp_axis_names(mesh):
        out *= mesh.shape[a]
    return out
