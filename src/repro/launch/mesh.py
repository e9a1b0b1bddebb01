"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``xla_force_host_platform_device_count`` *before* first jax init.

  single-pod : (16, 16)    = ("data", "model")      — 256 chips (one v5e pod)
  multi-pod  : (2, 16, 16) = ("pod", "data", "model") — 512 chips

The "pod" axis is an extra pure-DP dimension by default (gradient reduction
over DCN); nothing below assumes its size is 2 — scaling out = growing it.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
               devices=None) -> Mesh:
    # Auto axes: the sharding rules place arrays with
    # ``with_sharding_constraint``, which Explicit axes (the make_mesh
    # default) refuse
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: Optional[int] = None, devices=None) -> Mesh:
    """(data, model) mesh over ``devices`` (default: every device)."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    model = model or 1
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"), devices)


def mesh_chips(mesh: Mesh) -> int:
    return mesh.devices.size
