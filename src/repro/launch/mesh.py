"""Production mesh builders.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods × 256 = 512 chips as (pod=2, data=16, model=16) — the
"pod" axis is the rack-to-rack boundary LUMORPH's fibers cascade across;
gradient all-reduce runs over ("pod", "data").

Functions, not module-level constants: importing this module must never
touch jax device state (the dry-run pins the device count *before* any
jax initialization).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over however many (real or fake) devices exist — tests."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def data_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.shape else ("data",)
