"""Production mesh construction.

A *function*, not a module-level constant — importing this module must never
touch jax device state (the dry-run pins the device count before first use).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16×16 chips per pod ("data","model"); 2 pods adds a leading "pod"
    axis.  v5e-256 pod topology; DCN spans the "pod" axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Whatever devices exist, as (data, model) — for tests/examples."""
    n = len(jax.devices())
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=_auto(2))


def _auto(n: int) -> tuple:
    """Auto (compiler-propagated) sharding on every axis — the model code
    places its own constraints; ``jax.make_mesh`` defaults to Explicit."""
    return (jax.sharding.AxisType.Auto,) * n
