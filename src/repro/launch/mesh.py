"""Production mesh construction.

Kept as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
initialization, and smoke tests must keep seeing 1 device.

Every mesh axis is ``AxisType.Auto``: the model code places values with
``with_sharding_constraint`` and lets the compiler propagate, which
``jax.make_mesh``'s default of explicit axes refuses.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod mesh, or 2×16×16 two-pod mesh.

    Axes: ``pod`` — pure data parallelism across pods (gradient all-reduce
    crosses the inter-pod link once per step); ``data`` — FSDP + batch
    sharding inside a pod; ``model`` — tensor/expert parallelism.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh with auto axes (elastic restarts re-mesh through
    this)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist — CI / single-host runs."""
    n = len(jax.devices())
    data = max(1, n // model)
    return make_mesh((data, model), ("data", "model"))


def data_axes_of(mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
