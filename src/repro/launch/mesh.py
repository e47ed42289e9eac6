"""Production mesh construction.

Importing this module never touches jax device state; call
:func:`make_production_mesh` only after the launcher has configured the
platform (the dry-run sets ``--xla_force_host_platform_device_count=512``
before any jax import)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod (single pod) or 2×16×16 = 512 chips.

    Axes: ``pod`` — data-parallel across the cross-pod (DCN-class) links;
    ``data`` — batch / FSDP / ZeRO axis; ``model`` — tensor/expert
    parallel axis, kept innermost so its collectives ride the fastest ICI
    neighborhoods.  Axes are ``Auto``: the model code places shardings with
    ``with_sharding_constraint``, which only accepts auto axes."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """A mesh over whatever devices exist locally (tests / examples)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"need {data * model} devices, have {n}")
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
