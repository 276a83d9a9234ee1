"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state.  Single-pod: 16x16 = 256 chips (v5e pod),
multi-pod: 2x16x16 = 512 chips with a leading "pod" data-parallel axis.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model: int = 1):
    """Debug mesh over however many local devices exist."""
    n = jax.device_count()
    assert n % model == 0, (n, model)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
