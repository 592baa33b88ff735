"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to materialize the placeholder devices.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the models place arrays
    with ``with_sharding_constraint`` and GSPMD propagation, which the
    ``Explicit`` axes that ``jax.make_mesh`` now defaults to refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the pod axis is the
    outer data-parallel (DCN) axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, pods: int = 0):
    """Small mesh for CI-scale multi-device tests (subprocess with a handful
    of forced host devices)."""
    if pods:
        return make_mesh((pods, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))
