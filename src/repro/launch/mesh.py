"""Production mesh builders.

Single pod: (16, 16) = 256 v5e chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model").

Functions (not module constants) so importing never touches jax device
state — the dry-run must set XLA_FLAGS before first jax init. Axes are
Auto: the model code places activations with sharding constraints
(``Runtime.constrain``), which Explicit axes would reject.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math

    need = math.prod(shape)
    devices = jax.devices()[:need]
    return _mesh(shape, axes, devices)


def make_debug_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU-device tests (requires host platform devices)."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
