"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device state
(the dry-run sets XLA_FLAGS before any jax initialization; smoke tests see the
single real device). Make a mesh current with ``with jax.set_mesh(mesh):``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Mesh over ``jax.devices()`` with every axis auto-sharded (tests, the
    NAHAS mesh-search's h-space knob, ``launch/train.py --mesh``)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips, axes (data, model).
    Multi-pod: 2 pods × 256 = 512 chips, axes (pod, data, model); only
    DP gradient all-reduce crosses the pod (DCN) boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
