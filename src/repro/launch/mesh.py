"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state.  The dry-run
launcher sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before any jax import; smoke tests and benchmarks see the real single
CPU device and use ``make_local_mesh``.

Production topology (TPU v5e target):
  single-pod : (16, 16)    axes ("data", "model")   — 256 chips
  multi-pod  : (2, 16, 16) axes ("pod", "data", "model") — 512 chips,
               "pod" is an outer data axis; gradient reduction crosses
               the inter-pod links once per step.
"""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Mesh over the locally available devices (CPU tests)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Axes over which the global batch is sharded."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_parallel_size(mesh: Mesh) -> int:
    size = 1
    for a in batch_axes(mesh):
        size *= mesh.shape[a]
    return size
