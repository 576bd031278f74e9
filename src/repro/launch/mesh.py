"""Production mesh definitions (functions, never module-level constants —
importing this module must not touch jax device state).

Target hardware: TPU v5e pods, 256 chips each (16 x 16 ICI torus).
  single-pod : (16, 16)      axes ("data", "model")
  multi-pod  : (2, 16, 16)   axes ("pod", "data", "model"), pods joined by DCN

"data" carries DP + FSDP (weights/optimizer sharded over it); "model" carries
TP + EP; "pod" carries the paper's channels (pure DP + the partitioner split).
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "batch_axes"]


def _make_mesh(shape, axes):
    """make_mesh with Auto axis types on every axis."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(axes: Tuple[str, ...] = ("data", "model")):
    """1-device mesh with production axis names (CPU smoke tests)."""
    return _make_mesh((1,) * len(axes), axes)


def batch_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes that shard the batch dimension (everything but TP)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
