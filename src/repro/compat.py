"""Version compatibility shims for the installed jax.

* ``shard_map`` — ``jax.shard_map`` with ``axis_names`` given as any
  iterable (the axes to run Manual; None = all of them).
"""
from __future__ import annotations

import jax

__all__ = ["shard_map"]


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map`` with ``axis_names`` as any iterable of mesh axes
    mapped Manual inside ``f`` (None = all of them)."""
    kwargs = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kwargs)
