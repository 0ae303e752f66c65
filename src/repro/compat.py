"""Mesh and shard_map helpers for the installed JAX (0.9).

Every mesh/shard_map call in ``src/`` goes through these two helpers, so
the options this repo relies on are set in one place: Auto axis types on
every mesh axis, and replication (vma) checks off in shard_map regions.
"""
from __future__ import annotations

from typing import Optional, Sequence, Set

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def shard_map(f, *, mesh, in_specs, out_specs,
              axis_names: Optional[Set[str]] = None):
    """``jax.shard_map`` with replication checks disabled.

    ``axis_names``: the manual axes of a partial-manual region; ``None``
    means fully manual (all axes).
    """
    kw = {} if axis_names is None else {"axis_names": set(axis_names)}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False, **kw)
