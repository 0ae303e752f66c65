"""jit'd public wrapper for the grouped matmul.

``impl``: "pallas" (the TPU kernel), "interpret" (the Pallas body
executed in Python, for CPU validation) or "xla" (XLA's ragged dot).
Every impl gives the rows of each group the same product; rows after the
last group are undefined, so callers read only rows inside a group.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from . import kernel, ref


@functools.partial(jax.jit, static_argnames=("tm", "impl"))
def grouped_matmul(lhs: jax.Array, w: jax.Array, group_sizes: jax.Array,
                   layer: Optional[jax.Array] = None, *, tm: int,
                   impl: str = "pallas") -> jax.Array:
    """``lhs`` (M, K) in group order, ``w`` (G, K, N) or a stack of layers
    (L, G, K, N) read at ``layer``, ``group_sizes`` (G,) each a multiple
    of ``tm``."""
    if impl == "xla":
        return ref.grouped_matmul(lhs, w, group_sizes, layer)
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    return kernel.grouped_matmul_pallas(lhs, w, group_sizes, tm=tm,
                                        layer=layer,
                                        interpret=impl == "interpret")
