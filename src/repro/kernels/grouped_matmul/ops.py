"""jit'd public wrappers for the grouped matmul and the routed FFN.

``impl``: "pallas" (the TPU kernel), "interpret" (the Pallas body
executed in Python, for CPU validation) or "xla" (the reference: XLA's
ragged dot, or every held expert over every token).  Every impl of the
grouped matmul gives the rows of each group the same product; rows after
the last group are undefined, so callers read only rows inside a group.
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


@functools.partial(jax.jit, static_argnames=("tm", "impl"))
def routed_ffn(x: jax.Array, rank: jax.Array, gate: jax.Array,
               tiles: jax.Array, source: jax.Array, wg: jax.Array,
               wu: jax.Array, wd: jax.Array,
               layer: Optional[jax.Array] = None, *, tm: int,
               impl: str = "pallas") -> jax.Array:
    """A layer's held experts over its token rows ``x`` (T, d), routed by
    ``rank`` and ``gate`` (E, T); see ``kernel.routed_ffn_pallas``.  The
    reference reads neither ``tiles`` nor ``source``."""
    if impl == "xla":
        return ref.routed_ffn(x, rank, gate, wg, wu, wd, layer)
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    return kernel.routed_ffn_pallas(x, rank, gate, tiles, source, wg, wu,
                                    wd, tm=tm, layer=layer,
                                    interpret=impl == "interpret")
