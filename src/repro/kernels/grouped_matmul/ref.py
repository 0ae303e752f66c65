"""Reference grouped matmul: XLA's ragged dot over the same row layout."""
from __future__ import annotations

from typing import Optional

import jax


def grouped_matmul(lhs: jax.Array, w: jax.Array, group_sizes: jax.Array,
                   layer: Optional[jax.Array] = None) -> jax.Array:
    """``lhs`` (M, K) rows in group order, ``w`` (G, K, N) or a stack
    (L, G, K, N) taken at ``layer``, ``group_sizes`` (G,): rows of group
    g times ``w[g]``; rows after the last group come out zero."""
    if layer is not None:
        w = jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
    return jax.lax.ragged_dot(lhs, w, group_sizes)
