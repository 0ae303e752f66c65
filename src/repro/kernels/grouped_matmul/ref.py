"""References in XLA: the grouped matmul as a ragged dot over the same row
layout, and the routed FFN as every held expert over every token."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def grouped_matmul(lhs: jax.Array, w: jax.Array, group_sizes: jax.Array,
                   layer: Optional[jax.Array] = None) -> jax.Array:
    """``lhs`` (M, K) rows in group order, ``w`` (G, K, N) or a stack
    (L, G, K, N) taken at ``layer``, ``group_sizes`` (G,): rows of group
    g times ``w[g]``; rows after the last group come out zero."""
    if layer is not None:
        w = jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
    return jax.lax.ragged_dot(lhs, w, group_sizes)


def routed_ffn(x: jax.Array, rank: jax.Array, gate: jax.Array,
               wg: jax.Array, wu: jax.Array, wd: jax.Array,
               layer: Optional[jax.Array] = None) -> jax.Array:
    """``x`` (T, d), ``rank`` and ``gate`` (E, T) (each token's row among
    an expert's, -1 where not routed, and its gate), ``wg``/``wu``
    (E, d, f) and ``wd`` (E, f, d) or stacks taken at ``layer``: each
    token's gated sum of the SwiGLU experts routed to it."""
    if layer is not None:
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
                      for w in (wg, wu, wd))
    f32 = jnp.float32
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, wg,
                               preferred_element_type=f32)) \
        * jnp.einsum("td,edf->etf", x, wu, preferred_element_type=f32)
    y = jnp.einsum("etf,efd->etd", h.astype(x.dtype), wd,
                   preferred_element_type=f32)
    gate = jnp.where(rank >= 0, gate, 0.0)
    return jnp.einsum("etd,et->td", y, gate,
                      precision=jax.lax.Precision.HIGHEST).astype(x.dtype)
