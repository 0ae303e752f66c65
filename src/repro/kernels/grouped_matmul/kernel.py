"""Pallas TPU grouped matmul for experts held at a chip's share.

Rows arrive in group (expert) order and every group's rows fill whole
tiles of ``tm`` rows, so each tile belongs to one group.  The grid walks
the tiles in order, and a tile's weight block is its group's whole
``(K, tn)`` slab: consecutive tiles of one group keep the block index, so
the pipeline fetches each group's weights once per ``tn`` columns while
the next group's slab streams in behind the current tile's matmul.  At
batch 1 a held expert sees a few dozen rows, so this is a stream of
weight reads: a block of several MiB per step keeps the DMA engine busy
and the per-step cost small beside it.  The grid's tile count is traced,
so tiles past the last group are never visited and their rows are left
unwritten.  A stack of layers' weights is read at a traced layer index
inside the kernel, so a layer loop hands it the whole stack and no step
copies a layer out of it.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# largest weight block: two of them (double buffering) and the row tiles
# must fit the core's scoped vector memory
BLOCK_BYTES = 8 * 1024 * 1024


def _columns(k: int, n: int, itemsize: int) -> int:
    """The widest ``tn`` (all of ``n``, or a multiple of 128 dividing it)
    whose ``(k, tn)`` weight block fits ``BLOCK_BYTES``."""
    if k * n * itemsize <= BLOCK_BYTES or n % 128:
        return n
    best = 128
    for tn in range(128, n + 1, 128):
        if n % tn == 0 and k * tn * itemsize <= BLOCK_BYTES:
            best = tn
    return best


def _kernel(tile_group_ref, layer_ref, x_ref, w_ref, o_ref):
    del tile_group_ref, layer_ref
    o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def grouped_matmul_pallas(lhs: jax.Array, w: jax.Array,
                          group_sizes: jax.Array, *, tm: int,
                          layer: Optional[jax.Array] = None,
                          interpret: bool = False) -> jax.Array:
    """``lhs`` (M, K), ``w`` (G, K, N), or a stack (L, G, K, N) read at
    ``layer`` in place, ``group_sizes`` (G,) multiples of ``tm`` summing
    to at most M: out (M, N) with the rows of group g equal to
    ``lhs[rows of g] @ w[g]``; rows after the last group are
    undefined."""
    if layer is None:
        w, layer = w[None], 0
    M, K = lhs.shape
    _, G, _, N = w.shape
    if M % tm:
        raise ValueError(f"{M} rows are not whole tiles of {tm}")
    tiles = group_sizes // tm
    tile_group = jnp.repeat(jnp.arange(G, dtype=jnp.int32), tiles,
                            total_repeat_length=M // tm)
    n_tiles = jnp.sum(tiles).astype(jnp.int32)
    item = jnp.dtype(w.dtype).itemsize
    tn = _columns(K, N, item)
    vmem = 2 * (tm * K + K * tn + tm * tn) * item + (4 << 20)
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // tn, n_tiles),
            in_specs=[
                pl.BlockSpec((tm, K), lambda j, i, tg, ly: (i, 0)),
                pl.BlockSpec((None, None, K, tn),
                             lambda j, i, tg, ly: (ly[0], tg[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, tg, ly: (i, j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        interpret=interpret,
    )(tile_group, jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), lhs, w)
