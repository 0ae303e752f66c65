"""Pallas TPU kernels for the routed experts a chip holds.

Two kernels, picked by ``models/moe.dropless_moe_ffn`` from the layer's
shapes (``routed_ffn_fits``):

- ``routed_ffn_pallas`` runs a whole layer's held experts in one call
  when the layer's token rows and an f32 accumulator over them fit the
  core's vector memory beside two experts' weights (one robot's 273 rows
  at kimi-vl-a3b-vla's widths do).  The tokens stay resident; the grid
  walks the held experts given rows, and each step streams one expert's
  ``wg``, ``wu`` and ``wd`` while the previous expert computes.  A tile
  of an expert's rows is gathered from the resident tokens by a one-hot
  matmul built from the routing tables, run through the expert's SwiGLU,
  and added back to the tokens it came from, times their gates, by the
  transposed one-hot: no routed row is written to HBM.
- ``grouped_matmul_pallas`` serves larger token blocks: rows arrive in
  group (expert) order and every group's rows fill whole tiles of ``tm``
  rows, so each tile belongs to one group.  The grid walks the tiles in
  order, and a tile's weight block is its group's whole ``(K, tn)`` slab:
  consecutive tiles of one group keep the block index, so the pipeline
  fetches each group's weights once per ``tn`` columns while the next
  group's slab streams in behind the current tile's matmul.

At batch 1 a held expert sees a few dozen rows, so both are streams of
weight reads: a block of several MiB per step keeps the DMA engine busy
and the per-step cost small beside it.  The grid's length is traced, so
experts or tiles past the last one given rows are never visited.  A
stack of layers' weights is read at a traced layer index inside the
kernel, so a layer loop hands it the whole stack and no step copies a
layer out of it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# largest weight block: two of them (double buffering) and the row tiles
# must fit the core's scoped vector memory
BLOCK_BYTES = 8 * 1024 * 1024
# rows of a routed-kernel tile: the MXU is 128 rows wide, so a tile of up
# to 128 rows costs it what a tile of a few rows does
ROUTED_TILE = 128
# vector memory the routed kernel may ask for (a v5e core has 128 MiB)
ROUTED_VMEM = 64 * 1024 * 1024


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


def routed_vmem_bytes(tokens: int, d: int, f: int, itemsize: int) -> int:
    """Vector memory of ``routed_ffn_pallas`` over ``tokens`` rows of
    width ``d`` and experts ``f`` wide: two experts' weights (double
    buffering), the token rows and their routed sum (each double
    buffered), the f32 accumulator and a tile's combined product, and room
    for a tile's temporaries."""
    return (2 * 3 * d * f * itemsize + 4 * tokens * d * itemsize
            + 2 * tokens * d * 4 + (8 << 20))


def routed_ffn_fits(tokens: int, d: int, f: int, itemsize: int) -> bool:
    """Whether a layer of these shapes runs in ``routed_ffn_pallas``."""
    return routed_vmem_bytes(tokens, d, f, itemsize) <= ROUTED_VMEM


def _split3(y):
    """f32 ``y`` as three bf16 parts whose sum is ``y`` to f32 rounding."""
    hi = y.astype(jnp.bfloat16)
    r = y - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    return hi, mid, (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _routed_kernel(source_ref, tiles_ref, layer_ref, rank_ref, gate_ref,
                   x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *, tm):
    del source_ref, layer_ref
    e = pl.program_id(0)
    f32 = jnp.float32

    @pl.when(e == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(j, carry):
        rank, gate = rank_ref[pl.ds(e, 1), :], gate_ref[pl.ds(e, 1), :]
        # sel[i, t]: token t is row j * tm + i of expert e
        row = jax.lax.broadcasted_iota(jnp.int32, (tm, rank.shape[1]), 0)
        sel = rank == (row + j * tm).astype(f32)
        xs = jnp.dot(sel.astype(x_ref.dtype), x_ref[...],
                     preferred_element_type=f32).astype(x_ref.dtype)
        h = jax.nn.silu(jnp.dot(xs, wg_ref[...], preferred_element_type=f32)) \
            * jnp.dot(xs, wu_ref[...], preferred_element_type=f32)
        y = jnp.dot(h.astype(x_ref.dtype), wd_ref[...],
                    preferred_element_type=f32)
        y = y * jnp.sum(jnp.where(sel, gate, 0.0), axis=1, keepdims=True)
        # acc[t] += y[i] where sel[i, t]: the one-hot is exact in bf16,
        # y goes in as three bf16 parts summed in f32
        ones = jnp.concatenate([sel.astype(jnp.bfloat16)] * 3, axis=0)
        acc_ref[...] += jax.lax.dot_general(
            ones, jnp.concatenate(_split3(y), axis=0),
            (((0,), (0,)), ((), ())), preferred_element_type=f32)
        return carry

    jax.lax.fori_loop(0, tiles_ref[e], tile, 0)

    @pl.when(e == pl.num_programs(0) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def routed_ffn_pallas(x: jax.Array, rank: jax.Array, gate: jax.Array,
                      tiles: jax.Array, source: jax.Array, wg: jax.Array,
                      wu: jax.Array, wd: jax.Array, *, tm: int,
                      layer: Optional[jax.Array] = None,
                      interpret: bool = False) -> jax.Array:
    """A layer's held experts in one call.  ``x`` (T, d) the layer's
    token rows; ``rank`` (E, T) f32, each token's row among a held
    expert's rows (-1 where it is not routed to the expert), ``gate``
    (E, T) f32 its gate there; ``tiles`` (E,) each expert's tiles of
    ``tm`` rows; ``source`` (E,) the expert whose weights each step
    fetches: itself if it has rows, else one that has (the next, else
    the last), so that no step fetches weights it does not use;
    ``wg``, ``wu`` (E, d, f) and ``wd`` (E, f, d), or stacks
    (L, E, ., .) read at ``layer`` in place.  Returns (T, d): for each
    token, the sum over the held experts routed to of its gate times the
    expert's SwiGLU of it."""
    if layer is None:
        wg, wu, wd, layer = wg[None], wu[None], wd[None], 0
    T, d = x.shape
    E, f = wg.shape[1], wg.shape[-1]

    def weights(e, src, t, ly):
        return ly[0], src[e], 0, 0

    def whole(e, src, t, ly):
        return 0, 0

    return pl.pallas_call(
        functools.partial(_routed_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((T, d), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(E,),
            in_specs=[
                pl.BlockSpec((E, T), whole),
                pl.BlockSpec((E, T), whole),
                pl.BlockSpec((T, d), whole),
                pl.BlockSpec((None, None, d, f), weights),
                pl.BlockSpec((None, None, d, f), weights),
                pl.BlockSpec((None, None, f, d), weights),
            ],
            out_specs=pl.BlockSpec((T, d), whole),
            scratch_shapes=[pltpu.VMEM((T, d), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=routed_vmem_bytes(
                T, d, f, jnp.dtype(wg.dtype).itemsize)),
        interpret=interpret,
    )(source, tiles, jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      rank, gate, x, wg, wu, wd)
