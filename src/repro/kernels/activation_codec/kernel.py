"""Pallas TPU kernels: fused int8 / packed-int4 activation codecs.

The quantise kernels fuse abs-max reduction, scale computation, rounding
(and, for int4, nibble packing) in one VMEM pass, so the HBM traffic is
exactly read-bf16 + write-quantised + write-scales (vs 3+ passes for the
naive lowering).

Grid: one axis over row tiles; each cell holds ``rt`` full rows.  The
per-(row, 128-block) scales of a row tile form a ``(rt, D/128)`` block
that spans the whole trailing dimension of the scale array — the TPU
lowering only accepts blocks whose last two dims are multiples of
(8, 128) or equal the array's, and ``D/128`` is far below 128 at real
widths (32 at d_model 4096).  Inside a cell the kernel walks the row's
128-lane blocks with static slices, so every load and store is
lane-aligned; the scale columns are assembled with a lane-iota select
rather than unaligned single-lane stores.

Rows: any count.  The wrappers pick ``rt`` (a multiple of 8, at most
``ROW_TILE``, with the input tile kept near ``TILE_BYTES``) to split the
rows into as few tiles as that allows with the least padding, zero-pad
the rows up to a whole number of tiles and slice the padding off the
outputs.  An OpenVLA request cuts at 273 rows (256 patches + 17 text
tokens): three 96-row tiles, 15 padded rows.

int4 packing pairs element ``j`` with element ``j + 128`` of each 256-lane
pair (the ref.py layout), so both nibble sources are themselves 128-lane
aligned slices: the pack is a mul-add on the VPU, never a strided lane
shuffle.  All nibble math is arithmetic in int32 (biased by +7, byte
offset −128) — no bitwise ops, which keeps the same code exact in
interpret mode on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_TILE = 256
LANE_TILE = 128
TILE_BYTES = 1 << 20        # input bytes per row tile (x2 double-buffered)


def _row_tiling(R: int, D: int, itemsize: int):
    """(row tile, padded row count) for ``R`` rows of ``D`` elements."""
    cap = max(8, min(ROW_TILE, TILE_BYTES // (D * itemsize)) // 8 * 8)
    n = -(-R // cap)
    per_tile = -(-R // n)
    rt = -(-per_tile // 8) * 8
    return rt, n * rt


def _pad_rows(a: jax.Array, Rp: int) -> jax.Array:
    R = a.shape[0]
    return a if Rp == R else jnp.pad(a, ((0, Rp - R), (0, 0)))


def _set_col(s, j: int, col):
    """``s`` with column ``j`` replaced by the (rt, 1) column ``col``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(lane == j, col, s)


def _quant_kernel(x_ref, q_ref, s_ref):
    rt, nb = s_ref.shape
    s = jnp.zeros((rt, nb), jnp.float32)
    for j in range(nb):
        blk = pl.ds(j * LANE_TILE, LANE_TILE)
        x = x_ref[:, blk].astype(jnp.float32)             # (rt, 128)
        amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q_ref[:, blk] = jnp.clip(jnp.round(x / scale), -127,
                                 127).astype(jnp.int8)
        s = _set_col(s, j, scale)
    s_ref[...] = s


def _dequant_kernel(q_ref, s_ref, o_ref, *, dtype):
    s = s_ref[...]
    for j in range(s.shape[1]):
        blk = pl.ds(j * LANE_TILE, LANE_TILE)
        o_ref[:, blk] = (q_ref[:, blk].astype(jnp.float32)
                         * s[:, j:j + 1]).astype(dtype)


def _check_width(D: int, mult: int):
    if D % mult:
        raise ValueError(f"codec kernel needs the last dim % {mult} == 0, "
                         f"got {D}")


def quantize_int8_pallas(x: jax.Array, *, interpret: bool = False):
    """x: (R, D) bf16/f32, D % 128 == 0 -> (int8 (R, D), f32 (R, D/128))."""
    R, D = x.shape
    _check_width(D, LANE_TILE)
    rt, Rp = _row_tiling(R, D, x.dtype.itemsize)
    nb = D // LANE_TILE
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(Rp // rt,),
        in_specs=[pl.BlockSpec((rt, D), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rt, D), lambda i: (i, 0)),
                   pl.BlockSpec((rt, nb), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Rp, D), jnp.int8),
                   jax.ShapeDtypeStruct((Rp, nb), jnp.float32)],
        interpret=interpret,
    )(_pad_rows(x, Rp))
    return q[:R], s[:R]


def dequantize_int8_pallas(q: jax.Array, s: jax.Array, dtype=jnp.bfloat16,
                           *, interpret: bool = False):
    R, D = q.shape
    _check_width(D, LANE_TILE)
    rt, Rp = _row_tiling(R, D, jnp.dtype(dtype).itemsize)
    nb = D // LANE_TILE
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, dtype=dtype),
        grid=(Rp // rt,),
        in_specs=[pl.BlockSpec((rt, D), lambda i: (i, 0)),
                  pl.BlockSpec((rt, nb), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rt, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, D), dtype),
        interpret=interpret,
    )(_pad_rows(q, Rp), _pad_rows(s, Rp))
    return out[:R]


# ------------------------------------------------------------------- int4
def _quant4_kernel(x_ref, p_ref, s_ref):
    rt, nb = s_ref.shape
    s = jnp.zeros((rt, nb), jnp.float32)
    for j in range(nb // 2):
        x = x_ref[:, pl.ds(2 * j * LANE_TILE, 2 * LANE_TILE)].astype(
            jnp.float32)                                  # (rt, 256)
        lo, hi = x[:, :LANE_TILE], x[:, LANE_TILE:]
        amax_lo = jnp.max(jnp.abs(lo), axis=1, keepdims=True)
        amax_hi = jnp.max(jnp.abs(hi), axis=1, keepdims=True)
        # constant multiply to stay bit-identical with ref.py under jit
        s_lo = jnp.where(amax_lo > 0, amax_lo * (1.0 / 7.0), 1.0)
        s_hi = jnp.where(amax_hi > 0, amax_hi * (1.0 / 7.0), 1.0)
        q_lo = jnp.clip(jnp.round(lo / s_lo), -7, 7).astype(jnp.int32) + 7
        q_hi = jnp.clip(jnp.round(hi / s_hi), -7, 7).astype(jnp.int32) + 7
        p_ref[:, pl.ds(j * LANE_TILE, LANE_TILE)] = (
            q_lo + 16 * q_hi - 128).astype(jnp.int8)
        s = _set_col(_set_col(s, 2 * j, s_lo), 2 * j + 1, s_hi)
    s_ref[...] = s


def _dequant4_kernel(p_ref, s_ref, o_ref, *, dtype):
    s = s_ref[...]
    for j in range(s.shape[1] // 2):
        p = p_ref[:, pl.ds(j * LANE_TILE, LANE_TILE)].astype(jnp.int32) + 128
        lo = (p % 16 - 7).astype(jnp.float32) * s[:, 2 * j:2 * j + 1]
        hi = (p // 16 - 7).astype(jnp.float32) * s[:, 2 * j + 1:2 * j + 2]
        o_ref[:, pl.ds(2 * j * LANE_TILE, LANE_TILE)] = lo.astype(dtype)
        o_ref[:, pl.ds((2 * j + 1) * LANE_TILE, LANE_TILE)] = hi.astype(dtype)


def quantize_int4_pallas(x: jax.Array, *, interpret: bool = False):
    """x: (R, D) bf16/f32, D % 256 == 0 ->
    (int8 packed (R, D/2), f32 scales (R, D/128))."""
    R, D = x.shape
    _check_width(D, 2 * LANE_TILE)
    rt, Rp = _row_tiling(R, D, x.dtype.itemsize)
    nb = D // LANE_TILE
    p, s = pl.pallas_call(
        _quant4_kernel,
        grid=(Rp // rt,),
        in_specs=[pl.BlockSpec((rt, D), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rt, D // 2), lambda i: (i, 0)),
                   pl.BlockSpec((rt, nb), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Rp, D // 2), jnp.int8),
                   jax.ShapeDtypeStruct((Rp, nb), jnp.float32)],
        interpret=interpret,
    )(_pad_rows(x, Rp))
    return p[:R], s[:R]


def dequantize_int4_pallas(p: jax.Array, s: jax.Array, dtype=jnp.bfloat16,
                           *, interpret: bool = False):
    R, Dh = p.shape
    D = 2 * Dh
    _check_width(D, 2 * LANE_TILE)
    rt, Rp = _row_tiling(R, D, jnp.dtype(dtype).itemsize)
    nb = D // LANE_TILE
    out = pl.pallas_call(
        functools.partial(_dequant4_kernel, dtype=dtype),
        grid=(Rp // rt,),
        in_specs=[pl.BlockSpec((rt, Dh), lambda i: (i, 0)),
                  pl.BlockSpec((rt, nb), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rt, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, D), dtype),
        interpret=interpret,
    )(_pad_rows(p, Rp), _pad_rows(s, Rp))
    return out[:R]
