"""Gradient compression: int8 ring all-reduce with per-chunk scales.

A classic bandwidth optimisation for data-parallel training: the ring
all-reduce moves int8 + fp32-scale chunks instead of bf16/f32 gradients —
~2-4x fewer wire bytes on the gradient collective (the dominant collective
term of the train_4k cells; see EXPERIMENTS.md §Perf).

Implemented with ``shard_map`` + ``lax.ppermute``: reduce-scatter phase with
per-hop requantisation, then an int8 all-gather phase.  Error feedback for
the *initial* quantisation is kept by the caller (train loop state);
per-hop requantisation noise is the standard trade-off.
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


Tree = Any


def _quant(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def _dequant(q: jax.Array, s: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * s


def ring_allreduce_int8(x: jax.Array, axis: str, rank=None) -> jax.Array:
    """Sum `x` (identical shape on each shard) over `axis`, int8 on the wire.

    Call inside shard_map.  x: any shape; internally chunked N-ways.
    `rank`: this shard's index along `axis`; pass it explicitly from
    partial-manual shard_map regions (axis_index lowers to PartitionId,
    which GSPMD rejects there).
    """
    N = jax.lax.axis_size(axis)
    if N == 1:
        return x
    r = jax.lax.axis_index(axis) if rank is None else rank
    perm = [(i, (i + 1) % N) for i in range(N)]
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % N
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, jnp.float32)])
    chunks = flat.reshape(N, -1)

    # ---- reduce-scatter: after N-1 hops, rank r owns chunk (r+1) % N
    def rs_step(k, chunks):
        send_idx = (r - k) % N
        send = jax.lax.dynamic_index_in_dim(chunks, send_idx, 0,
                                            keepdims=False)
        q, s = _quant(send)
        q = jax.lax.ppermute(q, axis, perm)
        s = jax.lax.ppermute(s, axis, perm)
        recv_idx = (r - k - 1) % N
        upd = jax.lax.dynamic_index_in_dim(chunks, recv_idx, 0,
                                           keepdims=False) + _dequant(q, s)
        return jax.lax.dynamic_update_index_in_dim(chunks, upd, recv_idx, 0)

    chunks = jax.lax.fori_loop(0, N - 1, rs_step, chunks)

    # ---- all-gather: circulate completed chunks (int8 on the wire)
    def ag_step(k, chunks):
        send_idx = (r + 1 - k) % N
        send = jax.lax.dynamic_index_in_dim(chunks, send_idx, 0,
                                            keepdims=False)
        q, s = _quant(send)
        q = jax.lax.ppermute(q, axis, perm)
        s = jax.lax.ppermute(s, axis, perm)
        recv_idx = (r - k) % N
        return jax.lax.dynamic_update_index_in_dim(
            chunks, _dequant(q, s), recv_idx, 0)

    chunks = jax.lax.fori_loop(0, N - 1, ag_step, chunks)
    out = chunks.reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(orig_shape).astype(orig_dtype)


def compressed_psum_tree(tree: Tree, axis: str) -> Tree:
    return jax.tree_util.tree_map(
        lambda g: ring_allreduce_int8(g, axis), tree)


# ------------------------------------------------------- error feedback (EF)
def ef_compress(grads: Tree, ef: Tree) -> Tuple[Tree, Tree]:
    """One-shot int8 quantisation with error feedback: returns
    (dequantised grads to feed the ring, new residual)."""
    def one(g, e):
        tgt = g.astype(jnp.float32) + e
        q, s = _quant(tgt)
        deq = _dequant(q, s)
        return deq.astype(g.dtype), tgt - deq

    out = jax.tree_util.tree_map(one, grads, ef)
    g2 = jax.tree_util.tree_map(lambda o: o[0], out,
                                is_leaf=lambda x: isinstance(x, tuple))
    ef2 = jax.tree_util.tree_map(lambda o: o[1], out,
                                 is_leaf=lambda x: isinstance(x, tuple))
    return g2, ef2
