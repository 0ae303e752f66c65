"""jit'd public wrappers for the activation codecs (int8 + packed int4).

``impl``: "jnp" (XLA everywhere), "pallas" (TPU target), "interpret"
(Pallas body executed in Python — CPU validation).  Arbitrary-rank inputs
are flattened to (rows, D).  The Pallas kernels quantize in
``ref.BLOCK``-lane blocks only; any other ``block`` needs ``impl="jnp"``
and is refused otherwise, so the implementation asked for is the one
that runs.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from . import kernel, ref


def _kernel_block(impl: str, block: int) -> bool:
    """True when ``impl`` selects the Pallas kernel (raises on a block
    size the kernel does not implement)."""
    if impl == "jnp":
        return False
    if block != ref.BLOCK:
        raise ValueError(f"the Pallas codec kernels use {ref.BLOCK}-lane "
                         f"blocks; block={block} needs impl='jnp'")
    return True


@functools.partial(jax.jit, static_argnames=("impl", "block"))
def quantize(x: jax.Array, impl: str = "jnp", block: int = ref.BLOCK
             ) -> Tuple[jax.Array, jax.Array]:
    shape = x.shape
    D = shape[-1]
    if not _kernel_block(impl, block):
        return ref.quantize_int8(x, block)
    rows = x.size // D
    x2 = x.reshape(rows, D)
    q, s = kernel.quantize_int8_pallas(x2, interpret=(impl == "interpret"))
    return q.reshape(shape), s.reshape(*shape[:-1], D // block)


@functools.partial(jax.jit, static_argnames=("impl", "block", "dtype"))
def dequantize(q: jax.Array, s: jax.Array, dtype=jnp.bfloat16,
               impl: str = "jnp", block: int = ref.BLOCK) -> jax.Array:
    shape = q.shape
    D = shape[-1]
    if not _kernel_block(impl, block):
        return ref.dequantize_int8(q, s, dtype, block)
    rows = q.size // D
    out = kernel.dequantize_int8_pallas(
        q.reshape(rows, D), s.reshape(rows, D // block), dtype,
        interpret=(impl == "interpret"))
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("impl", "block"))
def quantize_int4(x: jax.Array, impl: str = "jnp", block: int = ref.BLOCK
                  ) -> Tuple[jax.Array, jax.Array]:
    """(..., D) with D % (2*block) == 0 -> (packed int8 (..., D/2),
    f32 scales (..., D/block))."""
    shape = x.shape
    D = shape[-1]
    if not _kernel_block(impl, block):
        return ref.quantize_int4(x, block)
    rows = x.size // D
    p, s = kernel.quantize_int4_pallas(x.reshape(rows, D),
                                       interpret=(impl == "interpret"))
    return (p.reshape(*shape[:-1], D // 2),
            s.reshape(*shape[:-1], D // block))


@functools.partial(jax.jit, static_argnames=("impl", "block", "dtype"))
def dequantize_int4(p: jax.Array, s: jax.Array, dtype=jnp.bfloat16,
                    impl: str = "jnp", block: int = ref.BLOCK) -> jax.Array:
    shape = p.shape
    Dh = shape[-1]
    if not _kernel_block(impl, block):
        return ref.dequantize_int4(p, s, dtype, block)
    rows = p.size // Dh
    out = kernel.dequantize_int4_pallas(
        p.reshape(rows, Dh), s.reshape(rows, 2 * Dh // block), dtype,
        interpret=(impl == "interpret"))
    return out.reshape(*shape[:-1], 2 * Dh)
