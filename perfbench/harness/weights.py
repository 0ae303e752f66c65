"""Seeded weights, drawn on the device in one jitted call.

The tree layout comes from the configuration's reference module
(``param_shapes``); every leaf is drawn in bfloat16, the type the model
is served in.  Stacked leaves (a leading layer axis) are filled one
layer at a time inside the program, so the float32 draw never exists
for more than one layer and the peak stays near the weights' own bytes.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

NORM_JITTER = 0.1


def _is_leaf(x) -> bool:
    return isinstance(x, dict) and "shape" in x


def _draw(key, leaf, dtype):
    shape = leaf["shape"]

    def one(k, shp):
        z = jax.random.normal(k, shp, jnp.float32)
        if leaf["init"] == "norm":
            return (1.0 + NORM_JITTER * z).astype(dtype)
        scale = leaf["scale"]
        if scale is None:
            scale = shp[-2] ** -0.5 if len(shp) >= 2 else 1.0
        return (z * scale).astype(dtype)

    if len(shape) < 3 and not (leaf["init"] == "norm" and len(shape) == 2):
        return one(key, shape)

    def fill(i, buf):
        return jax.lax.dynamic_update_index_in_dim(
            buf, one(jax.random.fold_in(key, i), shape[1:]), i, 0)

    return jax.lax.fori_loop(0, shape[0], fill, jnp.zeros(shape, dtype))


def make_params(shapes: Dict, key: jax.Array, dtype=jnp.bfloat16) -> Dict:
    """The whole parameter tree from ``key``, in one device program."""
    leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_leaf)

    @jax.jit
    def build(k):
        return jax.tree_util.tree_unflatten(
            treedef, [_draw(jax.random.fold_in(k, i), leaf, dtype)
                      for i, leaf in enumerate(leaves)])

    return build(key)


def tree_shapes(shapes: Dict) -> Dict:
    """``{path: shape}`` of a layout, for comparing it with another."""
    flat = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_leaf)[0]
    return {jax.tree_util.keystr(p): tuple(leaf["shape"]) for p, leaf in flat}


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key (``PRNGKey`` layout) that keeps all 64 bits of
    ``seed``; ``PRNGKey`` itself drops the high word."""
    seed = int(seed) % (1 << 64)
    return jnp.array([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32)
