"""Logical-axis sharding: ParamSpec trees, rule resolution, activation constraints.

Every parameter is declared once as a :class:`ParamSpec` carrying *logical*
axis names.  At launch time the rules map logical axes -> mesh axes
(``make_rules``), which gives us — without allocating anything —

* ``jax.ShapeDtypeStruct`` trees for ``.lower()`` (dry-run),
* ``NamedSharding`` trees for ``in_shardings``,
* random-init trees for tests/examples.

Activation shardings inside model code go through :func:`shard`, which is a
no-op unless a mesh context has been installed via :func:`use_mesh` — so the
same model code runs on 1 CPU device and on the 512-device production mesh.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Tree = Any


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]          # logical axis names
    dtype: Any = jnp.bfloat16
    init: str = "normal"                     # normal | zeros | ones
    scale: Optional[float] = None            # None -> 1/sqrt(fan_in)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def spec(shape, axes, dtype=jnp.bfloat16, init="normal", scale=None) -> ParamSpec:
    return ParamSpec(tuple(shape), tuple(axes), dtype, init, scale)


# --------------------------------------------------------------------------- rules
def make_rules(cfg, mesh: Optional[Mesh], shape_kind: str = "train",
               strategy: str = "tp") -> Dict[str, Any]:
    """Resolve logical-axis -> mesh-axis rules for a (config, mesh, shape) cell.

    Strategies:
      * ``tp`` (baseline, paper-faithful to a Megatron-style deployment):
        weights shard their big output dim over ``model``; activations are
        model-replicated between blocks (2 all-reduces per layer).
      * ``fsdp`` (§Perf hillclimb for small-model training): weights shard
        over ``(data, model)`` jointly (ZeRO-3); activations shard over
        batch only — GSPMD turns the per-layer collectives into parameter
        all-gathers + gradient reduce-scatters, removing the O(activations)
        all-reduce wire.
      * ``batch`` shards on ``(pod, data)`` except for ``long_decode``
        (global_batch=1) where it stays replicated.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh is not None else {}
    batch_axes = tuple(a for a in ("pod", "data") if a in axis_sizes)
    batch_rule = None if shape_kind == "long_decode" else (batch_axes or None)

    if strategy == "fsdp":
        w = ("data", "model") if "data" in axis_sizes else ("model",)
        # true FSDP: data-parallel over EVERY chip; params sharded over all
        fsdp_batch = tuple(a for a in ("pod", "data", "model")
                           if a in axis_sizes) or None
        batch_rule = None if shape_kind == "long_decode" else fsdp_batch
        return {
            "d_model": None, "vocab": w, "q_heads": w, "kv_heads": w,
            "head_dim": None, "ff": w, "experts": w, "moe_ff": None,
            "inner": w, "state": None, "lora": None, "layers": None,
            "dit": None, "vit_ff": w, "vit_heads": w,
            "batch": batch_rule, "seq": None,
            "act_heads": None, "act_kv_heads": None, "act_ff": None,
            "act_inner": None, "act_vocab": None, "act_experts": None,
            "cache_kv_heads": None, "cache_seq": None, "cache_seq_sp": None,
            None: None,
        }

    rules: Dict[str, Any] = {
        # weights
        "d_model": None,
        "vocab": "model",
        "q_heads": "model",          # flattened H*hd dim — always divisible
        "kv_heads": "model",         # flattened KV*hd dim — always divisible
        "head_dim": None,
        "ff": "model",
        "experts": "model",
        "moe_ff": None,
        "inner": "model",            # mamba2 d_inner / ssm heads
        "state": None,
        "lora": None,
        "layers": None,              # stacked-layer leading dim
        "dit": None,
        "vit_ff": "model",
        "vit_heads": "model",
        # activations (KV head tensors left to propagation: small KV-head
        # counts shard unevenly; XLA pads/partially-replicates better than a
        # forced constraint — see EXPERIMENTS.md §Perf iteration log)
        "batch": batch_rule,
        "seq": None,
        "act_heads": "model",
        "act_kv_heads": None,
        "act_ff": "model",
        "act_inner": "model",
        "act_vocab": "model",
        "act_experts": "model",
        # decode caches: shard KV-head dim (uneven counts get padded)
        "cache_kv_heads": "model",
        "cache_seq": None,
        # sequence-parallel flash-decode cache (cfg.decode_attn == "sp")
        "cache_seq_sp": "model",
        None: None,
    }
    return rules


# ---------------------------------------------------------------- mesh context
class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: Optional[Dict[str, Any]] = None


_CTX = _Ctx()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[Dict[str, Any]]):
    """Install (mesh, rules) so that in-model ``shard()`` constraints apply."""
    old = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = old


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def axis_size(name: str) -> int:
    m = _CTX.mesh
    if m is None or name not in m.axis_names:
        return 1
    return dict(zip(m.axis_names, m.devices.shape))[name]


def resolve(axes: Tuple[Optional[str], ...], rules=None) -> P:
    rules = rules if rules is not None else (_CTX.rules or {})
    out = []
    for a in axes:
        r = rules.get(a)
        if isinstance(r, tuple) and len(r) == 0:
            r = None
        out.append(r)
    return P(*out)


def rule_flag(name: str) -> Any:
    """Read an out-of-band flag stashed in the active rules dict."""
    return (_CTX.rules or {}).get(name)


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Apply a with_sharding_constraint if a mesh context is installed."""
    if _CTX.mesh is None or _CTX.rules is None:
        return x
    s = NamedSharding(_CTX.mesh, resolve(axes))
    return jax.lax.with_sharding_constraint(x, s)


# --------------------------------------------------------------- tree utilities
def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def tree_map_specs(fn, tree: Tree) -> Tree:
    return jax.tree_util.tree_map(fn, tree, is_leaf=is_spec)


def shape_tree(specs: Tree) -> Tree:
    """ParamSpec tree -> ShapeDtypeStruct tree (no allocation; for .lower())."""
    return tree_map_specs(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs)


def sharding_tree(specs: Tree, mesh: Mesh, rules: Dict[str, Any]) -> Tree:
    return tree_map_specs(
        lambda s: NamedSharding(mesh, resolve(s.axes, rules)), specs)


def spec_bytes(specs: Tree) -> int:
    leaves = jax.tree_util.tree_leaves(specs, is_leaf=is_spec)
    total = 0
    for s in leaves:
        n = 1
        for d in s.shape:
            n *= d
        total += n * jnp.dtype(s.dtype).itemsize
    return total


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "scale",
                                             "stacked"))
def _draw_leaf(key: jax.Array, shape, dtype, scale: float, stacked: bool):
    """``normal * scale`` drawn straight into ``dtype`` on the default
    device.  A ``stacked`` leaf (leading ``layers`` axis) is filled one
    layer slice at a time inside a loop, so the float32 draw never exists
    for more than one layer: the peak is the leaf plus one layer."""
    def draw(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) * scale).astype(dtype)

    if not stacked:
        return draw(key, shape)

    def fill(i, buf):
        return jax.lax.dynamic_update_index_in_dim(
            buf, draw(jax.random.fold_in(key, i), shape[1:]), i, 0)

    return jax.lax.fori_loop(0, shape[0], fill, jnp.zeros(shape, dtype))


def init_params(specs: Tree, key: jax.Array) -> Tree:
    """Materialise a random parameter tree from a ParamSpec tree.

    Each leaf is drawn by its own jitted program in its target dtype, so
    initialising a model whose bf16 parameters nearly fill the device
    needs no float32 copy of any whole leaf."""
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, max(len(leaves), 1))

    def one(s: ParamSpec, k):
        if s.init == "zeros":
            return jnp.zeros(s.shape, s.dtype)
        if s.init == "ones":
            return jnp.ones(s.shape, s.dtype)
        fan_in = s.shape[-2] if len(s.shape) >= 2 else max(s.shape[-1], 1)
        scale = s.scale if s.scale is not None else fan_in ** -0.5
        return _draw_leaf(k, s.shape, jnp.dtype(s.dtype), float(scale),
                          stacked=s.axes[:1] == ("layers",))

    return jax.tree_util.tree_unflatten(treedef, [one(s, k) for s, k in zip(leaves, keys)])
