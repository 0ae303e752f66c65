"""Mixture-of-Experts FFN with expert parallelism.

Design (DESIGN.md §5): experts shard on the ``model`` mesh axis.  Inside a
``shard_map`` region each model-shard owns ``E/model`` experts; tokens are
replicated across ``model`` (they arrive that way from the attention block),
so dispatch is **local gather -> batched expert matmul -> local scatter-add**,
and the only collective is one ``psum`` over ``model`` to combine expert
contributions — the same wire cost as a dense TP FFN's all-reduce.  This is
the TPU-native analogue of DeepSeek-style EP all-to-all dispatch: because
activations are model-replicated under our 2D (data, model) layout, the
all-to-all degenerates into the combine-psum, avoiding the classic GShard
one-hot dispatch einsums (which would cost more FLOPs than the experts
themselves at these expert counts).

Capacity-and-drop semantics follow GShard: per-expert capacity
``C = ceil(T * top_k / E * capacity_factor)``; overflow tokens are dropped
(contribute zero for that expert slot).  A load-balancing auxiliary loss is
returned alongside the output.

When no mesh is installed (CPU tests) the same local routine runs with all
experts on one shard, so numerics are identical modulo capacity.

A ``moe_dropless`` layer is told which experts it holds, ``[expert_start,
expert_start + experts_held)``, as one chip of an expert-parallel
deployment holds them.  It routes over all ``n_experts``, computes only
its held experts' part of the output (plus the shared experts, which
every chip computes alike) and drops nothing: the (token, choice) pairs
routed to held experts are laid out expert by expert and run through
grouped matmuls (``kernels/grouped_matmul``) at the rows actually routed.
On one chip it runs without the exchange that would sum the chips'
parts.  Its routing is by config: softmax, or DeepSeek-V3's sigmoid
scores with bias-corrected selection (``moe_scoring="sigmoid"``).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..compat import shard_map

from ..kernels.grouped_matmul import ops as gmm
from .layers import linear_spec, mlp
from .sharding import current_mesh, shard, spec

# the scopes of a dropless layer's routing and of its routed experts'
# dispatch, grouped matmuls and combine (read back from the compiled
# program's op_name metadata)
ROUTER, EXPERTS = "router", "experts"
# rows of a grouped-matmul tile: each held expert's rows start on one
ROWS_PER_TILE = 32
# a dropless layer's counts: (token, choice) pairs routed to held
# experts, the most on one held expert, pairs left out of every group, and
# held experts given at least one row (the only ones whose weights the
# grouped matmuls read)
COUNTS = ("routed", "max_load", "dropped", "reached")
# the routed experts' weights, which a layer loop may hand over as whole
# stacks for the grouped matmuls to read in place
EXPERT_WEIGHTS = ("wg", "wu", "wd")


def _pad_experts(n_experts: int, shards: int) -> int:
    return int(math.ceil(n_experts / shards) * shards)


def padded_expert_count(E: int, max_shards: int = 16) -> int:
    """Expert-table leading dim: jit in_shardings require even divisibility
    on the `model` axis (16), so 40 experts (granite) pad to 48.  Counts
    that already divide 16 — or that 16 divides — stay unchanged (keeps
    reduced test configs small)."""
    if E % max_shards == 0 or max_shards % E == 0:
        return E
    return _pad_experts(E, max_shards)


def moe_specs(cfg, layers: Optional[int] = None) -> Dict:
    d, fe = cfg.d_model, cfg.moe_d_ff
    E = cfg.n_experts_held if cfg.moe_dropless \
        else padded_expert_count(cfg.n_experts)
    L = () if layers is None else (layers,)
    lax = () if layers is None else ("layers",)
    out = {
        "router": spec(L + (d, cfg.n_experts), lax + ("d_model", None),
                       scale=0.02),
        "wg": spec(L + (E, d, fe), lax + ("experts", "d_model", "moe_ff")),
        "wu": spec(L + (E, d, fe), lax + ("experts", "d_model", "moe_ff")),
        "wd": spec(L + (E, fe, d), lax + ("experts", "moe_ff", "d_model")),
    }
    if cfg.moe_scoring == "sigmoid":
        out["e_score_correction_bias"] = spec(
            L + (cfg.n_experts,), lax + (None,), init="zeros")
    if cfg.n_shared_experts:
        fs = fe * cfg.n_shared_experts
        out["shared"] = {
            "wg": linear_spec(d, fs, ("d_model", "ff"), layers),
            "wu": linear_spec(d, fs, ("d_model", "ff"), layers),
            "wd": linear_spec(fs, d, ("ff", "d_model"), layers),
        }
    return out


def capacity(tokens: int, n_experts: int, top_k: int, factor: float) -> int:
    c = int(math.ceil(tokens * top_k / n_experts * factor))
    return max(4, ((c + 3) // 4) * 4)


def _route(x2d: jax.Array, router: jax.Array, top_k: int):
    """x2d: (T, d). Returns (gates (T,k) f32, eids (T,k) i32, aux_loss)."""
    logits = jnp.einsum("td,de->te", x2d, router,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, top_k)
    gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-9)
    # GShard aux loss: E * sum_e(frac_tokens_e * mean_prob_e)
    E = probs.shape[-1]
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(eids[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    return gates, eids, aux


def _local_expert_ffn(x2d, gates, eids, wg, wu, wd, *, E, e0, C):
    """Gather->FFN->scatter for the E_loc experts [e0, e0+E_loc) on this shard.

    x2d: (T, d); gates/eids: (T, k); wg/wu: (E_loc, d, f); wd: (E_loc, f, d).
    Returns partial output (T, d) covering only local experts.
    """
    T, d = x2d.shape
    k = eids.shape[1]
    E_loc = wg.shape[0]
    # position of each (token, choice) in its expert's queue
    onehot = jax.nn.one_hot(eids, E, dtype=jnp.int32).sum(1)      # (T, E)
    pos_all = jnp.cumsum(onehot, axis=0) - onehot                  # (T, E)
    pos = jnp.take_along_axis(pos_all, eids, axis=1)               # (T, k)
    local = (eids >= e0) & (eids < e0 + E_loc) & (pos < C)
    slot = jnp.where(local, (eids - e0) * C + pos, E_loc * C)      # sentinel
    tok = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None], (T, k))
    idx = jnp.full((E_loc * C + 1,), T, jnp.int32)
    idx = idx.at[slot.reshape(-1)].set(tok.reshape(-1), mode="drop")
    idx = idx[: E_loc * C]                                          # (E_loc*C,)
    xpad = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)], 0)
    buf = xpad[idx].reshape(E_loc, C, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) \
        * jnp.einsum("ecd,edf->ecf", buf, wu)
    out = jnp.einsum("ecf,efd->ecd", h, wd).reshape(E_loc * C, d)
    gbuf = jnp.zeros((E_loc * C + 1,), jnp.float32)
    gbuf = gbuf.at[slot.reshape(-1)].set(gates.reshape(-1).astype(jnp.float32),
                                         mode="drop")[: E_loc * C]
    contrib = out * gbuf[:, None].astype(out.dtype)
    y = jnp.zeros((T + 1, d), x2d.dtype).at[idx].add(contrib, mode="drop")
    return y[:T]


def _route_sigmoid(x2d, router, bias, top_k: int, scale: float):
    """DeepSeek-V3 routing: scores ``s = sigmoid(x W_r)`` in float32, the
    top ``k`` of ``s + bias`` selected (the bias is used for selection
    only), gates ``scale * s_i / sum_top s_j``.  Returns (gates (T,k) f32,
    eids (T,k) i32)."""
    s = jax.nn.sigmoid(jnp.einsum("td,de->te", x2d, router,
                                  preferred_element_type=jnp.float32))
    _, eids = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    sel = jnp.take_along_axis(s, eids, axis=-1)
    return scale * sel / (jnp.sum(sel, -1, keepdims=True) + 1e-20), eids


def _dispatch(eids: jax.Array, e0: int, E: int, tm: int, rows: int):
    """Lay the (token, choice) pairs routed to experts ``[e0, e0 + E)``
    out expert by expert, each expert's rows starting on a tile of ``tm``.

    Returns ``(held (T*k,) bool, pos (T*k,) the row of each held pair,
    src (rows,) the token each row reads, sizes (E,) rows per expert
    rounded up to whole tiles, counts int32[4]: see ``COUNTS``)``.  Rows
    that no pair fills read token 0 and are never read back."""
    T, k = eids.shape
    local = eids.reshape(-1) - e0
    held = (local >= 0) & (local < E)
    key = jnp.where(held, local, E)
    onehot = jax.nn.one_hot(key, E + 1, dtype=jnp.int32)[:, :E]  # (T*k, E)
    load = jnp.sum(onehot, axis=0)                                # (E,)
    sizes = (load + tm - 1) // tm * tm
    start = jnp.cumsum(sizes) - sizes
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    pos = jnp.where(held, start[jnp.minimum(key, E - 1)] + rank, rows)
    src = jnp.zeros((rows,), jnp.int32).at[pos].set(
        jnp.arange(T * k, dtype=jnp.int32) // k, mode="drop")
    routed = jnp.sum(load)
    inside = jnp.sum(held & (pos < jnp.sum(sizes)))
    counts = jnp.stack([routed, jnp.max(load), routed - inside,
                        jnp.sum(load > 0)])
    return held, pos, src, sizes, counts.astype(jnp.int32)


def dropless_moe_ffn(cfg, p: Dict, x: jax.Array,
                     layer: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of a MoE layer plus its shared experts, no
    token dropped.  x: (B, S, d) -> (out (B, S, d), counts int32[4]: see
    ``COUNTS``).  With ``layer``, the ``EXPERT_WEIGHTS`` of ``p`` are the
    whole stacks (L, E, ., .), read at that layer in place."""
    B, S, d = x.shape
    k, e0, E = cfg.moe_top_k, cfg.expert_start, p["wg"].shape[-3]
    x2d = x.reshape(B * S, d)
    with jax.named_scope(ROUTER):
        if cfg.moe_scoring == "sigmoid":
            gates, eids = _route_sigmoid(x2d, p["router"],
                                         p["e_score_correction_bias"], k,
                                         cfg.moe_routed_scale)
        else:
            gates, eids, _ = _route(x2d, p["router"], k)
    tm = ROWS_PER_TILE
    # every pair could route to a held expert, and each expert's rows
    # round up to whole tiles
    rows = -(-(B * S * k + E * (tm - 1)) // tm) * tm
    with jax.named_scope(EXPERTS):
        held, pos, src, sizes, counts = _dispatch(eids, e0, E, tm, rows)
        xs = x2d[src]
        kw = {"tm": tm, "impl": cfg.moe_impl}
        h = jax.nn.silu(gmm.grouped_matmul(xs, p["wg"], sizes, layer, **kw)
                        ) * gmm.grouped_matmul(xs, p["wu"], sizes, layer, **kw)
        ys = gmm.grouped_matmul(h, p["wd"], sizes, layer, **kw)
        yp = jnp.where(held[:, None], ys[jnp.minimum(pos, rows - 1)], 0)
        w = jnp.where(held, gates.reshape(-1), 0.0).reshape(B * S, k)
        y = jnp.einsum("tkd,tk->td", yp.reshape(B * S, k, d), w,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    y = y.reshape(B, S, d)
    if cfg.n_shared_experts:
        y = y + mlp(p["shared"], x)
    return y, counts


def no_counts() -> jax.Array:
    return jnp.zeros((len(COUNTS),), jnp.int32)


def add_counts(a: jax.Array, b: jax.Array) -> jax.Array:
    """Two spans' counts as one: pairs, drops and experts reached add,
    loads take the larger."""
    return jnp.stack([a[0] + b[0], jnp.maximum(a[1], b[1]), a[2] + b[2],
                      a[3] + b[3]])


def moe_ffn(cfg, p: Dict, x: jax.Array, layer: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B,S,d), aux): the load-balancing loss, or a
    dropless layer's counts (``dropless_moe_ffn``, which takes
    ``layer``)."""
    if cfg.moe_dropless:
        return dropless_moe_ffn(cfg, p, x, layer)
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    mesh = current_mesh()
    shards, data_shards = 1, 1
    batch_axes = ()
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        shards = sizes.get("model", 1)
        from .sharding import resolve
        batch_rule = resolve(("batch",))[0]
        if batch_rule is not None:
            batch_axes = (batch_rule,) if isinstance(batch_rule, str) else tuple(batch_rule)
            for a in batch_axes:
                data_shards *= sizes.get(a, 1)
    wg, wu, wd = p["wg"], p["wu"], p["wd"]
    E_tbl = wg.shape[0]           # spec-level padded expert count
    if E_tbl % shards:            # runtime fallback for odd test meshes
        padn = _pad_experts(E_tbl, shards) - E_tbl
        wg = jnp.concatenate([wg, jnp.zeros((padn,) + wg.shape[1:], wg.dtype)], 0)
        wu = jnp.concatenate([wu, jnp.zeros((padn,) + wu.shape[1:], wu.dtype)], 0)
        wd = jnp.concatenate([wd, jnp.zeros((padn,) + wd.shape[1:], wd.dtype)], 0)
    E_pad = wg.shape[0]
    E_loc = E_pad // shards
    x2d = x.reshape(B * S, d)
    gates, eids, aux = _route(x2d, p["router"], k)
    # capacity is per *local* token block: tokens stay data-sharded in the
    # shard_map region, replicated only across `model`.
    C = capacity(B * S // data_shards, E, k, cfg.moe_capacity_factor)

    if mesh is None or shards == 1:
        y = _local_expert_ffn(x2d, gates, eids, wg, wu, wd, E=E, e0=0, C=C)
    else:
        def shard_fn(x2d_, gates_, eids_, wg_, wu_, wd_):
            midx = jax.lax.axis_index("model")
            y_ = _local_expert_ffn(x2d_, gates_, eids_, wg_, wu_, wd_,
                                   E=E, e0=midx * E_loc, C=C)
            return jax.lax.psum(y_, "model")

        # tokens: sharded over the batch axes, replicated over `model`
        tok_spec = P(batch_axes if batch_axes else None, None)
        y = shard_map(
            shard_fn, mesh=mesh,
            in_specs=(tok_spec, tok_spec, tok_spec,
                      P("model"), P("model"), P("model")),
            out_specs=tok_spec,
        )(x2d, gates, eids, wg, wu, wd)

    y = y.reshape(B, S, d)
    if cfg.n_shared_experts:
        y = y + mlp(p["shared"], x)
    return shard(y, "batch", "seq", None), aux
