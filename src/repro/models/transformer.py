"""Decoder-only LM assembly for the dense and MoE families.

Layer parameters are **stacked** along a leading ``layers`` dim regardless of
execution mode, so checkpoints are mode-independent:

* ``cfg.scan_layers=True``  -> ``lax.scan`` over the stack (fast compiles;
  used by tests/examples/training).
* ``cfg.scan_layers=False`` -> static unroll (exact per-op HLO costs; used by
  the multi-pod dry-run, because XLA's cost analysis counts a scan body only
  once — DESIGN.md §6).

Caches follow the same convention: stacked ``(L, ...)`` arrays, scanned or
statically indexed.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as A
from .layers import (embed, embed_spec, mlp, mlp_specs, rmsnorm, rmsnorm_spec,
                     softmax_xent, unembed)
from .moe import moe_ffn, moe_specs
from .sharding import shard, spec

Tree = Any


# ================================================================= specs
def dense_block_specs(cfg, layers: Optional[int] = None, d_ff: Optional[int] = None):
    d = cfg.d_model
    ff = d_ff if d_ff is not None else cfg.d_ff
    out = {
        "ln1": rmsnorm_spec(d, layers),
        "attn": A.mla_specs(cfg, layers) if cfg.use_mla else A.attn_specs(cfg, layers),
        "mlp": mlp_specs(d, ff, layers),
    }
    if not cfg.parallel_block:
        out["ln2"] = rmsnorm_spec(d, layers)
    return out


def moe_block_specs(cfg, layers: Optional[int] = None):
    d = cfg.d_model
    return {
        "ln1": rmsnorm_spec(d, layers),
        "attn": A.mla_specs(cfg, layers) if cfg.use_mla else A.attn_specs(cfg, layers),
        "ln2": rmsnorm_spec(d, layers),
        "moe": moe_specs(cfg, layers),
    }


def trunk_specs(cfg) -> Dict:
    """The stacked blocks of each layer group (``_groups``)."""
    return {name: (moe_block_specs if is_moe else dense_block_specs)(cfg, n)
            for name, n, is_moe in _groups(cfg)}


def lm_specs(cfg) -> Dict:
    V, d = cfg.vocab_size, cfg.d_model
    specs: Dict = {"embed": embed_spec(V, d), "final_norm": rmsnorm_spec(d)}
    specs.update(trunk_specs(cfg))
    if not cfg.tie_embeddings:
        specs["head"] = embed_spec(V, d)
    return specs


# ================================================================ block fwd
def _self_attn(cfg, p, x, positions, *, return_kv=False):
    if cfg.use_mla:
        return A.mla_forward(cfg, p, x, positions, causal=cfg.causal,
                             return_kv=return_kv)
    return A.attn_forward(cfg, p, x, positions, causal=cfg.causal,
                          return_kv=return_kv)


def block_forward(cfg, p: Dict, x: jax.Array, positions: jax.Array,
                  *, is_moe: bool, return_kv: bool = False, layer=None):
    """Returns (x, kv_cache_or_None, aux) — aux as ``moe.moe_ffn`` gives
    it, 0 for a dense block.  ``layer``: see ``moe.dropless_moe_ffn``."""
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if cfg.parallel_block and not is_moe:
        # command-r: shared-norm parallel residual
        a = _self_attn(cfg, p["attn"], h, positions, return_kv=return_kv)
        a, kv = a if return_kv else (a, None)
        m = mlp(p["mlp"], h)
        return shard(x + a + m, "batch", "seq", None), kv, jnp.float32(0)
    a = _self_attn(cfg, p["attn"], h, positions, return_kv=return_kv)
    a, kv = a if return_kv else (a, None)
    x = x + a
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if is_moe:
        m, aux = moe_ffn(cfg, p["moe"], h, layer)
    else:
        m, aux = mlp(p["mlp"], h), jnp.float32(0)
    return shard(x + m, "batch", "seq", None), kv, aux


def block_decode(cfg, p: Dict, x: jax.Array, pos, cache: Dict, *, is_moe: bool):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if cfg.use_mla:
        a, cache = A.mla_decode(cfg, p["attn"], h, pos, cache)
    else:
        a, cache = A.attn_decode(cfg, p["attn"], h, pos, cache)
    if cfg.parallel_block and not is_moe:
        return x + a + mlp(p["mlp"], h), cache
    x = x + a
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    m = moe_ffn(cfg, p["moe"], h)[0] if is_moe else mlp(p["mlp"], h)
    return x + m, cache


# ================================================================ stack run
def _layer_slice(tree: Tree, i: int) -> Tree:
    return jax.tree_util.tree_map(lambda w: w[i], tree)


def run_stack(cfg, blocks_p: Tree, x: jax.Array, fwd_one, n_layers: int,
              *, remat: bool, collect=False):
    """fwd_one(layer_params, x) -> (x, ys, aux). Scan or unroll the stack."""
    if cfg.scan_layers:
        def body(h, pl):
            h, ys, aux = fwd_one(pl, h)
            return h, (ys, aux)
        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        x, (ys, auxs) = jax.lax.scan(body, x, blocks_p)
        return x, ys, jnp.sum(auxs)
    ys_list, aux = [], jnp.float32(0)
    fn = jax.checkpoint(fwd_one, prevent_cse=False) if remat else fwd_one
    for i in range(n_layers):
        x, ys, a = fn(_layer_slice(blocks_p, i), x)
        aux = aux + a
        if collect:
            ys_list.append(ys)
    if collect and ys_list and ys_list[0] is not None:
        ys = jax.tree_util.tree_map(lambda *l: jnp.stack(l), *ys_list)
    else:
        ys = None
    return x, ys, aux


def run_stack_decode(cfg, blocks_p: Tree, caches: Tree, x: jax.Array,
                     dec_one, n_layers: int):
    """dec_one(layer_params, x, cache) -> (x, cache)."""
    if cfg.scan_layers:
        def body(h, xs):
            pl, c = xs
            h, c = dec_one(pl, h, c)
            return h, c
        x, caches = jax.lax.scan(body, x, (blocks_p, caches))
        return x, caches
    new = []
    for i in range(n_layers):
        x, c = dec_one(_layer_slice(blocks_p, i), x, _layer_slice(caches, i))
        new.append(c)
    caches = jax.tree_util.tree_map(lambda *l: jnp.stack(l), *new)
    return x, caches


# ================================================================ LM api
def _groups(cfg):
    """[(name, n_layers, is_moe)] in execution order: a config with
    experts runs its leading dense layers, then its MoE layers."""
    if cfg.n_experts:
        g = []
        if cfg.first_dense_layers:
            g.append(("dense_blocks", cfg.first_dense_layers, False))
        g.append(("moe_blocks", cfg.n_layers - cfg.first_dense_layers, True))
        return g
    return [("blocks", cfg.n_layers, False)]


def lm_hidden(cfg, params: Dict, tokens: jax.Array, *, remat: Optional[bool] = None):
    """Token ids -> final hidden states (pre final-norm). Returns (h, aux)."""
    S = tokens.shape[1]
    positions = jnp.arange(S)
    x = embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    aux = jnp.float32(0)
    for name, n, is_moe in _groups(cfg):
        def one(pl, h, _moe=is_moe):
            h, _, a = block_forward(cfg, pl, h, positions, is_moe=_moe)
            return h, None, a
        x, _, a = run_stack(cfg, params[name], x, one, n,
                            remat=cfg.remat if remat is None else remat)
        aux = aux + a
    return x, aux


def lm_logits(cfg, params: Dict, h: jax.Array) -> jax.Array:
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    w = params["embed"] if cfg.tie_embeddings else params["head"]
    return unembed(w, h, cfg.vocab_size)


def lm_loss(cfg, params: Dict, tokens: jax.Array, labels: jax.Array,
            *, aux_coef: float = 0.01) -> jax.Array:
    h, aux = lm_hidden(cfg, params, tokens)
    logits = lm_logits(cfg, params, h)
    return softmax_xent(logits, labels) + aux_coef * aux


def lm_prefill(cfg, params: Dict, tokens: jax.Array):
    """Prefill: returns (last-position logits, stacked KV caches per group)."""
    S = tokens.shape[1]
    positions = jnp.arange(S)
    x = embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    caches: Dict = {}
    for name, n, is_moe in _groups(cfg):
        def one(pl, h, _moe=is_moe):
            h, kv, a = block_forward(cfg, pl, h, positions, is_moe=_moe,
                                     return_kv=True)
            return h, kv, a
        x, kv, _ = run_stack(cfg, params[name], x, one, n, remat=False,
                             collect=True)
        caches[name] = kv
    logits = lm_logits(cfg, params, x[:, -1:])
    return logits, caches


def lm_decode(cfg, params: Dict, caches: Dict, tokens: jax.Array, pos):
    """One decode step. tokens: (B,1); pos: scalar current position."""
    x = embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    new: Dict = {}
    for name, n, is_moe in _groups(cfg):
        def dec(pl, h, c, _moe=is_moe):
            return block_decode(cfg, pl, h, pos, c, is_moe=_moe)
        x, nc = run_stack_decode(cfg, params[name], caches[name], x, dec, n)
        new[name] = nc
    logits = lm_logits(cfg, params, x)
    return logits, new


def lm_cache_specs(cfg, batch: int, max_len: int) -> Dict:
    out = {}
    for name, n, _ in _groups(cfg):
        if cfg.use_mla:
            per = A.mla_cache_specs(cfg, batch, max_len)
        else:
            per = A.kv_cache_specs(cfg, batch, max_len)
        out[name] = jax.tree_util.tree_map(
            lambda s: spec((n,) + s.shape, ("layers",) + s.axes,
                           dtype=s.dtype, init="zeros"),
            per, is_leaf=lambda v: hasattr(v, "axes"))
    return out
