"""Attention: GQA / MHA / MLA / cross-attention, with decode caches.

Sharding strategy (DESIGN.md §5):
  * Q/O projections shard the flattened head dim on ``model`` (H*hd and
    KV*hd are always divisible by 16 even when the head *count* is not).
  * Decode caches are stored FLAT as ``(B, S, KV*hd)`` sharded on the last
    dim — the exact sharding of the KV projection output, so cache writes
    need no resharding and jit in_shardings stay evenly divisible for every
    arch (KV head counts of 2/8 would otherwise shard unevenly).  The
    per-head view needed by the attention einsum is an intermediate
    reshape, which GSPMD re-tiles freely.
  * MLA stores the compressed ``(c_kv, k_pe)`` cache (paper-faithful to
    DeepSeek-V2) and decodes in the absorbed form: attention runs in the
    512-dim latent space, never materialising per-head K/V at decode time.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compat import shard_map
from .layers import apply_rope, dense, linear_spec
from .sharding import ParamSpec, current_mesh, shard, spec


# ============================================================== specs
def attn_specs(cfg, layers: Optional[int] = None, cross: bool = False) -> Dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    out = {
        "wq": linear_spec(d, H * hd, ("d_model", "q_heads"), layers),
        "wk": linear_spec(d, KV * hd, ("d_model", "kv_heads"), layers),
        "wv": linear_spec(d, KV * hd, ("d_model", "kv_heads"), layers),
        "wo": linear_spec(H * hd, d, ("q_heads", "d_model"), layers),
    }
    if cfg.qkv_bias and not cross:
        out["bq"] = _bias(H * hd, "q_heads", layers)
        out["bk"] = _bias(KV * hd, "kv_heads", layers)
        out["bv"] = _bias(KV * hd, "kv_heads", layers)
    return out


def _bias(n, axis, layers):
    if layers is None:
        return spec((n,), (axis,), init="zeros")
    return spec((layers, n), ("layers", axis), init="zeros")


def mla_specs(cfg, layers: Optional[int] = None) -> Dict:
    d, H = cfg.d_model, cfg.n_heads
    r, qk_n, qk_r, vd = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "wq": linear_spec(d, H * (qk_n + qk_r), ("d_model", "q_heads"), layers),
        "wkv_a": linear_spec(d, r + qk_r, ("d_model", "lora"), layers),
        "kv_norm": spec((r,) if layers is None else (layers, r),
                        ("lora",) if layers is None else ("layers", "lora"), init="ones"),
        "wk_b": linear_spec(r, H * qk_n, ("lora", "q_heads"), layers),
        "wv_b": linear_spec(r, H * vd, ("lora", "q_heads"), layers),
        "wo": linear_spec(H * vd, d, ("q_heads", "d_model"), layers),
    }


# ============================================================== core attention
# Above this many score elements (S*T) the XLA path switches to the blocked
# online-softmax formulation, which never materialises the full (S, T)
# score matrix — the jnp analogue of the Pallas flash kernel (and the form
# the dry-run compiles, since Pallas does not lower on the CPU backend).
_BLOCK_THRESHOLD = 2048 * 2048
_BQ, _BK = 2048, 8192
_NEG = -1e30


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *,
          causal: bool, q_pos: Optional[jax.Array] = None,
          kv_len: Optional[jax.Array] = None, impl: str = "xla") -> jax.Array:
    """q: (B,S,H,D); k,v: (B,H,T,D) (already GQA-expanded). fp32 softmax."""
    B, S, H, D = q.shape
    T = k.shape[2]
    if impl == "pallas" and causal and S > 1:
        from ..kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                                      causal=True)
    if S * T > _BLOCK_THRESHOLD and S > 1:
        return _blocked_sdpa(q, k, v, causal=causal, kv_len=kv_len)
    scale = D ** -0.5
    logits = jnp.einsum("bshd,bhtd->bhst", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = None
    if causal and S > 1:
        qp = q_pos if q_pos is not None else jnp.arange(S)
        mask = qp[:, None] >= jnp.arange(T)[None, :]
    if kv_len is not None:
        lm = jnp.arange(T)[None, :] < kv_len
        mask = lm if mask is None else (mask & lm)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bhtd->bshd", w, v)
    return out


def _blocked_sdpa(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool, kv_len=None,
                  bq: int = _BQ, bk: int = _BK) -> jax.Array:
    """Unrolled flash-style attention: per (q-chunk, kv-block) online softmax.

    Unrolled (python loops, not lax.scan) so the dry-run's HLO cost analysis
    counts every block exactly once (DESIGN.md §6); causally-dead blocks are
    skipped at trace time.  Peak memory per step is O(bq*bk) scores instead
    of O(S*T).
    """
    B, S, H, D = q.shape
    T = k.shape[2]
    Dv = v.shape[-1]          # MLA: value dim != query/key dim
    scale = D ** -0.5
    bq = min(bq, S)
    bk = min(bk, T)
    outs = []
    for qi in range(0, S, bq):
        nq = min(bq, S - qi)
        qc = q[:, qi:qi + nq]                            # (B,nq,H,D)
        m = jnp.full((B, H, nq, 1), _NEG, jnp.float32)
        l = jnp.zeros((B, H, nq, 1), jnp.float32)
        acc = jnp.zeros((B, nq, H, Dv), jnp.float32)
        for ki in range(0, T, bk):
            if causal and ki > qi + nq - 1:
                continue                                  # dead block
            nk = min(bk, T - ki)
            kc = k[:, :, ki:ki + nk]
            vc = v[:, :, ki:ki + nk]
            s = jnp.einsum("bshd,bhtd->bhst", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                qpos = qi + jnp.arange(nq)
                kpos = ki + jnp.arange(nk)
                s = jnp.where(qpos[:, None] >= kpos[None, :], s, _NEG)
            if kv_len is not None:
                s = jnp.where((ki + jnp.arange(nk))[None, :] < kv_len, s,
                              _NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(m_new <= _NEG / 2, 0.0, jnp.exp(s - m_new))
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha.transpose(0, 2, 1, 3) + jnp.einsum(
                "bhst,bhtd->bshd", p.astype(v.dtype), vc,
                preferred_element_type=jnp.float32)
            m = m_new
        l = jnp.where(l == 0.0, 1.0, l)
        outs.append((acc / l.transpose(0, 2, 1, 3)).astype(q.dtype))
    return jnp.concatenate(outs, axis=1)


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B,KV,T,D) -> (B,H,T,D); XLA fuses the broadcast into the einsum."""
    B, KV, T, D = k.shape
    if KV == n_heads:
        return k
    g = n_heads // KV
    return jnp.repeat(k, g, axis=1)


def _out_proj(out2d: jax.Array, wo: jax.Array) -> jax.Array:
    """Attention output projection; int8-ring TP combine when enabled."""
    from .layers import _use_int8_ring, int8_ring_proj
    if _use_int8_ring():
        return int8_ring_proj(out2d, wo)
    return dense(out2d, wo)


# ============================================================== GQA forward
def _qkv(cfg, p, x):
    """Project ``x`` to q ``(B,S,H,hd)`` and k, v ``(B,S,KV,hd)``.

    The projections leave their dots flat, ``(B,S,H*hd)``, as the weights
    lay them out; the head split, rope and the head-major transposes
    downstream run on these activations.  The barrier pins that order:
    without it the compiler gives the dots a head-major output layout and
    meets it by relayouting the weight, which inside a layer loop is a
    copy of the layer's weight out of the stack, and a transpose of it,
    every step (DESIGN §7)."""
    hd, H, KV = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    q = dense(x, p["wq"])
    k = dense(x, p["wk"])
    v = dense(x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q, k, v = jax.lax.optimization_barrier((q, k, v))
    B, S = x.shape[:2]
    q = shard(q.reshape(B, S, H, hd), "batch", "seq", "act_heads", None)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    return q, k, v


def attn_forward(cfg, p, x, positions, *, causal=True, rope=True,
                 return_kv=False, impl=None):
    """Full-sequence self attention (train / prefill)."""
    B, S, _ = x.shape
    q, k, v = _qkv(cfg, p, x)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    kt = k.transpose(0, 2, 1, 3)   # (B,KV,T,D)
    vt = v.transpose(0, 2, 1, 3)
    out = _sdpa(q, _expand_kv(kt, cfg.n_heads), _expand_kv(vt, cfg.n_heads),
                causal=causal, q_pos=positions[0] if positions.ndim == 2 else positions,
                impl=impl or cfg.attn_impl)
    out = shard(out, "batch", "seq", "act_heads", None)
    y = _out_proj(out.reshape(B, S, -1), p["wo"])
    if return_kv:
        cax = "cache_seq_sp" if cfg.decode_attn == "sp" else None
        kax = None if cax else "kv_heads"
        kc = shard(k.reshape(B, S, -1), "batch", cax, kax)
        vc = shard(v.reshape(B, S, -1), "batch", cax, kax)
        return y, {"k": kc, "v": vc}
    return y


def attn_decode(cfg, p, x, pos, cache: Dict) -> Tuple[jax.Array, Dict]:
    """One-token decode. cache: {"k","v"}: (B, S_max, KV*hd); pos: scalar."""
    B, S, _ = x.shape
    assert S == 1
    hd, KV = cfg.resolved_head_dim, cfg.n_kv_heads
    q, k, v = _qkv(cfg, p, x)
    positions = jnp.full((1,), pos, jnp.int32)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.decode_attn == "sp" and current_mesh() is not None \
            and "model" in current_mesh().axis_names:
        # cache write happens inside the shard_map (a dynamic_update_slice
        # into the seq-sharded dim at the pjit level trips an XLA SPMD
        # internal check — §Perf A iteration log)
        out, kc, vc = _sp_flash_decode(cfg, q, cache["k"], cache["v"],
                                       k.reshape(B, 1, KV * hd),
                                       v.reshape(B, 1, KV * hd), pos)
        out = shard(out, "batch", "seq", "act_heads", None)
        y = _out_proj(out.reshape(B, 1, -1), p["wo"])
        return y, {"k": kc, "v": vc}
    kc = jax.lax.dynamic_update_slice(cache["k"], k.reshape(B, 1, KV * hd),
                                      (0, pos, 0))
    vc = jax.lax.dynamic_update_slice(cache["v"], v.reshape(B, 1, KV * hd),
                                      (0, pos, 0))
    kc = shard(kc, "batch", None, "kv_heads")
    vc = shard(vc, "batch", None, "kv_heads")
    if cfg.attn_impl == "pallas":
        T = kc.shape[1]
        k4 = kc.reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
        v4 = vc.reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
        from ..kernels.decode_attention import ops as da_ops
        out = da_ops.decode_attention(q[:, 0], k4, v4, kv_len=pos + 1)[:, None]
    else:
        T = kc.shape[1]
        k4 = kc.reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
        v4 = vc.reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
        out = _sdpa(q, _expand_kv(k4, cfg.n_heads), _expand_kv(v4, cfg.n_heads),
                    causal=False, kv_len=pos + 1)
    out = shard(out, "batch", "seq", "act_heads", None)
    y = _out_proj(out.reshape(B, 1, -1), p["wo"])
    return y, {"k": kc, "v": vc}


def _sp_flash_decode(cfg, q, kc, vc, k_new, v_new, pos):
    """Sequence-parallel flash-decode (cfg.decode_attn == "sp").

    Cache is sharded along the SEQUENCE dim over ``model``; each shard
    writes the new token into its own slice (if `pos` falls there) and
    computes complete attention scores for its slice (all heads local);
    shards combine with an online-softmax reduction: one pmax + two psums
    of (B, H)-sized stats/outputs per layer — replacing the baseline's
    per-layer all-gather of the whole KV cache (§Perf hillclimb A).
    shard_map is partial: only ``model`` is manual, batch stays auto.
    Global position ids enter pre-sharded (axis_index lowers to
    PartitionId, which GSPMD rejects in partial-manual regions).
    """
    from jax.sharding import PartitionSpec as P
    mesh = current_mesh()
    B, _, H, hd = q.shape
    KV = cfg.n_kv_heads
    T = kc.shape[1]
    tglob_full = jnp.arange(T, dtype=jnp.int32)

    def local(q_, k_, v_, kn, vn, tglob):
        Bl, Tl = k_.shape[0], k_.shape[1]   # LOCAL shapes (full-manual)
        t0 = tglob[0]
        # local cache write: only the owning shard lands the update
        idx = jnp.clip(pos - t0, 0, Tl - 1)
        k_upd = jax.lax.dynamic_update_slice(k_, kn, (0, idx, 0))
        v_upd = jax.lax.dynamic_update_slice(v_, vn, (0, idx, 0))
        mine = (pos >= t0) & (pos < t0 + Tl)
        k_ = jnp.where(mine, k_upd, k_)
        v_ = jnp.where(mine, v_upd, v_)
        k4 = k_.reshape(Bl, Tl, KV, hd).transpose(0, 2, 1, 3)
        v4 = v_.reshape(Bl, Tl, KV, hd).transpose(0, 2, 1, 3)
        k4 = _expand_kv(k4, H)
        v4 = _expand_kv(v4, H)
        s = jnp.einsum("bshd,bhtd->bhst", q_, k4,
                       preferred_element_type=jnp.float32) * (hd ** -0.5)
        s = jnp.where(tglob[None, None, None, :] < pos + 1, s, -1e30)
        m_loc = jnp.max(s, axis=-1, keepdims=True)            # (B,H,1,1)
        m = jax.lax.pmax(m_loc, "model")
        p_ = jnp.where(m <= -1e29, 0.0, jnp.exp(s - m))
        l = jax.lax.psum(jnp.sum(p_, -1, keepdims=True), "model")
        o = jnp.einsum("bhst,bhtd->bshd", p_.astype(v4.dtype), v4)
        o = jax.lax.psum(o, "model")
        l = jnp.where(l == 0.0, 1.0, l)
        out = (o / l.transpose(0, 2, 1, 3).astype(o.dtype)).astype(q_.dtype)
        return out, k_, v_

    # FULL-manual shard_map (all mesh axes): the partial-manual form trips
    # XLA SPMD internal checks at large host-device counts (§Perf A log).
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if batch_axes else None
    if q.shape[0] % max(
            int(np.prod([dict(zip(mesh.axis_names, mesh.devices.shape))[a]
                         for a in batch_axes])) if batch_axes else 1, 1):
        bspec = None  # batch=1 long-decode: keep batch replicated
    cspec = P(bspec, "model", None)
    return shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec), cspec, cspec, P(bspec), P(bspec), P("model")),
        out_specs=(P(bspec), cspec, cspec),
    )(q, kc, vc, k_new, v_new, tglob_full)


def kv_cache_specs(cfg, batch: int, max_len: int) -> Dict:
    import jax.numpy as _jnp
    hd, KV = cfg.resolved_head_dim, cfg.n_kv_heads
    if cfg.decode_attn == "sp":
        ax = ("batch", "cache_seq_sp", None)
    else:
        ax = ("batch", None, "kv_heads")
    dt = _jnp.dtype(cfg.dtype)
    return {
        "k": spec((batch, max_len, KV * hd), ax, dtype=dt, init="zeros"),
        "v": spec((batch, max_len, KV * hd), ax, dtype=dt, init="zeros"),
    }


# ============================================================== cross attention
def cross_attn_forward(cfg, p, x, kv_x=None, kv_cache: Optional[Dict] = None):
    """Cross attention; pass kv_x once (prefill) or a precomputed kv_cache
    stored flat as (B, T, KV*hd)."""
    B, S, _ = x.shape
    hd, H, KV = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    q = dense(x, p["wq"]).reshape(B, S, H, hd)
    q = shard(q, "batch", "seq", "act_heads", None)
    if kv_cache is None:
        kv_cache = {
            "k": shard(dense(kv_x, p["wk"]), "batch", None, "kv_heads"),
            "v": shard(dense(kv_x, p["wv"]), "batch", None, "kv_heads"),
        }
    T = kv_cache["k"].shape[1]
    k4 = kv_cache["k"].reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
    v4 = kv_cache["v"].reshape(B, T, KV, hd).transpose(0, 2, 1, 3)
    out = _sdpa(q, _expand_kv(k4, H), _expand_kv(v4, H), causal=False)
    out = shard(out, "batch", "seq", "act_heads", None)
    return dense(out.reshape(B, S, -1), p["wo"]), kv_cache


# ============================================================== MLA (deepseek)
def _mla_q(cfg, p, x, positions):
    B, S, _ = x.shape
    H = cfg.n_heads
    qn, qr = cfg.qk_nope_dim, cfg.qk_rope_dim
    # flat projection output, as in ``_qkv``: the head split would
    # otherwise relayout (copy) the weight in a layer loop
    q = jax.lax.optimization_barrier(dense(x, p["wq"]))
    q = q.reshape(B, S, H, qn + qr)
    q_nope, q_pe = q[..., :qn], q[..., qn:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
    return q_nope, q_pe


def _mla_latent(cfg, p, x, positions):
    from .layers import rmsnorm
    r, qr = cfg.kv_lora_rank, cfg.qk_rope_dim
    kv_a = dense(x, p["wkv_a"])                    # (B,S,r+qr)
    c_kv = rmsnorm(kv_a[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope(kv_a[..., r:], positions, cfg.rope_theta)  # (B,S,qr)
    return c_kv, k_pe


def mla_forward(cfg, p, x, positions, *, causal=True, return_kv=False):
    """Training/prefill MLA: decompress K/V per head (naive form)."""
    B, S, _ = x.shape
    H = cfg.n_heads
    qn, vd, r = cfg.qk_nope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    q_nope, q_pe = _mla_q(cfg, p, x, positions)
    c_kv, k_pe = _mla_latent(cfg, p, x, positions)
    k_nope, v = jax.lax.optimization_barrier((dense(c_kv, p["wk_b"]),
                                              dense(c_kv, p["wv_b"])))
    k_nope = k_nope.reshape(B, S, H, qn)
    v = v.reshape(B, S, H, vd)
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                                  (B, S, H, cfg.qk_rope_dim))], -1)
    q = shard(q, "batch", "seq", "act_heads", None)
    k = shard(k, "batch", "seq", "act_heads", None)
    out = _sdpa(q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
                causal=causal,
                q_pos=positions[0] if positions.ndim == 2 else positions,
                impl=cfg.attn_impl)
    out = shard(out, "batch", "seq", "act_heads", None)
    y = dense(out.reshape(B, S, -1), p["wo"])
    if return_kv:
        return y, {"c_kv": shard(c_kv, "batch", None, None),
                   "k_pe": shard(k_pe, "batch", None, None)}
    return y


def mla_decode(cfg, p, x, pos, cache: Dict) -> Tuple[jax.Array, Dict]:
    """Absorbed-form MLA decode: attention in the compressed latent space."""
    B, S, _ = x.shape
    assert S == 1
    H = cfg.n_heads
    qn, qr, vd, r = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    positions = jnp.full((1,), pos, jnp.int32)
    q_nope, q_pe = _mla_q(cfg, p, x, positions)          # (B,1,H,qn),(B,1,H,qr)
    c_new, kpe_new = _mla_latent(cfg, p, x, positions)   # (B,1,r),(B,1,qr)
    ckv = jax.lax.dynamic_update_slice(cache["c_kv"], c_new, (0, pos, 0))
    kpe = jax.lax.dynamic_update_slice(cache["k_pe"], kpe_new, (0, pos, 0))
    # absorb W_kb into q: q_lat (B,1,H,r)
    wk_b = p["wk_b"].reshape(r, H, qn)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, wk_b)
    logits = (jnp.einsum("bshr,btr->bhst", q_lat, ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bshr,btr->bhst", q_pe, kpe,
                           preferred_element_type=jnp.float32))
    logits = logits * ((qn + qr) ** -0.5)
    kv_len = pos + 1
    mask = jnp.arange(ckv.shape[1])[None, :] < kv_len
    logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhst,btr->bshr", w, ckv)            # (B,1,H,r)
    wv_b = p["wv_b"].reshape(r, H, vd)
    out = jnp.einsum("bshr,rhv->bshv", ctx, wv_b)
    y = dense(out.reshape(B, 1, -1), p["wo"])
    return y, {"c_kv": ckv, "k_pe": kpe}


def mla_cache_specs(cfg, batch: int, max_len: int) -> Dict:
    import jax.numpy as _jnp
    dt = _jnp.dtype(cfg.dtype)
    return {
        "c_kv": spec((batch, max_len, cfg.kv_lora_rank), ("batch", None, None),
                     dtype=dt, init="zeros"),
        "k_pe": spec((batch, max_len, cfg.qk_rope_dim), ("batch", None, None),
                     dtype=dt, init="zeros"),
    }
