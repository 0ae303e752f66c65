"""Shared building blocks: RMSNorm, RoPE, SwiGLU MLP, embeddings."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .sharding import ParamSpec, shard, spec


# ------------------------------------------------------------------- norms
def rmsnorm_spec(d: int, layers: Optional[int] = None) -> ParamSpec:
    if layers is None:
        return spec((d,), ("d_model",), init="ones")
    return spec((layers, d), ("layers", "d_model"), init="ones")


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    """fp32 variance accumulation, but the full-size tensor math stays in
    the input dtype — an fp32 upcast of the (B, S, d) stream would double
    the dominant activation buffers and drag the TP all-reduces to fp32."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return (x * inv) * w.astype(x.dtype)


# -------------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D) or (B, S, D); positions: (S,).

    Rotate-half rope in float32, ``x * cos + rotate_half(x) * sin`` with
    ``rotate_half(x) = [-x2, x1]``.  The rotation is a product with a fixed
    signed permutation, exact (one nonzero term per output, at HIGHEST
    precision).  On a TPU it runs as one small dot that reads ``x`` in
    bf16 and writes the head-major result, where splitting ``x`` into
    halves along its minor dimension costs a float32 copy of ``x`` and two
    more passes over it (DESIGN §7)."""
    dt = x.dtype
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                            # (D/2,)
    ang = positions[:, None].astype(jnp.float32) * freqs    # (S, D/2)
    ang = jnp.concatenate([ang, ang], axis=-1)              # (S, D)
    if x.ndim == 4:
        ang = ang[None, :, None, :]
    else:
        ang = ang[None, :, :]
    eye = jnp.eye(d // 2, dtype=dt)
    zero = jnp.zeros_like(eye)
    rot = jnp.block([[zero, eye], [-eye, zero]])
    x_rot = jnp.einsum("...d,de->...e", x, rot,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    out = x.astype(jnp.float32) * jnp.cos(ang) + x_rot * jnp.sin(ang)
    return out.astype(dt)


# ------------------------------------------------------------------- linear
def linear_spec(d_in: int, d_out: int, axes=("d_model", "ff"),
                layers: Optional[int] = None, **kw) -> ParamSpec:
    if layers is None:
        return spec((d_in, d_out), axes, **kw)
    return spec((layers, d_in, d_out), ("layers",) + tuple(axes), **kw)


def dense(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.einsum("...d,df->...f", x, w)


# -------------------------------------------------------------------- mlp
def mlp_specs(d: int, ff: int, layers: Optional[int] = None) -> dict:
    return {
        "wg": linear_spec(d, ff, ("d_model", "ff"), layers),
        "wu": linear_spec(d, ff, ("d_model", "ff"), layers),
        "wd": linear_spec(ff, d, ("ff", "d_model"), layers),
    }


def int8_ring_proj(h: jax.Array, w: jax.Array) -> jax.Array:
    """Row-parallel projection whose TP combine runs as an int8 ring
    all-reduce (inference-only §Perf variant, cfg.tp_collective="int8_ring"):
    each model-shard computes its partial (B, S, d) product and the partials
    are summed with int8+scale chunks on the wire — ~2x less collective
    traffic than the bf16 all-reduce that dominates prefill cells.

    h: (..., F) sharded on F over `model`; w: (F, d) sharded on F.
    """
    from jax.sharding import PartitionSpec as P
    from ..train.compression import ring_allreduce_int8
    from .sharding import axis_size, current_mesh
    mesh = current_mesh()
    ranks = jnp.arange(axis_size("model"), dtype=jnp.int32)

    def local(h_, w_, r_):
        part = jnp.einsum("...f,fd->...d", h_, w_)
        return ring_allreduce_int8(part, "model", rank=r_[0])

    hspec = P(*((None,) * (h.ndim - 1) + ("model",)))
    from ..compat import shard_map
    return shard_map(local, mesh=mesh,
                     in_specs=(hspec, P("model", None), P("model")),
                     out_specs=P(*((None,) * h.ndim)),
                     axis_names={"model"})(h, w, ranks)


def _use_int8_ring() -> bool:
    from .sharding import current_mesh, rule_flag
    m = current_mesh()
    return bool(rule_flag("__tp_int8__")) and m is not None \
        and "model" in m.axis_names


def mlp(p: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(dense(x, p["wg"])) * dense(x, p["wu"])
    h = shard(h, "batch", "seq", "act_ff")
    if _use_int8_ring():
        return int8_ring_proj(h, p["wd"])
    return dense(h, p["wd"])


# -------------------------------------------------------------- embeddings
VOCAB_PAD = 16   # embedding tables pad to a multiple of the model axis


def padded_vocab(vocab: int) -> int:
    return ((vocab + VOCAB_PAD - 1) // VOCAB_PAD) * VOCAB_PAD


def embed_spec(vocab: int, d: int) -> ParamSpec:
    """Table padded so the vocab dim always shards evenly on `model`
    (granite 49155 / mamba 50280 / seamless 256206 are not 16-divisible);
    pad rows are masked out of the logits in :func:`unembed`."""
    return spec((padded_vocab(vocab), d), ("vocab", "d_model"), scale=1.0)


def embed(w: jax.Array, tokens: jax.Array) -> jax.Array:
    out = jnp.take(w, tokens, axis=0)
    return shard(out, "batch", "seq", None)


def unembed(w: jax.Array, x: jax.Array, vocab: Optional[int] = None
            ) -> jax.Array:
    """x @ w.T -> logits (sharded on vocab); pad slots masked to -inf."""
    logits = jnp.einsum("...d,vd->...v", x, w)
    V_pad = w.shape[0]
    if vocab is not None and vocab != V_pad:
        ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
        logits = jnp.where(ids < vocab, logits,
                           jnp.asarray(-1e30, logits.dtype))
    return shard(logits, "batch", "seq", "act_vocab")


# ---------------------------------------------------------------- softmax xent
def softmax_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Token-mean cross entropy, fp32 accumulation, vocab-sharded safe.

    The label pick uses an iota-mask + masked reduce instead of
    ``take_along_axis``: a gather over the vocab-sharded axis would force
    GSPMD to all-gather the full logits; the mask+reduce stays elementwise
    (fused) and reduces with a cheap psum.
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    V = logits.shape[-1]
    vocab_ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                         logits.ndim - 1)
    ll = jnp.sum(jnp.where(vocab_ids == labels[..., None], logits, 0.0),
                 axis=-1)
    return jnp.mean(lse - ll)
