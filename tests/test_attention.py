"""Self attention against a plain einsum reference (``models/attention``).

The reference contracts the input against each projection weight viewed
as ``(D, heads, hd)``, applies rope and the softmax in float32, and
expands grouped k/v heads by repetition.  ``_qkv``, ``attn_forward`` and
``attn_decode`` must match it to the rounding of their dtype: MHA and
GQA, with and without q/k/v biases, rope on and off.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import attention as A

B, S, D, H, HD = 2, 12, 64, 4, 16
# float32 runs the same arithmetic as the reference; bfloat16 rounds the
# projections and the attention output once each
TOL = {"float32": 1e-5, "bfloat16": 2 ** -6}


def _cfg(kv, bias, dtype):
    return ModelConfig(name="attn", d_model=D, n_heads=H, n_kv_heads=kv,
                       head_dim=HD, rope_theta=10_000.0, qkv_bias=bias,
                       dtype=dtype)


def _params(cfg, key):
    kv = cfg.n_kv_heads * HD
    shapes = {"wq": (D, H * HD), "wk": (D, kv), "wv": (D, kv),
              "wo": (H * HD, D)}
    if cfg.qkv_bias:
        shapes.update(bq=(H * HD,), bk=(kv,), bv=(kv,))
    keys = jax.random.split(key, len(shapes))
    dt = jnp.dtype(cfg.dtype)
    # matrices N(0, 1/fan_in), biases N(0, 1/4)
    return {n: (jax.random.normal(k, s) / np.sqrt(s[0] if len(s) == 2
                                                 else 4)).astype(dt)
            for k, (n, s) in zip(keys, sorted(shapes.items()))}


def _f32(t):
    return np.array(jnp.asarray(t, jnp.float32))


def _rope(x, pos, theta):
    """x: (B, S, n, hd) float32; pos: (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (np.arange(0, 2 * half, 2) / (2 * half))
    ang = np.asarray(pos, np.float64)[:, None] * freqs
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ref_qkv(cfg, p, x):
    x = _f32(x)
    KV = cfg.n_kv_heads

    def proj(w, b, n):
        y = np.einsum("bsd,dnk->bsnk", x, _f32(p[w]).reshape(D, n, HD))
        return y + _f32(p[b]).reshape(n, HD) if b in p else y

    return proj("wq", "bq", H), proj("wk", "bk", KV), proj("wv", "bv", KV)


def _ref_attend(cfg, p, q, k, v, mask):
    """q: (B,S,H,hd); k, v: (B,T,KV,hd); mask: (S, T) -> (B,S,D)."""
    g = H // cfg.n_kv_heads
    k, v = np.repeat(k, g, axis=2), np.repeat(v, g, axis=2)
    s = np.einsum("bshd,bthd->bhst", q, k) / np.sqrt(HD)
    s = np.where(mask, s, -np.inf)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    out = np.einsum("bhst,bthd->bshd", w, v).reshape(q.shape[0], -1, H * HD)
    return out @ _f32(p["wo"])


def _close(got, ref, dtype):
    got = _f32(got)
    err = np.max(np.abs(got - ref)) / max(1.0, np.max(np.abs(ref)))
    assert err <= TOL[dtype], err


CASES = [(kv, bias) for kv in (H, 2) for bias in (False, True)]
IDS = [f"{'mha' if kv == H else 'gqa'}-{'bias' if b else 'nobias'}"
       for kv, b in CASES]
DTYPES = ["float32", "bfloat16"]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kv,bias", CASES, ids=IDS)
def test_qkv_matches_reference(kv, bias, dtype):
    cfg = _cfg(kv, bias, dtype)
    p = _params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D)).astype(dtype)
    got = jax.jit(lambda p, x: A._qkv(cfg, p, x))(p, x)
    for g, r in zip(got, _ref_qkv(cfg, p, x)):
        assert g.shape == r.shape
        _close(g, r, dtype)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("rope", [False, True], ids=["norope", "rope"])
@pytest.mark.parametrize("kv,bias", CASES, ids=IDS)
def test_attn_forward_matches_reference(kv, bias, rope, dtype):
    cfg = _cfg(kv, bias, dtype)
    p = _params(cfg, jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, D)).astype(dtype)
    pos = jnp.arange(S)
    got = jax.jit(lambda p, x: A.attn_forward(cfg, p, x, pos, rope=rope))(
        p, x)
    q, k, v = _ref_qkv(cfg, p, x)
    if rope:
        q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    causal = np.arange(S)[:, None] >= np.arange(S)[None, :]
    _close(got, _ref_attend(cfg, p, q, k, v, causal), dtype)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kv,bias", CASES, ids=IDS)
def test_attn_decode_matches_reference(kv, bias, dtype):
    cfg = _cfg(kv, bias, dtype)
    p = _params(cfg, jax.random.PRNGKey(4))
    T, pos = 16, 9
    x = jax.random.normal(jax.random.PRNGKey(5), (B, 1, D)).astype(dtype)
    ck, cv = (jax.random.normal(k, (B, T, kv * HD)).astype(dtype)
              for k in jax.random.split(jax.random.PRNGKey(6)))
    y, cache = jax.jit(lambda p, x, c: A.attn_decode(cfg, p, x, pos, c))(
        p, x, {"k": ck, "v": cv})
    q, k, v = _ref_qkv(cfg, p, x)
    q = _rope(q, np.array([pos]), cfg.rope_theta)
    k = _rope(k, np.array([pos]), cfg.rope_theta)
    keys = _f32(ck).reshape(B, T, kv, HD)
    vals = _f32(cv).reshape(B, T, kv, HD)
    keys[:, pos], vals[:, pos] = k[:, 0], v[:, 0]
    _close(cache["k"].reshape(B, T, kv, HD)[:, pos], k[:, 0], dtype)
    _close(cache["v"].reshape(B, T, kv, HD)[:, pos], v[:, 0], dtype)
    mask = (np.arange(T) <= pos)[None, :]
    _close(y, _ref_attend(cfg, p, q, keys, vals, mask), dtype)
