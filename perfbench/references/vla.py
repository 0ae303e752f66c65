"""Plain float32 reference of the split VLA models (OpenVLA, CogACT).

Written from the architecture alone, in straightforward ``jax.numpy``,
with every matrix product at ``Precision.HIGHEST``.  It imports nothing
of the system under test and reads only the parameter tree that the
benchmark itself draws (``param_shapes`` gives its layout).

Architecture, as the configuration runs it:

* ViT stand-in over patch embeddings: learned position table, then
  ``vit_layers`` pre-norm blocks (RMSNorm, bidirectional multi-head
  attention with rotary positions over 64-wide heads, SwiGLU MLP of
  width ``4 * vit_dim``), a final RMSNorm and a projection to
  ``d_model``.  (The published OpenVLA vision tower is a SigLIP/DINOv2
  pair; patch embeddings stand in for it and its camera frontend.)
* Llama-2 trunk over ``[image ; text]``: pre-norm blocks with causal
  rotary multi-head attention and a SwiGLU MLP.
* The cut: the activation after trunk block ``split - vit_layers`` is
  quantized per (row, 128-lane block; the whole row where the width is
  not a multiple of 128) to int8 with scale ``amax / 127`` and
  dequantized, as the int8 wire codec ships it.
* Heads: ``detok`` — final RMSNorm, logits of the last ``action_dim``
  positions over the vocabulary; ``dit`` — final RMSNorm, the last
  position as the cognition feature, DDIM sampling over
  ``diffusion_steps`` of an adaLN-zero DiT (LayerNorm without affine,
  tanh-GELU MLP, bidirectional attention over the action horizon).

``quant="int8"`` (or ``"fp8"``) computes every matrix product from
fake-quantized operands (per row of the left operand, per column of the
right): the control that a correct comparison has to reject.

Blocks run in loops, one layer at a time from the stacked weights, so
the reference fits next to the served model's parameters on one chip.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
CUT_BLOCK = 128
VIT_HEAD_DIM = 64
DIT_FREQ_DIM = 64
DIT_LN_EPS = 1e-6
VOCAB_PAD = 16


# ------------------------------------------------------------- parameters
def _leaf(shape, init="normal", scale=None):
    """``init``: normal (times ``scale``, default 1/sqrt(fan_in)) or norm
    (1 + 0.1 normal: gains that differ from one, so a path that ignores
    them is seen)."""
    return {"shape": tuple(int(s) for s in shape), "init": init,
            "scale": scale}


def padded_vocab(v: int) -> int:
    return -(-v // VOCAB_PAD) * VOCAB_PAD


def param_shapes(m: Dict) -> Dict:
    """The parameter tree the split executor reads, leaf by leaf."""
    d, L = m["d_model"], m["n_layers"]
    hd = m["head_dim"] or d // m["n_heads"]
    H, KV, ff = m["n_heads"], m["n_kv_heads"], m["d_ff"]
    dv, Lv = m["vit_dim"], m["vit_layers"]
    Vp = padded_vocab(m["vocab_size"])

    def attn(width, heads, kv, hdim, n):
        return {"wq": _leaf((n, width, heads * hdim)),
                "wk": _leaf((n, width, kv * hdim)),
                "wv": _leaf((n, width, kv * hdim)),
                "wo": _leaf((n, heads * hdim, width))}

    def mlp(width, f, n):
        return {"wg": _leaf((n, width, f)), "wu": _leaf((n, width, f)),
                "wd": _leaf((n, f, width))}

    vh = min(VIT_HEAD_DIM, dv)
    tree = {
        "vit": {
            "pos_embed": _leaf((m["n_patches"], dv), scale=0.02),
            "blocks": {"ln1": _leaf((Lv, dv), "norm"),
                       "attn": attn(dv, dv // vh, dv // vh, vh, Lv),
                       "ln2": _leaf((Lv, dv), "norm"),
                       "mlp": mlp(dv, 4 * dv, Lv)},
            "norm": _leaf((dv,), "norm"),
            "proj": _leaf((dv, d)),
        },
        "embed": _leaf((Vp, d), scale=1.0),
        "blocks": {"ln1": _leaf((L, d), "norm"), "attn": attn(d, H, KV, hd, L),
                   "ln2": _leaf((L, d), "norm"), "mlp": mlp(d, ff, L)},
        "final_norm": _leaf((d,), "norm"),
        "head": _leaf((Vp, d), scale=1.0),
        "action": {},
    }
    if m["vla_action_head"] == "dit":
        dd, n, a = m["dit_dim"], m["dit_layers"], m["action_dim"]
        tree["action"] = {
            "x_in": _leaf((a, dd)), "cond": _leaf((d, dd)),
            "t_emb": _leaf((DIT_FREQ_DIM, dd)),
            "blocks": {"mod": _leaf((n, dd, 6 * dd)),
                       "wq": _leaf((n, dd, dd)), "wk": _leaf((n, dd, dd)),
                       "wv": _leaf((n, dd, dd)), "wo": _leaf((n, dd, dd)),
                       "w1": _leaf((n, dd, 4 * dd)),
                       "w2": _leaf((n, 4 * dd, dd))},
            "final_mod": _leaf((dd, 2 * dd)), "out": _leaf((dd, a)),
        }
    elif m["vla_action_head"] != "detok":
        raise ValueError(f"no reference for head {m['vla_action_head']!r}")
    return tree


# ------------------------------------------------------------- arithmetic
def _fake_int8(x, axis):
    """Symmetric int8 quantize/dequantize along ``axis`` (absmax/127)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _fake_fp8(x, axis):
    """float8 e4m3 quantize/dequantize, scaled along ``axis`` so that the
    largest magnitude maps to the format's largest (448)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


_FAKE = {"int8": _fake_int8, "fp8": _fake_fp8}


def _mm(spec, a, b, quant):
    """einsum in float32 at HIGHEST.  ``quant`` ("int8" or "fp8")
    fake-quantizes the left operand over its contracted (last) axis and
    the right one over its contracted axis, as a low-precision matmul
    with per-row and per-column scales would."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if quant:
        lhs, rhs = spec.split("->")[0].split(",")
        a = _FAKE[quant](a, a.ndim - 1)
        contracted = [i for i, c in enumerate(rhs) if c in lhs and
                      c not in spec.split("->")[1]]
        b = _FAKE[quant](b, tuple(contracted))
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _dense(x, w, quant):
    return _mm("...d,df->...f", x, w, quant)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """x: (B, S, H, D); rotate the two halves of each head."""
    S, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, causal, quant):
    """q, k, v: (B, S, H, D) -> (B, S, H, D)."""
    s = _mm("bshd,bthd->bhst", q, k, quant) * q.shape[-1] ** -0.5
    if causal:
        S = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return _mm("bhst,bthd->bshd", p, v, quant)


def _block(x, p, *, heads, eps, theta, causal, quant):
    """Pre-norm transformer block (rotary attention + SwiGLU MLP)."""
    B, S, _ = x.shape
    h = _rmsnorm(x, p["ln1"], eps)
    a = p["attn"]
    q = _dense(h, a["wq"], quant).reshape(B, S, heads, -1)
    k = _dense(h, a["wk"], quant).reshape(B, S, heads, -1)
    v = _dense(h, a["wv"], quant).reshape(B, S, heads, -1)
    o = _attention(_rope(q, theta), _rope(k, theta), v, causal, quant)
    x = x + _dense(o.reshape(B, S, -1), a["wo"], quant)
    h = _rmsnorm(x, p["ln2"], eps)
    mp = p["mlp"]
    g = jax.nn.silu(_dense(h, mp["wg"], quant)) * _dense(h, mp["wu"], quant)
    return x + _dense(g, mp["wd"], quant)


def _layer(stack, i):
    return jax.tree_util.tree_map(lambda w: w[i], stack)


@jax.jit
def cut_roundtrip(x):
    """The int8 wire codec's semantics on the cut activation."""
    *lead, D = x.shape
    blk = CUT_BLOCK if D % CUT_BLOCK == 0 else D
    xb = x.reshape(*lead, D // blk, blk)
    return _fake_int8(xb, -1).reshape(x.shape)


# ------------------------------------------------------------ the stages
def _blocks(x, stack, lo, hi, **kw):
    """Blocks ``[lo, hi)`` of a stacked tree, one layer per loop step."""
    return jax.lax.fori_loop(
        lo, hi, lambda i, h: _block(h, _layer(stack, i), **kw), x)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _vit(m, quant, vit, embed, patches, tokens):
    """ViT over the patches, projected, beside the text embeddings."""
    dv = m["vit_dim"]
    x = patches.astype(jnp.float32) + vit["pos_embed"].astype(jnp.float32)
    x = _blocks(x, vit["blocks"], 0, m["vit_layers"],
                heads=dv // min(VIT_HEAD_DIM, dv), eps=m["norm_eps"],
                theta=m["rope_theta"], causal=False, quant=quant)
    img = _dense(_rmsnorm(x, vit["norm"], m["norm_eps"]), vit["proj"], quant)
    txt = jnp.take(embed, tokens, axis=0).astype(jnp.float32)
    return jnp.concatenate([img, txt], axis=1)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _trunk(m, quant, blocks, lo, hi, x):
    return _blocks(x, blocks, lo, hi, heads=m["n_heads"], eps=m["norm_eps"],
                   theta=m["rope_theta"], causal=True, quant=quant)


@jax.jit
def cut_roundtrip(x):
    """The int8 wire codec's semantics on the cut activation."""
    *lead, D = x.shape
    blk = CUT_BLOCK if D % CUT_BLOCK == 0 else D
    xb = x.reshape(*lead, D // blk, blk)
    return _fake_int8(xb, -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _detok(m, quant, final_norm, head, x):
    h = _rmsnorm(x[:, -m["action_dim"]:], final_norm, m["norm_eps"])
    return _mm("bsd,vd->bsv", h, head[:m["vocab_size"]], quant)


def _ln(x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + DIT_LN_EPS)


def _timestep_embed(t):
    half = DIT_FREQ_DIM // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half) / half)
    ang = t[:, None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], -1)


def _dit_block(m, quant, pl, x, sc):
    """adaLN-zero DiT block; ``sc`` is silu(condition)."""
    dd, nh = m["dit_dim"], m["dit_heads"]
    B, Hn, _ = x.shape
    sh1, sc1, g1, sh2, sc2, g2 = jnp.split(
        _dense(sc, pl["mod"], quant)[:, None], 6, -1)
    h = _ln(x) * (1 + sc1) + sh1
    q = _dense(h, pl["wq"], quant).reshape(B, Hn, nh, dd // nh)
    k = _dense(h, pl["wk"], quant).reshape(B, Hn, nh, dd // nh)
    v = _dense(h, pl["wv"], quant).reshape(B, Hn, nh, dd // nh)
    o = _attention(q, k, v, False, quant).reshape(B, Hn, dd)
    x = x + g1 * _dense(o, pl["wo"], quant)
    h = _ln(x) * (1 + sc2) + sh2
    return x + g2 * _dense(jax.nn.gelu(_dense(h, pl["w1"], quant),
                                       approximate=True), pl["w2"], quant)


def _dit_eps(m, quant, p, x, t, cog):
    """Predicted noise for actions ``x`` (B, horizon, action_dim) at
    denoising step ``t``."""
    B = x.shape[0]
    cond = _dense(cog, p["cond"], quant) + _dense(
        _timestep_embed(jnp.full((B,), t)), p["t_emb"], quant)
    sc = jax.nn.silu(cond)
    x = jax.lax.fori_loop(
        0, m["dit_layers"],
        lambda i, h: _dit_block(m, quant, _layer(p["blocks"], i), h, sc),
        _dense(x, p["x_in"], quant))
    sh, s = jnp.split(_dense(sc, p["final_mod"], quant)[:, None], 2, -1)
    return _dense(_ln(x) * (1 + s) + sh, p["out"], quant)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _dit_sample(m, quant, final_norm, action, x, noise):
    """DDIM over ``diffusion_steps`` from ``noise``, conditioned on the
    final-normed last position of ``x``."""
    cog = _rmsnorm(x[:, -1], final_norm, m["norm_eps"])
    n = m["diffusion_steps"]
    alphas = jnp.cumprod(1.0 - jnp.linspace(1e-4, 0.02, n))

    def step(i, a):
        t = n - 1 - i
        ab = alphas[t]
        ab_prev = jnp.where(t > 0, alphas[jnp.maximum(t - 1, 0)], 1.0)
        eps = _dit_eps(m, quant, action, a, t, cog)
        x0 = (a - jnp.sqrt(1 - ab) * eps) / jnp.sqrt(ab)
        return jnp.sqrt(ab_prev) * x0 + jnp.sqrt(1 - ab_prev) * eps

    return jax.lax.fori_loop(0, n, step, noise.astype(jnp.float32))


def dit_noise(m: Dict, key, batch: int):
    """The sampler's starting noise for one call of ``batch`` rows."""
    return jax.random.normal(key, (batch, m["action_horizon"],
                                   m["action_dim"]), jnp.float32)


def forward(m: Dict, params: Dict, patches, tokens, split: int, *,
            noise: Optional[jax.Array] = None, quant: str = ""
            ) -> Dict[str, jax.Array]:
    """Reference outputs for a batch of observations served at executor
    ``split`` (ViT blocks count first): ``cut`` — the activation after
    the int8 round trip; ``logits`` (detok) or ``action`` (dit, needs
    ``noise``)."""
    mh = _Frozen(tuple(sorted(m.items())))
    edge = jnp.int32(split - m["vit_layers"])
    x = _vit(mh, quant, params["vit"], params["embed"], patches, tokens)
    x = _trunk(mh, quant, params["blocks"], jnp.int32(0), edge, x)
    x = cut_roundtrip(x)
    out = {"cut": x}
    x = _trunk(mh, quant, params["blocks"], edge, jnp.int32(m["n_layers"]),
               x)
    if m["vla_action_head"] == "detok":
        out["logits"] = _detok(mh, quant, params["final_norm"],
                               params["head"], x)
    else:
        out["action"] = _dit_sample(mh, quant, params["final_norm"],
                                    params["action"], x, noise)
    return out


class _Frozen(dict):
    """A hashable view of the model sizes, for jit's static arguments."""

    def __init__(self, items):
        super().__init__(items)
        self._key = items

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key == other._key
