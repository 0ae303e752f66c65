"""Plain float32 reference of a split VLA on a MoE + MLA trunk with a
SigLIP-shaped vision tower (kimi-vl-a3b-vla).

Written from the architecture alone, in straightforward ``jax.numpy``,
with every matrix product at ``Precision.HIGHEST`` (and under
``jax.default_matmul_precision("highest")``).  It imports nothing
of the system under test and reads only the parameter tree that the
benchmark itself draws (``param_shapes`` gives its layout).  The int8
cut, the controls (``quant``), the detok head's vocabulary padding and
the hashable sizes come from ``references/vla.py``.

Architecture, as the configuration runs it:

* Vision tower (MoonViT): patch embeddings plus a learned position
  table, then ``vit_layers`` pre-LayerNorm blocks: biased q, k, v, o with
  ``vit_heads`` heads and 2D rotary positions over the patch grid (the
  head's dims in pairs ``(2i, 2i+1)``; pair i turns by
  ``pos * 10000 ** (-4 * (i // 2) / head_dim)``, ``pos`` the row for even
  i and the column for odd i), bidirectional softmax attention, and a
  biased tanh-GELU MLP of width ``vit_ff``; a final LayerNorm.
* Projector: LayerNorm per patch, ``vit_merge`` x ``vit_merge`` pixel
  shuffle (row-major grid; a token's features ordered by row offset,
  column offset, channel), Linear, exact GELU, Linear to ``d_model``.
* Trunk over ``[image ; text]``, pre-RMSNorm blocks with MLA attention:
  ``q = h W_q`` split per head into ``qk_nope_dim`` and ``qk_rope_dim``;
  ``[c | k_pe] = h W_kv_a`` with ``c`` RMS-normed; ``k_nope = c W_k_b``,
  ``v = c W_v_b`` per head; rotary (rotate-half, ``rope_theta``) on the
  query's rope dims and on ``k_pe``, which every head shares; causal
  softmax at ``1 / sqrt(qk_nope_dim + qk_rope_dim)``.  The first
  ``first_dense_layers`` blocks end in a SwiGLU of width ``d_ff``; the
  rest in a MoE: scores ``s = sigmoid(h W_r)`` over all ``n_experts``,
  the top ``moe_top_k`` of ``s + e_score_correction_bias`` selected,
  gates ``moe_routed_scale * s_i / sum_top s_j``, and
  ``y = sum_{i in top, i held} g_i E_i(h) + Shared(h)`` with SwiGLU
  experts of width ``moe_d_ff`` and shared experts of width
  ``n_shared_experts * moe_d_ff``.  Only experts ``[expert_start,
  expert_start + experts_held)`` are held: what the others would add is
  left out, as on the chip that holds this share.
* The cut after trunk block ``split - vit_layers``, through the int8 wire
  codec; the detok head's logits at the last ``action_dim`` positions.

``routing`` gives the experts each row chose in each MoE layer, and
``dispatch_counts`` what the held experts get from one call's choices:
the routed work that ``expert_roofline`` charges, taken from this
reference rather than from the program's own counters.

Requests run through the stages in blocks of ``REQUEST_BLOCK``, and the
blocks in loops one layer at a time from the stacked weights, so the
reference fits next to the served model's parameters on one chip.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from references import vla as _vla
from references.vla import (HIGHEST, _Frozen, _leaf, _rmsnorm, _rope,
                            cut_roundtrip, padded_vocab)

LN_EPS = 1e-5
VIT_ROPE_THETA = 10_000.0
REQUEST_BLOCK = 4
VOCAB_CHUNK = 20_480
# The seeded draw keeps the tokens' states apart, as a trained model's
# are, so that the router spreads them over the experts, and keeps the
# trunk stable under rounding.  With every matrix at 1/sqrt(fan_in),
# attention's near-uniform averages and the GELUs' mean pile a direction
# that every token shares onto the states (at the published widths and
# depth the mean token holds ~80 % of their energy, and a few experts
# take every row).  So: the output matrix of the tower's attention and of
# every trunk branch (attention, dense MLP, routed and shared experts) at
# a further 1/sqrt(2 depth) (GPT-2's residual scaling; in the trunk it
# also keeps a routing choice that rounding flips from moving a row
# much); each GELU (the tower's MLP, the projector) fed at
# ``1 / GELU_GAIN`` of the usual scale and read out at ``GELU_GAIN``
# times, so that it works near its linear part and its mean stays small;
# and a selection bias that breaks ties without pinning the choice (a
# trained bias balances the experts' loads).
GELU_GAIN = 4.0
SELECTION_BIAS = 0.01


# ------------------------------------------------------------- parameters
def param_shapes(m: Dict) -> Dict:
    """The parameter tree the split executor reads, leaf by leaf."""
    if (m["vit_block"], m["vla_action_head"]) != ("siglip", "detok"):
        raise ValueError("this reference has a SigLIP tower and the detok "
                         "head only")
    d, L, nd = m["d_model"], m["n_layers"], m["first_dense_layers"]
    nm = L - nd
    H, r = m["n_heads"], m["kv_lora_rank"]
    qn, qr, vd = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    dv, Lv, ff = m["vit_dim"], m["vit_layers"], m["vit_ff"]
    dm = m["vit_merge"] ** 2 * dv
    E, Eh, fe = m["n_experts"], m["experts_held"], m["moe_d_ff"]
    fs = m["n_shared_experts"] * fe
    Vp = padded_vocab(m["vocab_size"])

    def bias(*shape):
        return _leaf(shape, scale=0.02)

    def gain(shape, g):
        return _leaf(shape, scale=g * shape[-2] ** -0.5)

    def out(shape, depth):
        """A residual branch's output matrix."""
        return gain(shape, (2 * depth) ** -0.5)

    def mla(n):
        return {"wq": _leaf((n, d, H * (qn + qr))),
                "wkv_a": _leaf((n, d, r + qr)),
                "kv_norm": _leaf((n, r), "norm"),
                "wk_b": _leaf((n, r, H * qn)), "wv_b": _leaf((n, r, H * vd)),
                "wo": out((n, H * vd, d), L)}

    def swiglu(n, f, *lead):
        return {"wg": _leaf((n, *lead, d, f)), "wu": _leaf((n, *lead, d, f)),
                "wd": out((n, *lead, f, d), L)}

    vit_attn = {k: _leaf((Lv, dv, dv)) for k in ("wq", "wk", "wv")}
    vit_attn["wo"] = out((Lv, dv, dv), Lv)
    vit_attn.update({k: bias(Lv, dv) for k in ("bq", "bk", "bv", "bo")})
    return {
        "vit": {
            "pos_embed": _leaf((m["n_patches"], dv), scale=0.02),
            "blocks": {"ln1": _leaf((Lv, dv), "norm"), "ln1_b": bias(Lv, dv),
                       "attn": vit_attn,
                       "ln2": _leaf((Lv, dv), "norm"), "ln2_b": bias(Lv, dv),
                       "mlp": {"w1": gain((Lv, dv, ff), 1 / GELU_GAIN),
                               "b1": bias(Lv, ff),
                               "w2": gain((Lv, ff, dv), GELU_GAIN),
                               "b2": bias(Lv, dv)}},
            "norm": _leaf((dv,), "norm"), "norm_b": bias(dv),
            "proj": {"ln": _leaf((dv,), "norm"), "ln_b": bias(dv),
                     "w1": gain((dm, dm), 1 / GELU_GAIN), "b1": bias(dm),
                     "w2": gain((dm, d), GELU_GAIN), "b2": bias(d)},
        },
        "embed": _leaf((Vp, d), scale=1.0),
        "dense_blocks": {"ln1": _leaf((nd, d), "norm"), "attn": mla(nd),
                         "ln2": _leaf((nd, d), "norm"),
                         "mlp": swiglu(nd, m["d_ff"])},
        "moe_blocks": {"ln1": _leaf((nm, d), "norm"), "attn": mla(nm),
                       "ln2": _leaf((nm, d), "norm"),
                       "moe": {"router": _leaf((nm, d, E)),
                               "e_score_correction_bias": _leaf(
                                   (nm, E), scale=SELECTION_BIAS),
                               **swiglu(nm, fe, Eh),
                               "shared": swiglu(nm, fs)}},
        "final_norm": _leaf((d,), "norm"),
        "head": _leaf((Vp, d), scale=1.0),
        "action": {},
    }


# ------------------------------------------------------------- arithmetic
def _mm(spec, a, b, quant):
    """``references.vla._mm``, and ``quant="bf16"``: both operands
    rounded to bfloat16 first, as a program that keeps its activations
    and weights in bfloat16 multiplies them (a witness of what that
    precision alone does to the outputs, not a control)."""
    if quant == "bf16":
        a, b = (t.astype(jnp.bfloat16) for t in (a, b))
        quant = ""
    return _vla._mm(spec, a, b, quant)


def _dense(x, w, quant, b=None):
    y = _mm("...d,df->...f", x, w, quant)
    return y if b is None else y + b.astype(jnp.float32)


def _layernorm(x, w, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _rope_2d(x, side):
    """x: (B, P, H, D) over a ``side`` x ``side`` grid, row-major."""
    P, D = x.shape[1], x.shape[-1]
    pos = jnp.arange(P)
    grid = jnp.stack([pos // side, pos % side], -1).astype(jnp.float32)
    freqs = VIT_ROPE_THETA ** (-jnp.arange(0, D, 4, dtype=jnp.float32) / D)
    # pair i: frequency i // 2, the row for even i, the column for odd i
    ang = grid[:, None, :] * freqs[None, :, None]           # (P, D/4, 2)
    ang = ang.reshape(P, D // 2)[None, :, None, :]
    xp = x.reshape(*x.shape[:-1], D // 2, 2)
    a, b = xp[..., 0], xp[..., 1]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(x.shape)


def _softmax_attention(q, k, v, causal, quant):
    """q, k: (B, S, H, Dk); v: (B, S, H, Dv) -> (B, S, H, Dv)."""
    s = _mm("bshd,bthd->bhst", q, k, quant) * q.shape[-1] ** -0.5
    if causal:
        S = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return _mm("bhst,bthd->bshd", jax.nn.softmax(s, axis=-1), v, quant)


def _layer(stack, i):
    return jax.tree_util.tree_map(lambda w: w[i], stack)


def _vit_block(m, quant, p, x):
    B, P, dv = x.shape
    H = m["vit_heads"]
    a = p["attn"]
    z = _layernorm(x, p["ln1"], p["ln1_b"])
    q, k, v = (_dense(z, a[f"w{n}"], quant, a[f"b{n}"]).reshape(B, P, H, -1)
               for n in "qkv")
    side = math.isqrt(P)
    o = _softmax_attention(_rope_2d(q, side), _rope_2d(k, side), v, False,
                           quant)
    x = x + _dense(o.reshape(B, P, dv), a["wo"], quant, a["bo"])
    z = _layernorm(x, p["ln2"], p["ln2_b"])
    mp = p["mlp"]
    z = jax.nn.gelu(_dense(z, mp["w1"], quant, mp["b1"]), approximate=True)
    return x + _dense(z, mp["w2"], quant, mp["b2"])


def _mla(m, quant, a, h):
    B, S, _ = h.shape
    H, r = m["n_heads"], m["kv_lora_rank"]
    qn, qr = m["qk_nope_dim"], m["qk_rope_dim"]
    q = _dense(h, a["wq"], quant).reshape(B, S, H, qn + qr)
    kv = _dense(h, a["wkv_a"], quant)
    c = _rmsnorm(kv[..., :r], a["kv_norm"], m["norm_eps"])
    k_pe = _rope(kv[..., None, r:], m["rope_theta"])          # (B, S, 1, qr)
    k_nope = _dense(c, a["wk_b"], quant).reshape(B, S, H, qn)
    v = _dense(c, a["wv_b"], quant).reshape(B, S, H, -1)
    q = jnp.concatenate([q[..., :qn], _rope(q[..., qn:], m["rope_theta"])],
                        -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, S, H, qr))], -1)
    o = _softmax_attention(q, k, v, True, quant)
    return _dense(o.reshape(B, S, -1), a["wo"], quant)


def _swiglu(p, h, quant):
    g = jax.nn.silu(_dense(h, p["wg"], quant)) * _dense(h, p["wu"], quant)
    return _dense(g, p["wd"], quant)


def _moe(m, quant, p, h):
    """The held experts' part of the MoE output plus the shared experts,
    and the experts each row chose (B, S, moe_top_k)."""
    s = jax.nn.sigmoid(_dense(h, p["router"], quant))          # (B, S, E)
    _, top = jax.lax.top_k(s + p["e_score_correction_bias"].astype(
        jnp.float32), m["moe_top_k"])
    chosen = jnp.sum(jax.nn.one_hot(top, m["n_experts"]), -2)  # (B, S, E)
    g = m["moe_routed_scale"] * s * chosen \
        / jnp.sum(s * chosen, -1, keepdims=True)
    e0, Eh = m["expert_start"], m["experts_held"]
    g = g[..., e0:e0 + Eh]                                     # held experts
    a = jax.nn.silu(_mm("bsd,edf->bsef", h, p["wg"], quant)) \
        * _mm("bsd,edf->bsef", h, p["wu"], quant)
    y = _mm("bsef,efd->bsed", a, p["wd"], quant)
    return jnp.einsum("bsed,bse->bsd", y, g, precision=HIGHEST) \
        + _swiglu(p["shared"], h, quant), top


def _trunk_block(m, quant, p, x, is_moe):
    """The block's output and, in a MoE block, the experts each row
    chose."""
    h = _rmsnorm(x, p["ln1"], m["norm_eps"])
    x = x + _mla(m, quant, p["attn"], h)
    h = _rmsnorm(x, p["ln2"], m["norm_eps"])
    if not is_moe:
        return x + _swiglu(p["mlp"], h, quant), None
    y, top = _moe(m, quant, p["moe"], h)
    return x + y, top


# ------------------------------------------------------------ the stages
@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _vision(m, quant, vit, embed, patches, tokens):
    """Tower and projector over the patches, beside the text
    embeddings."""
    x = patches.astype(jnp.float32) + vit["pos_embed"].astype(jnp.float32)
    x = jax.lax.fori_loop(
        0, m["vit_layers"],
        lambda i, h: _vit_block(m, quant, _layer(vit["blocks"], i), h), x)
    x = _layernorm(x, vit["norm"], vit["norm_b"])
    p = vit["proj"]
    x = _layernorm(x, p["ln"], p["ln_b"])
    B, P, dv = x.shape
    k, side = m["vit_merge"], math.isqrt(P)
    x = x.reshape(B, side // k, k, side // k, k, dv)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(B, P // k ** 2, k * k * dv)
    x = jax.nn.gelu(_dense(x, p["w1"], quant, p["b1"]), approximate=False)
    img = _dense(x, p["w2"], quant, p["b2"])
    txt = jnp.take(embed, tokens, axis=0).astype(jnp.float32)
    return jnp.concatenate([img, txt], axis=1)


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _trunk(m, quant, dense, moe, lo, hi, x, chosen):
    """Trunk blocks ``[lo, hi)``: the dense blocks first, then the MoE
    blocks, each writing its rows' choices into ``chosen`` (MoE layers,
    B, S, moe_top_k)."""
    nd, nm = m["first_dense_layers"], m["n_layers"] - m["first_dense_layers"]
    x = jax.lax.fori_loop(
        jnp.clip(lo, 0, nd), jnp.clip(hi, 0, nd),
        lambda i, h: _trunk_block(m, quant, _layer(dense, i), h, False)[0],
        x)

    def moe_block(i, carry):
        h, top = _trunk_block(m, quant, _layer(moe, i), carry[0], True)
        return h, carry[1].at[i].set(top)
    return jax.lax.fori_loop(jnp.clip(lo - nd, 0, nm),
                             jnp.clip(hi - nd, 0, nm), moe_block, (x, chosen))


@functools.partial(jax.jit, static_argnames=("m", "quant"))
def _detok(m, quant, final_norm, head, x):
    """Logits at the last ``action_dim`` positions, the vocabulary in
    chunks."""
    h = _rmsnorm(x[:, -m["action_dim"]:], final_norm, m["norm_eps"])
    V = m["vocab_size"]
    return jnp.concatenate(
        [_mm("bsd,vd->bsv", h, head[c:min(c + VOCAB_CHUNK, V)], quant)
         for c in range(0, V, VOCAB_CHUNK)], -1)


def forward(m: Dict, params: Dict, patches, tokens, split: int, *,
            noise: Optional[jax.Array] = None, quant: str = ""
            ) -> Dict[str, jax.Array]:
    """Reference outputs for a batch of observations served at executor
    ``split`` (the tower's blocks count first): ``cut`` — the activation
    after the int8 round trip; ``logits``."""
    del noise
    out = _requests(m, params, patches, tokens, split, quant)
    return {"cut": out["cut"], "logits": out["logits"]}


def routing(m: Dict, params: Dict, patches, tokens, split: int, *,
            quant: str = "") -> np.ndarray:
    """The experts each row of each observation chose in each MoE layer,
    all ``n_experts`` counted: (B, MoE layers, S, moe_top_k)."""
    out = _requests(m, params, patches, tokens, split, quant)
    return np.asarray(out["chosen"]).transpose(1, 0, 2, 3)


def dispatch_counts(m: Dict, chosen: np.ndarray) -> Dict[str, int]:
    """What the held experts get from one call: ``chosen`` is
    ``routing``'s output for the call's observations, (B, MoE layers, S,
    moe_top_k).  ``routed`` (row, choice) pairs on held experts and
    ``reached`` (MoE layer, held expert) pairs given at least one row."""
    e0, Eh = m["expert_start"], m["experts_held"]
    ids = np.swapaxes(chosen, 0, 1).reshape(chosen.shape[1], -1)
    held = (ids >= e0) & (ids < e0 + Eh)
    reached = sum(len(np.unique(row[h])) for row, h in zip(ids, held))
    return {"routed": int(held.sum()), "reached": int(reached)}


def _requests(m, params, patches, tokens, split, quant):
    if m["vla_action_head"] != "detok":
        raise ValueError(f"no reference for head {m['vla_action_head']!r}")
    mh = _Frozen(tuple(sorted(m.items())))
    edge = jnp.int32(split - m["vit_layers"])
    dense, moe = params["dense_blocks"], params["moe_blocks"]
    outs = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, patches.shape[0], REQUEST_BLOCK):
            outs.append(_block_of_requests(
                mh, quant, params, dense, moe, edge,
                patches[lo:lo + REQUEST_BLOCK], tokens[lo:lo + REQUEST_BLOCK]))
    return {k: jnp.concatenate([o[k] for o in outs], axis=int(k == "chosen"))
            for k in outs[0]}


def _block_of_requests(mh, quant, params, dense, moe, edge, patches, tokens):
    x = _vision(mh, quant, params["vit"], params["embed"], patches, tokens)
    nm = mh["n_layers"] - mh["first_dense_layers"]
    chosen = jnp.zeros((nm, *x.shape[:2], mh["moe_top_k"]), jnp.int32)
    x, chosen = _trunk(mh, quant, dense, moe, jnp.int32(0), edge, x, chosen)
    x = cut_roundtrip(x)
    out = {"cut": x}
    x, out["chosen"] = _trunk(mh, quant, dense, moe, edge,
                              jnp.int32(mh["n_layers"]), x, chosen)
    out["logits"] = _detok(mh, quant, params["final_norm"], params["head"],
                           x)
    return out
