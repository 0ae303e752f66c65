"""VLA models — the paper's own evaluation targets (OpenVLA, CogACT).

Structure: ViT encoder (patch embeddings -> vit blocks -> project to LLM
width)  +  LLM backbone  +  action decoder S_dec ∈ {detok, MLP, LSTM,
diffusion, DiT} (paper §IV-A structure model).  The image frontend proper
(conv patchify) is stubbed: inputs are patch embeddings (B, n_patches,
vit_dim), matching the assignment's STUB rule and the dry-run input specs.

The flattened layer graph of these models is what RoboECC segments; see
``core/structure.py`` which mirrors this file's block ordering.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from . import attention as A
from .layers import dense, embed, embed_spec, linear_spec, mlp, mlp_specs, \
    rmsnorm, rmsnorm_spec, softmax_xent, unembed
from .sharding import spec
from .transformer import _groups, block_forward, run_stack, trunk_specs


# ------------------------------------------------------------------ ViT
# MoonViT's rotary base for the patch grid's rows and columns
VIT_ROPE_THETA = 10_000.0
VIT_LN_EPS = 1e-5


def _vit_cfg(cfg):
    """The tower's block config: heads, MLP width from the config (the
    stand-in's 64-wide heads and 4x MLP by default)."""
    dv = cfg.vit_dim
    heads = cfg.vit_heads or dv // min(64, dv)
    return cfg.replace(d_model=dv, n_heads=heads, n_kv_heads=heads,
                       head_dim=dv // heads, d_ff=cfg.vit_ff or 4 * dv,
                       causal=False, use_mla=False, parallel_block=False,
                       qkv_bias=cfg.vit_block == "siglip")


def vit_tokens(cfg) -> int:
    """Image tokens the tower hands the trunk."""
    return cfg.n_patches // cfg.vit_merge ** 2


def _bias(n, layers=None):
    shape = (n,) if layers is None else (layers, n)
    return spec(shape, (None,) * len(shape), init="zeros")


def vit_specs(cfg) -> Dict:
    dv, Lv = cfg.vit_dim, cfg.vit_layers
    vc = _vit_cfg(cfg)
    if cfg.vit_block != "siglip":
        return {
            "pos_embed": spec((cfg.n_patches, dv), (None, None), scale=0.02),
            "blocks": {
                "ln1": rmsnorm_spec(dv, Lv),
                "attn": A.attn_specs(vc, Lv),
                "ln2": rmsnorm_spec(dv, Lv),
                "mlp": mlp_specs(dv, vc.d_ff, Lv),
            },
            "norm": rmsnorm_spec(dv),
            "proj": linear_spec(dv, cfg.d_model, ("d_model", None)),
        }
    ff, dm = vc.d_ff, cfg.vit_merge ** 2 * dv
    attn = dict(A.attn_specs(vc, Lv), bo=_bias(dv, Lv))
    return {
        "pos_embed": spec((cfg.n_patches, dv), (None, None), scale=0.02),
        "blocks": {
            "ln1": rmsnorm_spec(dv, Lv), "ln1_b": _bias(dv, Lv),
            "attn": attn,
            "ln2": rmsnorm_spec(dv, Lv), "ln2_b": _bias(dv, Lv),
            "mlp": {"w1": linear_spec(dv, ff, (None, "ff"), Lv),
                    "b1": _bias(ff, Lv),
                    "w2": linear_spec(ff, dv, ("ff", None), Lv),
                    "b2": _bias(dv, Lv)},
        },
        "norm": rmsnorm_spec(dv), "norm_b": _bias(dv),
        "proj": {"ln": rmsnorm_spec(dv), "ln_b": _bias(dv),
                 "w1": linear_spec(dm, dm, (None, None)), "b1": _bias(dm),
                 "w2": linear_spec(dm, cfg.d_model, (None, "d_model")),
                 "b2": _bias(cfg.d_model)},
    }


def layernorm(x, w, b, eps=VIT_LN_EPS):
    """LayerNorm with gain and bias; statistics in float32."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = jnp.square(xf - mu).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def rope_2d(x: jax.Array, rows: jax.Array, cols: jax.Array,
            theta: float = VIT_ROPE_THETA) -> jax.Array:
    """MoonViT's 2D rotary embedding.  x: (B, P, H, D), D a multiple of 4;
    rows, cols: (P,) grid positions.  The head splits into D/2 pairs
    (2i, 2i+1); pair i turns by ``pos * theta ** (-4 * (i // 2) / D)``
    with ``pos`` the row for even i and the column for odd i.  As in
    ``layers.apply_rope``, the pair swap is a dot with a fixed signed
    permutation, exact at HIGHEST precision."""
    dt, D = x.dtype, x.shape[-1]
    freqs = theta ** (-jnp.arange(0, D, 4, dtype=jnp.float32) / D)  # D/4
    pos = jnp.stack([rows, cols], -1).astype(jnp.float32)          # (P, 2)
    ang = (pos[:, None, :] * freqs[None, :, None]).reshape(-1, D // 2)
    ang = jnp.repeat(ang, 2, axis=-1)[None, :, None, :]            # (1,P,1,D)
    rot = jnp.kron(jnp.eye(D // 2, dtype=dt),
                   jnp.array([[0, 1], [-1, 0]], dt))   # (x0, x1) -> (-x1, x0)
    x_rot = jnp.einsum("...d,de->...e", x, rot,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    out = x.astype(jnp.float32) * jnp.cos(ang) + x_rot * jnp.sin(ang)
    return out.astype(dt)


def _siglip_block(vc, pl, h, rows, cols):
    """Pre-LayerNorm block: biased attention with 2D rope, tanh-GELU
    MLP."""
    B, P, _ = h.shape
    a = pl["attn"]
    q, k, v = A._qkv(vc, a, layernorm(h, pl["ln1"], pl["ln1_b"]))
    o = A._sdpa(rope_2d(q, rows, cols),
                rope_2d(k, rows, cols).transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=False)
    h = h + dense(o.reshape(B, P, -1), a["wo"]) + a["bo"].astype(h.dtype)
    m = pl["mlp"]
    z = layernorm(h, pl["ln2"], pl["ln2_b"])
    z = jax.nn.gelu(dense(z, m["w1"]) + m["b1"].astype(h.dtype),
                    approximate=True)
    return h + dense(z, m["w2"]) + m["b2"].astype(h.dtype)


def _merge_project(cfg, p, x):
    """k x k pixel shuffle of the patch grid, then the projector:
    LayerNorm per patch, Linear, GELU, Linear to d_model."""
    B, P, dv = x.shape
    k, side = cfg.vit_merge, int(round(P ** 0.5))
    x = layernorm(x, p["ln"], p["ln_b"])
    x = x.reshape(B, side // k, k, side // k, k, dv).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(B, P // k ** 2, k * k * dv)
    x = jax.nn.gelu(dense(x, p["w1"]) + p["b1"].astype(x.dtype),
                    approximate=False)
    return dense(x, p["w2"]) + p["b2"].astype(x.dtype)


def vit_encode(cfg, p, patches: jax.Array) -> jax.Array:
    """patches: (B, n_patches, vit_dim) -> (B, vit_tokens, d_model)."""
    vit_cfg = _vit_cfg(cfg)
    x = patches.astype(jnp.dtype(cfg.dtype)) + p["pos_embed"].astype(
        jnp.dtype(cfg.dtype))
    positions = jnp.arange(x.shape[1])

    if cfg.vit_block == "siglip":
        side = int(round(x.shape[1] ** 0.5))
        rows, cols = positions // side, positions % side

        def one(pl, h):
            return _siglip_block(vit_cfg, pl, h, rows, cols), None, \
                jnp.float32(0)
    else:
        def one(pl, h):
            a = A.attn_forward(vit_cfg, pl["attn"],
                               rmsnorm(h, pl["ln1"], cfg.norm_eps), positions,
                               causal=False)
            h = h + a
            h = h + mlp(pl["mlp"], rmsnorm(h, pl["ln2"], cfg.norm_eps))
            return h, None, jnp.float32(0)

    x, _, _ = run_stack(vit_cfg, p["blocks"], x, one, cfg.vit_layers,
                        remat=False)
    if cfg.vit_block == "siglip":
        return _merge_project(cfg, p["proj"],
                              layernorm(x, p["norm"], p["norm_b"]))
    x = rmsnorm(x, p["norm"], cfg.norm_eps)
    return dense(x, p["proj"])


# ------------------------------------------------------------- action heads
def action_head_specs(cfg) -> Dict:
    d, a, h = cfg.d_model, cfg.action_dim, cfg.action_horizon
    kind = cfg.vla_action_head
    if kind in ("detok", ""):
        return {}
    if kind == "mlp":
        return {
            "w1": linear_spec(d, 4 * d, ("d_model", "ff")),
            "w2": linear_spec(4 * d, d, ("ff", "d_model")),
            "out": linear_spec(d, a * h, ("d_model", None)),
        }
    if kind == "lstm":
        return {
            "wx": linear_spec(d, 4 * d, ("d_model", "ff")),
            "wh": linear_spec(d, 4 * d, ("d_model", "ff")),
            "b": spec((4 * d,), ("ff",), init="zeros"),
            "out": linear_spec(d, a, ("d_model", None)),
        }
    if kind == "diffusion":  # small conditional denoising MLP
        return {
            "in": linear_spec(a * h + d + 64, d, (None, "d_model")),
            "mid": linear_spec(d, d, ("d_model", None)),
            "out": linear_spec(d, a * h, ("d_model", None)),
        }
    if kind == "dit":
        dd = cfg.dit_dim
        return {
            "x_in": linear_spec(a, dd, (None, None)),
            "cond": linear_spec(d, dd, ("d_model", None)),
            "t_emb": linear_spec(64, dd, (None, None)),
            "blocks": {
                "mod": linear_spec(dd, 6 * dd, (None, None), cfg.dit_layers,
                                   init="zeros"),
                "wq": linear_spec(dd, dd, (None, "q_heads"), cfg.dit_layers),
                "wk": linear_spec(dd, dd, (None, "q_heads"), cfg.dit_layers),
                "wv": linear_spec(dd, dd, (None, "q_heads"), cfg.dit_layers),
                "wo": linear_spec(dd, dd, ("q_heads", None), cfg.dit_layers),
                "w1": linear_spec(dd, 4 * dd, (None, "ff"), cfg.dit_layers),
                "w2": linear_spec(4 * dd, dd, ("ff", None), cfg.dit_layers),
            },
            "final_mod": linear_spec(dd, 2 * dd, (None, None), init="zeros"),
            "out": linear_spec(dd, a, (None, None), init="zeros"),
        }
    raise ValueError(f"unknown action head {kind!r}")


def _timestep_embed(t: jax.Array, dim: int = 64) -> jax.Array:
    half = dim // 2
    freqs = jnp.exp(-jnp.log(10_000.0) * jnp.arange(half) / half)
    ang = t[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], -1)


def _dit_block(cfg, pl, x, cond):
    """x: (B, H, dd); cond: (B, dd). adaLN-zero DiT block."""
    dd = cfg.dit_dim
    nh = cfg.dit_heads
    hd = dd // nh
    m = dense(jax.nn.silu(cond.astype(jnp.float32)).astype(x.dtype),
              pl["mod"])
    sh1, sc1, g1, sh2, sc2, g2 = jnp.split(m[:, None, :], 6, axis=-1)
    h = _ln(x) * (1 + sc1) + sh1
    B, H, _ = x.shape
    q = dense(h, pl["wq"]).reshape(B, H, nh, hd)
    k = dense(h, pl["wk"]).reshape(B, H, nh, hd).transpose(0, 2, 1, 3)
    v = dense(h, pl["wv"]).reshape(B, H, nh, hd).transpose(0, 2, 1, 3)
    o = A._sdpa(q, k, v, causal=False)
    x = x + g1 * dense(o.reshape(B, H, dd), pl["wo"])
    h = _ln(x) * (1 + sc2) + sh2
    x = x + g2 * dense(jax.nn.gelu(dense(h, pl["w1"])), pl["w2"])
    return x


def _ln(x):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype)


def dit_denoise(cfg, p, noisy: jax.Array, t: jax.Array, cognition: jax.Array):
    """noisy: (B, horizon, action_dim); t: (B,); cognition: (B, d_model)."""
    x = dense(noisy.astype(jnp.dtype(cfg.dtype)), p["x_in"])
    cond = dense(cognition, p["cond"]) + dense(
        _timestep_embed(t).astype(jnp.dtype(cfg.dtype)), p["t_emb"])

    def one(pl, h):
        return _dit_block(cfg, pl, h, cond), None, jnp.float32(0)

    x, _, _ = run_stack(cfg, p["blocks"], x, one, cfg.dit_layers, remat=False)
    m = dense(jax.nn.silu(cond.astype(jnp.float32)).astype(x.dtype),
              p["final_mod"])
    sh, sc = jnp.split(m[:, None, :], 2, axis=-1)
    return dense(_ln(x) * (1 + sc) + sh, p["out"])     # predicted noise


def dit_sample(cfg, p, cognition: jax.Array, key: jax.Array) -> jax.Array:
    """DDIM sampling over cfg.diffusion_steps."""
    B = cognition.shape[0]
    a, h = cfg.action_dim, cfg.action_horizon
    x = jax.random.normal(key, (B, h, a), jnp.float32)
    n = cfg.diffusion_steps
    betas = jnp.linspace(1e-4, 0.02, n)
    alphas = jnp.cumprod(1.0 - betas)

    def step(x, i):
        t = n - 1 - i
        ab = alphas[t]
        ab_prev = jnp.where(t > 0, alphas[jnp.maximum(t - 1, 0)], 1.0)
        eps = dit_denoise(cfg, p, x, jnp.full((B,), t), cognition)
        x0 = (x - jnp.sqrt(1 - ab) * eps.astype(jnp.float32)) / jnp.sqrt(ab)
        x = jnp.sqrt(ab_prev) * x0 + jnp.sqrt(1 - ab_prev) * eps.astype(
            jnp.float32)
        return x, None

    x, _ = jax.lax.scan(step, x, jnp.arange(n))
    return x


# ------------------------------------------------------------------ VLA model
def vla_specs(cfg) -> Dict:
    """Tower, embedding, the trunk's layer groups (``transformer._groups``:
    ``blocks``, or ``dense_blocks`` and ``moe_blocks``), head."""
    return {
        "vit": vit_specs(cfg),
        "embed": embed_spec(cfg.vocab_size, cfg.d_model),
        **trunk_specs(cfg),
        "final_norm": rmsnorm_spec(cfg.d_model),
        "head": embed_spec(cfg.vocab_size, cfg.d_model),
        "action": action_head_specs(cfg),
    }


def vla_backbone(cfg, params, patches, tokens, *, remat=False):
    """ViT + LLM over [img ; text] -> hidden states (B, P+S, d)."""
    img = vit_encode(cfg, params["vit"], patches)
    txt = embed(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    x = jnp.concatenate([img, txt], axis=1)
    positions = jnp.arange(x.shape[1])
    for name, n, is_moe in _groups(cfg):
        def one(pl, h, _moe=is_moe):
            h, _, _ = block_forward(cfg, pl, h, positions, is_moe=_moe)
            return h, None, jnp.float32(0)

        x, _, _ = run_stack(cfg, params[name], x, one, n, remat=remat)
    return rmsnorm(x, params["final_norm"], cfg.norm_eps)


def detok_logits(cfg, params, h: jax.Array) -> jax.Array:
    """Detok head: logits (B, action_dim, vocab) at the last
    ``action_dim`` positions of the final-normed hidden states ``h``."""
    return unembed(params["head"], h[:, -cfg.action_dim:], cfg.vocab_size)


def detok_action(logits: jax.Array) -> jax.Array:
    """Greedy action tokens -> action (B, 1, action_dim): 256 uniform
    bins over [-1, 1] at the vocab tail."""
    toks = jnp.argmax(logits, -1)                         # (B, action_dim)
    act = (toks.astype(jnp.float32) % 256) / 127.5 - 1.0
    return act[:, None, :]


def vla_forward(cfg, params, patches, tokens, key=None):
    """Inference: returns action (B, horizon, action_dim)."""
    h = vla_backbone(cfg, params, patches, tokens)
    kind = cfg.vla_action_head
    if kind in ("detok", ""):
        return detok_action(detok_logits(cfg, params, h))
    cog = h[:, -1]                                        # cognition feature
    if kind == "mlp":
        p = params["action"]
        z = jax.nn.gelu(dense(cog, p["w1"]))
        z = jax.nn.gelu(dense(z, p["w2"]))
        return dense(z, p["out"]).reshape(
            -1, cfg.action_horizon, cfg.action_dim)
    if kind == "lstm":
        p = params["action"]
        B, d = cog.shape
        hs = jnp.zeros((B, d), cog.dtype)
        cs = jnp.zeros((B, d), jnp.float32)

        def step(carry, _):
            hs, cs = carry
            g = dense(cog, p["wx"]) + dense(hs, p["wh"]) + p["b"]
            i, f, o, c = jnp.split(g.astype(jnp.float32), 4, -1)
            cs = jax.nn.sigmoid(f) * cs + jax.nn.sigmoid(i) * jnp.tanh(c)
            hs = (jax.nn.sigmoid(o) * jnp.tanh(cs)).astype(cog.dtype)
            return (hs, cs), dense(hs, p["out"])

        _, acts = jax.lax.scan(step, (hs, cs), None, length=cfg.action_horizon)
        return acts.swapaxes(0, 1)
    if kind == "diffusion":
        p = params["action"]
        B = cog.shape[0]
        key = key if key is not None else jax.random.PRNGKey(0)
        x = jax.random.normal(key, (B, cfg.action_horizon * cfg.action_dim))
        n = cfg.diffusion_steps
        for t in range(n - 1, -1, -1):
            te = _timestep_embed(jnp.full((B,), t))
            inp = jnp.concatenate(
                [x.astype(cog.dtype), cog, te.astype(cog.dtype)], -1)
            eps = dense(jax.nn.gelu(dense(jax.nn.gelu(dense(inp, p["in"])),
                                          p["mid"])), p["out"])
            x = x - eps.astype(jnp.float32) / n
        return x.reshape(B, cfg.action_horizon, cfg.action_dim)
    if kind == "dit":
        key = key if key is not None else jax.random.PRNGKey(0)
        return dit_sample(cfg, params["action"], cog, key)
    raise ValueError(kind)


def vla_loss(cfg, params, patches, tokens, action_labels, key) -> jax.Array:
    """Training loss: detok -> xent on binned action tokens; else regression/
    diffusion loss on the action chunk."""
    h = vla_backbone(cfg, params, patches, tokens, remat=cfg.remat)
    kind = cfg.vla_action_head
    if kind in ("detok", ""):
        logits = detok_logits(cfg, params, h)
        bins = jnp.clip(((action_labels[:, 0] + 1) * 127.5), 0, 255).astype(
            jnp.int32)
        return softmax_xent(logits, bins)
    cog = h[:, -1]
    if kind == "dit":
        p = params["action"]
        B = cog.shape[0]
        k1, k2 = jax.random.split(key)
        t = jax.random.randint(k1, (B,), 0, cfg.diffusion_steps)
        noise = jax.random.normal(k2, action_labels.shape)
        betas = jnp.linspace(1e-4, 0.02, cfg.diffusion_steps)
        ab = jnp.cumprod(1.0 - betas)[t][:, None, None]
        noisy = jnp.sqrt(ab) * action_labels + jnp.sqrt(1 - ab) * noise
        eps = dit_denoise(cfg, p, noisy, t, cog)
        return jnp.mean((eps.astype(jnp.float32) - noise) ** 2)
    pred = vla_forward(cfg, params, patches, tokens, key)
    return jnp.mean((pred.astype(jnp.float32)
                     - action_labels.astype(jnp.float32)) ** 2)
