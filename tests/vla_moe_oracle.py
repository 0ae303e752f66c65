"""Float32 oracle of the MoE + MLA VLA (kimi-vl-a3b-vla) for the CPU
tests: the architecture written down again in plain ``jax.numpy``, from
the parameter tree the program builds, with nothing of the program's
code.  The chip's reference (``perfbench/references/vla_moe.py``) holds
the same mathematics in blocks that fit beside the served weights.

``forward(cfg, params, patches, tokens, split)`` returns the cut after
trunk block ``split - vit_layers`` (through the int8 codec: per row and
128-lane block, scale ``amax / 127``) and the detok logits at the last
``action_dim`` positions.
"""
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def f32(x):
    return jnp.asarray(x, jnp.float32)


def mm(x, w):
    return jnp.einsum("...d,df->...f", f32(x), f32(w), precision=HI)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * f32(w)


def layernorm(x, w, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * f32(w) + f32(b)


def rope_half(x, theta):
    """Rotate-half rope over axis 1 positions; x: (B, S, H, D)."""
    S, D = x.shape[1], x.shape[-1]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def rope_grid(x, side, theta=10_000.0):
    """Pairs (2i, 2i+1) turn by pos * theta^(-4 (i // 2) / D), pos the
    patch's row for even i, its column for odd i."""
    P, D = x.shape[1], x.shape[-1]
    i = jnp.arange(D // 2)
    row, col = jnp.arange(P) // side, jnp.arange(P) % side
    pos = jnp.where(i % 2 == 0, row[:, None], col[:, None])       # (P, D/2)
    ang = (pos * theta ** (-4.0 * (i // 2) / D))[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     b * jnp.cos(ang) + a * jnp.sin(ang)], -1)
    return out.reshape(x.shape)


def attention(q, k, v, causal):
    s = jnp.einsum("bshd,bthd->bhst", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    if causal:
        S = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(s, -1), v,
                      precision=HI)


def tower(cfg, p, patches):
    x = f32(patches) + f32(p["pos_embed"])
    B, P, dv = x.shape
    H, side = cfg.vit_heads, math.isqrt(P)
    for i in range(cfg.vit_layers):
        b = jax.tree_util.tree_map(lambda w: w[i], p["blocks"])
        a = b["attn"]
        z = layernorm(x, b["ln1"], b["ln1_b"])
        q, k, v = (mm(z, a["w" + n]) + f32(a["b" + n]) for n in "qkv")
        q, k, v = (t.reshape(B, P, H, -1) for t in (q, k, v))
        o = attention(rope_grid(q, side), rope_grid(k, side), v, False)
        x = x + mm(o.reshape(B, P, dv), a["wo"]) + f32(a["bo"])
        z = layernorm(x, b["ln2"], b["ln2_b"])
        z = jax.nn.gelu(mm(z, b["mlp"]["w1"]) + f32(b["mlp"]["b1"]),
                        approximate=True)
        x = x + mm(z, b["mlp"]["w2"]) + f32(b["mlp"]["b2"])
    pr = p["proj"]
    x = layernorm(layernorm(x, p["norm"], p["norm_b"]), pr["ln"], pr["ln_b"])
    k = cfg.vit_merge
    x = x.reshape(B, side // k, k, side // k, k, dv).transpose(
        0, 1, 3, 2, 4, 5).reshape(B, P // k ** 2, k * k * dv)
    x = jax.nn.gelu(mm(x, pr["w1"]) + f32(pr["b1"]), approximate=False)
    return mm(x, pr["w2"]) + f32(pr["b2"])


def mla(cfg, a, h):
    B, S, _ = h.shape
    H, r, qn, qr = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim, \
        cfg.qk_rope_dim
    q = mm(h, a["wq"]).reshape(B, S, H, qn + qr)
    kv = mm(h, a["wkv_a"])
    c = rmsnorm(kv[..., :r], a["kv_norm"], cfg.norm_eps)
    k_pe = rope_half(kv[..., None, r:], cfg.rope_theta)
    q = jnp.concatenate([q[..., :qn], rope_half(q[..., qn:],
                                                cfg.rope_theta)], -1)
    k = jnp.concatenate([mm(c, a["wk_b"]).reshape(B, S, H, qn),
                         jnp.broadcast_to(k_pe, (B, S, H, qr))], -1)
    v = mm(c, a["wv_b"]).reshape(B, S, H, -1)
    return mm(attention(q, k, v, True).reshape(B, S, -1), a["wo"])


def swiglu(p, h):
    return mm(jax.nn.silu(mm(h, p["wg"])) * mm(h, p["wu"]), p["wd"])


def route(cfg, p, h):
    """Gates over all experts (zero where not chosen)."""
    s = jax.nn.sigmoid(mm(h, p["router"]))
    _, top = jax.lax.top_k(s + f32(p["e_score_correction_bias"]),
                           cfg.moe_top_k)
    chosen = jnp.sum(jax.nn.one_hot(top, cfg.n_experts), -2)
    return cfg.moe_routed_scale * s * chosen \
        / jnp.sum(s * chosen, -1, keepdims=True)


def moe(cfg, p, h, e0, held):
    """Experts [e0, e0 + held) of the routed part, plus the shared
    experts; the expert tables hold exactly those experts."""
    g = route(cfg, p, h)[..., e0:e0 + held]
    y = sum(g[..., e:e + 1] * swiglu({n: p[n][e] for n in ("wg", "wu", "wd")},
                                     h) for e in range(held))
    return y + swiglu(p["shared"], h)


def block(cfg, p, x, is_moe):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    x = x + mla(cfg, p["attn"], h)
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    if not is_moe:
        return x + swiglu(p["mlp"], h)
    return x + moe(cfg, p["moe"], h, cfg.expert_start, cfg.n_experts_held)


def trunk_layer(cfg, params, x, i):
    nd = cfg.first_dense_layers
    name, j = ("dense_blocks", i) if i < nd else ("moe_blocks", i - nd)
    p = jax.tree_util.tree_map(lambda w: w[j], params[name])
    return block(cfg, p, x, i >= nd)


def int8_roundtrip(x):
    *lead, D = x.shape
    xb = x.reshape(*lead, D // 128, 128)
    s = jnp.max(jnp.abs(xb), -1, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return (jnp.clip(jnp.round(xb / s), -127, 127) * s).reshape(x.shape)


def forward(cfg, params, patches, tokens, split):
    x = jnp.concatenate([tower(cfg, params["vit"], patches),
                         f32(params["embed"][tokens])], 1)
    e = split - cfg.vit_layers
    for i in range(e):
        x = trunk_layer(cfg, params, x, i)
    cut = int8_roundtrip(x)
    x = cut
    for i in range(e, cfg.n_layers):
        x = trunk_layer(cfg, params, x, i)
    h = rmsnorm(x[:, -cfg.action_dim:], params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", h,
                        f32(params["head"][:cfg.vocab_size]), precision=HI)
    return cut, logits
