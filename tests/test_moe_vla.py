"""kimi-vl-a3b-vla at CPU size against the float32 oracle
(``tests/vla_moe_oracle.py``): the split executor at every cut, the
expert shares of a MoE layer, dropless routing under forced skew, the
DeepSeek-V3 gates, the dense stand-ins unchanged, and the planner's
graph of a MoE VLA trunk.

The executor runs here in float32, so it and the oracle differ only in
the order of their sums (~1e-6 relative).  Each tolerance below is a few
times that: with the executor in bfloat16, 44 % of the cut's values
miss it and the first test fails.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import vla_moe_oracle as O
from repro.configs import get_config
from repro.models import build
from repro.models import moe as M
from repro.runtime.partition import SplitPlan, VLASplitExecutor

# the published structure at CPU widths: dense layer 0 then MoE layers,
# MLA, a SigLIP-shaped tower with 2D rope and a 2x2 merge; experts
# [4, 8) of 16 held, top-4, two shared experts
SMALL = dict(n_layers=3, d_model=128, n_heads=4, n_kv_heads=4,
             kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
             d_ff=192, vocab_size=512, n_experts=16, experts_held=4,
             expert_start=4, moe_top_k=4, moe_d_ff=48, vit_layers=2,
             vit_dim=64, vit_heads=4, vit_ff=96, n_patches=16,
             dtype="float32")
TEXT = 5


def _cfg(**kw):
    return get_config("kimi-vl-a3b-vla").replace(**dict(SMALL, **kw))


def _params(cfg, seed=0):
    """The program's layout with every leaf jittered, so zero-initialized
    biases and the selection bias take part."""
    p = build(cfg).init(jax.random.PRNGKey(seed))
    leaves, tree = jax.tree_util.tree_flatten(p)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        w + 0.05 * jax.random.normal(k, w.shape, w.dtype)
        for w, k in zip(leaves, keys)])


def _inputs(cfg, batch=2):
    kp, kt = jax.random.split(jax.random.PRNGKey(7))
    return (jax.random.normal(kp, (batch, cfg.n_patches, cfg.vit_dim)),
            jax.random.randint(kt, (batch, TEXT), 0, cfg.vocab_size))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) / np.max(np.abs(b))


def test_split_executor_matches_the_oracle_at_every_cut():
    cfg = _cfg()
    params = _params(cfg)
    patches, tokens = _inputs(cfg)
    Lv, L = cfg.vit_layers, cfg.n_layers
    ex = VLASplitExecutor(cfg, SplitPlan(Lv, Lv + L, codec="int8"))
    for split in range(Lv, Lv + L + 1):
        _, logits, payload = ex.run(params, patches, tokens, split,
                                    return_logits=True)
        q, s = np.asarray(payload["q"], np.float64), np.asarray(payload["s"])
        cut = (q.reshape(*s.shape, 128) * s[..., None]).reshape(q.shape)
        want_cut, want_logits = O.forward(cfg, params, patches, tokens,
                                          split)
        want_cut = np.asarray(want_cut, np.float64)
        # the int8 codes agree but where a value sits within the sums'
        # rounding of a code boundary: one code (amax / 127) apart, rare
        off = np.abs(cut - want_cut) > 1e-5 * np.max(np.abs(want_cut))
        step = np.repeat(s, 128, axis=-1).reshape(cut.shape)
        assert off.mean() < 1e-3, (split, off.mean())
        assert np.all(np.abs(cut - want_cut)[off] <= 1.01 * step[off])
        # a code one step apart moves a logit by ~1e-4 of the largest
        assert _rel(logits[..., :cfg.vocab_size], want_logits) < 1e-4, split


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of a MoE layer, the shared experts counted
    once, give what the layer with every expert held gives."""
    whole = _cfg(experts_held=0, expert_start=0)
    p = jax.tree_util.tree_map(lambda w: w[0],
                               _params(whole)["moe_blocks"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, whole.d_model))
    full, _ = M.dropless_moe_ffn(whole, p, x)
    shared = O.swiglu(p["shared"], x)
    E, n = whole.n_experts, 4
    parts = []
    for c in range(n):
        cfg = whole.replace(experts_held=E // n, expert_start=c * E // n)
        mine = dict(p, **{k: p[k][c * E // n:(c + 1) * E // n]
                          for k in M.EXPERT_WEIGHTS})
        y, counts = M.dropless_moe_ffn(cfg, mine, x)
        assert int(counts[2]) == 0
        parts.append(np.asarray(y) - np.asarray(shared))
    assert _rel(sum(parts) + shared, full) < 1e-5
    # and the uncut layer is the oracle's
    assert _rel(full, O.moe(whole, p, x, 0, E)) < 1e-5


def test_forced_skew_drops_nothing():
    """Every token chooses the same experts, so one held expert takes all
    of them, far past a tile: nothing is dropped and the output is the
    oracle's."""
    cfg = _cfg(moe_top_k=2)
    p = jax.tree_util.tree_map(lambda w: w[0],
                               _params(cfg)["moe_blocks"])["moe"]
    bias = jnp.zeros((cfg.n_experts,)).at[jnp.array([5, 12])].set(50.0)
    p = dict(p, e_score_correction_bias=bias)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 40, cfg.d_model))
    y, counts = M.dropless_moe_ffn(cfg, p, x)
    T = 3 * 40
    # expert 5 is held ([4, 8)), 12 is not: one held expert reached
    assert [int(c) for c in counts] == [T, T, 0, 1]
    assert T > 3 * M.ROWS_PER_TILE
    assert _rel(y, O.moe(cfg, p, x, cfg.expert_start, 4)) < 1e-5


def test_v3_gates_by_hand():
    """Sigmoid scores; the bias picks the experts but does not weigh
    them; gates normalised over the chosen and scaled by 2.446."""
    cfg = get_config("kimi-vl-a3b-vla")
    assert (cfg.moe_scoring, cfg.moe_routed_scale, cfg.moe_top_k) \
        == ("sigmoid", 2.446, 6)
    x = jax.random.normal(jax.random.PRNGKey(5), (6, 32))
    router = jax.random.normal(jax.random.PRNGKey(6), (32, 64)) / 6
    bias = jnp.zeros((64,)).at[7].set(5.0)          # expert 7 always in
    gates, eids = M._route_sigmoid(x, router, bias, 6, 2.446)
    s = np.asarray(jax.nn.sigmoid(x @ router), np.float64)
    for t in range(6):
        top = np.argsort(-(s[t] + np.asarray(bias)))[:6]
        assert set(np.asarray(eids[t])) == set(top) and 7 in top
        want = 2.446 * s[t, np.asarray(eids[t])] / s[t, top].sum()
        np.testing.assert_allclose(np.asarray(gates[t]), want, rtol=1e-5)
        np.testing.assert_allclose(float(gates[t].sum()), 2.446, rtol=1e-5)


def test_dispatch_counts_come_back_on_request():
    cfg = _cfg()
    params = _params(cfg)
    patches, tokens = _inputs(cfg, batch=1)
    Lv = cfg.vit_layers
    ex = VLASplitExecutor(cfg, SplitPlan(Lv, Lv + cfg.n_layers,
                                         codec="int8"))
    action, logits, payload, counts = ex.run(
        params, patches, tokens, Lv + 2, return_logits=True,
        return_counts=True)
    plain = ex.run(params, patches, tokens, Lv + 2, return_logits=True)
    np.testing.assert_array_equal(np.asarray(plain[1]), np.asarray(logits))
    assert set(counts) == set(M.COUNTS) and int(counts["dropped"]) == 0
    # the oracle's choices: pairs routed to held experts, over both MoE
    # layers on either side of the cut
    x = O.f32(jnp.concatenate([O.tower(cfg, params["vit"], patches),
                               O.f32(params["embed"][tokens])], 1))
    routed, load, reached = 0, 0, 0
    for i in range(cfg.n_layers):
        if i == 2:
            x = O.int8_roundtrip(x)                  # the cut at Lv + 2
        if i >= cfg.first_dense_layers:
            p = jax.tree_util.tree_map(lambda w: w[i - 1],
                                       params["moe_blocks"])
            h = O.rmsnorm(x + O.mla(cfg, p["attn"], O.rmsnorm(
                x, p["ln1"], cfg.norm_eps)), p["ln2"], cfg.norm_eps)
            held = np.asarray(O.route(cfg, p["moe"], h))[..., 4:8] > 0
            routed += int(held.sum())
            load = max(load, int(held.sum(axis=(0, 1)).max()))
            reached += int((held.sum(axis=(0, 1)) > 0).sum())
        x = O.trunk_layer(cfg, params, x, i)
    assert int(counts["routed"]) == routed and int(counts["max_load"]) == load
    assert int(counts["reached"]) == reached


@pytest.mark.parametrize("arch", ["openvla-7b", "cogact-7b"])
def test_dense_standins_run_as_before(arch):
    """The stand-ins' tower and trunk, through the generalised code, give
    bit for bit what the code before it gave (written out here)."""
    from repro.models import attention as A
    from repro.models import vla as V
    from repro.models.layers import dense, mlp, rmsnorm
    from repro.models.transformer import run_stack
    from repro.runtime.partition import _run_blocks
    cfg = get_config(arch).reduced()
    params = build(cfg).init(jax.random.PRNGKey(0))
    patches = jax.random.normal(jax.random.PRNGKey(1),
                                (1, cfg.n_patches, cfg.vit_dim))

    def old_tower(p):
        dv = cfg.vit_dim
        hd = min(64, dv)
        vc = cfg.replace(d_model=dv, n_heads=dv // hd, n_kv_heads=dv // hd,
                         head_dim=hd, d_ff=4 * dv, causal=False,
                         use_mla=False, parallel_block=False, qkv_bias=False)
        x = patches.astype(jnp.bfloat16) + p["pos_embed"]
        pos = jnp.arange(x.shape[1])

        def one(pl, h):
            h = h + A.attn_forward(vc, pl["attn"], rmsnorm(
                h, pl["ln1"], cfg.norm_eps), pos, causal=False)
            return h + mlp(pl["mlp"], rmsnorm(h, pl["ln2"], cfg.norm_eps)), \
                None, jnp.float32(0)
        x, _, _ = run_stack(vc, p["blocks"], x, one, cfg.vit_layers,
                            remat=False)
        return dense(rmsnorm(x, p["norm"], cfg.norm_eps), p["proj"])

    tower = jax.jit(lambda p: V.vit_encode(cfg, p, patches))(params["vit"])
    np.testing.assert_array_equal(np.asarray(tower, np.float32), np.asarray(
        jax.jit(old_tower)(params["vit"]), np.float32))
    Lv, L = cfg.vit_layers, cfg.n_layers
    ex = VLASplitExecutor(cfg, SplitPlan(Lv, Lv + L))
    x = jnp.concatenate([tower, params["embed"][jnp.zeros((1, 5), int)]], 1)
    pos = jnp.arange(x.shape[1])
    for lo, hi in ((Lv, Lv + 1), (Lv + 1, Lv + L), (Lv, Lv + L)):
        got = jax.jit(lambda a, b: ex._span(params, x, pos, a, b))(lo, hi)
        old = jax.jit(lambda a, b: _run_blocks(
            cfg, params["blocks"], x, pos, a - Lv, b - Lv,
            is_moe=False))(lo, hi)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(old, np.float32))


def test_planner_prices_the_standins_as_before():
    """Graph totals and Alg. 1 splits and pools of the two stand-ins, as
    planned before the MoE VLA trunk existed."""
    from repro.core.structure import build_graph, total_flops, \
        total_weight_bytes
    from repro.launch import serve
    want = {"openvla-7b": (58, 13827047424.0, 531084345344.0, (27, 26, 27)),
            "cogact-7b": (69, 13819183104.0, 570108674048.0, (26, 25, 26))}
    for arch, (n, wb, fl, plan) in want.items():
        cfg = get_config(arch)
        g = build_graph(cfg)
        assert (len(g), total_weight_bytes(g), total_flops(g)) == (n, wb, fl)
        ctl, _ = serve.build_controller(cfg, "int8", predictor_epochs=0)
        assert (ctl.split, ctl.pool.start, ctl.pool.end) == plan


def test_planner_prices_a_held_moe_block_by_hand():
    """One MoE block of kimi-vl-a3b-vla at 16 of 64 experts: MLA, the
    router and its bias, two shared and sixteen held experts; FLOPs at
    the routed share k * held / E of the rows."""
    from repro.core.structure import Workload, build_graph
    cfg = get_config("kimi-vl-a3b-vla").replace(experts_held=16)
    w = Workload(decode_steps=0)
    S, T = w.s_new, w.s_ctx
    g = build_graph(cfg, w)
    moe = [c for c in g if c.kind == "moe"]
    assert len(moe) == 26 and g[cfg.vit_layers + 1].kind == "llm"
    d, H, r = 2048, 16, 512
    mla_w = d * H * 192 + d * (r + 64) + r * H * 256 + H * 128 * d
    ffn_w = 18 * 3 * d * 1408 + d * 64 + 64
    assert moe[0].weight_bytes == 2 * (mla_w + ffn_w)
    mla_f = 2 * S * mla_w + 2 * S * T * H * 192 + 2 * S * T * H * 128
    ffn_f = 2 * S * d * 64 + 2 * S * 3 * d * 1408 * (6 * 16 / 64 + 2)
    assert moe[0].flops == pytest.approx(mla_f + ffn_f, rel=1e-12)
    # the graph holds what one chip of the deployment holds, but for the
    # embedding table and the norms
    assert sum(c.weight_bytes for c in g) + 2 * 163840 * d \
        == pytest.approx(2 * cfg.n_params(), rel=1e-3)
