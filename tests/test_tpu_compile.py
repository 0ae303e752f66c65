"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

Nothing runs: each test lowers a kernel at the widths the served models
use and compiles it with the TPU compiler for a chip that is described,
not attached — which refuses block shapes not aligned to the tiling, and
kernels that need more fast memory than a core has, exactly as the chip
would.  The topology is described inside a module-scoped fixture (never
at import), and the persistent compilation cache is off around the
compiles, since entries written for a described chip cannot be read back
without one.
"""
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.activation_codec import ops as codec_ops
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.ssd_scan import ops as ssd_ops

CUT = (273, 4096)        # one OpenVLA request's cut: 256 patches + 17 tokens


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure: cannot describe
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("name", ["int8_quantize", "int8_dequantize",
                                  "int4_quantize", "int4_dequantize"])
def test_codec_kernels_compile_at_cut_shape(one_chip, name):
    R, D = CUT
    nb = D // 128
    cases = {
        "int8_quantize": (lambda x: codec_ops.quantize(x, impl="pallas"),
                          [((1, R, D), jnp.bfloat16)]),
        "int8_dequantize": (
            lambda q, s: codec_ops.dequantize(q, s, impl="pallas"),
            [((1, R, D), jnp.int8), ((1, R, nb), jnp.float32)]),
        "int4_quantize": (
            lambda x: codec_ops.quantize_int4(x, impl="pallas"),
            [((1, R, D), jnp.bfloat16)]),
        "int4_dequantize": (
            lambda p, s: codec_ops.dequantize_int4(p, s, impl="pallas"),
            [((1, R, D // 2), jnp.int8), ((1, R, nb), jnp.float32)]),
    }
    fn, shapes = cases[name]
    _compile(fn, one_chip, *shapes)


def test_flash_attention_compiles_32_heads(one_chip):
    shape = ((1, 512, 32, 128), jnp.bfloat16)
    _compile(lambda q, k, v: fa_ops.flash_attention(q, k, v, impl="pallas"),
             one_chip, shape, shape, shape)


def test_decode_attention_compiles_2k_cache(one_chip):
    kv = ((1, 32, 2048, 128), jnp.bfloat16)
    _compile(lambda q, k, v, n: da_ops.decode_attention(q, k, v, n,
                                                        impl="pallas"),
             one_chip, ((1, 32, 128), jnp.bfloat16), kv, kv,
             ((), jnp.int32))


def test_ssd_scan_compiles_mamba2_widths(one_chip):
    # mamba2-1.3b: d_inner 4096 = 64 heads x 64, state 128, chunk 256
    B, T, H, P, N = 1, 512, 64, 64, 128
    _compile(lambda x, dt, A, Bm, Cm: ssd_ops.ssd_scan(
        x, dt, A, Bm, Cm, chunk=256, impl="pallas"), one_chip,
        ((B, T, H, P), jnp.bfloat16), ((B, T, H), jnp.float32),
        ((H,), jnp.float32), ((B, T, N), jnp.bfloat16),
        ((B, T, N), jnp.bfloat16))


def test_openvla_split_programs_fit_one_chip(one_chip):
    """The served openvla-7b edge and cloud programs (int8 cut, published
    widths and depth) compile for one v5e and fit its memory next to the
    resident parameters, with temporaries far below one layer's weights
    (a loop that copied weight slices or stacks would show here)."""
    from repro.configs import get_config
    from repro.models import build
    from repro.models.sharding import shape_tree
    from repro.runtime.partition import SplitPlan, VLASplitExecutor

    cfg = get_config("openvla-7b")
    Lv, R = cfg.vit_layers, cfg.n_patches + 17
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shape_tree(build(cfg).param_specs))
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(params))
    ex = VLASplitExecutor(cfg, SplitPlan(Lv + 1, Lv + 2, codec="int8"))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    split = sds((), jnp.int32)
    edge = ex._edge.lower(params, sds((1, cfg.n_patches, cfg.vit_dim),
                                      jnp.bfloat16),
                          sds((1, 17), jnp.int32), split).compile()
    cloud = ex._cloud.lower(
        params, {"q": sds((1, R, cfg.d_model), jnp.int8),
                 "s": sds((1, R, cfg.d_model // 128), jnp.float32)},
        split, sds((2,), jnp.uint32)).compile()
    layer_bytes = 2 * (4 * cfg.d_model ** 2 + 3 * cfg.d_model * cfg.d_ff)
    bytes_limit = 16_909_336_064        # what one v5e's memory_stats reports
    for prog in (edge, cloud):
        m = prog.memory_analysis()
        assert m.temp_size_in_bytes < layer_bytes / 4
        assert param_bytes + m.temp_size_in_bytes \
            + m.output_size_in_bytes < bytes_limit


@pytest.mark.parametrize("case", ["trunk_b1", "trunk_b4", "vit"])
def test_layer_loops_read_stacked_weights_in_place(one_chip, case):
    """Every projection dot in a layer loop reads its layer of the stacked
    weights in place, the index fused into the dot: no step writes a
    slice, copy or relayout of a weight.  Openvla-7b's trunk at published
    widths and depth (one robot's 273 rows, and four robots'), and its
    1024-wide ViT tower.  At a depth of two the stacks fit the chip's
    vector memory and the compiler prefetches them, which a published
    depth never allows, so the loops keep their depth (it costs no
    compile time: the body compiles once)."""
    from repro.configs import get_config
    from repro.launch.hlo_analysis import (loop_stack_readers,
                                           loop_weight_copies)
    from repro.models import vla as V
    from repro.models.sharding import shape_tree
    from repro.models.transformer import dense_block_specs
    from repro.runtime.partition import _run_blocks

    cfg = get_config("openvla-7b")
    R = cfg.n_patches + 17

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if case == "vit":
        weights = shape_tree(V.vit_specs(cfg))
        fn = lambda w, x: V.vit_encode(cfg, w, x)
        args = (sds((1, cfg.n_patches, cfg.vit_dim)),)
    else:
        B = 1 if case == "trunk_b1" else 4
        weights = shape_tree(dense_block_specs(cfg, cfg.n_layers))
        fn = lambda w, x, lo: _run_blocks(cfg, w, x, jnp.arange(R), lo,
                                          cfg.n_layers, is_moe=False)
        args = (sds((B, R, cfg.d_model)), sds((), jnp.int32))
    weights = jax.tree_util.tree_map(lambda s: sds(s.shape, s.dtype), weights)
    text = jax.jit(fn).lower(weights, *args).compile().as_text()
    stacks = [w.shape for w in jax.tree_util.tree_leaves(weights)
              if w.ndim == 3]
    assert loop_weight_copies(text, stacks) == []
    readers = loop_stack_readers(text, stacks)
    assert len(readers) >= 6
    assert {kind for _, kind in readers} == {"kOutput"}, readers


def _kimi_chip_share():
    """kimi-vl-a3b-vla as its benchmark cell serves it: experts [0, 16) of
    64 held, published widths and depth, the grouped matmuls in the
    Pallas kernel as the served path runs them on a TPU."""
    from repro.configs import get_config
    return get_config("kimi-vl-a3b-vla").replace(experts_held=16,
                                                 moe_impl="pallas")


def test_grouped_matmul_compiles_at_expert_widths(one_chip):
    """The held experts' kernel for one call's rows (273 x 6 pairs, each
    expert's rows on whole tiles) against the 26-layer stacks, read at a
    traced layer."""
    from repro.kernels.grouped_matmul import kernel as gk
    from repro.models.moe import ROWS_PER_TILE as tm
    M = -(-(273 * 6 + 16 * (tm - 1)) // tm) * tm
    for K, N in ((2048, 1408), (1408, 2048)):
        _compile(lambda x, w, g, i: gk.grouped_matmul_pallas(
            x, w, g, tm=tm, layer=i), one_chip, ((M, K), jnp.bfloat16),
            ((26, 16, K, N), jnp.bfloat16), ((16,), jnp.int32),
            ((), jnp.int32))


def test_routed_ffn_compiles_at_expert_widths(one_chip):
    """The routed kernel for one robot's 273 rows at kimi-vl-a3b-vla's
    widths against the 26-layer stacks of 16 held experts, read at a
    traced layer: within its vector-memory budget, which the compile
    holds it to."""
    from repro.kernels.grouped_matmul import kernel as gk
    from repro.models.moe import _tables
    T, d, f, E = 273, 2048, 1408, 16
    assert gk.routed_ffn_fits(T, d, f, 2)
    assert not gk.routed_ffn_fits(4 * T, d, f, 2)    # four robots' rows
    tm = gk.ROUTED_TILE

    def layer(x, gates, eids, wg, wu, wd, i):
        rank, gate, tiles, source, _ = _tables(gates, eids, 0, E, tm)
        return gk.routed_ffn_pallas(x, rank, gate, tiles, source, wg, wu,
                                    wd, tm=tm, layer=i)

    _compile(layer, one_chip, ((T, d), jnp.bfloat16),
             ((T, 6), jnp.float32), ((T, 6), jnp.int32),
             ((26, E, d, f), jnp.bfloat16), ((26, E, d, f), jnp.bfloat16),
             ((26, E, f, d), jnp.bfloat16), ((), jnp.int32))


def test_kimi_moe_loop_runs_one_routed_kernel(one_chip):
    """kimi-vl-a3b-vla's MoE layer loop at batch 1, as served on a TPU:
    from the routing to the routed sum, each layer runs one routed-kernel
    call and at most 11 other operations under ``experts``, none of them a
    gather or scatter; no operation outside ``experts`` fuses an
    instruction named under it (the benchmark files such a fusion, the
    attention's or shared experts' dots among them, under ``experts``);
    and nothing in the loop writes more rows of the model's or an
    expert's width than the layer has tokens."""
    import re
    from repro.launch.hlo_analysis import computations, while_bodies
    from repro.models import moe as M
    from repro.models import vla as V
    from repro.models.sharding import shape_tree
    from repro.models.transformer import trunk_specs
    from repro.runtime.partition import _run_blocks

    cfg = _kimi_chip_share()
    R = V.vit_tokens(cfg) + 17

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    weights = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype),
        shape_tree(trunk_specs(cfg)["moe_blocks"]))
    n = weights["ln1"].shape[0]
    fn = lambda w, x, lo: _run_blocks(cfg, w, x, jnp.arange(R), lo, n,
                                      is_moe=True, counts=M.no_counts())
    text = jax.jit(fn).lower(weights, sds((1, R, cfg.d_model)),
                             sds((), jnp.int32)).compile().as_text()
    (body,) = while_bodies(text).values()
    plumbing = ("bitcast", "get-tuple-element", "tuple", "parameter",
                "constant")
    ops = [(name, op, result, re.search(r'op_name="([^"]*)"', attrs))
           for name, result, op, _, attrs in body if op not in plumbing]
    experts = [(name, op, m.group(1)) for name, op, _, m in ops
               if m and f"/{M.EXPERTS}/" in m.group(1)]
    comps = computations(text)

    def fused(attrs):
        for c in re.findall(r"calls=%([\w.\-]+)", attrs):
            for _, _, _, _, inner in comps.get(c, []):
                yield inner
                yield from fused(inner)

    named = {e[0] for e in experts}
    leaks = [name for name, _, _, _, attrs in body if name not in named
             and any(f"/{M.EXPERTS}/" in a for a in fused(attrs))]
    assert not leaks, leaks
    kernels = [name for name, op, where in experts if op == "custom-call"]
    assert len(kernels) == 1, kernels
    assert [where for name, op, where in experts if op == "custom-call"
            ][0].endswith("jit(routed_ffn)/pallas_call")
    assert len(experts) <= 12, experts
    assert not [e for e in experts if "gather" in e[2] or "scatter" in e[2]]
    for name, _, result, _ in ops:
        dims = [int(x) for x in re.search(r"\[([\d,]*)\]", result).group(1)
                .split(",") if x]
        if dims and dims[-1] in (cfg.d_model, cfg.moe_d_ff):
            assert math.prod(dims[:-1]) <= R, (name, result)


@pytest.mark.parametrize("case", ["dense", "moe", "vit"])
def test_moe_vla_loops_read_stacked_weights_in_place(one_chip, case):
    """kimi-vl-a3b-vla's layer loops at batch 1: the dense layer 0 and the
    26 MoE layers (MLA; router, shared experts and the held experts'
    grouped-matmul kernel) and the SigLIP-shaped tower read every stacked
    weight in place, by a dot fusion or the kernel, with no per-step
    copy.  The dense group is one layer deep, its stacks one layer each:
    the compiler may prefetch them into vector memory (an asynchronous
    copy into memory space 1), which is their one read, not a copy."""
    from repro.launch.hlo_analysis import (loop_stack_readers,
                                           loop_weight_copies)
    from repro.models import moe as M
    from repro.models import vla as V
    from repro.models.sharding import shape_tree
    from repro.models.transformer import trunk_specs
    from repro.runtime.partition import _run_blocks

    cfg = _kimi_chip_share()
    R = V.vit_tokens(cfg) + 17

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if case == "vit":
        weights = shape_tree(V.vit_specs(cfg))
        fn = lambda w, x: V.vit_encode(cfg, w, x)
        args = (sds((1, cfg.n_patches, cfg.vit_dim)),)
    else:
        name = {"dense": "dense_blocks", "moe": "moe_blocks"}[case]
        weights = shape_tree(trunk_specs(cfg)[name])
        n = weights["ln1"].shape[0]
        fn = lambda w, x, lo: _run_blocks(
            cfg, w, x, jnp.arange(R), lo, n, is_moe=case == "moe",
            counts=M.no_counts() if case == "moe" else None)
        args = (sds((1, R, cfg.d_model)), sds((), jnp.int32))
    weights = jax.tree_util.tree_map(lambda s: sds(s.shape, s.dtype), weights)
    text = jax.jit(fn).lower(weights, *args).compile().as_text()
    stacks = [w.shape for w in jax.tree_util.tree_leaves(weights)
              if w.ndim >= 3]
    copies = loop_weight_copies(text, stacks)
    if case == "dense":
        starts = {line.split(" = ")[0].strip().lstrip("%"): line
                  for line in text.splitlines() if " copy-start(" in line}
        copies = [c for c in copies if not (
            c.startswith("copy-done") or "S(1)" in starts.get(c, "")
            .split(" copy-start(")[0].split(", ")[0])]
    assert copies == []
    kinds = {kind for _, kind in loop_stack_readers(text, stacks)}
    if case == "moe":
        assert kinds == {"kOutput", "custom-call"}, kinds
        # the kernel sits in the loop body itself, not in a branch of a
        # platform switch
        assert " conditional(" not in text
    elif case == "vit":
        assert kinds == {"kOutput"}, kinds


def test_kimi_split_programs_fit_one_chip(one_chip):
    """The served kimi-vl-a3b-vla edge and cloud programs at the chip's
    share (11.2 GB of weights) compile for one v5e and fit its memory,
    with temporaries far below one MoE layer's held experts."""
    from repro.models import build
    from repro.models import vla as V
    from repro.models.sharding import shape_tree
    from repro.runtime.partition import SplitPlan, VLASplitExecutor

    cfg = _kimi_chip_share()
    Lv, R = cfg.vit_layers, V.vit_tokens(cfg) + 17
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shape_tree(build(cfg).param_specs))
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(params))
    assert 11.0e9 < param_bytes < 11.4e9
    ex = VLASplitExecutor(cfg, SplitPlan(Lv, Lv + 2, codec="int8"))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    split = sds((), jnp.int32)
    edge = ex._edge.lower(params, sds((1, cfg.n_patches, cfg.vit_dim),
                                      jnp.bfloat16),
                          sds((1, 17), jnp.int32), split).compile()
    cloud = ex._cloud.lower(
        params, {"q": sds((1, R, cfg.d_model), jnp.int8),
                 "s": sds((1, R, cfg.d_model // 128), jnp.float32)},
        split, sds((2,), jnp.uint32)).compile()
    experts_bytes = 2 * 16 * 3 * cfg.d_model * cfg.moe_d_ff
    for prog in (edge, cloud):
        m = prog.memory_analysis()
        assert m.temp_size_in_bytes < experts_bytes / 8
        assert param_bytes + m.temp_size_in_bytes \
            + m.output_size_in_bytes < 16_909_336_064
