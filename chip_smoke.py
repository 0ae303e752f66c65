#!/usr/bin/env python3
"""Chip smoke test: openvla-7b split co-inference on one TPU.

Runs the served path of ``repro.launch.serve`` — Alg. 1 split and pool,
the VLA split executor with the int8 codec on the cut — at openvla-7b's
published widths and full depth, with seeded random weights, seeded
patch embeddings and 17 text tokens per request: one warm-up request,
then four timed ones.  On the chip it checks

* the split run with the raw codec against the unsplit forward
  (``models.vla.vla_backbone`` + the detok head) on the same parameters,
* the int8-codec run against the raw run,

both on the logits at the ``action_dim`` action positions (with random
weights the arg-max action flips on rounding, the logits do not), and
each Pallas codec kernel at the cut shape (1, 273, 4096) against
``kernels/activation_codec/ref.py``.

Earlier lines report the device, the depth run, compile seconds per
program, per-request wall ms, device memory and every comparison beside
its tolerance.  The last line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or when any phase or check fails, it exits non-zero and
prints no such line.  Everything runs in this one process, which owns
the chip.

    python3 chip_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "openvla-7b"
SEED = 0
N_REQUESTS = 4          # timed requests, after one warm-up
CUT_ROWS = 256 + 17     # patches + text tokens of one OpenVLA request

# Both logits comparisons use max|a - b| / max|b| over the action-position
# logits (the metric tests/test_runtime.py uses for the int8 cut).
# Split vs unsplit: the same bf16 operations in two different programs
# (a loop over indexed layers against a scan over the stack); XLA may
# fuse and accumulate them differently, and 56 blocks of bf16 residual
# stream (2^-8 = 3.9e-3 relative per rounding) can carry a few such
# roundings into the logits — a few parts in a thousand, held to 2e-2.
RAW_VS_UNSPLIT_TOL = 2e-2
# int8 vs raw: one per-(row, 128-block) quantization of the cut
# activation, error <= amax/254 per element (0.4 % of the block max),
# carried through the 30 blocks after the cut — the bound the CPU test
# of the int8 cut holds the split executor to.
INT8_VS_RAW_TOL = 5e-2
# Codec kernels against ref.py on the same input.  Quantize: division
# and rounding in Mosaic and in XLA may differ in the last float32 bit,
# which can move a value across a rounding boundary — at most one code
# step — and the scale by a few ulps.  Dequantize from the same codes:
# one float32 multiply rounded to bf16, so at most one bf16 rounding.
CODE_STEP_TOL = 1
SCALE_REL_TOL = 1e-6
DEQUANT_REL_TOL = 2.0 ** -8


def _log(msg: str) -> None:
    print(msg, flush=True)


def _rel_err(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        return float("inf")
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


class Checks:
    """Every comparison beside its tolerance; ``ok`` only if all pass."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, err: float, tol: float) -> None:
        passed = err <= tol
        _log(f"check {name}: {err:.6g} (tol {tol:.6g}) "
             f"{'ok' if passed else 'FAIL'}")
        if not passed:
            self.failed.append(name)


def _codec_kernel_checks(check: Checks, d_model: int, seed: int) -> None:
    """Each Pallas codec kernel once at the cut shape against ref.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.activation_codec import ops, ref

    x = jax.random.normal(jax.random.PRNGKey(seed), (1, CUT_ROWS, d_model),
                          jnp.bfloat16)

    def codes4(p):
        p = np.asarray(p, np.int32) + 128
        return np.concatenate([p % 16, p // 16], axis=-1)

    q_p, s_p = ops.quantize(x, impl="pallas")
    q_r, s_r = ref.quantize_int8(x)
    check("int8 quantize codes (max step diff)",
          float(np.max(np.abs(np.asarray(q_p, np.int32)
                              - np.asarray(q_r, np.int32)))), CODE_STEP_TOL)
    check("int8 quantize scales (rel)", _rel_err(s_p, s_r), SCALE_REL_TOL)
    check("int8 dequantize (rel)",
          _rel_err(ops.dequantize(q_r, s_r, impl="pallas"),
                   ref.dequantize_int8(q_r, s_r)), DEQUANT_REL_TOL)

    p_p, s4_p = ops.quantize_int4(x, impl="pallas")
    p_r, s4_r = ref.quantize_int4(x)
    check("int4 quantize codes (max step diff)",
          float(np.max(np.abs(codes4(p_p) - codes4(p_r)))), CODE_STEP_TOL)
    check("int4 quantize scales (rel)", _rel_err(s4_p, s4_r), SCALE_REL_TOL)
    check("int4 dequantize (rel)",
          _rel_err(ops.dequantize_int4(p_r, s4_r, impl="pallas"),
                   ref.dequantize_int4(p_r, s4_r)), DEQUANT_REL_TOL)


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's default backend is "
              f"{dev.platform}. Nothing was run.", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    from repro.core.hardware import chip_peaks
    from repro.launch import serve
    from repro.models import build
    from repro.models import vla as V

    cache_dir = enable_compile_cache()
    peaks = chip_peaks(dev.device_kind)
    _log(f"device: {dev.platform} {dev.device_kind} x{len(devices)} "
         f"(peaks {peaks.peak_flops / 1e12:.0f} TFLOP/s bf16, "
         f"{peaks.hbm_bw / 1e9:.0f} GB/s HBM); compile cache {cache_dir}")

    cfg = serve.serving_config(ARCH)
    _log(f"{cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} heads x "
         f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vit_dim {cfg.vit_dim}, "
         f"vocab {cfg.vocab_size}")
    _log(f"depth run: {cfg.n_layers} LLM blocks, {cfg.vit_layers} ViT blocks "
         f"(the published depth)")

    ctl, net = serve.build_controller(cfg, "int8", SEED, predictor_epochs=0)
    ex_q8 = serve.build_executor(cfg, ctl, "int8")
    ex_raw = serve.build_executor(cfg, ctl, "")
    split = serve.executor_index(cfg, ctl.graph, ctl.split)
    _log(f"Alg.1 split {ctl.split}/{len(ctl.graph)}, pool "
         f"[{ctl.pool.start},{ctl.pool.end}) -> executor split {split}, "
         f"pool [{ex_q8.plan.pool_start},{ex_q8.plan.pool_end})")

    t0 = time.perf_counter()
    params = build(cfg).init(jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    n_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    mem = dev.memory_stats() or {}
    _log(f"init: {time.perf_counter() - t0:.2f} s, params {n_bytes} bytes, "
         f"peak_bytes_in_use {mem.get('peak_bytes_in_use')}, "
         f"bytes_limit {mem.get('bytes_limit')}")

    key = jax.random.PRNGKey(SEED + 1)
    inputs0 = serve.make_inputs(cfg, jax.random.fold_in(key, 0))

    def unsplit_logits(params, patches, tokens):
        h = V.vla_backbone(cfg, params, patches, tokens)
        return V.detok_logits(cfg, params, h)

    unsplit = jax.jit(unsplit_logits)
    t0 = time.perf_counter()
    unsplit.lower(params, *inputs0).compile()
    compile_s = {"unsplit": time.perf_counter() - t0}
    for tag, ex in (("int8", ex_q8), ("raw", ex_raw)):
        for prog, sec in serve.compile_programs(ex, params, inputs0,
                                                split).items():
            compile_s[f"{prog}_{tag}"] = sec
    _log("compile s: " + ", ".join(f"{k} {v:.2f}"
                                   for k, v in compile_s.items()))
    _log(f"compile s total: {sum(compile_s.values()):.2f}")

    done = serve.serve_requests(ctl, net, ex_q8, params, cfg, key,
                                1 + N_REQUESTS)
    wall_ms = [sv.wall_s * 1e3 for _, sv in done[1:]]
    _log("wall ms per request (after warm-up): "
         + ", ".join(f"{w:.3f}" for w in wall_ms))

    check = Checks()
    q8 = done[0][1]
    raw = serve.serve_request(ex_raw, params, inputs0, split)
    ref = unsplit(params, *inputs0)
    vocab = cfg.vocab_size
    want = (1, cfg.action_dim)
    for name, lg in (("unsplit", ref), ("raw", raw.logits),
                     ("int8", q8.logits)):
        if lg.shape[:2] != want:
            raise RuntimeError(f"{name} logits shape {lg.shape}, want "
                               f"{want} + (vocab,)")
    check("split raw vs unsplit logits (rel)",
          _rel_err(raw.logits[..., :vocab], ref[..., :vocab]),
          RAW_VS_UNSPLIT_TOL)
    check("split int8 vs raw logits (rel)",
          _rel_err(q8.logits[..., :vocab], raw.logits[..., :vocab]),
          INT8_VS_RAW_TOL)
    _codec_kernel_checks(check, cfg.d_model, SEED + 2)

    mem = dev.memory_stats() or {}
    _log(f"memory: peak_bytes_in_use {mem.get('peak_bytes_in_use')}, "
         f"bytes_limit {mem.get('bytes_limit')}")
    if check.failed:
        print(f"chip_smoke: failed checks: {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
