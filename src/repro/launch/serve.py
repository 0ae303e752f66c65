"""End-to-end RoboECC serving driver.

Drives the paper pipeline on one model: structure + hardware models ->
Alg. 1 split -> parameter-sharing pool -> (optionally) LSTM predictor and
per-request fine-grained adjustment, with the split executor running both
halves of every request on this host's default device.  The data plane
executes the same configuration the control plane planned — openvla-7b
at its published widths by default, or its CPU-sized ``reduced()``
variant with ``--reduced``.  Each request prints its wall time on the
host clock, taken around work that ends in ``jax.block_until_ready``,
beside the latency the cost model predicts for the modeled edge/cloud
devices and network.

    PYTHONPATH=src python -m repro.launch.serve --requests 8
    PYTHONPATH=src python -m repro.launch.serve --reduced --predictor-epochs 0
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compile_cache import enable_compile_cache
from ..configs import get_config
from ..configs.base import ModelConfig
from ..core import (NetworkSim, PredictorConfig, RoboECC, Workload,
                    generate_trace)
from ..core.hardware import A100, ORIN
from ..core.structure import LayerCost
from ..models import build
from ..runtime.partition import (LMSplitExecutor, SplitPlan,
                                 VLASplitExecutor, payload_bytes)

# graph node kinds that are blocks of the executor's stacked backbone
_BACKBONE_KINDS = ("llm", "moe")


def serving_config(arch: str, reduced: bool = False) -> ModelConfig:
    """The configuration both planes use: published widths, or the
    CPU-sized ``reduced()`` variant."""
    cfg = get_config(arch)
    return cfg.reduced() if reduced else cfg


def build_controller(cfg: ModelConfig, codec: str = "int8", seed: int = 0,
                     predictor_epochs: int = 120, seq: int = 17
                     ) -> Tuple[RoboECC, NetworkSim]:
    """Alg. 1 + pool for ``cfg`` (Orin edge, A100 cloud), and the seeded
    bandwidth trace requests are served under.  ``predictor_epochs > 0``
    trains the LSTM bandwidth predictor, which turns on per-request split
    adjustment; 0 serves every request at the Alg. 1 split."""
    ctl = RoboECC(cfg, ORIN, A100, workload=Workload(s_new=seq),
                  cloud_budget_bytes=0.9 * cfg.n_params() * 2,
                  codec=codec or None)
    trace = generate_trace(4000, seed=seed)
    window = PredictorConfig().window
    if predictor_epochs > 0:
        ctl.fit_predictor(trace[:3000],
                          PredictorConfig(epochs=predictor_epochs))
        window = ctl.predictor.cfg.window
    net = NetworkSim(trace[3000:])
    net.step(window)
    return ctl, net


def executor_index(cfg: ModelConfig, graph: List[LayerCost],
                   split: int) -> int:
    """Map a graph split (nodes ``[0, split)`` on the edge) onto the
    executor's layer indexing: the backbone blocks before the cut, offset
    by the ViT depth for VLAs.  A cut inside the ViT or the head lands on
    the nearest backbone boundary, the range the executor can cut in."""
    n_blocks = sum(c.kind in _BACKBONE_KINDS for c in graph[:split])
    return (cfg.vit_layers if cfg.family == "vla" else 0) + n_blocks


def build_executor(cfg: ModelConfig, ctl: RoboECC, codec: str = "int8"):
    """The split executor for ``cfg``'s family, its pool mapped from the
    controller's Alg. 1 pool.  Served on a TPU, a dropless MoE layer's
    grouped matmuls run the Pallas kernel."""
    if cfg.moe_dropless and jax.default_backend() == "tpu":
        cfg = cfg.replace(moe_impl="pallas")
    lo = executor_index(cfg, ctl.graph, ctl.pool.start)
    hi = executor_index(cfg, ctl.graph, ctl.pool.end)
    plan = SplitPlan(lo, hi, codec=codec)
    if cfg.family == "vla":
        return VLASplitExecutor(cfg, plan)
    if cfg.family in ("dense", "moe"):
        return LMSplitExecutor(cfg, plan)
    raise ValueError(f"no split executor for family {cfg.family!r}")


def make_inputs(cfg: ModelConfig, key: jax.Array, seq: int = 17
                ) -> Tuple[jax.Array, ...]:
    """One request's seeded inputs, made on the device: ``(patches,
    tokens)`` for a VLA (patch embeddings stand in for the camera
    frontend), ``(tokens,)`` for an LM."""
    kp, kt = jax.random.split(key)
    tokens = jax.random.randint(kt, (1, seq), 0, cfg.vocab_size)
    if cfg.family != "vla":
        return (tokens,)
    patches = jax.random.normal(kp, (1, cfg.n_patches, cfg.vit_dim),
                                jnp.dtype(cfg.dtype))
    return patches, tokens


def compile_programs(ex, params, inputs: Tuple[jax.Array, ...],
                     split: int) -> Dict[str, float]:
    """Compile the executor's edge and cloud programs ahead of the first
    request; returns seconds per program.  Later ``run`` calls with the
    same shapes reuse these executables."""
    split = jnp.int32(ex.plan.clamp(split))
    edge_args = (params, *inputs, split)
    t0 = time.perf_counter()
    ex._edge.lower(*edge_args).compile()
    t1 = time.perf_counter()
    cloud_args = (params, jax.eval_shape(ex._edge, *edge_args), split)
    if isinstance(ex, VLASplitExecutor):
        cloud_args += (jax.random.PRNGKey(0),)
    ex._cloud.lower(*cloud_args).compile()
    return {"edge": t1 - t0, "cloud": time.perf_counter() - t1}


@dataclasses.dataclass
class Served:
    out: jax.Array            # action (VLA) or logits (LM)
    logits: Optional[jax.Array]
    payload: Dict[str, Any]
    wall_s: float


def serve_request(ex, params, inputs: Tuple[jax.Array, ...], split: int,
                  key: Optional[jax.Array] = None) -> Served:
    """One co-inference, timed on the host clock until every output is
    on the device; the wait is the host span ``roboecc/serve/wait``."""
    t0 = time.perf_counter()
    if isinstance(ex, VLASplitExecutor):
        out, logits, payload = ex.run(params, *inputs, split, key,
                                      return_logits=True)
    else:
        out, payload = ex.run(params, *inputs, split)
        logits = out
    with jax.profiler.TraceAnnotation("roboecc/serve/wait"):
        jax.block_until_ready((out, logits, payload))
    return Served(out, logits, payload, time.perf_counter() - t0)


def serve_requests(ctl: RoboECC, net: NetworkSim, ex, params,
                   cfg: ModelConfig, key: jax.Array, n: int, seq: int = 17
                   ) -> List[Tuple[Any, Served]]:
    """Serve ``n`` requests, request ``i`` on inputs seeded by
    ``fold_in(key, i)`` at the split the controller picks for its tick;
    prints one line per request and returns ``(tick, served)`` pairs."""
    out = []
    for rid in range(n):
        tick = ctl.tick(net)
        split = executor_index(cfg, ctl.graph, tick.split)
        inputs = make_inputs(cfg, jax.random.fold_in(key, rid), seq)
        served = serve_request(ex, params, inputs, split)
        print(f"req {rid}: split {tick.split} (executor {split}) "
              f"modeled {tick.total_s * 1e3:.3f} ms, "
              f"wall {served.wall_s * 1e3:.3f} ms", flush=True)
        out.append((tick, served))
    return out


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="openvla-7b")
    ap.add_argument("--reduced", action="store_true",
                    help="plan and serve the CPU-sized reduced() variant")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--seq", type=int, default=17,
                    help="text tokens per request")
    ap.add_argument("--codec", default="int8",
                    help="wire codec on the cut: '', 'int8' or 'int4'")
    ap.add_argument("--predictor-epochs", type=int, default=120,
                    help="LSTM predictor training epochs; 0 serves every "
                         "request at the Alg. 1 split")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = serving_config(args.arch, args.reduced)
    ctl, net = build_controller(cfg, args.codec, args.seed,
                                args.predictor_epochs, args.seq)
    ex = build_executor(cfg, ctl, args.codec)
    print(f"{cfg.name}{' (reduced)' if args.reduced else ''}: "
          f"d_model {cfg.d_model}, {cfg.n_layers} blocks, "
          f"device {jax.devices()[0].device_kind}")
    print(f"Alg.1 split {ctl.split}/{len(ctl.graph)} "
          f"pool [{ctl.pool.start},{ctl.pool.end}) -> executor pool "
          f"[{ex.plan.pool_start},{ex.plan.pool_end}) "
          f"overhead={ctl.pool.overhead_frac * 100:.2f}%")

    params = build(cfg).init(jax.random.PRNGKey(args.seed))
    key = jax.random.PRNGKey(args.seed + 1)
    compiled = compile_programs(ex, params, make_inputs(cfg, key, args.seq),
                                executor_index(cfg, ctl.graph, ctl.split))
    print("compile s: " + ", ".join(f"{k} {v:.2f}"
                                    for k, v in compiled.items()))
    done = serve_requests(ctl, net, ex, params, cfg, key, args.requests,
                          args.seq)
    modeled = [t.total_s for t, _ in done]
    wall = [sv.wall_s for _, sv in done]
    wire = [payload_bytes(sv.payload) for _, sv in done]
    print(f"served {args.requests} requests: modeled mean "
          f"{np.mean(modeled) * 1e3:.3f} ms, wall mean "
          f"{np.mean(wall) * 1e3:.2f} ms on "
          f"{jax.devices()[0].platform}")
    print(f"cut payload: {np.mean(wire) / 1e3:.1f} KB/request "
          f"(codec={args.codec or 'raw'})")


if __name__ == "__main__":
    main()
