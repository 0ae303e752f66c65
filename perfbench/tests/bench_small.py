"""A CPU-sized run of any benchmark cell, for the benchmark's tests: the
published widths cut so that a test run holds them, every code path of
a real run kept (controller, predictor, served path, reference)."""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

SMALL_MODEL = {"n_layers": 4, "d_model": 128, "n_heads": 4,
               "n_kv_heads": 4, "head_dim": 32, "d_ff": 256,
               "vocab_size": 512, "vit_layers": 2, "vit_dim": 64,
               "n_patches": 16, "dit_layers": 2, "dit_dim": 64,
               "dit_heads": 2, "diffusion_steps": 3, "action_horizon": 4}
SMALL_TRAFFIC = {"check_requests": 12, "warmup_steps": 1,
                 "predictor_epochs": 4}


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def small_run(workload, seed=2**31 + 5, controller=None, model=None, **kw):
    """``controller`` is merged into the mix's controller section,
    ``model`` into ``SMALL_MODEL``."""
    import run
    spec = run.load_cell(workload)
    traffic = dict(SMALL_TRAFFIC)
    ctl = dict(spec["traffic"]["controller"], **(controller or {}))
    ctl["predictor_epochs"] = min(ctl["predictor_epochs"],
                                  traffic.pop("predictor_epochs"))
    traffic["controller"] = ctl
    model = dict(SMALL_MODEL, **(model or {}))
    return run.run(workload, seed, 0.5, False, require_chip=False,
                   overrides={"model": model, "traffic": traffic}, **kw)
