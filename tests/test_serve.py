"""The served path (`repro.launch.serve`) and `chip_smoke.py` on the CPU:
family dispatch, Alg. 1 graph-to-executor index mapping, the split vs
unsplit logits comparison at reduced size, the CLI, and the smoke
script's refusal to run without a TPU."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import RoboECC, Workload
from repro.core.hardware import A100, ORIN
from repro.launch import serve
from repro.models import build
from repro.models import vla as V
from repro.runtime.partition import (LMSplitExecutor, SplitPlan,
                                     VLASplitExecutor)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _cpu_env(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")])
    return env


def test_executor_index_maps_openvla_graph():
    """openvla-7b's graph is 24 ViT blocks, the ViT projection, 32 LLM
    blocks and the detok head (58 nodes); a graph split counts the LLM
    blocks on the edge, offset by the ViT depth."""
    cfg = get_config("openvla-7b")
    ctl = RoboECC(cfg, ORIN, A100, workload=Workload(s_new=17),
                  cloud_budget_bytes=0.9 * cfg.n_params() * 2, codec="int8")
    g = ctl.graph
    assert len(g) == 58
    Lv = cfg.vit_layers
    assert serve.executor_index(cfg, g, 0) == Lv          # inside the ViT
    assert serve.executor_index(cfg, g, Lv + 1) == Lv     # after vit.proj
    assert serve.executor_index(cfg, g, Lv + 3) == Lv + 2
    assert serve.executor_index(cfg, g, len(g)) == Lv + cfg.n_layers
    ex = serve.build_executor(cfg, ctl, "int8")
    assert isinstance(ex, VLASplitExecutor)
    lo = serve.executor_index(cfg, g, ctl.pool.start)
    hi = serve.executor_index(cfg, g, ctl.pool.end)
    assert (ex.plan.pool_start, ex.plan.pool_end) == (lo, hi)
    assert Lv <= lo <= hi <= Lv + cfg.n_layers
    assert ex.plan.clamp(serve.executor_index(cfg, g, ctl.split)) == \
        serve.executor_index(cfg, g, ctl.split)


@pytest.mark.parametrize("arch,kind", [("openvla-7b", VLASplitExecutor),
                                       ("llama3.2-3b", LMSplitExecutor),
                                       ("granite-moe-3b-a800m",
                                        LMSplitExecutor)])
def test_build_executor_dispatches_on_family(arch, kind):
    cfg = serve.serving_config(arch, reduced=True)
    ctl, _ = serve.build_controller(cfg, "int8", predictor_epochs=0)
    assert isinstance(serve.build_executor(cfg, ctl, "int8"), kind)


def test_build_executor_refuses_family_without_executor():
    cfg = serve.serving_config("mamba2-1.3b", reduced=True)
    ctl, _ = serve.build_controller(cfg, "", predictor_epochs=0)
    with pytest.raises(ValueError, match="no split executor"):
        serve.build_executor(cfg, ctl, "")


@pytest.fixture(scope="module")
def openvla_reduced():
    cfg = serve.serving_config("openvla-7b", reduced=True).replace(
        n_layers=4)
    params = build(cfg).init(jax.random.PRNGKey(0))
    inputs = serve.make_inputs(cfg, jax.random.PRNGKey(1))
    h = V.vla_backbone(cfg, params, *inputs)
    return cfg, params, inputs, V.detok_logits(cfg, params, h)


def test_split_vs_unsplit_logits_at_reduced_size(openvla_reduced):
    """The chip smoke test's comparison at CPU size: action-position
    logits of the split run (raw cut) against the unsplit forward at
    every split of a pool, and of the int8 cut against the raw cut."""
    cfg, params, inputs, ref = openvla_reduced
    Lv = cfg.vit_layers
    raw = VLASplitExecutor(cfg, SplitPlan(Lv, Lv + 4))
    q8 = VLASplitExecutor(cfg, SplitPlan(Lv, Lv + 4, codec="int8"))
    assert ref.shape == (1, cfg.action_dim, cfg.vocab_size)
    for split in range(Lv, Lv + 5):
        r = serve.serve_request(raw, params, inputs, split)
        q = serve.serve_request(q8, params, inputs, split)
        assert r.logits.shape == ref.shape
        assert _rel(r.logits, ref) <= 2e-2, split
        assert _rel(q.logits, r.logits) <= 5e-2, split
        assert r.out.shape == (1, 1, cfg.action_dim)
        assert r.wall_s > 0


def test_serve_cli_reduced_openvla(tmp_path):
    """``serve --reduced`` plans and serves the reduced openvla-7b
    through the VLA executor and reports wall time beside the model."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--reduced",
         "--predictor-epochs", "0", "--requests", "2"],
        capture_output=True, text=True, env=_cpu_env(tmp_path), cwd=ROOT,
        timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "openvla-7b (reduced)" in out.stdout
    assert "req 1:" in out.stdout and "wall" in out.stdout
    assert "served 2 requests" in out.stdout


def test_chip_smoke_refuses_cpu(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, env=_cpu_env(tmp_path), cwd=ROOT,
        timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_compile_cache_dir_follows_env(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and no directory is set in
    code; without it the cache goes to one fixed, git-ignored path in
    the checkout."""
    from repro import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
        assert compile_cache.enable_compile_cache() == "/cache/from/env"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
