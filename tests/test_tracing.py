"""Tracing in the served path: each tier's compiled program keeps the
``jax.named_scope`` names of ``runtime/partition.SCOPES`` as ``op_name``
metadata, for the detok and DiT heads, a MoE trunk's routing and experts
and a split LM, and the executors dispatch without a recorder."""
import inspect
import re

import jax
import jax.numpy as jnp
import pytest

from repro.launch import serve
from repro.models import build
from repro.runtime.partition import (SCOPES, LMSplitExecutor,
                                     VLASplitExecutor)


def _scopes(hlo_text):
    """Scope names found in the program's ``op_name`` metadata."""
    return {part for name in re.findall(r'op_name="([^"]*)"', hlo_text)
            for part in name.split("/") if part in SCOPES}


def _compiled(arch):
    cfg = serve.serving_config(arch, reduced=True)
    ctl, _ = serve.build_controller(cfg, "int8", predictor_epochs=0)
    ex = serve.build_executor(cfg, ctl, "int8")
    params = build(cfg).init(jax.random.PRNGKey(0))
    inputs = serve.make_inputs(cfg, jax.random.PRNGKey(1))
    split = jnp.int32(ex.plan.pool_start)
    edge_args = (params, *inputs, split)
    cloud_args = (params, jax.eval_shape(ex._edge, *edge_args), split,
                  jax.random.PRNGKey(2))
    return cfg, (ex._edge.lower(*edge_args).compile().as_text(),
                 ex._cloud.lower(*cloud_args).compile().as_text())


@pytest.mark.parametrize("arch,head", [("openvla-7b", "detok"),
                                       ("cogact-7b", "dit"),
                                       ("kimi-vl-a3b-vla", "detok")])
def test_vla_tiers_keep_every_scope(arch, head):
    cfg, (edge, cloud) = _compiled(arch)
    assert cfg.vla_action_head == head
    moe = {"router", "experts"} if cfg.moe_dropless else set()
    assert _scopes(edge) == {"vision", "trunk", "encode"} | moe
    assert _scopes(cloud) == {"decode", "trunk", "head"} | moe


def test_lm_tiers_keep_their_scopes():
    cfg, (edge, cloud) = _compiled("llama3.2-3b")
    assert cfg.family == "dense"
    assert _scopes(edge) == {"trunk", "encode"}
    assert _scopes(cloud) == {"decode", "trunk", "head"}


def test_executors_take_no_recorder():
    for cls in (LMSplitExecutor, VLASplitExecutor):
        assert "recorder" not in inspect.signature(cls.run).parameters
