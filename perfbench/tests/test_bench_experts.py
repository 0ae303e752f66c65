"""The routed experts' readings (``harness/experts.py``): the scope map
nests ``router`` and ``experts`` inside a tier's ``trunk`` and leaves the
rest of the loop unmapped, device time under ``experts`` adds up per
call over both tiers, on hand-built inputs, and each traced call's
routed work comes from the reference's routing of its observation,
equal to the program's own counts on a CPU-sized cell."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from harness import experts, inside  # noqa: E402

HLO = """HloModule jit__cloud_fwd, is_scheduled=true

%fused_gmm (param_0: bf16[4]) -> bf16[4] {
  %param_0 = bf16[4]{0} parameter(0)
  ROOT %g = bf16[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(_cloud_fwd)/trunk/while/body/experts/gather"}
}

%body (p: (s32[], bf16[4])) -> (s32[], bf16[4]) {
  %p = (s32[], bf16[4]{0}) parameter(0)
  %x = bf16[4]{0} get-tuple-element(%p), index=1
  %attn = bf16[4]{0} add(%x, %x), metadata={op_name="jit(_cloud_fwd)/trunk/while/body/dot_general"}
  %top = bf16[4]{0} sort(%attn), metadata={op_name="jit(_cloud_fwd)/trunk/while/body/router/top_k"}
  %fusion.7 = bf16[4]{0} fusion(%top), kind=kLoop, calls=%fused_gmm
  %kernel.1 = bf16[4]{0} custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(_cloud_fwd)/trunk/while/body/experts/jit(grouped_matmul)/pallas_call"}
  ROOT %t = (s32[], bf16[4]{0}) tuple(%p, %kernel.1)
}

ENTRY %main (a: bf16[4]) -> bf16[4] {
  %a = bf16[4]{0} parameter(0)
  %dq = bf16[4]{0} convert(%a), metadata={op_name="jit(_cloud_fwd)/decode/convert"}
  %while.1 = (s32[], bf16[4]{0}) while(%dq), body=%body
  ROOT %h = bf16[4]{0} copy(%a), metadata={op_name="jit(_cloud_fwd)/head/dot_general"}
}
"""


def test_scope_map_nests_routing_and_experts_in_the_trunk():
    m = experts.scope_map(HLO)
    assert m["top"] == "router"
    assert m["fusion.7"] == m["kernel.1"] == m["g"] == "experts"
    assert "attn" not in m and "dq" not in m and "h" not in m
    # the tier-level map is left as it was
    assert inside.SCOPES == ("vision", "trunk", "encode", "decode", "head")
    assert inside.scope_map(HLO)["kernel.1"] == "trunk"


class _Trace:
    """The two facts ``expert_seconds`` reads."""

    def __init__(self, ops, modules):
        self._ops, self._modules = ops, modules

    def ops(self):
        return self._ops

    def modules(self, fn):
        return self._modules[fn]


def test_expert_seconds_per_call_over_both_tiers():
    scopes = {"edge": {"k.1": "experts", "r.1": "router"},
              "cloud": {"k.2": "experts", "k.3": "experts"}}
    ops = [("%k.1 = custom-call()", 0.0, 1.0),      # edge, call 1
           ("%r.1 = sort()", 1.0, 1.5),
           ("%k.2 = custom-call()", 3.0, 4.0),      # cloud, call 1
           ("%k.3 = fusion()", 3.5, 4.5),           # overlaps k.2
           ("%k.1 = custom-call()", 10.0, 10.25),   # edge, call 2
           ("%while.1 = while()", 13.0, 16.0),
           ("%k.2 = custom-call()", 13.0, 14.0)]    # cloud, call 2
    tr = _Trace(ops, {"_edge_fwd": [(0.0, 2.0), (10.0, 11.0)],
                      "_cloud_fwd": [(3.0, 5.0), (13.0, 16.0)]})
    got = experts.expert_seconds(tr, {"edge": "_edge_fwd",
                                      "cloud": "_cloud_fwd"}, scopes)
    assert got == [1.0 + 1.5, 0.25 + 1.0]


def test_dispatch_counts_by_hand():
    """Held experts [2, 4) of 8, one call of two observations, two MoE
    layers of three rows choosing two experts each."""
    import numpy as np
    from references import vla_moe as R
    m = {"expert_start": 2, "experts_held": 2}
    chosen = np.array([[[[2, 0], [3, 2], [5, 6]],       # obs 0, layer 0
                        [[0, 1], [4, 5], [6, 7]]],      # obs 0, layer 1
                       [[[2, 7], [0, 1], [1, 0]],       # obs 1, layer 0
                        [[1, 3], [0, 4], [5, 6]]]])     # obs 1, layer 1
    # layer 0: expert 2 three times, expert 3 once; layer 1: expert 3 once
    assert R.dispatch_counts(m, chosen) == {"routed": 5, "reached": 3}


def _small_cell(seed=0):
    """A CPU-sized kimi-vl-a3b-vla cell in float32: the benchmark's
    weights, the reference, two observations of one robot."""
    import json
    import types

    import jax
    import numpy as np
    from harness.weights import make_params, seed_key
    from references import vla_moe as R
    with open(os.path.join(BENCH, "configs", "kimi-vl-a3b-vla.json")) as f:
        m = json.load(f)["model"]
    m.update(n_layers=3, d_model=128, n_heads=4, n_kv_heads=4,
             kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
             d_ff=192, vocab_size=512, n_experts=16, experts_held=4,
             expert_start=4, moe_top_k=4, moe_d_ff=48, vit_layers=2,
             vit_dim=64, vit_heads=4, vit_ff=96, n_patches=16,
             dtype="float32")
    rng = np.random.default_rng(seed)
    return types.SimpleNamespace(
        ref=R, m=m, params=make_params(R.param_shapes(m), seed_key(seed),
                                       jax.numpy.float32),
        patches=rng.standard_normal((2, 1, 16, 64), np.float32),
        tokens=rng.integers(0, 512, (2, 1, 5), np.int32))


def test_call_counts_come_from_the_reference_routing():
    """Each completed call's routed work, from the reference's routing of
    its observation at its split: the same for calls that repeat, none
    for a failed call, and equal to the counts the program itself gives
    on the same weights in float32."""
    import jax
    from repro.configs import get_config
    from repro.runtime.partition import SplitPlan, VLASplitExecutor
    cell = _small_cell()
    Lv, L = cell.m["vit_layers"], cell.m["n_layers"]
    calls = [{"ring": 0, "split": Lv + 1}, {"ring": 1, "split": Lv},
             {"ring": 0, "split": None}, {"ring": 0, "split": Lv + 1}]
    got = experts.call_counts(cell, calls)
    assert len(got) == 3 and got[0] == got[2]
    cfg = get_config("kimi-vl-a3b-vla").replace(**cell.m)
    ex = VLASplitExecutor(cfg, SplitPlan(Lv, Lv + L, codec="int8"))
    for (ring, split), n in zip(((0, Lv + 1), (1, Lv)), got):
        want = ex.run(cell.params, cell.patches[ring], cell.tokens[ring],
                      split, jax.random.PRNGKey(0), return_counts=True)[-1]
        assert n == {k: int(want[k]) for k in ("routed", "reached")}
        assert int(want["dropped"]) == 0
        assert 0 < n["reached"] <= 4 * (L - 1)


def test_reference_bf16_witness_rounds_both_operands():
    """``quant="bf16"`` multiplies the bfloat16 roundings of both
    operands in float32, and leaves the plain reference alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from references import vla_moe as R
    a = jax.random.normal(jax.random.PRNGKey(0), (3, 8))
    b = jax.random.normal(jax.random.PRNGKey(1), (8, 5))
    want = np.asarray(a.astype(jnp.bfloat16), np.float64) \
        @ np.asarray(b.astype(jnp.bfloat16), np.float64)
    got = R._mm("td,df->tf", a, b, "bf16")
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    plain = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    np.testing.assert_allclose(R._mm("td,df->tf", a, b, ""), plain,
                               rtol=1e-6, atol=1e-6)
    assert np.abs(np.asarray(got) - plain).max() > 1e-4
