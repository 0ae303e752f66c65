"""The FLOP and byte functions of the MoE + MLA VLA (``costs/vla_moe.py``)
against counts made by hand, and against the reference's parameter
tree."""
import math
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from costs import vla_moe as C  # noqa: E402
from references import vla_moe as R  # noqa: E402

M = {"d_model": 8, "n_heads": 2, "kv_lora_rank": 4, "qk_nope_dim": 2,
     "qk_rope_dim": 2, "v_head_dim": 3, "d_ff": 16, "n_layers": 3,
     "first_dense_layers": 1, "n_experts": 8, "experts_held": 2,
     "moe_top_k": 2, "moe_d_ff": 4, "n_shared_experts": 1, "vit_layers": 2,
     "vit_dim": 4, "vit_ff": 6, "vit_merge": 2, "n_patches": 16,
     "vocab_size": 32, "action_dim": 3, "vla_action_head": "detok",
     "vit_block": "siglip"}
TEXT = 5
S = 16 // 4 + TEXT                                  # 4 image tokens


def _matmul(rows, k, n):
    return 2 * rows * k * n


def test_mla_flops_by_hand():
    proj = _matmul(S, 8, 2 * 4) + _matmul(S, 8, 4 + 2) + _matmul(S, 6, 8)
    pairs = S * (S + 1) // 2
    # per-head keys and values from the latent, then causal attention
    naive = _matmul(S, 4, 2 * 2) + _matmul(S, 4, 2 * 3) \
        + 2 * pairs * 2 * 4 + 2 * pairs * 2 * 3
    assert C.mla_flops(M, S) == proj + naive


def test_trunk_blocks_by_hand():
    mlp = 2 * _matmul(S, 8, 16) + _matmul(S, 16, 8)
    assert C.block_flops(M, S, 0) == C.mla_flops(M, S) + mlp
    router = _matmul(S, 8, 8)
    shared = 2 * _matmul(S, 8, 4) + _matmul(S, 4, 8)
    # the routed experts are left out of a block: the router decides them
    assert C.block_flops(M, S, 1) == C.mla_flops(M, S) + router + shared
    one_row = 2 * _matmul(1, 8, 4) + _matmul(1, 4, 8)
    # 11 rows on held experts, 3 (layer, held expert) pairs reached
    assert C.expert_cost(M, 11, 3) == (11 * one_row, 3 * 3 * 8 * 4 * 2)
    # a step counts the routed work at uniform routing: S rows choose 2
    # of 8 experts, S * 2 * 2 / 8 rows on the 2 held, in 2 MoE layers
    assert C.step_flops(M, TEXT) == C.vit_flops(M) + C.block_flops(M, S, 0) \
        + 2 * C.block_flops(M, S, 1) + 2 * S * 2 * 2 / 8 * one_row \
        + C.head_flops(M)


def test_tower_by_hand():
    P, dv = 16, 4
    block = 4 * _matmul(P, dv, dv) + 2 * (2 * P * P * dv) \
        + _matmul(P, dv, 6) + _matmul(P, 6, dv)
    proj = _matmul(4, 16, 16) + _matmul(4, 16, 8)
    assert C.vit_flops(M) == 2 * block + proj


@pytest.mark.parametrize("split", [2, 3, 4, 5])
def test_edge_and_cloud_split_the_model(split):
    B = 2
    fe, be = C.edge_cost(M, split, B, TEXT)
    fc, bc = C.cloud_cost(M, split, B, TEXT)
    routed = 2 * S * 2 * 2 / 8 * C.expert_flops(M, 1)
    assert fe + fc + B * routed == B * C.step_flops(M, TEXT)
    # the weights both tiers read are the reference's whole tree, less
    # the routed experts and the embedding table, plus the rows gathered
    # from the table
    leaves = [x["shape"] for x in _leaves(R.param_shapes(M))]
    Vp, experts = 32, 2 * 2 * 3 * 8 * 4
    weights = (sum(math.prod(s) for s in leaves) - experts - Vp * 8
               + B * TEXT * 8) * 2
    cut = B * S * 8 + (B * S * 8) // 128 * 4
    inputs = B * (16 * 4 * 2 + TEXT * 4)
    out = B * 3 * 32 * 2 + B * 3 * 4
    assert be + bc == weights + inputs + 2 * cut + out


def _leaves(tree):
    if "shape" in tree:
        return [tree]
    return [x for v in tree.values() for x in _leaves(v)]
