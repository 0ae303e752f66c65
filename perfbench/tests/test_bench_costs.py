"""The FLOP and byte functions against counts made by hand."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from costs import vla as C  # noqa: E402

M = {"d_model": 8, "n_heads": 2, "n_kv_heads": 2, "head_dim": 4,
     "d_ff": 16, "n_layers": 3, "vit_layers": 2, "vit_dim": 4,
     "n_patches": 2, "vocab_size": 32, "action_dim": 3,
     "vla_action_head": "detok", "action_horizon": 1}


def _matmul(rows, k, n):
    return 2 * rows * k * n                      # one multiply-add = 2


def test_llm_block_flops_by_hand():
    S = 3
    proj = 4 * _matmul(S, 8, 8)                  # q, k, v, o
    # causal: query i attends to i + 1 keys, per head scores + values
    attn = sum(2 * (i + 1) * 4 * 2 for i in range(S)) * 2
    mlp = 2 * _matmul(S, 8, 16) + _matmul(S, 16, 8)
    assert C.llm_block_flops(M, S) == proj + attn + mlp


def test_vit_flops_by_hand():
    P, dv = 2, 4
    block = 4 * _matmul(P, dv, dv) + 2 * (2 * P * P * dv) \
        + 2 * _matmul(P, dv, 4 * dv) + _matmul(P, 4 * dv, dv)
    assert C.vit_flops(M) == 2 * block + _matmul(P, dv, 8)


def test_detok_and_step_flops():
    assert C.head_flops(M) == _matmul(3, 8, 32)
    S = 2 + 5
    assert C.step_flops(M, 5) == C.vit_flops(M) \
        + 3 * C.llm_block_flops(M, S) + C.head_flops(M)


def test_dit_flops_by_hand():
    m = dict(M, vla_action_head="dit", dit_dim=4, dit_layers=1,
             diffusion_steps=2, action_horizon=2, action_dim=3)
    h, a, dd = 2, 3, 4
    block = _matmul(1, dd, 6 * dd) + 4 * _matmul(h, dd, dd) \
        + 2 * (2 * h * h * dd) + _matmul(h, dd, 4 * dd) \
        + _matmul(h, 4 * dd, dd)
    step = _matmul(h, a, dd) + _matmul(1, 64, dd) + block \
        + _matmul(1, dd, 2 * dd) + _matmul(h, dd, a)
    assert C.head_flops(m) == _matmul(1, 8, dd) + 2 * step


@pytest.mark.parametrize("split", [2, 3, 5])
def test_edge_and_cloud_split_the_trunk(split):
    text, B = 5, 2
    fe, be = C.edge_cost(M, split, B, text)
    fc, bc = C.cloud_cost(M, split, B, text)
    assert fe + fc == B * C.step_flops(M, text)
    block_w = (2 * 8 + 4 * 64 + 3 * 8 * 16) * 2        # norms, attn, mlp
    vit_w = (2 * 4 + 2 * (2 * 4 + 16 * 16) + 4 + 4 * 8) * 2
    emb = B * text * 8 * 2
    cut = B * 7 * 8 + (B * 7 * 8) // 128 * 4
    e = split - 2
    assert be == vit_w + e * block_w + emb + B * (2 * 4 * 2 + text * 4) \
        + cut
    head_w = (8 + 32 * 8) * 2
    logits = B * 3 * 32 * 2 + B * 3 * 4
    assert bc == cut + (3 - e) * block_w + head_w + logits
