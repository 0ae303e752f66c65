"""Operations and bytes of the split VLA programs on a MoE + MLA trunk
(kimi-vl-a3b-vla), from the shapes alone.

Conventions as in ``costs/vla.py``: a multiply-add is two FLOPs, causal
attention counts its lower triangle, the embedding lookup and elementwise
work (norms, rotary, softmax, routing, the codec) are free; bytes are
what a call must move at least once: the weights of the blocks it runs
(bfloat16), the embedding rows it gathers, its inputs and its outputs.

Terms, for ``S = n_patches / vit_merge^2 + text`` trunk rows and ``B``
observations per call:

* Tower block over ``P`` patches: ``8 P dv^2`` (q, k, v, o) + ``4 P^2
  dv`` (scores and values) + ``4 P dv ff`` (GELU MLP); projector ``2 P'
  dm (dm + d)`` over ``P' = P / k^2`` tokens of ``dm = k^2 dv``.
* MLA at ``S`` causal rows: projections ``2 S d H (qn + qr) + 2 S d (r +
  qr) + 2 S H vd d`` and the cheaper of two forms: per-head keys and
  values ``2 S r H (qn + vd)`` with ``4 hd`` scores and values over
  ``S (S + 1) / 2`` pairs at ``H (qn + qr)`` and ``H vd``; or the
  absorbed form, ``2 S H r (qn + vd)`` with scores and values in the
  latent, ``H (r + qr)`` and ``H r``.
* Dense layer: ``6 S d ff``.  MoE layer: the router ``2 S d E`` and the
  shared experts ``6 S d fs``.
* Routed experts: ``6 d fe`` a (token, choice) row on a held expert, and
  the weights of each held expert that gets a row.  How many rows and
  experts that is, the router decides call by call, and the least of
  both is nothing (every row may choose experts held elsewhere), so
  ``edge_cost`` and ``cloud_cost`` leave the routed experts out: a
  tier's share of its roofline is then a lower bound.  ``expert_cost``
  takes them from the call's routing (``routed``, ``reached``, as the
  reference computes them).  ``step_flops`` counts the model's routed
  work at uniform routing, ``S k Eh / E`` rows a layer.
* detok head: ``2 A d V``.
"""
from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2
F32 = 4
INT32 = 4
CUT_BLOCK = 128


def _vpad(m):
    return -(-m["vocab_size"] // 16) * 16


def image_tokens(m: Dict) -> int:
    return m["n_patches"] // m["vit_merge"] ** 2


def rows(m: Dict, text: int) -> int:
    return image_tokens(m) + text


def vit_flops(m: Dict) -> float:
    P, dv, ff, d = m["n_patches"], m["vit_dim"], m["vit_ff"], m["d_model"]
    dm = m["vit_merge"] ** 2 * dv
    block = 8 * P * dv ** 2 + 4 * P ** 2 * dv + 4 * P * dv * ff
    return m["vit_layers"] * block + 2 * image_tokens(m) * dm * (dm + d)


def mla_flops(m: Dict, S: int) -> float:
    d, H, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    qn, qr, vd = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    pairs = S * (S + 1) / 2
    proj = 2 * S * d * H * (qn + qr) + 2 * S * d * (r + qr) \
        + 2 * S * H * vd * d
    naive = 2 * S * r * H * (qn + vd) + 2 * pairs * H * (qn + qr + vd)
    absorbed = 2 * S * H * r * (qn + vd) + 2 * pairs * H * (2 * r + qr)
    return proj + min(naive, absorbed)


def expert_flops(m: Dict, rows: float) -> float:
    """Routed work of ``rows`` (token, choice) rows on held experts."""
    return rows * 6 * m["d_model"] * m["moe_d_ff"]


def block_flops(m: Dict, S: int, i: int) -> float:
    """Trunk block ``i`` (0-based) at ``S`` rows, without the routed
    experts."""
    d = m["d_model"]
    if i < m["first_dense_layers"]:
        return mla_flops(m, S) + 6 * S * d * m["d_ff"]
    fs = m["n_shared_experts"] * m["moe_d_ff"]
    return mla_flops(m, S) + 2 * S * d * m["n_experts"] + 6 * S * d * fs


def head_flops(m: Dict) -> float:
    return 2 * m["action_dim"] * m["d_model"] * m["vocab_size"]


def step_flops(m: Dict, text: int) -> float:
    """Model FLOPs of one robot control step (one observation), the
    routed work at uniform routing."""
    S = rows(m, text)
    n_moe = m["n_layers"] - m["first_dense_layers"]
    uniform = S * m["moe_top_k"] * m["experts_held"] / m["n_experts"]
    return vit_flops(m) + sum(block_flops(m, S, i)
                              for i in range(m["n_layers"])) \
        + n_moe * expert_flops(m, uniform) + head_flops(m)


def _mla_params(m: Dict) -> int:
    d, H, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    qn, qr, vd = m["qk_nope_dim"], m["qk_rope_dim"], m["v_head_dim"]
    return d * H * (qn + qr) + d * (r + qr) + r + r * H * (qn + vd) \
        + H * vd * d


def expert_params(m: Dict) -> int:
    """One routed expert's weights."""
    return 3 * m["d_model"] * m["moe_d_ff"]


def block_params(m: Dict, i: int) -> int:
    """Trunk block ``i``'s weights, without the routed experts."""
    d = m["d_model"]
    n = 2 * d + _mla_params(m)
    if i < m["first_dense_layers"]:
        return n + 3 * d * m["d_ff"]
    E, fs = m["n_experts"], m["n_shared_experts"] * m["moe_d_ff"]
    return n + d * E + E + 3 * d * fs


def _vit_params(m: Dict) -> int:
    dv, ff, d = m["vit_dim"], m["vit_ff"], m["d_model"]
    dm = m["vit_merge"] ** 2 * dv
    block = 4 * dv * dv + 4 * dv + 2 * dv * ff + ff + dv + 4 * dv
    proj = 2 * dv + dm * dm + dm + dm * d + d
    return m["n_patches"] * dv + m["vit_layers"] * block + 2 * dv + proj


def cut_bytes(m: Dict, text: int, batch: int) -> int:
    n = batch * rows(m, text) * m["d_model"]
    return n + n // CUT_BLOCK * F32                    # int8 codes + scales


def edge_cost(m: Dict, split: int, batch: int, text: int
              ) -> Tuple[float, float]:
    """``(flops, bytes)`` of one edge call: tower, projector, text
    embedding, trunk blocks ``[0, split - vit_layers)`` without their
    routed experts, int8 encode of the cut."""
    e = split - m["vit_layers"]
    S = rows(m, text)
    flops = batch * (vit_flops(m) + sum(block_flops(m, S, i)
                                        for i in range(e)))
    weights = (_vit_params(m) + sum(block_params(m, i) for i in range(e))
               + min(batch * text, m["vocab_size"]) * m["d_model"]) * BF16
    inputs = batch * (m["n_patches"] * m["vit_dim"] * BF16 + text * INT32)
    return flops, weights + inputs + cut_bytes(m, text, batch)


def cloud_cost(m: Dict, split: int, batch: int, text: int
               ) -> Tuple[float, float]:
    """``(flops, bytes)`` of one cloud call: int8 decode, trunk blocks
    ``[split - vit_layers, n_layers)`` without their routed experts, final
    norm and the detok head."""
    e = split - m["vit_layers"]
    S, L = rows(m, text), m["n_layers"]
    flops = batch * (sum(block_flops(m, S, i) for i in range(e, L))
                     + head_flops(m))
    weights = (sum(block_params(m, i) for i in range(e, L))
               + m["d_model"] + _vpad(m) * m["d_model"]) * BF16
    out = batch * m["action_dim"] * _vpad(m) * BF16 \
        + batch * m["action_dim"] * F32
    return flops, cut_bytes(m, text, batch) + weights + out


def expert_cost(m: Dict, routed: int, reached: int
                ) -> Tuple[float, float]:
    """``(flops, bytes)`` of one call's routed experts over both tiers,
    from its dispatch counts: ``routed`` (token, choice) rows on held
    experts and ``reached`` (layer, held expert) pairs given a row, whose
    weights are read."""
    return expert_flops(m, routed), reached * expert_params(m) * BF16
