"""Operations and bytes of the split VLA programs, from the shapes alone.

FLOPs count each multiply-add as two and only the work the algorithm
needs: causal attention over its lower triangle, the embedding lookup as
free, elementwise work (norms, rotary, softmax, the int8 codec) as free.
Bytes count what a call must move at least once: the weights of the
blocks that call runs (bfloat16), the rows of the embedding table it
gathers, its inputs and its outputs.  Activations inside a call are not
counted, so the least time they give is a lower bound.

Terms, for ``S = n_patches + text`` rows per observation and ``B``
observations per call:

* ViT block: ``8 P dv^2`` (q, k, v, o) + ``4 P^2 dv`` (scores and
  values, bidirectional) + ``24 P dv^2`` (SwiGLU of width ``4 dv``);
  projection ``2 P dv d``.
* LLM block: ``2 S d (H hd + 2 KV hd) + 2 S H hd d`` (projections) +
  ``4 hd H S (S + 1) / 2`` (causal scores and values) + ``6 S d ff``.
* detok head: ``2 A d V`` (unembedding at the ``A = action_dim``
  positions over the full vocabulary).
* DiT head, per denoising step over a horizon of ``h`` actions:
  ``2 h a dd`` (action in) + ``2 * 64 dd`` (timestep) + per block
  ``12 dd^2`` (modulation) + ``8 h dd^2`` + ``4 h^2 dd`` + ``16 h dd^2``,
  then ``4 dd^2`` (final modulation) + ``2 h dd a`` (out); the
  cognition projection ``2 d dd`` once per sample.
"""
from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2
F32 = 4
INT32 = 4
CUT_BLOCK = 128


def _hd(m):
    return m["head_dim"] or m["d_model"] // m["n_heads"]


def vit_flops(m: Dict) -> float:
    P, dv, d = m["n_patches"], m["vit_dim"], m["d_model"]
    block = 32 * P * dv ** 2 + 4 * P ** 2 * dv
    return m["vit_layers"] * block + 2 * P * dv * d


def llm_block_flops(m: Dict, S: int) -> float:
    d, H, KV, ff, hd = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["d_ff"], _hd(m))
    proj = 2 * S * d * (H * hd + 2 * KV * hd) + 2 * S * H * hd * d
    attn = 4 * hd * H * S * (S + 1) / 2
    return proj + attn + 6 * S * d * ff


def head_flops(m: Dict) -> float:
    d = m["d_model"]
    if m["vla_action_head"] == "detok":
        return 2 * m["action_dim"] * d * m["vocab_size"]
    dd, h, a = m["dit_dim"], m["action_horizon"], m["action_dim"]
    block = 12 * dd ** 2 + 24 * h * dd ** 2 + 4 * h ** 2 * dd
    step = (2 * h * a * dd + 2 * 64 * dd + m["dit_layers"] * block
            + 4 * dd ** 2 + 2 * h * dd * a)
    return 2 * d * dd + m["diffusion_steps"] * step


def rows(m: Dict, text: int) -> int:
    return m["n_patches"] + text


def step_flops(m: Dict, text: int) -> float:
    """Model FLOPs of one robot control step (one observation)."""
    S = rows(m, text)
    return vit_flops(m) + m["n_layers"] * llm_block_flops(m, S) \
        + head_flops(m)


def _llm_block_params(m: Dict) -> int:
    d, H, KV, ff, hd = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["d_ff"], _hd(m))
    return 2 * d + d * (H * hd + 2 * KV * hd) + H * hd * d + 3 * d * ff


def _vit_params(m: Dict) -> int:
    dv, d = m["vit_dim"], m["d_model"]
    block = 2 * dv + 4 * dv ** 2 + 12 * dv ** 2
    return m["n_patches"] * dv + m["vit_layers"] * block + dv + dv * d


def _head_params(m: Dict) -> int:
    d = m["d_model"]
    vp = -(-m["vocab_size"] // 16) * 16
    n = d + vp * d                                     # final norm, head
    if m["vla_action_head"] == "dit":
        dd, a, L = m["dit_dim"], m["action_dim"], m["dit_layers"]
        n += a * dd + d * dd + 64 * dd + dd * 2 * dd + dd * a
        n += L * (6 * dd ** 2 + 4 * dd ** 2 + 8 * dd ** 2)
    return n


def cut_bytes(m: Dict, text: int, batch: int) -> int:
    n = batch * rows(m, text) * m["d_model"]
    return n + n // CUT_BLOCK * F32                    # int8 codes + scales


def edge_cost(m: Dict, split: int, batch: int, text: int
              ) -> Tuple[float, float]:
    """``(flops, bytes)`` of one edge call: ViT, text embedding, trunk
    blocks ``[0, split - vit_layers)``, int8 encode of the cut."""
    e = split - m["vit_layers"]
    S = rows(m, text)
    flops = batch * (vit_flops(m) + e * llm_block_flops(m, S))
    weights = (_vit_params(m) + e * _llm_block_params(m)
               + min(batch * text, m["vocab_size"]) * m["d_model"]) * BF16
    inputs = batch * (m["n_patches"] * m["vit_dim"] * BF16 + text * INT32)
    return flops, weights + inputs + cut_bytes(m, text, batch)


def cloud_cost(m: Dict, split: int, batch: int, text: int
               ) -> Tuple[float, float]:
    """``(flops, bytes)`` of one cloud call: int8 decode, trunk blocks
    ``[split - vit_layers, n_layers)``, final norm and the action head."""
    e = split - m["vit_layers"]
    S = rows(m, text)
    flops = batch * ((m["n_layers"] - e) * llm_block_flops(m, S)
                     + head_flops(m))
    weights = ((m["n_layers"] - e) * _llm_block_params(m)
               + _head_params(m)) * BF16
    if m["vla_action_head"] == "detok":
        out = batch * m["action_dim"] * (-(-m["vocab_size"] // 16) * 16) \
            * BF16 + batch * m["action_dim"] * F32
    else:
        out = batch * m["action_horizon"] * m["action_dim"] * F32
    return flops, cut_bytes(m, text, batch) + weights + out
