"""Routed experts: device time per call of the operations under the
program's ``experts`` scope (dispatch, grouped matmuls and combine of the
held experts in every MoE layer), both tiers summed."""
from harness.experts import window_expert_s


def read(w):
    t = window_expert_s(w)
    return None if t is None else 1e3 * sum(t) / len(t)
