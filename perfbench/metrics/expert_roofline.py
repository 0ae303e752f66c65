"""Routed experts' share of their roofline: each traced call's least
time for its routed experts (the larger of their FLOPs over the peak
rate and the weight bytes of the held experts it reached over the peak
bandwidth, from the cost functions' ``expert_cost`` and the call's
routing as the reference computes it) over the device time under the
program's ``experts`` scope, summed over the window's calls."""
from harness.experts import call_counts, window_expert_s


def read(w):
    t = window_expert_s(w)
    counts = call_counts(w.cell, w.calls)
    if t is None or counts is None:
        return None
    least = 0.0
    for n in counts:
        flops, nbytes = w.cell.costs.expert_cost(w.cell.m, n["routed"],
                                                 n["reached"])
        least += max(flops / w.peak_flops, nbytes / w.peak_bw)
    return 100.0 * least / sum(t)
