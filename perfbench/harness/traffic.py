"""The general traffic generator: everything a mix needs, made from
``--seed`` and the mix's data file.

* ``observation_ring``: distinct observations (patch embeddings of the
  configuration's width and text token ids), drawn on the host, grouped
  into calls of ``batch`` robots.
* ``bandwidth_trace``: the link's bandwidth per control tick, from the
  mix's ``bandwidth_trace`` parameters: a two-state Markov regime (good
  and degraded), AR(1) log-noise, a diurnal swing and random congestion
  dips.  The arithmetic is a copy of the program's seeded trace
  generator at the time the benchmark was written (three bulk draws in
  the order regime uniforms, AR(1) normals, spike uniforms), kept here
  so that the yardstick does not move with the program.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def observation_ring(m: Dict, traffic: Dict, seed: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """``(patches, tokens)`` of shape ``(calls, batch, n_patches,
    vit_dim)`` (bfloat16) and ``(calls, batch, text_tokens)`` (int32):
    ``traffic["ring"]`` distinct observations in calls of
    ``traffic["batch"]``."""
    import ml_dtypes
    ring, batch = int(traffic["ring"]), int(traffic["batch"])
    if ring % batch:
        raise ValueError(f"ring {ring} is not a whole number of batches "
                         f"of {batch}")
    rng = _rng(seed, 1)
    calls = ring // batch
    patches = rng.standard_normal(
        (calls, batch, m["n_patches"], m["vit_dim"]), np.float32)
    tokens = rng.integers(0, m["vocab_size"],
                          (calls, batch, int(traffic["text_tokens"])),
                          dtype=np.int32)
    return patches.astype(ml_dtypes.bfloat16), tokens


def _regime_chain(u: np.ndarray, p_degrade: float, p_recover: float
                  ) -> np.ndarray:
    bad = np.zeros(len(u), dtype=bool)
    is_bad = False
    for t, x in enumerate(u):
        if not is_bad:
            if x < p_degrade:
                bad[t] = is_bad = True
        elif x < p_recover:
            is_bad = False
        else:
            bad[t] = True
    return bad


def bandwidth_trace(n_steps: int, p: Dict, seed: int) -> np.ndarray:
    """Bytes/s at each of ``n_steps`` ticks (see the module docstring)."""
    rng = np.random.default_rng(int(seed) % (1 << 64))
    n = int(n_steps)
    u_reg = rng.random(n)
    eps = rng.normal(0.0, p["ar_sigma"], n)
    u_spike = rng.random(n)
    bad = _regime_chain(u_reg, p["p_degrade"], p["p_recover"])
    # AR(1) as a convolution with rho**k, cut where |rho|**k < 1e-18
    rho = p["ar_rho"]
    klen = n if abs(rho) >= 1.0 else min(
        n, int(np.ceil(np.log(1e-18) / np.log(abs(rho)))) + 1)
    x = np.convolve(eps, rho ** np.arange(klen))[:n] if rho else eps
    base = np.where(bad, p["bad_bps"], p["mean_bps"])
    diurnal = 1.0 + p["diurnal_amp"] * np.sin(
        2 * np.pi * np.arange(n) / p["diurnal_period"])
    v = base * np.exp(x) * diurnal
    v = np.where(u_spike < p["spike_prob"], v * p["spike_depth"], v)
    return np.maximum(v, p["floor_bps"])
