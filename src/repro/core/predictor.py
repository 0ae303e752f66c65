"""Network fluctuation predictor — lightweight LSTM (paper §IV-B-1).

Pure-JAX LSTM trained on historical bandwidth traces to predict the
next-tick bandwidth.  Constraint Eq. 3: the input granularity ``t_input``
must be finer than ``min(t_cloud, t_edge)`` — enforced by
:func:`check_granularity`, which the controller calls with the modeled
per-tier latencies.

Inputs are log-normalised bandwidth windows; the model is deliberately tiny
(default hidden=64 → ~70 KB, vs the paper's 20.1 MB LSTM; Fig. 6 reports it
as negligible either way).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class PredictorConfig:
    window: int = 32
    hidden: int = 64
    lr: float = 1e-2
    epochs: int = 200
    batch: int = 64


def init_lstm(key: jax.Array, hidden: int) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s = hidden ** -0.5
    return {
        "wx": jax.random.normal(k1, (1, 4 * hidden), jnp.float32) * s,
        "wh": jax.random.normal(k2, (hidden, 4 * hidden), jnp.float32) * s,
        "b": jnp.zeros((4 * hidden,), jnp.float32),
        "head": jax.random.normal(k3, (hidden, 1), jnp.float32) * s,
    }


def lstm_forward(params: Dict, window: jax.Array) -> jax.Array:
    """window: (B, T) log-normalised -> (B,) next-value prediction."""
    B, T = window.shape
    H = params["wh"].shape[0]

    def cell(carry, x_t):
        h, c = carry
        g = x_t[:, None] @ params["wx"] + h @ params["wh"] + params["b"]
        i, f, o, u = jnp.split(g, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(u)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), None

    h0 = jnp.zeros((B, H)), jnp.zeros((B, H))
    (h, _), _ = jax.lax.scan(cell, h0, window.T)
    return (h @ params["head"])[:, 0]


def _normalise(bw: np.ndarray, ref: float) -> np.ndarray:
    return np.log(np.maximum(bw, 1.0) / ref)


@jax.jit
def _forecast(params: Dict, x: jax.Array, ref: jax.Array) -> jax.Array:
    """One compiled program for a forecast: the LSTM over the (1, W)
    normalised window and the way back to bytes/s.  ``ref`` is traced, so
    every predictor shares the program."""
    return jnp.exp(lstm_forward(params, x)[0]) * ref


@dataclasses.dataclass
class Predictor:
    params: Dict
    cfg: PredictorConfig
    ref_bps: float

    def predict(self, window_bps: np.ndarray) -> float:
        """Next-tick bandwidth: the window normalised on the host, one
        program on the device, one fetch."""
        x = _normalise(window_bps, self.ref_bps).astype(np.float32)[None, :]
        return float(_forecast(self.params, x, np.float32(self.ref_bps)))

    def n_bytes(self) -> int:
        return sum(v.size * v.dtype.itemsize
                   for v in jax.tree_util.tree_leaves(self.params))


def train_predictor(trace_bps: np.ndarray, cfg: PredictorConfig = PredictorConfig(),
                    seed: int = 0) -> Tuple[Predictor, list]:
    """Train on (window -> next tick) pairs from a historical trace."""
    ref = float(np.mean(trace_bps))
    x = _normalise(trace_bps, ref)
    W = cfg.window
    wins = np.stack([x[i:i + W] for i in range(len(x) - W)])
    tgts = x[W:]
    key = jax.random.PRNGKey(seed)
    params = init_lstm(key, cfg.hidden)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)

    @jax.jit
    def step(params, m, v, t, xb, yb):
        def loss_fn(p):
            pred = lstm_forward(p, xb)
            return jnp.mean((pred - yb) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(params)
        b1, b2, eps = 0.9, 0.999, 1e-8
        m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        mh = jax.tree_util.tree_map(lambda a: a / (1 - b1 ** t), m)
        vh = jax.tree_util.tree_map(lambda a: a / (1 - b2 ** t), v)
        params = jax.tree_util.tree_map(
            lambda p, a, b: p - cfg.lr * a / (jnp.sqrt(b) + eps),
            params, mh, vh)
        return params, m, v, loss

    rng = np.random.default_rng(seed)
    losses = []
    for e in range(1, cfg.epochs + 1):
        idx = rng.integers(0, len(wins), cfg.batch)
        params, m, v, loss = step(params, m, v, jnp.float32(e),
                                  jnp.asarray(wins[idx]), jnp.asarray(tgts[idx]))
        losses.append(float(loss))
    return Predictor(params, cfg, ref), losses


def check_granularity(t_input_s: float, t_cloud_s: float, t_edge_s: float
                      ) -> bool:
    """Paper Eq. 3: t_input < min(t_cloud, t_edge)."""
    return t_input_s < min(t_cloud_s, t_edge_s)
