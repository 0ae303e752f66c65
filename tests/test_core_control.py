"""Pool, adjustment, predictor, network sim, controller, elasticity."""
import io
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import (NetworkSim, PredictorConfig, RoboECC, Thresholds,
                        TraceConfig, Workload, adjust, build_graph,
                        build_pool, calibrate_thresholds, check_granularity,
                        generate_trace, lstm_forward, pool_transfer_profile,
                        search, train_predictor)
from repro.core.hardware import A100, ORIN
from repro.core.predictor import Predictor, _forecast, _normalise


@pytest.fixture(scope="module")
def openvla_graph():
    return build_graph(get_config("openvla-7b"), Workload())


def test_pool_overhead_band(openvla_graph):
    seg = search(openvla_graph, ORIN, A100, 10e6, cloud_budget_bytes=12.1e9)
    pool = build_pool(openvla_graph, seg.split, overhead_target=0.03)
    assert pool.start <= seg.split <= pool.end
    assert 0 < pool.overhead_frac <= 0.035
    assert len(list(pool.splits())) >= 2


def test_pool_prefers_many_candidates():
    g = build_graph(get_config("cogact-7b"), Workload(decode_steps=0))
    # put the split right at the llm -> dit boundary
    first_dit = next(i for i, c in enumerate(g) if c.kind == "dit")
    pool = build_pool(g, first_dit, overhead_target=0.026)
    # greedy-cheapest growth must pick up several cheap DiT layers
    assert pool.end - pool.start >= 3
    vols = pool_transfer_profile(g, pool)
    assert max(vols) > min(vols)   # spans a structure transition


def test_adjust_directions(openvla_graph):
    g = build_graph(get_config("cogact-7b"), Workload(decode_steps=0))
    first_dit = next(i for i, c in enumerate(g) if c.kind == "dit")
    pool = build_pool(g, first_dit)
    thr = Thresholds(high=2e6, low=-2e6)
    vols = pool_transfer_profile(g, pool)
    splits = list(pool.splits())
    up = adjust(g, pool, first_dit, 15e6, 10e6, thr)
    dn = adjust(g, pool, first_dit, 1e6, 10e6, thr)
    hold = adjust(g, pool, first_dit, 10.5e6, 10e6, thr)
    assert up.reason == "up" and up.split == splits[int(np.argmax(vols))]
    assert dn.reason == "down" and dn.split == splits[int(np.argmin(vols))]
    assert hold.reason == "hold" and hold.split == first_dit


def test_calibrate_thresholds():
    rng = np.random.default_rng(0)
    deltas = rng.normal(0, 1e6, 500)

    def eval_fn(thr):
        # toy objective: prefer moderate thresholds
        return abs(thr.high - 1.2e6) + abs(thr.low + 0.8e6)

    thr = calibrate_thresholds(deltas, eval_fn)
    assert thr.low < 0 < thr.high


def test_trace_reproducible():
    a = generate_trace(500, seed=7)
    b = generate_trace(500, seed=7)
    c = generate_trace(500, seed=8)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.min() > 0


def test_network_sim_transfer():
    tr = np.full(10, 10e6)
    net = NetworkSim(tr, rtt_s=0.005)
    assert abs(net.transfer_s(100e3) - (0.01 + 0.005)) < 1e-9


def test_predictor_beats_trivial():
    trace = generate_trace(3000, TraceConfig(ar_sigma=0.05), seed=3)
    pred, losses = train_predictor(trace[:2500],
                                   PredictorConfig(epochs=150), seed=0)
    # single-batch losses are noisy anchors; compare 10-epoch means so the
    # convergence check doesn't hinge on one lucky/unlucky first batch
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) * 0.8
    # one-step predictions should be in a sane band
    w = pred.cfg.window
    errs, base = [], []
    for t in range(2500, 2600):
        p = pred.predict(trace[t - w:t])
        errs.append(abs(p - trace[t]))
        base.append(abs(trace[t - 1] - trace[t]))
    assert np.median(errs) < 3 * np.median(base) + 1e5


@pytest.fixture(scope="module")
def forecast_case():
    """A briefly trained predictor and 200 windows of a seeded trace; the
    first is the padded window a fresh ``NetworkSim`` hands over."""
    trace = generate_trace(1400, seed=5)
    pred, _ = train_predictor(trace[:1000], PredictorConfig(epochs=10))
    w = pred.cfg.window
    net = NetworkSim(trace[1000:])
    net.step(3)
    windows = [net.window(w)]
    windows += [trace[t - w:t] for t in range(1100, 1299)]
    assert len(windows[0]) == w and windows[0][0] == windows[0][w - 4]
    return pred, windows


def test_forecast_is_exact(forecast_case):
    """One compiled program gives the eager composition's forecast bit for
    bit, and predictors with other reference rates share that program."""
    pred, windows = forecast_case
    ref = pred.ref_bps
    for win in windows:
        x = jnp.asarray(_normalise(win, ref), jnp.float32)[None, :]
        want = float(jnp.exp(lstm_forward(pred.params, x)[0]) * ref)
        assert pred.predict(win) == want
    jax.clear_caches()
    for scale in (1.0, 0.3, 7.5):
        other = Predictor(pred.params, pred.cfg, ref * scale)
        assert np.isfinite(other.predict(windows[0]))
    assert _forecast._cache_size() == 1


def test_forecast_compiles_one_program(forecast_case):
    pred, windows = forecast_case
    jax.clear_caches()
    buf = io.StringIO()
    handler = logging.StreamHandler(buf)
    logger = logging.getLogger("jax")
    logger.addHandler(handler)
    try:
        with jax.log_compiles():
            pred.predict(windows[1])
    finally:
        logger.removeHandler(handler)
    compiled = [line for line in buf.getvalue().splitlines()
                if line.startswith("Compiling ")]
    assert len(compiled) == 1 and compiled[0].startswith(
        "Compiling jit(_forecast)"), compiled


def test_granularity_check():
    assert check_granularity(0.05, 0.137, 0.094)
    assert not check_granularity(0.2, 0.137, 0.094)


def test_controller_end_to_end():
    cfg = get_config("openvla-7b")
    ctl = RoboECC(cfg, ORIN, A100, cloud_budget_bytes=12.1e9)
    trace = generate_trace(1500, seed=1)
    ctl.fit_predictor(trace[:1000], PredictorConfig(epochs=60))
    net = NetworkSim(trace[1000:])
    net.step(40)
    res = [ctl.tick(net) for _ in range(30)]
    assert all(r.total_s > 0 for r in res)
    assert all(ctl.pool.contains(r.split) for r in res)
    # warm adjustment decisions are fast (paper: 10.7ms on their host)
    warm = [r.adjust_overhead_s for r in res[5:]]
    assert np.mean(warm) < 0.25


def test_elastic_replan_cloud_only():
    cfg = get_config("openvla-7b")
    ctl = RoboECC(cfg, ORIN, A100, cloud_budget_bytes=12.1e9)
    assert ctl.split > 0
    # edge tier dies: model a dead edge as ~zero compute capability
    dead = ORIN.with_eta(1e-9, 1e-9)
    seg = ctl.replan(edge=dead)
    assert seg.split == 0          # cloud-only fallback


def test_elastic_pool_heartbeat_drives_replan_cycle():
    """ElasticPool heartbeat timeout -> on_change -> RoboECC.replan():
    losing the edge tier degrades to cloud-only (split=0); its re-join
    re-runs Alg. 1 and restores the original collaborative split."""
    from repro.runtime.scheduler import ElasticPool

    cfg = get_config("openvla-7b")
    ctl = RoboECC(cfg, ORIN, A100, cloud_budget_bytes=12.1e9)
    s0, pool0 = ctl.split, ctl.pool
    assert s0 > 0
    dead_edge = ORIN.with_eta(1e-9, 1e-9)
    replans = []

    def on_change(live):
        if "edge" in live:
            seg = ctl.replan(edge=ORIN, cloud_budget_bytes=12.1e9)
        else:
            # cloud-only fallback must host the whole model: lift the budget
            seg = ctl.replan(edge=dead_edge)
        replans.append(seg.split)

    pool = ElasticPool(on_change=on_change, timeout_s=1.0)
    pool.heartbeat("edge", 0.0)
    pool.heartbeat("cloud", 0.0)
    assert pool.live(0.5) == ["cloud", "edge"]

    pool.heartbeat("cloud", 2.0)          # edge silent past the timeout
    assert pool.live(2.0) == ["cloud"]
    assert ctl.split == 0                 # degraded to cloud-only
    assert ctl.pool.contains(0)

    pool.heartbeat("edge", 2.5)           # edge re-joins
    assert pool.live(2.5) == ["cloud", "edge"]
    assert ctl.split == s0                # Alg. 1 re-ran and restored plan
    assert (ctl.pool.start, ctl.pool.end) == (pool0.start, pool0.end)
    # on_change fired for join, loss, re-join (initial join included)
    assert replans[-2:] == [0, s0]
