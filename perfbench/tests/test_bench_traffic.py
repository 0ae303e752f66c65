"""The traffic generator: seeded, repeatable, and the copied bandwidth
trace equal to the program's generator at the time it was copied."""
import dataclasses
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from harness import traffic as gen  # noqa: E402

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))
SMALL = {"n_patches": 16, "vit_dim": 64, "vocab_size": 512}


def _mix(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", MIXES)
def test_observation_ring_is_seeded_and_distinct(mix):
    t = _mix(mix)
    p1, k1 = gen.observation_ring(SMALL, t, 2**31 + 11)
    p2, k2 = gen.observation_ring(SMALL, t, 2**31 + 11)
    p3, _ = gen.observation_ring(SMALL, t, 2**31 + 12)
    assert p1.shape == (t["ring"] // t["batch"], t["batch"], 16, 64)
    assert k1.shape == (t["ring"] // t["batch"], t["batch"],
                        t["text_tokens"])
    assert np.array_equal(p1, p2) and np.array_equal(k1, k2)
    assert not np.array_equal(p1, p3)
    flat = p1.reshape(t["ring"], -1).astype(np.float32)
    assert len({row.tobytes() for row in flat}) == t["ring"]
    assert k1.min() >= 0 and k1.max() < SMALL["vocab_size"]


@pytest.mark.parametrize("seed", [0, 1, 12345, 2**31 + 99])
def test_bandwidth_trace_equals_program_generator(seed):
    from repro.core.network import TraceConfig, generate_trace
    p = dataclasses.asdict(TraceConfig())
    for mix in MIXES:
        assert _mix(mix)["bandwidth_trace"] == p
    assert np.array_equal(gen.bandwidth_trace(4000, p, seed),
                          generate_trace(4000, seed=seed))


def test_bandwidth_trace_is_seeded():
    p = _mix("solo")["bandwidth_trace"]
    a = gen.bandwidth_trace(500, p, 7)
    assert np.array_equal(a, gen.bandwidth_trace(500, p, 7))
    assert not np.array_equal(a, gen.bandwidth_trace(500, p, 8))
    assert (a >= p["floor_bps"]).all()


TICKING = [m for m in MIXES if _mix(m)["controller"]["adjust"]]


@pytest.mark.parametrize("mix", TICKING)
def test_window_stays_inside_the_bandwidth_trace(mix):
    """A mix whose controller ticks every step holds ticks for a whole
    window at ten times today's fastest step rate (~26 steps/s on one TPU
    v5e), after the predictor's history, warm-up and a traced window."""
    from repro.core.predictor import PredictorConfig
    t = _mix(mix)
    ctl = t["controller"]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    left = (ctl["trace_ticks"] - ctl["train_ticks"] - PredictorConfig().window
            - t["warmup_steps"] - t["trace_steps"])
    assert left >= seconds * 260
