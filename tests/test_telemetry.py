"""Flight-recorder telemetry (``core/telemetry.py``) and the Chrome
trace exporter (``runtime/trace_export.py``): sketch accuracy against
numpy percentiles, reservoir bounds/uniformity, the recorder's
off/sampled/full bit-identity guarantee across both engines, the drift
audit's reconciliation identity, and trace-event JSON structure.

Property checks run twice, following the repo's pattern
(``tests/test_events.py``): via ``hypothesis`` when the optional dep is
installed, and always as seeded numpy sweeps through the same checkers.
"""
import dataclasses
import json
import math

import numpy as np
import pytest

from repro.core.telemetry import (DRIFT_STAGES, DriftAudit, FlightRecorder,
                                  MetricsRegistry, QuantileSketch, Reservoir,
                                  Span)
from repro.runtime.fleet import (ArrivalProcess, FleetConfig, FleetSimulator,
                                 ReplicaEvent, run_fleet)
from repro.runtime.trace_export import chrome_trace, export_chrome_trace

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# -------------------------------------------------------- quantile sketch
def _check_sketch_accuracy(values, max_centroids=128):
    """Sketch quantiles land within a few centroid-widths of the exact
    percentiles, exact count/sum/min/max/mean, bounded memory."""
    sk = QuantileSketch(max_centroids)
    sk.extend(values)
    arr = np.asarray(values, dtype=float)
    assert sk.count == len(arr)
    assert sk.min == arr.min() and sk.max == arr.max()
    assert sk.mean == pytest.approx(arr.mean(), rel=1e-12, abs=1e-12)
    assert sk.n_centroids <= 2 * max_centroids
    span = float(arr.max() - arr.min())
    for q in (0.0, 0.01, 0.25, 0.50, 0.75, 0.95, 0.99, 1.0):
        est = sk.quantile(q)
        exact = float(np.quantile(arr, q))
        # rank-error style bound: generous, but catches gross breakage
        assert abs(est - exact) <= 0.05 * span + 1e-12, (
            f"q={q}: sketch {est} vs exact {exact}")


def test_sketch_seeded_sweeps():
    rng = np.random.default_rng(0)
    _check_sketch_accuracy(rng.normal(5.0, 2.0, size=10_000))
    _check_sketch_accuracy(rng.lognormal(0.0, 1.0, size=10_000))
    _check_sketch_accuracy(rng.uniform(-1.0, 1.0, size=3_000))
    _check_sketch_accuracy(np.arange(1000)[::-1].astype(float))
    _check_sketch_accuracy([3.0])
    _check_sketch_accuracy([1.0, 1.0, 1.0, 1.0])


@pytest.mark.parametrize("xs", [
    [0.0, 1.0], [3.0, -2.0, 7.5], [5.0, 1.0, 1.0, 9.0, 4.0],
    [0.0] * 9 + [1.0] * 3,
    [0.0, 0.5, 0.75, 1.0, 2.0, 3.0, 6.0, 7.0, 8.0, 9.0, 10.0],
    [-40009.0, -40008.0, -40005.0, -40004.0, -40003.0, -40002.0, -9841.0,
     -8004.0, -8003.0] + [-1.0] * 3 + [0.0] * 24,
    [float(x * x) for x in range(-12, 13)]])
def test_sketch_small_samples_match_numpy(xs):
    """Samples of up to ``2 * max_centroids - 1`` values, or of at most
    ``max_centroids`` distinct ones, interpolate as ``np.quantile``."""
    sk = QuantileSketch(16)
    sk.extend(xs)
    for q in (0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99):
        assert sk.quantile(q) == pytest.approx(float(np.quantile(xs, q)),
                                               abs=1e-12)


def test_sketch_few_distinct_values_stay_exact():
    """A long stream of few distinct values keeps one exact centroid per
    value through every merge."""
    rng = np.random.default_rng(5)
    xs = rng.choice([-3.0, 0.0, 0.5, 2.0, 1e4], size=20_000,
                    p=[0.1, 0.6, 0.1, 0.15, 0.05])
    sk = QuantileSketch(16)
    sk.extend(xs)
    assert sk.n_centroids <= 2 * 16
    for q in (0.01, 0.05, 0.1, 0.25, 0.5, 0.69, 0.7, 0.75, 0.95, 0.99):
        assert sk.quantile(q) == float(np.quantile(xs, q))


def test_sketch_empty_and_tails():
    sk = QuantileSketch()
    assert math.isnan(sk.quantile(0.5)) and math.isnan(sk.mean)
    assert sk.snapshot() == {"n": 0}
    sk.extend(range(100))
    assert sk.quantile(0.0) == 0.0 and sk.quantile(1.0) == 99.0
    snap = sk.snapshot()
    assert snap["n"] == 100 and snap["min"] == 0.0 and snap["max"] == 99.0
    assert snap["p50"] <= snap["p95"] <= snap["p99"] <= snap["max"]


def test_sketch_deterministic_same_stream():
    rng = np.random.default_rng(3)
    xs = rng.exponential(1.0, size=5000)
    a, b = QuantileSketch(64), QuantileSketch(64)
    a.extend(xs)
    b.extend(xs)
    assert a.quantile(0.5) == b.quantile(0.5)
    assert a.quantile(0.99) == b.quantile(0.99)
    assert a._cent == b._cent


if HAVE_HYPOTHESIS:
    @settings(deadline=None, max_examples=30)
    @given(st.lists(st.floats(-1e6, 1e6), min_size=1, max_size=2000),
           st.sampled_from([16, 64, 128]))
    def test_sketch_accuracy_property(xs, mc):
        _check_sketch_accuracy(xs, mc)


# --------------------------------------------------------------- reservoir
def _check_reservoir(n_stream, cap, seed):
    r = Reservoir(cap, seed=seed)
    kept_flags = [r.offer(i) for i in range(n_stream)]
    assert len(r) == min(cap, n_stream)
    assert r.n_seen == n_stream
    # kept items are a subset of the stream, no duplicates
    assert len(set(r.items)) == len(r.items)
    assert all(0 <= x < n_stream for x in r.items)
    # the first min(cap, n) offers are always kept at offer time
    assert all(kept_flags[: min(cap, n_stream)])
    return r


def test_reservoir_bounds_seeded_sweeps():
    for n, cap, seed in [(10, 16, 0), (16, 16, 1), (1000, 16, 2),
                         (1000, 1, 3), (100_000, 64, 4)]:
        _check_reservoir(n, cap, seed)


def test_reservoir_deterministic_and_isolated():
    a = _check_reservoir(5000, 32, seed=7)
    b = _check_reservoir(5000, 32, seed=7)
    assert a.items == b.items
    c = _check_reservoir(5000, 32, seed=8)
    assert a.items != c.items            # astronomically unlikely to tie


def test_reservoir_uniformity():
    """Every stream position is kept with probability cap/n: the mean
    kept index over many seeds must sit near the stream midpoint."""
    n, cap = 2000, 20
    means = [np.mean(_check_reservoir(n, cap, seed).items)
             for seed in range(200)]
    assert abs(np.mean(means) - n / 2) < n * 0.02


def test_reservoir_rejects_bad_cap():
    with pytest.raises(ValueError):
        Reservoir(0)


if HAVE_HYPOTHESIS:
    @settings(deadline=None, max_examples=30)
    @given(st.integers(0, 3000), st.integers(1, 64), st.integers(0, 99))
    def test_reservoir_bounds_property(n, cap, seed):
        _check_reservoir(n, cap, seed)


# -------------------------------------------------------- metrics registry
def test_metrics_registry_snapshot():
    m = MetricsRegistry()
    m.inc("a/total")
    m.inc("a/total", 2)
    m.set_gauge("g", 3.5)
    for v in (1.0, 2.0, 3.0):
        m.observe("h", v)
    snap = m.snapshot()
    assert snap["counters"] == {"a/total": 3}
    assert snap["gauges"] == {"g": 3.5}
    assert snap["hists"]["h"]["n"] == 3
    assert snap["hists"]["h"]["mean"] == pytest.approx(2.0)
    json.dumps(snap)                     # snapshot must be JSON-clean


# -------------------------------------------------------------- drift audit
def test_drift_join_and_reconcile():
    d = DriftAudit()
    pred = {"edge_s": 0.1, "uplink_s": 0.2, "queue_s": 0.0,
            "service_s": 0.3, "down_s": 0.0, "total_s": 0.6}
    meas = {"edge_s": 0.1, "uplink_s": 0.25, "queue_s": 0.02,
            "service_s": 0.3, "down_s": 0.0, "total_s": 0.67}
    d.join(pred, meas)
    s = d.summary()
    assert s["n_joined"] == 1
    assert s["stages"]["uplink_s"]["mean_err"] == pytest.approx(0.05)
    assert s["stages"]["queue_s"]["mean_err"] == pytest.approx(0.02)
    assert s["reconcile_max_abs_s"] < 1e-12
    # a broken decomposition is caught by the reconciliation tracker
    bad = dict(meas, total_s=1.0)
    d.join(pred, bad)
    assert d.reconcile_max_abs_s == pytest.approx(0.33)


# ---------------------------------------------------------- flight recorder
def test_recorder_rejects_bad_mode():
    with pytest.raises(ValueError):
        FlightRecorder(mode="on")


def test_recorder_sampling_is_key_pure():
    r = FlightRecorder(mode="sampled", sample_every=16)
    keys = list(range(100_000))
    frac = sum(r.want(k) for k in keys) / len(keys)
    assert 0.04 < frac < 0.09            # ~1/16, hash-spread
    assert [r.want(k) for k in keys[:100]] \
        == [r.want(k) for k in keys[:100]]
    full = FlightRecorder(mode="full")
    assert all(full.want(k) for k in keys[:100])


def test_recorder_cont_hooks_only_for_opened_rids():
    r = FlightRecorder(mode="full")
    r.cont_admit(7, 0.1, 1.0, 1e6, "cloud0")       # never opened: ignored
    assert r.pop_cont(7) is None
    assert "cloud/preemptions" not in r.metrics.counters
    r.cont_open(7)
    r.cont_admit(7, 0.1, 1.0, 1e6, "cloud0")
    r.cont_preempt(7, 2.0, "cloud0")
    st_ = r.pop_cont(7)
    assert st_["queue_s"] == pytest.approx(0.1)
    assert st_["preempts"] == 1 and st_["replica"] == "cloud0"
    assert len(st_["spans"]) == 2
    assert r.metrics.counters["cloud/preemptions"] == 1
    assert r.pop_cont(7) is None                    # popped exactly once


def _record_one(r, **kw):
    args = dict(req=1, lane="robot:a", t0_s=0.0, edge_s=0.1, uplink_s=0.2,
                queue_s=0.05, service_s=0.3, down_s=0.05, total_s=0.7,
                replica="cloud0")
    args.update(kw)
    r.record_request(**args)


def test_record_request_span_group_monotone():
    r = FlightRecorder(mode="full")
    _record_one(r, enc_s=0.02, dec_s=0.01)
    (group,) = r.spans.items
    names = [s.name for s in group]
    assert names == ["edge", "encode", "uplink", "decode", "queue",
                     "service", "downlink"]
    # spans tile the request: each starts where the previous ended
    for a, b in zip(group, group[1:]):
        assert b.t0_s == pytest.approx(a.t0_s + a.dur_s)
    assert group[0].t0_s == 0.0
    end = group[-1].t0_s + group[-1].dur_s
    assert end == pytest.approx(0.7)
    # queue/service ride the replica lane, the rest the robot lane
    by_name = {s.name: s for s in group}
    assert by_name["queue"].lane == "replica:cloud0"
    assert by_name["service"].lane == "replica:cloud0"
    assert by_name["edge"].lane == "robot:a"


def test_record_request_metrics_and_outcomes():
    r = FlightRecorder(mode="full")
    _record_one(r)
    _record_one(r, outcome="hedged")
    snap = r.snapshot()
    assert snap["n_recorded"] == 2
    assert snap["metrics"]["counters"]["requests/total"] == 2
    assert snap["metrics"]["counters"]["requests/hedged"] == 1
    assert snap["metrics"]["hists"]["latency/total_s"]["n"] == 2


# --------------------------------------------------- fleet-level integration
def _cfg(telemetry, engine="ticks", **kw):
    return FleetConfig(n_robots=48, n_ticks=100, seed=7, engine=engine,
                       telemetry=telemetry, telemetry_sample_every=4, **kw)


FLEET_VARIANTS = [
    dict(),
    dict(streamed=True, codecs=("identity", "int8"), multicut=True),
    dict(continuous=True, queue_aware=True, kv_budget_bytes=2e8),
]


@pytest.mark.parametrize("kw", FLEET_VARIANTS)
def test_recorder_on_is_bit_identical_modulo_metrics(kw):
    """The acceptance gate: telemetry compiled in and ENABLED must not
    perturb the simulation — every report field except ``metrics`` is
    dataclass-equal across off/sampled/full, on both engines."""
    reps = {(eng, mode): run_fleet(_cfg(mode, eng, **kw))
            for eng in ("ticks", "events")
            for mode in ("off", "sampled", "full")}
    base = dataclasses.replace(reps[("ticks", "off")], metrics=None)
    for key, rep in reps.items():
        assert dataclasses.replace(rep, metrics=None) == base, key
    assert reps[("ticks", "off")].metrics is None
    full = reps[("ticks", "full")].metrics
    sampled = reps[("ticks", "sampled")].metrics
    assert 0 < sampled["n_recorded"] < full["n_recorded"]


def test_sampled_set_identical_across_engines():
    """Hash-of-key sampling: the events engine records exactly the same
    request count as the tick loop (arrival order differs, keys don't)."""
    for kw in FLEET_VARIANTS:
        a = run_fleet(_cfg("sampled", "ticks", **kw)).metrics
        b = run_fleet(_cfg("sampled", "events", **kw)).metrics
        assert a["n_recorded"] == b["n_recorded"]
        assert a["metrics"]["counters"] == b["metrics"]["counters"]


def test_drift_reconciliation_on_seeded_run():
    """Per-stage drift sums must re-sum to the measured request latency
    to float tolerance — the PR's acceptance criterion."""
    for kw in FLEET_VARIANTS:
        m = run_fleet(_cfg("full", "events", **kw)).metrics
        d = m["drift"]
        assert d["n_joined"] == m["n_recorded"]
        assert d["reconcile_max_abs_s"] < 1e-9
        for k in DRIFT_STAGES:
            if k in d["stages"]:
                assert math.isfinite(d["stages"][k]["mean_err"])


def test_open_loop_arrivals_recorded():
    cfg = _cfg("full", "events", continuous=True, slo_s=1.0,
               arrival_processes=(ArrivalProcess(
                   name="ap0", arch="llama3.2-3b", rate_hz=25.0),))
    rep = run_fleet(cfg)
    counters = rep.metrics["metrics"]["counters"]
    assert counters["requests/total"] == rep.metrics["n_recorded"]
    assert rep.metrics["drift"]["n_joined"] > 0


def test_report_summary_mentions_modern_fields():
    rep = run_fleet(_cfg("off"))
    s = rep.summary()
    assert "p99" in s and "p99.9" in s
    assert "queue" in s and "preemptions" in s


# ------------------------------------------------------------ trace export
def _traced_sim(**kw):
    cfg = _cfg("full", "events", **kw)
    sim = FleetSimulator(cfg)
    rep = sim.run()
    return sim, rep


def test_chrome_trace_structure(tmp_path):
    sim, rep = _traced_sim(continuous=True, queue_aware=True,
                           kv_budget_bytes=2e8)
    path = export_chrome_trace(sim.recorder, str(tmp_path / "t.trace.json"))
    with open(path) as f:
        tr = json.load(f)                # valid JSON on disk
    assert set(tr) == {"traceEvents", "displayTimeUnit", "otherData"}
    evs = tr["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    ms = [e for e in evs if e["ph"] == "M"]
    assert xs and ms and len(xs) + len(ms) == len(evs)
    for e in xs:
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert "req" in e["args"]
    # every (pid, tid) an X event uses is named by a thread_name record
    named = {(e["pid"], e["tid"]) for e in ms if e["name"] == "thread_name"}
    assert {(e["pid"], e["tid"]) for e in xs} <= named
    # one lane per replica, plus robot-cohort lanes
    lanes = {e["args"]["name"] for e in ms if e["name"] == "thread_name"}
    assert any(ln.startswith("replica:") for ln in lanes)
    assert any(ln.startswith("robot:") for ln in lanes)
    # X events are globally time-sorted (exporter contract)
    ts = [e["ts"] for e in xs]
    assert ts == sorted(ts)
    assert tr["otherData"]["mode"] == "full"
    assert tr["otherData"]["spans_kept"] <= tr["otherData"]["spans_seen"]


def test_chrome_trace_lane_pids_partition_families():
    sim, _ = _traced_sim()
    tr = chrome_trace(sim.recorder)
    ms = [e for e in tr["traceEvents"] if e["ph"] == "M"]
    fam_of_pid = {}
    for e in ms:
        if e["name"] != "thread_name":
            continue
        fam = e["args"]["name"].split(":", 1)[0]
        assert fam_of_pid.setdefault(e["pid"], fam) == fam, (
            "two lane families share a pid")


def test_trace_reservoir_cap_respected():
    cfg = _cfg("full", "events", telemetry_cap=32)
    sim = FleetSimulator(cfg)
    sim.run()
    assert len(sim.recorder.spans) <= 32
    assert sim.recorder.spans.n_seen > 32
    tr = chrome_trace(sim.recorder)
    assert tr["otherData"]["spans_kept"] <= 32
