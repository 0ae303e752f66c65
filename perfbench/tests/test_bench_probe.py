"""The reading of the program's own spans and scopes (``harness/inside.py``,
``probe.py``): scope maps and interval arithmetic on hand-built inputs,
the six ``roboecc/`` host spans nested in the benchmark's spans on a CPU
run, and the readings of 4-step traces recorded on one TPU v5e
(``probe.py --record``) with every tier's operation time attributed."""
import glob
import json
import math
import os

import pytest

from bench_small import SMALL_MODEL, SMALL_TRAFFIC
from test_bench_trace import Recorded

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = sorted(glob.glob(os.path.join(BENCH, "tests", "data", "probe",
                                         "*.xplane.pb.gz")))

HLO = """HloModule jit__cloud_fwd, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %mul = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(_cloud_fwd)/head/mul"}
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %gte = f32[4]{0} get-tuple-element(%p), index=1, metadata={op_name="jit(_cloud_fwd)/trunk/while/body/x"}
  ROOT %copy.3 = f32[4]{0} copy(%gte)
}

%cond (p.1: (s32[], f32[4])) -> pred[] {
  %p.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt = pred[] compare(%p.1, %p.1), direction=LT, metadata={op_name="lt"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %dq = f32[4]{0} convert(%a), metadata={op_name="jit(_cloud_fwd)/decode/jit(dequantize)/convert_element_type"}
  %copy.1 = f32[4]{0} copy(%dq)
  %while.1 = (s32[], f32[4]{0}) while(%copy.1), condition=%cond, body=%body
  %fusion.2 = f32[4]{0} fusion(%while.1), kind=kLoop, calls=%fused_computation
  ROOT %copy.2 = f32[4]{0} copy(%a)
}
"""


def test_scope_map_follows_callees_and_loops():
    """``copy.1`` and ``copy.2`` sit in a computation of three scopes and
    name none: they stay out of the map."""
    from harness.inside import scope_map
    assert scope_map(HLO) == {
        "mul": "head", "param_0": "head", "fusion.2": "head",
        "p": "trunk", "gte": "trunk", "copy.3": "trunk", "while.1": "trunk",
        "p.1": "trunk", "lt": "trunk", "dq": "decode"}


def test_scope_seconds_on_hand_built_intervals():
    from harness.inside import op_scopes, scope_seconds
    scopes = {"dq": "decode", "gte": "trunk", "copy.3": "trunk",
              "fusion.2": "head", "while.1": "trunk"}
    ops = [("%dq = f32[4] convert(%a)", 0.0, 2.0),
           ("%while.1 = (s32[]) while(%copy.1)", 2.0, 8.0),
           ("%gte = f32[4] get-tuple-element(%p)", 2.0, 4.0),
           ("%copy.3 = f32[4] copy(%gte)", 3.0, 5.0),
           ("%fusion.2 = f32[4] fusion(%while.1)", 6.0, 9.0),
           ("%copy.2 = f32[4] copy(%a)", 9.0, 10.5),
           ("%fusion.2 = f32[4] fusion(%while.1)", 21.0, 23.0),
           ("%fusion.2 = f32[4] fusion(%while.1)", 22.0, 24.0)]
    calls = op_scopes(ops, [(0.0, 10.0), (20.0, 30.0)], scopes)
    assert len(calls) == 2
    assert [scope_seconds(calls[0], s) for s in
            ("decode", "trunk", "head", "other")] == [2.0, 3.0, 3.0, 1.0]
    assert scope_seconds(calls[1], "head") == 3.0
    assert scope_seconds(calls[1], "trunk") == 0.0


def test_idle_gaps_in_program_on_hand_built_intervals():
    from harness.inside import group_by, idle_by_span, idle_inside
    busy = [(1.0, 2.0), (5.0, 6.0), (13.0, 14.0)]
    spans = {"roboecc/serve/edge": [(0.0, 4.0)],
             "roboecc/serve/wait": [(2.5, 7.0)]}
    gaps = idle_by_span(busy, 0.0, 16.0, spans, {"serve": [(0.0, 9.5)]})
    # [2, 5] splits at 2.5 and 4 (edge is the innermost over [2.5, 4]);
    # [6, 13] at 7 and 9.5
    assert gaps == {"roboecc/serve/edge": 3.0, "roboecc/serve/wait": 2.0,
                    "serve": 2.5, "other": 5.5}
    assert idle_inside(busy, [(0.0, 4.0), (2.5, 7.0)]) == 5.0
    assert group_by([(0.0, 10.0), (20.0, 30.0)],
                    [(1.0, 2.0), (21.0, 22.0), (25.0, 26.0), (40.0, 41.0)]
                    ) == [[(1.0, 2.0)], [(21.0, 22.0), (25.0, 26.0)]]


def _inside(a, outer):
    return any(lo <= a[0] and a[1] <= hi for lo, hi in outer)


def test_program_spans_nest_in_their_callers():
    """A CPU-sized ``solo`` run with the controller ticking: in each traced
    window, each step holds the three tick spans, in order, inside the
    benchmark's ``tick``, and the three serve spans, in order, inside its
    ``serve``."""
    import probe
    import run
    steps = 2
    spec = run.load_cell("openvla-7b-standin.solo")
    traffic = {k: v for k, v in SMALL_TRAFFIC.items()
               if k != "predictor_epochs"}
    traffic["controller"] = dict(spec["traffic"]["controller"],
                                 predictor_epochs=2)
    out = probe.probe("openvla-7b-standin.solo", 2**31 + 11, steps=steps,
                      require_chip=False,
                      overrides={"model": SMALL_MODEL, "traffic": traffic})
    names = {"tick": ("forecast", "adjust", "price"),
             "serve": ("edge", "cloud", "wait")}
    assert len(out["windows"]) == probe.WINDOWS
    for win in out["windows"]:
        spans, bench = win["spans"], win["bench_spans"]
        assert set(spans) == {f"{k}/{n}" for k, v in names.items()
                              for n in v}
        for caller, parts in names.items():
            assert len(bench[caller]) == steps
            for i in range(steps):
                seq = [spans[f"{caller}/{n}"][i] for n in parts]
                assert all(_inside(s, bench[caller][i:i + 1]) for s in seq)
                assert all(a[1] <= b[0] for a, b in zip(seq, seq[1:]))


def test_there_are_probe_recordings():
    assert len(RECORDED) == 4


def _recorded(path):
    from harness import inside
    from harness import trace as T
    base = path[:-len(".xplane.pb.gz")]
    with open(base + ".calls.json") as f:
        rec = json.load(f)
    with open(base + ".scopes.json") as f:
        scopes = json.load(f)
    pd = T.load(path)
    tr = T.Trace(pd)
    cell = Recorded(rec["workload"])
    ins = inside.Inside(tr, pd, cell.programs(), scopes, len(rec["calls"]))
    return ins, tr, cell, rec


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_scopes_and_spans_read(path):
    import run
    from harness import inside
    ins, tr, cell, rec = _recorded(path)
    for prog in ("edge", "cloud"):
        assert ins.attributed(prog) >= 0.95, (prog, ins.attributed(prog))
    w = run.Window(tr, cell, rec["calls"], run.peaks_for("TPU v5 lite"))
    got = {m: f(ins) for m, f in inside.METRICS.items()}
    solo = rec["workload"].endswith(".solo")
    want = ["head_ms", "codec_ms", "serve_gap_ms"] + (
        ["forecast_ms", "adjust_ms", "price_ms"] if solo else [])
    for m in want:
        assert got[m] is not None and math.isfinite(got[m]) \
            and got[m] >= 0, (m, got[m])
    cloud_ms = run._module("metrics", "cloud_ms").read(w)
    edge_ms = run._module("metrics", "edge_ms").read(w)
    assert got["head_ms"] < cloud_ms
    assert got["codec_ms"] < edge_ms + cloud_ms
    if solo:
        assert ins.tick_cover() >= 0.9
    else:
        assert got["forecast_ms"] is None and ins.tick_cover() is None
    assert ins.device_scopes() and ins.idle_gaps_in_program()
    # the recordings' clocks agree: each edge program starts after the
    # host began dispatching it
    assert all(t > 0 for t in ins.edge_after_dispatch_s())
    assert len(ins.edge_after_dispatch_s()) == len(rec["calls"])
    per_call = ins.programs_per_call()
    assert per_call["jit__edge_fwd"] == per_call["jit__cloud_fwd"] == 1


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_every_declared_metric_comes_out_of_a_recording(path):
    import run
    ins, tr, cell, rec = _recorded(path)
    w = run.Window(tr, cell, rec["calls"], run.peaks_for("TPU v5 lite"))
    for m in cell.spec["per_layer"]:
        v = run._module("metrics", m["name"]).read(w)
        assert v is not None and math.isfinite(v) and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, (m["name"], v)
