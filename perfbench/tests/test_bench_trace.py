"""The reduction from a device trace to the per-layer metrics, on small
traces recorded on one TPU v5e (four traced steps of each cell, from
``run.run(workload, seed, 1, True, keep_trace=path, overrides={"traffic":
{"trace_steps": 4, "check_requests": 4}})``, gzipped): every metric the
cell declares comes out, finite, and a share lies in (0, 100]."""
import glob
import json
import math
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
DATA = os.path.join(BENCH, "tests", "data")
TRACES = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb.gz")))


class Recorded:
    """The parts of a cell the reduction reads, rebuilt from files."""

    def __init__(self, workload):
        import run
        spec = run.load_cell(workload)
        self.spec = spec
        self.m = spec["config"]["model"]
        self.text = int(spec["traffic"]["text_tokens"])
        self.costs = run._module("costs", spec["config"]["reference"])

    def programs(self):
        return {"edge": "_edge_fwd", "cloud": "_cloud_fwd"}

    def call_costs(self, rec):
        s, b = rec["split"], rec["robots"]
        return {"edge": self.costs.edge_cost(self.m, s, b, self.text),
                "cloud": self.costs.cloud_cost(self.m, s, b, self.text)}

    def step_flops(self):
        return self.costs.step_flops(self.m, self.text)


def _window(path):
    import run
    from harness import trace as T
    with open(path[:-len(".xplane.pb.gz")] + ".calls.json") as f:
        rec = json.load(f)
    cell = Recorded(rec["workload"])
    tr = T.Trace(T.load(path))
    peaks = run.peaks_for("TPU v5 lite")
    return run.Window(tr, cell, rec["calls"], peaks), cell, tr


def test_there_are_recorded_traces():
    assert TRACES


@pytest.mark.parametrize("path", TRACES, ids=os.path.basename)
def test_every_declared_metric_comes_out(path):
    import run
    w, cell, tr = _window(path)
    assert w.busy_s > 0 and w.busy_s <= w.window_s
    for m in cell.spec["per_layer"]:
        v = run._module("metrics", m["name"]).read(w)
        assert v is not None and math.isfinite(v), m["name"]
        if m["unit"] == "%":
            assert 0 < v <= 100, (m["name"], v)
        else:
            assert v > 0, (m["name"], v)
    assert tr.top_ops() and tr.idle_gaps()


@pytest.mark.parametrize("path", TRACES, ids=os.path.basename)
def test_a_missing_program_fails_loudly(path):
    from harness.trace import TraceError
    w, cell, tr = _window(path)
    cell.programs = lambda: {"edge": "_no_such_fwd", "cloud": "_cloud_fwd"}
    with pytest.raises(TraceError, match="planes"):
        w.program_s("edge")


def test_module_names_match_with_any_suffix():
    from harness.trace import module_pattern
    p = module_pattern("_cloud_fwd")
    assert p.match("jit__cloud_fwd(12)")
    assert p.match("jit__cloud_fwd")
    assert p.match("jit__cloud_fwd.3")
    assert not p.match("jit__cloud_fwd_mid(1)")
    assert not p.match("jit__edge_fwd(1)")
