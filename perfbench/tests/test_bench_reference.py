"""The plain reference agrees with the served path at a CPU size, on
every cell's traffic."""
import pytest

from bench_small import cells, small_run


@pytest.mark.parametrize("workload", cells())
def test_served_path_matches_reference(workload):
    r = small_run(workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"step_p50_ms", "step_p95_ms",
                                 "steps_per_s", "setup_s"}
    assert list(r)[-1] == "checks"
