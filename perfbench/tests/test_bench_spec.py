"""BENCHMARK.json keeps to its contract, and every name in it finds the
file that serves it."""
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["perfbench"]
    assert SPEC["command"] == ["python3", "perfbench/run.py"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("perfbench/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [x["name"] for x in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))


def test_end_to_end_bounds():
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in [m["name"] for m in SPEC["end_to_end"]]


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert m["moves"] in [e["name"] for e in SPEC["end_to_end"]]
    assert os.path.isfile(os.path.join(BENCH, "metrics", f"{m['name']}.py"))
    cells = [w["name"] for w in SPEC["workloads"]]
    assert set(m.get("workloads", cells)) <= set(cells)


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_finds_its_files(w):
    conf = {c["name"]: c for c in SPEC["configs"]}[w["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    for kind in ("drivers", "references", "costs"):
        name = cfg["family"] if kind == "drivers" else cfg["reference"]
        assert os.path.isfile(os.path.join(BENCH, kind, f"{name}.py"))
    assert os.path.isfile(os.path.join(BENCH, "traffic",
                                       f"{w['traffic']}.json"))
    assert cfg["reduced"] == conf["reduced"]


def test_configs_are_the_registered_ones():
    import sys
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    for c in SPEC["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        reg = get_config(cfg["program_arch"])
        for k, v in cfg["model"].items():
            if k not in c["reduced"]:
                assert getattr(reg, k) == v, (c["name"], k)
