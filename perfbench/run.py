#!/usr/bin/env python3
"""Runs one benchmark cell once and prints its result as the last line.

    python3 perfbench/run.py --workload openvla-7b-standin.solo --seed 7 \\
        --seconds 20 --trace 0

Everything a cell needs is found by name: the cell and its metrics in
``BENCHMARK.json`` at the checkout root, the configuration in the file
it names, the traffic mix in ``perfbench/traffic/<traffic>.json``, the
driver, reference and cost functions of the configuration's family in
``perfbench/{drivers,references,costs}/<family>.py``, and each per-layer
metric's reader in ``perfbench/metrics/<name>.py``.

A run is one process that owns the chip.  It exits non-zero, and prints
no result, when JAX finds no accelerator or fewer chips than the cell
asks for.  Set-up (weights drawn on the device from the seed, controller
and predictor, compile or cache load, warm-up steps) is ``setup_s``.
With ``--trace 0`` it then runs closed-loop steps for ``--seconds`` and
reports the end-to-end metrics; with ``--trace 1`` it traces a fixed
number of whole steps (the mix's ``trace_steps``) and reports the
per-layer metrics, the device's busy and window seconds and a breakdown.
Either way a seeded sample of the requests served is then compared with
the float32 reference; each number compared is printed beside its limit
on standard error and under ``checks`` in the result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _module(kind: str, name: str):
    """The benchmark's file ``<kind>/<name>.py`` as a module, loaded once
    per process."""
    key = f"perfbench_{kind}_{name}"
    if key not in sys.modules:
        path = os.path.join(BENCH, kind, f"{name}.py")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def load_cell(workload: str) -> Dict:
    """The cell's entry, configuration, traffic mix and metric lists."""
    spec = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    cell = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])

    def mine(metrics):
        return [x for x in metrics
                if workload in x.get("workloads", [workload])]

    return {"cell": cell,
            "config": _json(os.path.join(ROOT, conf["file"])),
            "traffic": _json(os.path.join(BENCH, "traffic",
                                          f"{cell['traffic']}.json")),
            "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


def find_chips(n: int):
    """The accelerator devices, or ``NoChip``."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip(f"JAX finds no accelerator (platform "
                     f"{devices[0].platform}); nothing was run")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX finds "
                     f"{len(devices)}; nothing was run")
    return devices[:n]


def peaks_for(kind: str) -> Dict:
    table = _json(os.path.join(BENCH, "harness", "peaks.json"))
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r}; the table "
                         f"has {sorted(table)}")
    return table[kind]


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), for every
    program however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs compiled or loaded, and of them the loads from the
    persistent cache (one listener pair per process:
    ``CompileCounter.get()``)."""

    _one = None

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.compiles = 0
        self.loads = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, *_a, **_k):
            if name == event:
                self.compiles += 1

        def on_event(name, **_k):
            if name == "/jax/compilation_cache/cache_hits":
                self.loads += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.compiles, self.loads


# --------------------------------------------------------------- windows
def measure(cell, seconds: float) -> List[Dict]:
    """Closed-loop steps until ``seconds`` have passed; every step that
    started is completed and counted."""
    t0 = time.perf_counter()
    deadline = t0 + seconds
    i = 0
    while time.perf_counter() < deadline:
        cell.step(i)
        i += 1
    return list(cell.calls)


def end_to_end(calls: List[Dict]) -> Dict[str, float]:
    from harness import stats
    lat = [c["t1"] - c["t0"] for c in calls
           for _ in range(c["robots"] - c["failed"])]
    done = sum(c["robots"] - c["failed"] for c in calls)
    window_s = calls[-1]["t1"] - calls[0]["t0"]
    return {"step_p50_ms": stats.percentile_ms(lat, 50),
            "step_p95_ms": stats.percentile_ms(lat, 95),
            "steps_per_s": stats.rate_per_s(done, window_s),
            "window_s": window_s, "steps": done}


class Window:
    """What the per-layer readers see of one traced window."""

    def __init__(self, tr, cell, calls: List[Dict], peaks: Dict):
        self.trace = tr
        self.cell = cell
        self.calls = calls
        self.window_s = tr.window_s
        self.busy_s = tr.busy_s()
        self.robot_steps = sum(c["robots"] - c["failed"] for c in calls)
        self.step_flops = cell.step_flops()
        self.peak_flops = float(peaks["bf16_flops_per_s"])
        self.peak_bw = float(peaks["hbm_bytes_per_s"])

    def program_s(self, prog: str) -> List[float]:
        """Device seconds of each traced call of ``prog``; one execution
        per call, or a loud failure."""
        from harness.trace import TraceError
        fn = self.cell.programs()[prog]
        ev = self.trace.modules(fn)
        if len(ev) != len(self.calls):
            raise TraceError(self.trace.describe(
                f"{len(ev)} executions of {fn} for {len(self.calls)} calls"))
        return [b - a for a, b in ev]

    def program_mean_s(self, prog: str) -> float:
        t = self.program_s(prog)
        return sum(t) / len(t)

    def roofline_pct(self, prog: str) -> float:
        least = 0.0
        for c in self.calls:
            flops, nbytes = self.cell.call_costs(c)[prog]
            least += max(flops / self.peak_flops, nbytes / self.peak_bw)
        return 100.0 * least / sum(self.program_s(prog))


def traced(cell, steps: int, keep: Optional[str]):
    """``steps`` whole steps under the profiler; returns the Trace."""
    import jax
    from harness import trace as T
    with tempfile.TemporaryDirectory(prefix="bench_trace") as d:
        jax.profiler.start_trace(d, profiler_options=T.capture_options())
        try:
            with jax.profiler.TraceAnnotation(T.WINDOW):
                for i in range(steps):
                    cell.step(i)
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise T.TraceError(f"profiler wrote {found}, want one xplane")
        if keep:
            os.makedirs(os.path.dirname(os.path.abspath(keep)),
                        exist_ok=True)
            shutil.copy(found[0], keep)
        return T.Trace(T.load(found[0]))


# ------------------------------------------------------------------ run
def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_chip: bool = True, overrides: Optional[Dict] = None,
        controls=(), keep_trace: Optional[str] = None,
        fault=None, t_start: float = T_START) -> Dict:
    """One run of one cell; returns the result line as a dict.

    ``require_chip=False``, ``overrides`` (``{"model": {...},
    "traffic": {...}}`` merged into the files) and ``fault`` (called
    with the built cell, to break the timed path underneath) serve the
    benchmark's own tests; ``controls`` ("int8", "fp8") also read each
    number for the reference in that precision in the program's place
    (``calibrate.py``); ``keep_trace`` copies the traced window's
    ``.xplane.pb`` there, with its calls beside it.  ``setup_s`` counts
    from ``t_start``, the process start by default."""
    spec = load_cell(workload)
    cfg, traffic = spec["config"], dict(spec["traffic"])
    overrides = overrides or {}
    cfg = dict(cfg, model=dict(cfg["model"], **overrides.get("model", {})))
    traffic.update(overrides.get("traffic", {}))
    n_chips = int(spec["cell"]["chips"])

    import jax
    if require_chip:
        devices = find_chips(n_chips)
    else:
        devices = jax.devices()[:n_chips]
    dev = devices[0]
    peaks = peaks_for(dev.device_kind) if require_chip else \
        {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    cache = enable_cache() if require_chip else "off"
    counter = CompileCounter.get()
    t_jax = time.perf_counter() - t_start

    driver = _module("drivers", cfg["family"])
    cell = driver.Cell(cfg, traffic, seed, log, _module)
    if fault is not None:
        fault(cell)
    setup_s = time.perf_counter() - t_start
    phases = dict(jax_s=t_jax, **cell.phases)
    c0 = counter.snapshot()
    log(f"{workload} seed {seed}: device {dev.platform} {dev.device_kind} "
        f"x{len(devices)}, cache {cache}")
    log("setup s: " + ", ".join(f"{k} {v:.3f}" for k, v in phases.items())
        + f", total {setup_s:.3f}; programs {c0[0]}, of them loaded from "
        f"the cache {c0[1]}")

    metrics: Dict[str, float] = {}
    traced_out: Dict = {}
    if trace:
        steps = int(traffic["trace_steps"])
        tr = traced(cell, steps, keep_trace)
        calls = list(cell.calls)
        if keep_trace:
            with open(keep_trace + ".calls.json", "w") as f:
                json.dump({"workload": workload, "calls": calls}, f)
        w = Window(tr, cell, calls, peaks)
        from harness.trace import TraceError
        for m in spec["per_layer"]:
            value = _module("metrics", m["name"]).read(w)
            if value is None:
                raise TraceError(tr.describe(
                    f"declared metric {m['name']} found nothing to read"))
            metrics[m["name"]] = value
        traced_out["device"] = {"busy_s": w.busy_s, "window_s": w.window_s}
        traced_out["breakdown"] = {"device_ops": tr.top_ops(),
                                   "idle_gaps": tr.idle_gaps()}
        log(f"traced {steps} steps: window {w.window_s:.6f} s, busy "
            f"{w.busy_s:.6f} s")
    else:
        calls = measure(cell, seconds)
        e2e = end_to_end(calls)
        log(f"window {e2e['window_s']:.3f} s, {len(calls)} calls, "
            f"{e2e['steps']} robot steps")
        metrics.update({m["name"]: e2e[m["name"]]
                        for m in spec["end_to_end"] if m["name"] in e2e})
        metrics["setup_s"] = setup_s
    c1 = counter.snapshot()
    if c1 != c0:
        log(f"warning: {c1[0] - c0[0]} programs compiled or loaded inside "
            f"the window")
    attempted = sum(c["robots"] for c in calls)
    failed = sum(c["failed"] for c in calls)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    cell.release()
    gc.collect()
    t = time.perf_counter()
    checks = cell.check(cfg["check"]["limits"], controls=controls)
    c2 = counter.snapshot()
    log(f"reference over {checks.pop('compared')} requests: "
        f"{time.perf_counter() - t:.3f} s (programs {c2[0] - c1[0]}, of "
        f"them loaded from the cache {c2[1] - c1[1]})")
    correct = all(c["value"] <= c["limit"] for c in checks.values()
                  if "limit" in c)
    units = {m["name"]: m["unit"] for m in spec["end_to_end"]
             + spec["per_layer"]}
    result = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in metrics.items()},
        "device": dict({"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices()),
                        "memory_peak_bytes": int(peak)},
                       **traced_out.get("device", {})),
    }
    if "breakdown" in traced_out:
        result["breakdown"] = traced_out["breakdown"]
    result["checks"] = checks
    for name, c in checks.items():
        if "limit" in c:
            log(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
                + ("ok" if c["value"] <= c["limit"] else "FAIL"))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        log(f"run.py: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
