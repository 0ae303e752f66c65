#!/usr/bin/env python3
"""Traces one benchmark cell and reads the program's own spans and scopes.

    python3 perfbench/probe.py --workload openvla-7b-standin.solo \\
        --seed 7 --seconds 20 [--record DIR]

Set-up is ``run.py``'s.  With ``--seconds`` above 0 it first runs an
untraced closed-loop window and reports its end-to-end numbers; then it
traces the mix's ``trace_steps`` whole steps twice over (and, with
``--record DIR``, 4 more), compiles both tiers afresh for their scope
maps, and prints as the last line one JSON object.  Under ``windows`` it
holds, for each of the two traced windows: the declared per-layer metrics
as ``run.py`` reads them, the program-span readings of
``harness/inside.py`` (``head_ms``, ``codec_ms``, ``forecast_ms``,
``adjust_ms``, ``price_ms``, ``serve_gap_ms``), device time by
``<program>/<scope>``, each tier's share of operation time that has a
scope, idle time by the innermost span, programs per call, the
``roboecc/tick/*`` spans' share of the benchmark's ``tick`` span, each
edge program's start after its dispatch began, and the traced steps'
median.  The first traced window of a process can read its host and
device clocks more than a millisecond apart (an edge program then starts
before its dispatch span), which moves every host-span attribution of
device time: ``clocks_agree`` says whether every edge program started
after its dispatch, and only such a window's host-span readings are
worth reporting.  The object also gives what one ``TraceAnnotation``
costs with no profiler session.  The recorded window is kept as
``DIR/<workload>.xplane.pb.gz`` with ``.calls.json`` and ``.scopes.json``
(instruction -> scope per tier) beside it, for the benchmark's tests.
Nothing is compared with the reference: this is a reading, not a
benchmark run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, Optional  # noqa: E402

import run  # noqa: E402  (puts src/ and perfbench/ on the path)

RECORD_STEPS = 4
WINDOWS = 2


def annotation_cost_ns(n: int = 200_000) -> float:
    """Host nanoseconds to enter and leave one ``TraceAnnotation`` with no
    profiler session running."""
    import jax
    TA = jax.profiler.TraceAnnotation
    t = time.perf_counter()
    for _ in range(n):
        with TA("roboecc/cost"):
            pass
    return 1e9 * (time.perf_counter() - t) / n


def compiled_texts(cell) -> Dict[str, str]:
    """Each tier's compiled HLO text for the shapes the cell serves,
    compiled afresh.  JAX's in-process caches and its persistent cache
    key a program by its code alone, without the ``op_name`` metadata, so
    a program they hand back carries the metadata of whichever commit
    first compiled the same code (a parent without scopes, say); the
    instruction names are the same either way.  This clears the
    in-process caches: call it after the last traced window."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    ex = cell.ex
    split = jnp.int32(ex.plan.clamp(cell.split0))
    edge_args = (cell.params, jax.device_put(cell.patches[0]),
                 jax.device_put(cell.tokens[0]), split)
    payload = jax.eval_shape(ex._edge, *edge_args)
    was = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return {"edge": ex._edge.lower(*edge_args).compile().as_text(),
                "cloud": ex._cloud.lower(cell.params, payload, split,
                                         cell.keys[0]).compile().as_text()}
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


class Unsampled:
    """The cell stepped with negative step numbers, which keep no request
    for the reference comparison: the probe compares nothing, and its
    windows may follow each other in one process."""

    def __init__(self, cell):
        self.cell = cell
        self.calls = cell.calls

    def step(self, i: int):
        return self.cell.step(-1 - i)


def window(cell, steps: int, path: str):
    """``steps`` traced steps, the profile kept at ``path``; returns the
    ``Trace`` and the calls."""
    cell.calls.clear()
    tr = run.traced(Unsampled(cell), steps, path)
    return tr, list(cell.calls)


def read(cell, tr, ins, calls, spec, peaks) -> Dict:
    """Every reading of one traced window."""
    from harness import inside
    w = run.Window(tr, cell, calls, peaks)
    metrics = {m["name"]: run._module("metrics", m["name"]).read(w)
               for m in spec["per_layer"]}
    metrics.update({k: f(ins) for k, f in inside.METRICS.items()})
    steps = [c["t1"] - c["t0"] for c in calls]
    after = [1e3 * t for t in ins.edge_after_dispatch_s()]
    return {"metrics": metrics, "calls": len(calls),
            "device_scopes": ins.device_scopes(),
            "attributed": {p: ins.attributed(p) for p in cell.programs()},
            "idle_gaps_in_program": ins.idle_gaps_in_program(),
            "programs_per_call": ins.programs_per_call(),
            "tick_cover": ins.tick_cover(),
            "edge_after_dispatch_ms": after,
            "clocks_agree": bool(after) and min(after) > 0,
            "traced_step_p50_ms": 1e3 * statistics.median(steps),
            "busy_s": w.busy_s, "window_s": w.window_s}


def record(raw: str, tr, calls, scopes: Dict[str, Dict[str, str]],
           workload: str, out_dir: str) -> None:
    """Keeps a traced window for the benchmark's tests: the profile
    gzipped, its calls, and the part of each tier's scope map that its
    operations use."""
    from harness import inside
    os.makedirs(out_dir, exist_ok=True)
    base = os.path.join(out_dir, workload)
    with open(raw, "rb") as f, gzip.open(base + ".xplane.pb.gz", "wb") as g:
        shutil.copyfileobj(f, g)
    with open(base + ".calls.json", "w") as f:
        json.dump({"workload": workload, "calls": calls}, f)
    names = {inside.instruction(n) for n, _, _ in tr.ops()}
    with open(base + ".scopes.json", "w") as f:
        json.dump({p: {k: v for k, v in sorted(m.items()) if k in names}
                   for p, m in scopes.items()}, f)


def probe(workload: str, seed: int, seconds: float = 0.0, *,
          steps: Optional[int] = None, record_dir: Optional[str] = None,
          require_chip: bool = True, overrides: Optional[Dict] = None,
          t_start: float = T_START) -> Dict:
    """Set-up as ``run.run``, an optional untraced window, ``WINDOWS``
    traced windows (and a recorded one), then the tiers' compiled texts
    and the readings of each traced window.  ``require_chip`` and
    ``overrides`` as in ``run.run``; on a CPU the device readings are left
    out."""
    from harness import inside, trace as T
    spec = run.load_cell(workload)
    overrides = overrides or {}
    cfg = spec["config"]
    cfg = dict(cfg, model=dict(cfg["model"], **overrides.get("model", {})))
    traffic = dict(spec["traffic"], **overrides.get("traffic", {}))
    import jax
    n_chips = int(spec["cell"]["chips"])
    if require_chip:
        devices = run.find_chips(n_chips)
        peaks = run.peaks_for(devices[0].device_kind)
        run.enable_cache()
    else:
        devices = jax.devices()[:n_chips]
        peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    cell = run._module("drivers", cfg["family"]).Cell(
        cfg, traffic, seed, run.log, run._module)
    out = {"workload": workload, "seed": seed,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind},
           "setup_s": time.perf_counter() - t_start,
           "annotation_ns": annotation_cost_ns()}
    if seconds > 0:
        out["untraced"] = run.end_to_end(run.measure(Unsampled(cell),
                                                     seconds))
    with tempfile.TemporaryDirectory(prefix="probe") as d:
        paths = [os.path.join(d, f"window{i}.xplane.pb")
                 for i in range(WINDOWS)]
        wins = [window(cell, steps or int(traffic["trace_steps"]), p)
                for p in paths]
        if record_dir:
            rec_path = os.path.join(d, "record.xplane.pb")
            rec = window(cell, RECORD_STEPS, rec_path)
        scopes = {p: inside.scope_map(t)
                  for p, t in compiled_texts(cell).items()}
        insides = [inside.Inside(tr, T.load(p), cell.programs(), scopes,
                                 len(calls))
                   for (tr, calls), p in zip(wins, paths)]
        if record_dir:
            record(rec_path, *rec, scopes, workload, record_dir)
    if require_chip:
        out["windows"] = [read(cell, tr, ins, calls, spec, peaks)
                          for (tr, calls), ins in zip(wins, insides)]
    else:
        from harness.trace import HOST_SPANS
        out["windows"] = [
            {"spans": ins.spans,
             "bench_spans": {k: tr.host.get(k, []) for k in HOST_SPANS}}
            for (tr, _), ins in zip(wins, insides)]
    cell.release()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)
    try:
        out = probe(args.workload, args.seed, args.seconds,
                    record_dir=args.record)
    except run.NoChip as e:
        run.log(f"probe.py: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
