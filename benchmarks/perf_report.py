"""§Perf report: baseline vs hillclimb-variant artifact comparison."""
from __future__ import annotations

import json
import os
from typing import Dict, Optional

from .roofline import ARTIFACT_DIR, analytic_memory_s, row


def load(arch: str, shape: str, mesh: str = "16x16", tag: str = ""
         ) -> Optional[Dict]:
    t = f"__{tag}" if tag else ""
    path = os.path.join(ARTIFACT_DIR, f"{arch}__{shape}__{mesh}{t}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        d = json.load(f)
    return d if d.get("status") == "ok" else None


def compare(arch: str, shape: str, tag: str, label: str) -> str:
    base = load(arch, shape)
    var = load(arch, shape, tag=tag)
    if base is None or var is None:
        return f"*(artifact missing for {arch} x {shape} [{tag}])*"
    rb, rv = row(base), row(var)

    def fmt(r, d):
        bound = max(r["compute_ms"], r["memory_analytic_ms"],
                    r["collective_ms"])
        return (f"| {d} | {r['compute_ms']:.2f} | {r['memory_analytic_ms']:.2f} | "
                f"{r['collective_ms']:.2f} | {r['dominant']} | "
                f"{bound:.2f} | {r['compute_ms'] / bound:.2f} |")

    hdr = ("| variant | compute ms | memory ms | collective ms | dominant | "
           "bound ms | roofline fraction |\n|---|---|---|---|---|---|---|")
    bb = max(rb["compute_ms"], rb["memory_analytic_ms"], rb["collective_ms"])
    vb = max(rv["compute_ms"], rv["memory_analytic_ms"], rv["collective_ms"])
    gain = bb / vb if vb else float("inf")
    return "\n".join([hdr, fmt(rb, "baseline (paper-faithful TP)"),
                      fmt(rv, label),
                      f"\n**step-bound improvement: x{gain:.2f}**"])


def collective_kinds(arch: str, shape: str, tag: str = "") -> str:
    d = load(arch, shape, tag=tag)
    if d is None:
        return "(missing)"
    out = []
    for k, v in d["collectives"]["by_kind"].items():
        out.append(f"{k}: n={v['count']} wire16={v['wire_bytes_bf16'] / 2**30:.2f}GiB")
    return "; ".join(out)
