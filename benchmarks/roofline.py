"""§Roofline reader: dry-run artifacts -> per-cell roofline table.

Reads benchmarks/artifacts/dryrun/*.json and emits, per (arch x shape) on
the single-pod mesh: the three terms, the dominant bottleneck, MODEL_FLOPS /
HLO_FLOPS, and an analytic memory term (HLO "bytes accessed" on the CPU
backend over-counts fused traffic; the analytic term models weights+cache
+activation DRAM traffic — both are reported).
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from repro.core.hardware import chip_peaks

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "artifacts", "dryrun")

V5E = chip_peaks("TPU v5 lite")


def load_cells(mesh: str = "16x16", tag: str = "") -> List[Dict]:
    cells = []
    suffix = f"__{tag}.json" if tag else ".json"
    for path in sorted(glob.glob(os.path.join(ARTIFACT_DIR, "*.json"))):
        name = os.path.basename(path)
        if f"__{mesh}" not in name:
            continue
        if tag:
            if not name.endswith(suffix):
                continue
        elif name.count("__") != 2:
            continue
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def analytic_memory_s(cell: Dict) -> Optional[float]:
    """DRAM-traffic estimate per step from the residency breakdown."""
    r = cell.get("analytic_residency_per_device")
    if not r:
        return None
    kind = cell["shape"]
    p = r.get("params", 0.0)
    if kind.startswith("train"):
        traffic = 3 * p + 2 * r.get("adam_moments", 0.0) \
            + 3 * r.get("remat_activations", 0.0) \
            + 2 * r.get("logits_shard", 0.0)
    elif kind.startswith("prefill"):
        traffic = p + 2 * r.get("kv_cache", 0.0) \
            + 4 * r.get("working_set", 0.0)
    else:
        traffic = p + r.get("kv_cache", 0.0) + r.get("working_set", 0.0)
    return traffic / V5E.hbm_bw


def row(cell: Dict) -> Dict:
    pd = cell["per_device"]
    rf = cell["roofline"]
    mem_a = analytic_memory_s(cell)
    comp = rf["compute_s"]
    coll = rf["collective_s"]
    # older artifacts zeroed collective-permute wire (no replica_groups);
    # patch in bytes*0.5 (bf16-equivalent) from the by_kind summary
    cp = cell.get("collectives", {}).get("by_kind", {}).get(
        "collective-permute")
    if cp and cp.get("wire_bytes_bf16", 0) == 0 and cp.get("bytes", 0) > 0:
        coll = coll + 0.5 * cp["bytes"] / V5E.ici_bw
    dom_terms = {"compute": comp, "memory(analytic)": mem_a or 0.0,
                 "collective": coll}
    dominant = max(dom_terms, key=dom_terms.get)
    bound = max(dom_terms.values())
    frac = comp / bound if bound else 0.0
    return {
        "arch": cell["arch"], "shape": cell["shape"], "mesh": cell["mesh"],
        "compute_ms": comp * 1e3,
        "memory_hlo_ms": rf["memory_s"] * 1e3,
        "memory_analytic_ms": (mem_a or 0.0) * 1e3,
        "collective_ms": coll * 1e3,
        "dominant": dominant,
        "roofline_fraction": frac,
        "useful_flops_ratio": cell.get("useful_flops_ratio", 0.0),
        "peak_gib_cpu": pd["peak_hbm_bytes"] / 2 ** 30,
        "est_gib_tpu": cell["analytic_residency_per_device"]["total"] / 2 ** 30
        if cell.get("analytic_residency_per_device") else 0.0,
        "compile_s": cell.get("compile_s", 0.0),
    }


def table(mesh: str = "16x16", tag: str = "") -> List[Dict]:
    rows = []
    for cell in load_cells(mesh, tag):
        if cell["status"] == "ok":
            rows.append(row(cell))
        else:
            rows.append({"arch": cell["arch"], "shape": cell["shape"],
                         "mesh": cell["mesh"], "dominant": cell["status"],
                         "reason": cell.get("reason",
                                            cell.get("error", ""))[:90]})
    return rows


def markdown(mesh: str = "16x16", tag: str = "") -> str:
    rows = table(mesh, tag)
    hdr = ("| arch | shape | compute ms | mem(HLO) ms | mem(analytic) ms | "
           "coll ms | dominant | useful-FLOPs | est GiB/dev |")
    sep = "|" + "---|" * 9
    out = [hdr, sep]
    for r in rows:
        if "compute_ms" not in r:
            out.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | "
                       f"{r['dominant']}: {r.get('reason', '')} | — | — |")
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_ms']:.2f} | "
            f"{r['memory_hlo_ms']:.1f} | {r['memory_analytic_ms']:.2f} | "
            f"{r['collective_ms']:.2f} | {r['dominant']} | "
            f"{r['useful_flops_ratio']:.2f} | {r['est_gib_tpu']:.2f} |")
    return "\n".join(out)


def run(quiet=False) -> List[str]:
    lines = []
    for r in table():
        if "compute_ms" in r:
            lines.append(
                f"roofline_{r['arch']}_{r['shape']},"
                f"{max(r['compute_ms'], r['memory_analytic_ms'], r['collective_ms']) * 1e3:.0f},"
                f"dom={r['dominant']} comp={r['compute_ms']:.2f}ms "
                f"coll={r['collective_ms']:.2f}ms "
                f"useful={r['useful_flops_ratio']:.2f}")
            if not quiet:
                print("  " + lines[-1])
    return lines


if __name__ == "__main__":
    print(markdown())
