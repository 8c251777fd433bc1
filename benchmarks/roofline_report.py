"""Aggregate artifacts/dryrun/*.json into the §Roofline table."""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List


def load_reports(out_dir: str = "artifacts/dryrun") -> List[Dict]:
    rows = []
    for f in sorted(pathlib.Path(out_dir).glob("*.json")):
        if f.name == "SWEEP_SUMMARY.json":
            continue
        rows.append(json.loads(f.read_text()))
    # recompute model-flops-derived metrics with the CURRENT accounting
    # (decode cells add attention-over-cache flops)
    from repro.configs import SHAPES, get_config
    from repro.roofline.analysis import model_flops_for
    for r in rows:
        mf = model_flops_for(get_config(r["arch"]), SHAPES[r["shape"]])
        r["model_flops"] = mf
        if r.get("hlo_flops_total"):
            r["useful_fraction"] = mf / r["hlo_flops_total"]
            crit = max(r["compute_s"], r["memory_s"], r["collective_s"])
            r["roofline_fraction"] = (r["useful_fraction"]
                                      * r["compute_s"] / crit if crit else 0.0)
    return rows


def roofline_table(out_dir: str = "artifacts/dryrun", mesh: str = "single") -> str:
    rows = [r for r in load_reports(out_dir) if r.get("mesh") == mesh]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    lines = [
        "| arch | shape | compute_s | memory_s | collective_s | dominant "
        "| useful(6ND/HLO) | roofline_frac | temp GiB/dev |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        mem = r.get("bytes_per_device", {})
        temp = (mem.get("temp") or 0) / 2**30
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | {r['dominant']} | "
            f"{r['useful_fraction']:.2f} | {r['roofline_fraction']:.3f} | {temp:.1f} |")
    return "\n".join(lines)


def summarize(out_dir: str = "artifacts/dryrun") -> List[Dict]:
    rows = load_reports(out_dir)
    out = []
    for r in rows:
        rec = {"figure": "roofline", "cell": f"{r['arch']}×{r['shape']}×{r['mesh']}",
               "dominant": r["dominant"]}
        if r["mesh"] == "single":  # multi cells are plain (scan-once) compiles:
            rec["roofline_frac"] = round(r.get("roofline_fraction", 0.0), 3)
            rec["useful"] = round(r.get("useful_fraction", 0.0), 2)
        else:
            rec["note"] = "compile+memory proof only (no fit)"
        out.append(rec)
    return out
