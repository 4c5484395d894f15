"""Aggregate dry-run artifacts into the §Roofline table (deliverable (g)).

Reads benchmarks/artifacts/dryrun/*.json (produced by
`python -m repro.launch.dryrun --all`) and emits:
  * CSV lines for benchmarks.run,
  * benchmarks/artifacts/roofline_table.md — the EXPERIMENTS.md table.
"""
from __future__ import annotations

import glob
import json
import os

from .common import ARTIFACTS, csv_line


def run() -> list[str]:
    lines = []
    paths = sorted(glob.glob(os.path.join(ARTIFACTS, "dryrun", "*.json")))
    if not paths:
        return [csv_line("roofline/missing", 0.0,
                         "run `python -m repro.launch.dryrun --all` first")]
    rows = []
    for p in paths:
        r = json.load(open(p))
        if r["status"] != "ok" or "roofline" not in r:
            rows.append(r)
            continue
        rf = r["roofline"]
        terms = {
            "compute": rf["compute_s"],
            "memory": rf["memory_s"],
            "collective": rf["collective_s"],
        }
        dom = rf["dominant"]
        total = max(sum(terms.values()), 1e-30)
        # roofline fraction: share of the (serial-sum) step bound that is
        # compute at peak — 1.0 == perfectly compute-bound at roofline
        frac = terms["compute"] / total
        r["_summary"] = {
            "terms": terms, "dominant": dom, "roofline_fraction": frac,
            "model_ratio": rf.get("model_flops_ratio", 0.0),
        }
        rows.append(r)
        if r["mesh"] == "pod16x16":  # assignment: roofline table single-pod
            lines.append(csv_line(
                f"roofline/{r['arch']}__{r['shape']}", 0.0,
                f"c={terms['compute']*1e3:.1f}ms m={terms['memory']*1e3:.1f}ms "
                f"x={terms['collective']*1e3:.1f}ms dom={dom} "
                f"frac={frac:.2f} model_ratio={r['_summary']['model_ratio']:.2f}",
            ))

    md = [
        "| arch | shape | mesh | compute (s) | memory (s) | collective (s) |"
        " dominant | MODEL/HLO flops | fits 16GiB |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["status"] == "skip":
            md.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | — | — | — |"
                f" skipped: {r['reason']} | — | — |"
            )
            continue
        if r["status"] != "ok":
            md.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | — | — | — |"
                f" ERROR | — | — |"
            )
            continue
        s = r.get("_summary")
        fits = r["memory"]["fits_16GiB"]
        if s is None:
            md.append(
                f"| {r['arch']} | {r['shape']} | {r['mesh']} | — | — | — |"
                f" compile-only | — | {fits} |"
            )
            continue
        t = s["terms"]
        md.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} |"
            f" {t['compute']:.3f} | {t['memory']:.3f} | {t['collective']:.4f} |"
            f" {s['dominant']} | {s['model_ratio']:.2f} | {fits} |"
        )
    os.makedirs(ARTIFACTS, exist_ok=True)
    with open(os.path.join(ARTIFACTS, "roofline_table.md"), "w") as f:
        f.write("\n".join(md) + "\n")
    lines.append(csv_line(
        "roofline/table", 0.0,
        f"cells={len(rows)} -> benchmarks/artifacts/roofline_table.md",
    ))
    return lines


if __name__ == "__main__":
    for line in run():
        print(line)
