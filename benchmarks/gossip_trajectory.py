"""BENCH_gossip.json — the standardized gossip perf-trajectory artifact.

Every entry snapshots the simulation hot path's measured performance at
one commit: the fig3 smoke wall-clocks (from the smoke artifact
`fig3_smoke_lax`) plus the value-pass microbenchmark sweep.  The file lives at the
repo root and is append-only (one entry per (commit, label);
re-running replaces that entry), so future PRs diff their numbers
against a measured baseline instead of an empty trajectory.

The fig3 numbers are read from whatever smoke artifacts are on disk —
regenerate them FIRST so the entry reflects the code being stamped
(`REPRO_BENCH_SMOKE=1 tools/ci.sh` does this in the right order).
Entries measured on an uncommitted tree are stamped `<sha>-dirty`.

    python -m benchmarks.gossip_trajectory [--label msg] [--no-kernels]

Also exposed as the `gossip` suite in `benchmarks.run`.
"""
from __future__ import annotations

import glob
import json
import os
import subprocess
import time

from .common import ARTIFACTS, csv_line, load_artifact

TRAJECTORY = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_gossip.json",
)
SMOKE_ARTIFACTS = {"lax": "fig3_smoke_lax"}


def _git_commit() -> str:
    """Short HEAD sha, suffixed `-dirty` when the working tree differs
    from it — measurements from uncommitted trees must not masquerade
    as the clean commit's record."""
    repo = os.path.dirname(TRAJECTORY)
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10, cwd=repo,
        ).stdout.strip() or "unknown"
        dirty = subprocess.run(
            ["git", "status", "--porcelain", "--untracked-files=no"],
            capture_output=True, text=True, timeout=10, cwd=repo,
        ).stdout.strip()
        return f"{sha}-dirty" if dirty else sha
    except Exception:
        return "unknown"


def load_trajectory() -> list:
    if not os.path.exists(TRAJECTORY):
        return []
    return json.load(open(TRAJECTORY))


def validate_entry(entry: dict) -> None:
    """Reject a malformed NEW entry before it lands in the trajectory.

    Every new entry must stamp a real `unix_time` and every present
    fig3 smoke record must carry the measured `jit_warmup_s` — `null`
    placeholders made the earliest entries useless for warmup-cost
    trend lines.  Historical entries already in the file are NOT
    backfilled or re-validated; the gate applies at append time only.
    """
    ut = entry.get("unix_time")
    if not isinstance(ut, int) or ut <= 0:
        raise ValueError(f"gossip_trajectory entry missing unix_time: {ut!r}")
    for smoke, rec in entry.get("fig3_smoke", {}).items():
        if "missing" in rec:
            continue
        if not isinstance(rec.get("jit_warmup_s"), (int, float)):
            raise ValueError(
                f"gossip_trajectory entry fig3_smoke[{smoke!r}] lacks "
                f"jit_warmup_s — regenerate the smoke artifact "
                f"(REPRO_BENCH_SMOKE=1 tools/ci.sh) before recording"
            )


def record_entry(entry: dict) -> None:
    """Append `entry`, replacing any prior entry for the same
    (commit, label) — re-running at one commit updates in place while
    distinct labels (e.g. a pinned baseline) survive.  New entries are
    validated (`validate_entry`); the historical tail is left as-is."""
    validate_entry(entry)
    key = (entry["commit"], entry.get("label", ""))
    traj = [
        e for e in load_trajectory()
        if (e.get("commit"), e.get("label", "")) != key
    ]
    traj.append(entry)
    with open(TRAJECTORY, "w") as f:
        json.dump(traj, f, indent=1)


def build_entry(label: str = "", kernels: bool = True) -> dict:
    entry = {
        "commit": _git_commit(),
        "unix_time": int(time.time()),
        "label": label,
        "fig3_smoke": {},
    }
    for smoke, name in SMOKE_ARTIFACTS.items():
        art = load_artifact(name)
        if art is None:
            entry["fig3_smoke"][smoke] = {
                "missing": f"benchmarks/artifacts/{name}.json — run "
                           "REPRO_BENCH_SMOKE=1 tools/ci.sh first"
            }
            continue
        entry["fig3_smoke"][smoke] = {
            "n": sorted(int(n) for a in art["summary"].values() for n in a)[0],
            "trials": art["trials"],
            "jit_warmup_s": art.get("jit_warmup_s"),
            "wall_clock_s": art["wall_clock_s"],
            "plan_build_s": art.get("plan_build_s"),
            "messages_mean": {
                algo: next(iter(rows.values()))["messages_mean"]
                for algo, rows in art["summary"].items()
            },
        }
    large = {}
    for path in sorted(glob.glob(os.path.join(ARTIFACTS, "large_n_*.json"))):
        name = os.path.splitext(os.path.basename(path))[0]
        if name.endswith("_check"):
            continue
        art = load_artifact(name)
        large[name] = {
            "n": art["n"],
            "trials": art["trials"],
            "fixed_ticks_scale": art["fixed_ticks_scale"],
            "messages": art["messages"],
            "err": art["err"],
            "wall_clock_s": art["wall_clock_s"],
            "graph_gen_s": art.get("graph_gen_s"),
            "plan_build_s": art["plan_build_s"],
            "workers": art.get("workers"),
            "setup": art.get("setup"),
            "memory": art["memory"],
            "overlap_ratio": (art.get("overlap") or {}).get("ratio"),
        }
    if large:
        entry["large_n"] = large
    if kernels:
        from .kernel_bench import pair_apply_bench

        entry["pair_apply_us"] = pair_apply_bench(as_rows=False)
    return entry


def run(label: str = "", kernels: bool = True) -> list[str]:
    entry = build_entry(label=label, kernels=kernels)
    record_entry(entry)
    lines = []
    for smoke, rec in entry["fig3_smoke"].items():
        if "missing" in rec:
            lines.append(csv_line(f"gossip/fig3_smoke_{smoke}", 0.0,
                                  rec["missing"]))
            continue
        ms = rec["wall_clock_s"].get("multiscale", 0.0)
        lines.append(csv_line(
            f"gossip/fig3_smoke_{smoke}", ms * 1e6,
            f"n={rec['n']} multiscale_wall={ms:.2f}s "
            f"msgs={rec['messages_mean'].get('multiscale', 0):.0f}",
        ))
    for name, rec in entry.get("large_n", {}).items():
        setup = rec.get("setup") or {}
        setup_note = (
            f"setup_cold={setup['cold_s']:.2f}s "
            f"setup_warm={setup['warm_s']:.3f}s "
            if setup else ""
        )
        lines.append(csv_line(
            f"gossip/{name}", rec["wall_clock_s"]["execute_cold"] * 1e6,
            f"n={rec['n']} msgs={rec['messages'][0]} "
            f"graph={rec.get('graph_gen_s') or 0.0:.2f}s "
            f"plan={rec['plan_build_s'].get('total', 0.0):.2f}s "
            f"{setup_note}"
            f"warm={rec['wall_clock_s']['execute_warm']:.2f}s",
        ))
    for key, us in entry.get("pair_apply_us", {}).items():
        lines.append(csv_line(f"gossip/pair_apply_{key}", us, "see kernels"))
    lines.append(csv_line(
        "gossip/trajectory", 0.0,
        f"entries={len(load_trajectory())} -> BENCH_gossip.json "
        f"commit={entry['commit']}",
    ))
    return lines


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--label", default="")
    ap.add_argument("--no-kernels", action="store_true")
    args = ap.parse_args()
    for line in run(label=args.label, kernels=not args.no_kernels):
        print(line)
