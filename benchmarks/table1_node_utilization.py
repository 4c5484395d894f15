"""Paper Table I + Fig. 6: transmissions by representative-role count
(random representative election, 5 levels).

Expected: nodes that served as representatives more often transmit
more, but even 3-time representatives stay modest; the average node
sends fewer messages than it has neighbors.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import multiscale_gossip, random_geometric_graph

from .common import csv_line, save_artifact


def run(n: int = 2000, eps: float = 1e-4, k: int = 5, seed: int = 0,
        trials: int = 1, artifact: str = "table1_node_utilization") -> list[str]:
    t0 = time.time()
    g = random_geometric_graph(n, seed=11)
    x0 = np.random.default_rng(1).normal(0, 1, n)
    r = multiscale_gossip(g, x0, eps=eps, k=k, seed=seed, rep_mode="random",
                          weighted=True, trials=trials)
    # trial-mean per-node sends (a single trial keeps the historical
    # numbers bit-for-bit; the election — rep_counts — is plan-shared)
    node_sends = np.atleast_2d(r.node_sends).mean(axis=0)
    rows = {}
    for count in sorted(np.unique(r.rep_counts), reverse=True):
        sel = r.rep_counts == count
        rows[int(count)] = {
            "nodes": int(sel.sum()),
            "mean_sends": float(node_sends[sel].mean()),
            "std_sends": float(node_sends[sel].std()),
        }
    avg_degree = float(g.degrees.mean())
    payload = {
        "n": n, "k": k, "trials": trials, "rows": rows,
        "all_mean": float(node_sends.mean()),
        "all_std": float(node_sends.std()),
        "avg_degree": avg_degree,
        "mean_below_degree": bool(node_sends.mean() < avg_degree),
    }
    save_artifact(artifact, payload)
    us = (time.time() - t0) * 1e6
    out = []
    for count, row in rows.items():
        out.append(csv_line(
            f"table1/reps_{count}x", us / max(len(rows), 1),
            f"nodes={row['nodes']} mean={row['mean_sends']:.1f} "
            f"std={row['std_sends']:.1f}",
        ))
    out.append(csv_line(
        "table1/all_nodes", 0.0,
        f"mean={payload['all_mean']:.1f} std={payload['all_std']:.1f} "
        f"avg_degree={avg_degree:.0f} "
        f"mean<degree={payload['mean_below_degree']} (paper: true)",
    ))
    return out


if __name__ == "__main__":
    from .common import bench_cli

    bench_cli(run)
