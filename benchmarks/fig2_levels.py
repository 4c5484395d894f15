"""Paper Fig. 2: messages to 1e-4 accuracy vs number of hierarchy
levels k.  Expected: diminishing reward beyond 4-5 levels."""
from __future__ import annotations

import time

import numpy as np

from repro.core import build_plan, multiscale_gossip, random_geometric_graph

from .common import csv_line, save_artifact, timed


def run(n: int = 2000, trials: int = 3, eps: float = 1e-4,
        max_k: int = 6, artifact: str = "fig2_levels") -> list[str]:
    rows = {}
    plan_build_s: dict = {}
    graph_gen: list[float] = []
    t0 = time.time()
    for k in range(2, max_k + 1):
        msgs, errs, builds = [], [], []
        for t in range(trials):
            g, g_dt = timed(random_geometric_graph, n, seed=100 + t)
            graph_gen.append(g_dt)
            x0 = np.random.default_rng(t).normal(0, 1, n)
            # the plan multiscale_gossip would build internally, made
            # explicit so its build_seconds breakdown can be recorded
            plan = build_plan(g, k=k, seed=t)
            builds.append(plan.build_seconds or {})
            r = multiscale_gossip(
                g, x0, eps=eps, k=k, seed=t, weighted=True, plan=plan,
            )
            msgs.append(r.messages)
            errs.append(r.error(x0))
        rows[k] = {
            "messages_mean": float(np.mean(msgs)),
            "messages_std": float(np.std(msgs)),
            "err_mean": float(np.mean(errs)),
        }
        stages = sorted({s for b in builds for s in b})
        plan_build_s[k] = {
            s: float(np.mean([b.get(s, 0.0) for b in builds])) for s in stages
        }
    save_artifact(
        artifact,
        {"n": n, "eps": eps, "rows": rows, "plan_build_s": plan_build_s,
         "graph_gen_s": float(np.mean(graph_gen))},
    )
    total_us = (time.time() - t0) * 1e6
    out = []
    best_k = min(rows, key=lambda k: rows[k]["messages_mean"])
    for k, r in rows.items():
        out.append(csv_line(
            f"fig2/levels_k{k}", total_us / len(rows),
            f"messages={r['messages_mean']:.0f} err={r['err_mean']:.2e}",
        ))
    out.append(csv_line(
        "fig2/diminishing_reward", total_us,
        f"best_k={best_k} n={n} (paper: 4-5 levels suffice)",
    ))
    return out


if __name__ == "__main__":
    from .common import bench_cli

    bench_cli(run)
