"""Paper Fig. 4: CDF of per-node transmissions (n=2000, eps=1e-4).

Expected (paper): the busiest multiscale node transmits less than
~22% of path-averaging nodes do — load is spread, no hot relays.

Multiscale trials run vmapped through the plan/execute engine; the CDF
aggregates node sends over all trials.  Wall-clock per algorithm is
recorded in the artifact.
"""
from __future__ import annotations

import numpy as np

from repro.core import multiscale_gossip, path_averaging, random_geometric_graph

from .common import csv_line, save_artifact, timed


def run(n: int = 2000, eps: float = 1e-4, seed: int = 0, trials: int = 3,
        artifact: str = "fig4_cdf") -> list[str]:
    g = random_geometric_graph(n, seed=42)
    x0 = np.random.default_rng(7).normal(0, 1, n)
    ms, t_ms = timed(
        multiscale_gossip, g, x0, eps=eps, seed=seed, weighted=True,
        trials=trials,
    )
    pa_runs, t_pa = timed(lambda: [
        path_averaging(g, x0, eps=eps, seed=seed + t) for t in range(trials)
    ])
    ms_by_trial = np.atleast_2d(ms.node_sends)
    ms_sends = np.sort(ms_by_trial.ravel())
    pa_sends = np.sort(np.concatenate([r.node_sends for r in pa_runs]))
    # fraction of PA nodes transmitting more than the busiest MS node,
    # paired per trial (the pooled max over T trials is an order
    # statistic that would bias the single-run paper metric downward)
    frac_per_trial = [
        float((pa_runs[t].node_sends > ms_by_trial[t].max()).mean())
        for t in range(trials)
    ]
    frac_pa_above_ms_max = float(np.mean(frac_per_trial))
    # per-trial busiest-node means match the paper's single-run metric;
    # the quantiles/CDFs below pool ALL trials' nodes and are labeled so
    # (a pooled max is an order statistic that grows with T)
    ms_max = float(np.mean([ms_by_trial[t].max() for t in range(trials)]))
    pa_max = float(np.mean([r.node_sends.max() for r in pa_runs]))
    qs = [0.5, 0.9, 0.99, 1.0]
    stride = max(1, len(ms_sends) // 200)
    payload = {
        "n": n,
        "trials": trials,
        "trial_mode": "vmapped",
        "wall_clock_s": {"multiscale": t_ms, "path_averaging": t_pa},
        "ms_max_trial_mean": ms_max,
        "pa_max_trial_mean": pa_max,
        "frac_pa_above_ms_max_per_trial": frac_per_trial,
        "ms_quantiles_pooled": {
            str(q): float(np.quantile(ms_sends, q)) for q in qs
        },
        "pa_quantiles_pooled": {
            str(q): float(np.quantile(pa_sends, q)) for q in qs
        },
        "frac_pa_nodes_above_ms_max": frac_pa_above_ms_max,
        "ms_cdf_sends_pooled": ms_sends[::stride].tolist(),
        "pa_cdf_sends_pooled": pa_sends[::stride].tolist(),
    }
    save_artifact(artifact, payload)
    us = (t_ms + t_pa) * 1e6
    return [
        csv_line(
            "fig4/ms_max_vs_pa", us,
            f"ms_max={ms_max:.0f} pa_max={pa_max:.0f} "
            f"frac_pa_above_ms_max={frac_pa_above_ms_max:.2f} "
            "(paper: ~0.22)",
        )
    ]


if __name__ == "__main__":
    from .common import bench_cli

    bench_cli(run)
