"""Paper Fig. 5 + §VI-C: operation under transmission failures, plus
the beyond-paper failure-scenario matrix.

Handshake model: per-hop ACK/retransmit — the trajectory is unchanged,
cost inflates by iid Geometric(p) per single-hop transmission.  Priced
per trial with `repro.core.price_messages` (supersedes the scalar
`handshake_cost`), so the artifact records the spread, not just a
trial-mean point.  Expected: multiscale degrades much less than path
averaging as p drops, because its messages travel <= O(n^(1/3)) hops.

Message-loss model: transmissions fail permanently — neither algorithm
meets eps; we report achieved error and message blow-up (paper observed
multiscale ~0.06, path averaging ~0.02 achieved accuracy, with PA's
messages exploding).  Loss runs use the same `trials` seeds as the
reliable runs (multiscale vmapped in one call, path averaging seeded
per trial) and the artifact records per-trial errors and their spread.

Scenario matrix (`repro.core.scenarios`): churn / stragglers / regional
outage / Byzantine drops replayed over ONE shared plan in
fixed-iterations mode, each cell reporting achieved error (all nodes
and survivors) and the priced medium cost (energy with retransmissions
at `scenario_retransmit_p` and congestion).
"""
from __future__ import annotations

import numpy as np

from repro.core import (
    CostModel,
    ExecOptions,
    FailureModel,
    build_plan,
    multiscale_gossip,
    path_averaging,
    price_messages,
    random_geometric_graph,
    run_scenario_matrix,
    scenario_matrix,
)

from .common import csv_line, save_artifact, timed


def run(n: int = 2000, eps: float = 1e-4,
        ps=(0.5, 0.6, 0.7, 0.8, 0.9, 1.0), trials: int = 3,
        scenario_trials: int = 0, scenario_scale: float = 0.25,
        scenario_retransmit_p: float = 0.9,
        artifact: str = "fig5_failures") -> list[str]:
    """`scenario_trials > 0` appends the failure-scenario matrix (at the
    same n, fixed-iterations mode) to the artifact and CSV."""
    g = random_geometric_graph(n, seed=21)
    x0 = np.random.default_rng(3).normal(0, 1, n)
    timing = {}
    ms, timing["multiscale"] = timed(
        multiscale_gossip, g, x0, eps=eps, seed=0, weighted=True,
        trials=trials,
    )
    pa_runs, timing["path_averaging"] = timed(lambda: [
        path_averaging(g, x0, eps=eps, seed=t) for t in range(trials)
    ])
    ms_trial_msgs = np.atleast_1d(np.asarray(ms.messages, np.int64))
    pa_trial_msgs = np.asarray([r.messages for r in pa_runs], np.int64)
    ms_msgs = int(ms_trial_msgs.mean())
    pa_msgs = int(pa_trial_msgs.mean())
    # per-trial handshake pricing: each trial's own message count drawn
    # through its own NegBinomial — the artifact keeps mean AND spread
    rng = np.random.default_rng(0)
    handshake = {}
    for p in ps:
        cm = CostModel(retransmit_p=p)
        c_ms = price_messages(ms_trial_msgs, cm, rng)
        c_pa = price_messages(pa_trial_msgs, cm, rng)
        handshake[str(p)] = {
            "multiscale": int(c_ms.physical_transmissions.mean()),
            "path_averaging": int(c_pa.physical_transmissions.mean()),
            "multiscale_per_trial": c_ms.physical_transmissions.tolist(),
            "path_averaging_per_trial": c_pa.physical_transmissions.tolist(),
            "multiscale_std": float(c_ms.physical_transmissions.std()),
            "path_averaging_std": float(c_pa.physical_transmissions.std()),
        }

    # message-loss model (changes the trajectory): bounded budgets, the
    # same `trials` seeds as the reliable runs (multiscale vmapped)
    loss_p = 0.9
    loss_opts = ExecOptions(max_ticks_per_level=60_000)
    ms_loss, timing["multiscale_loss"] = timed(
        multiscale_gossip, g, x0, eps=eps, seed=0, weighted=True,
        trials=trials, options=loss_opts, failures=FailureModel(loss_p=loss_p),
    )
    pa_loss, timing["path_averaging_loss"] = timed(lambda: [
        path_averaging(g, x0, eps=eps, seed=t, loss_p=loss_p,
                       max_iters=60_000)
        for t in range(trials)
    ])
    ms_loss_errs = np.atleast_1d(ms_loss.error(x0))
    pa_loss_errs = np.asarray([r.error(x0) for r in pa_loss])
    loss_model = {
        "p": loss_p,
        "trials": trials,
        "multiscale": {
            "err": float(ms_loss_errs.mean()),
            "err_std": float(ms_loss_errs.std()),
            "err_per_trial": ms_loss_errs.tolist(),
            "messages": int(np.atleast_1d(ms_loss.messages).mean()),
            "messages_per_trial":
                np.atleast_1d(ms_loss.messages).tolist(),
        },
        "path_averaging": {
            "err": float(pa_loss_errs.mean()),
            "err_std": float(pa_loss_errs.std()),
            "err_per_trial": pa_loss_errs.tolist(),
            "messages": int(np.mean([r.messages for r in pa_loss])),
            "messages_per_trial": [int(r.messages) for r in pa_loss],
        },
    }

    scenarios = None
    if scenario_trials > 0:
        plan = build_plan(g, seed=0)
        sc_cost = CostModel(retransmit_p=scenario_retransmit_p,
                            congestion_alpha=0.01)
        sc_res, timing["scenario_matrix"] = timed(
            run_scenario_matrix, g, x0, scenario_matrix(),
            eps=eps, trials=scenario_trials, seed=0, weighted=True,
            fixed_ticks_scale=scenario_scale, cost=sc_cost,
            plan=plan,
        )
        scenarios = {
            r.scenario.name: {
                "description": r.scenario.description,
                "err_mean": r.err_mean,
                "err_std": r.err_std,
                "err_per_trial": r.errors.tolist(),
                "survivor_err_mean": float(r.survivor_errors.mean()),
                "messages_mean": float(r.messages.mean()),
                "energy_mean": r.energy_mean,
                "retransmissions_mean": float(r.cost.retransmissions.mean()),
                "congestion_mean": float(r.cost.congestion.mean()),
            }
            for r in sc_res
        }

    payload = {
        "n": n,
        "eps": eps,
        "trials": trials,
        "trial_mode": "vmapped",
        "wall_clock_s": {k: float(v) for k, v in timing.items()},
        "handshake": handshake,
        "reliable_messages": {
            "multiscale": ms_msgs, "path_averaging": pa_msgs,
            "multiscale_per_trial": ms_trial_msgs.tolist(),
            "path_averaging_per_trial": pa_trial_msgs.tolist(),
        },
        "loss_model": loss_model,
    }
    if scenarios is not None:
        payload["scenario_matrix"] = {
            "trials": scenario_trials,
            "fixed_ticks_scale": scenario_scale,
            "retransmit_p": scenario_retransmit_p,
            "scenarios": scenarios,
        }
    save_artifact(artifact, payload)
    us = sum(timing.values()) * 1e6
    out = []
    for p in ps:
        h = handshake[str(p)]
        out.append(csv_line(
            f"fig5/handshake_p{p}", us / len(ps),
            f"ms={h['multiscale']} pa={h['path_averaging']} "
            f"ratio={h['path_averaging']/max(h['multiscale'],1):.2f}",
        ))
    lm = loss_model
    out.append(csv_line(
        f"fig5/loss_model_p{loss_p}_trials{trials}", 0.0,
        f"ms_err={lm['multiscale']['err']:.3f}"
        f"±{lm['multiscale']['err_std']:.3f} "
        f"pa_err={lm['path_averaging']['err']:.3f}"
        f"±{lm['path_averaging']['err_std']:.3f} "
        "(accuracy floor, §VI-C-2)",
    ))
    if scenarios is not None:
        for name, row in scenarios.items():
            out.append(csv_line(
                f"fig5/scenario_{name}", 0.0,
                f"err={row['err_mean']:.3f}±{row['err_std']:.3f} "
                f"surv_err={row['survivor_err_mean']:.3f} "
                f"energy={row['energy_mean']:.0f}",
            ))
    return out


if __name__ == "__main__":
    from .common import bench_cli

    bench_cli(run)
