"""Paper Fig. 3: total messages to eps=1e-4 vs network size, for
MultiscaleGossip (auto-k), MultiscaleGossipFI (fixed iterations),
MultiscaleGossip2level (k=2, a=1/2), and path averaging [13].

Expected (paper): every multiscale variant uses noticeably fewer
transmissions than path averaging, near-linear growth in n.

Multiscale variants run through the plan/execute core: one
`HierarchyPlan` per (n, partition config), all trials vmapped into a
single compiled call.  Wall-clock per algorithm is recorded in the
artifact.

Standalone:  python -m benchmarks.fig3_vs_path_averaging \
                 [--sizes 500,1000] [--trials 3]
"""
from __future__ import annotations

import numpy as np

from repro.core import (
    build_plan, multiscale_gossip, path_averaging, random_geometric_graph,
)

from .common import csv_line, save_artifact, timed


def _warm_jit() -> float:
    """Absorb one-time XLA/LLVM process-init cost before the timed rows.

    Compiles a throwaway executor on a tiny unrelated graph: none of the
    timed configurations share shapes with it (so nothing timed is
    pre-cached), but backend initialization, first-compile allocator
    warmup, etc. stop being attributed to whichever algorithm happens to
    run first.  Returns the warmup seconds (recorded in the artifact).
    """
    def warm():
        # two distinct tiny compiles: the first absorbs backend/LLVM
        # init, the second the remaining first-recompile overhead
        # (allocator, lowering-rule caches)
        for n in (24, 40):
            g = random_geometric_graph(n, seed=9)
            multiscale_gossip(g, np.zeros(n), eps=1e-2, seed=0)

    _, dt = timed(warm)
    return dt


def run(sizes=(500, 1000, 2000, 4000, 8000), trials: int = 3,
        eps: float = 1e-4,
        artifact: str = "fig3_vs_path_averaging") -> list[str]:
    algo_names = ["multiscale", "multiscale_fi", "multiscale_2level",
                  "path_averaging"]
    table: dict = {a: {} for a in algo_names}
    timing: dict = {a: 0.0 for a in algo_names}
    plan_build_s: dict = {}
    graph_gen_s: dict = {}
    warmup_s = _warm_jit()

    def record(name, n, res, x0, dt):
        timing[name] += dt
        errs = np.atleast_1d(res.error(x0))
        msgs = np.atleast_1d(res.messages)
        table[name][n] = [
            {"messages": int(m), "err": float(e)} for m, e in zip(msgs, errs)
        ]

    for n in sizes:
        g, g_dt = timed(random_geometric_graph, n, seed=1000 + n)
        graph_gen_s[int(n)] = float(g_dt)
        x0 = np.stack([
            np.random.default_rng(n + t).normal(0, 1, n) for t in range(trials)
        ])
        plan_auto = build_plan(g, seed=0)          # shared by auto-k variants
        plan_2l = build_plan(g, k=2, a=0.5, seed=0)
        plan_build_s[int(n)] = {
            "auto_k": dict(plan_auto.build_seconds or {}),
            "k2": dict(plan_2l.build_seconds or {}),
        }
        ms_variants = {
            "multiscale": dict(plan=plan_auto),
            "multiscale_fi": dict(plan=plan_auto, fixed_ticks_scale=1.0),
            "multiscale_2level": dict(plan=plan_2l),
        }
        def run_ms(name):
            r, dt = timed(
                multiscale_gossip, g, x0 if trials > 1 else x0[0], eps=eps,
                seed=0, weighted=True, trials=trials,
                **ms_variants[name],
            )
            return name, r, dt

        # rows run serially: overlapping path averaging (GIL-holding
        # numpy) with the executors' tracing phase (also GIL-holding)
        # inflated both rows with contention on small hosts — serialized
        # timings are attributable per algorithm
        for name in ms_variants:
            name, r, dt = run_ms(name)
            record(name, n, r, x0 if trials > 1 else x0[0], dt)
        pa, pa_dt = timed(lambda: [
            path_averaging(g, x0[t], eps=eps, seed=t)
            for t in range(trials)
        ])
        timing["path_averaging"] += pa_dt
        table["path_averaging"][n] = [
            {"messages": int(r.messages), "err": float(r.error(x0[t]))}
            for t, r in enumerate(pa)
        ]

    summary = {
        name: {
            n: {
                "messages_mean": float(np.mean([x["messages"] for x in v])),
                "err_mean": float(np.mean([x["err"] for x in v])),
            }
            for n, v in rows.items()
        }
        for name, rows in table.items()
    }
    # scaling exponents (log-log fit)
    fits = {}
    for name, rows in summary.items():
        ns = sorted(rows)
        if len(ns) > 1:
            fits[name] = float(np.polyfit(
                np.log([float(n) for n in ns]),
                np.log([rows[n]["messages_mean"] for n in ns]), 1
            )[0])
        else:
            fits[name] = None  # a single size has no slope (avoid NaN JSON)
    save_artifact(
        artifact,
        {
            "eps": eps,
            "trials": trials,
            # trials share one deployment per n (graph seed 1000+n, the
            # vmapped plan/execute design): messages variance is gossip
            # noise only, NOT across-graph variance as in the paper's
            # error bars; x0 is redrawn per trial
            "trial_mode": "vmapped-shared-graph",
            "graph_seeds": {int(n): 1000 + int(n) for n in sizes},
            "jit_warmup_s": float(warmup_s),
            "wall_clock_s": {k: float(v) for k, v in timing.items()},
            "graph_gen_s": graph_gen_s,
            "plan_build_s": plan_build_s,
            "summary": summary,
            "scaling_exponent": fits,
        },
    )
    out = []
    n_big = max(sizes)
    for name, rows in summary.items():
        calls = len(sizes) * trials
        exp = f"{fits[name]:.2f}" if fits[name] is not None else "n/a"
        out.append(csv_line(
            f"fig3/{name}", timing[name] * 1e6 / calls,
            f"messages@n{n_big}={rows[n_big]['messages_mean']:.0f} "
            f"exponent={exp} wall={timing[name]:.1f}s",
        ))
    ratio = (
        summary["path_averaging"][n_big]["messages_mean"]
        / summary["multiscale"][n_big]["messages_mean"]
    )
    out.append(csv_line(
        "fig3/pa_over_multiscale", 0.0,
        f"ratio@n{n_big}={ratio:.2f} (paper: multiscale wins, Fig.3)",
    ))
    return out


if __name__ == "__main__":
    from .common import bench_cli

    bench_cli(run)
