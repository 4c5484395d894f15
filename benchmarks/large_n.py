"""Large-n gossip benchmark: the CSR plan/execute path at 10^5-10^6
nodes, with a dense-path oracle cross-check at an overlapping size.

Two parts:

1. **Overlap parity** — at `overlap_n` (fig2-sized, where the historical
   dense/loop builder is still affordable) the benchmark builds the plan
   with BOTH `build_plan` methods (`reference`: the per-cell/per-group
   loop builder; `vectorized`: the CSR fast path) and executes each with
   the identical engine config.  The message counts must agree within
   ±15%; the builders are in fact bitwise-identical, so the recorded
   ratio is exactly 1.0 and any future drift is a plan-construction bug,
   not noise.

2. **Large-n run** — one fixed-iterations (FI) trial at `n` through the
   vectorized builder and the lax presampled engine: cold setup (graph
   generation via the streamed bucket builder + plan build, with the
   per-stage `build_seconds` breakdown, forced with `refresh=True`),
   warm setup (a content-addressed plan-cache hit via
   `core.plan_cache.setup_plan` — the acceptance bar is warm < 5% of
   cold), cold execute (includes compile) and warm execute wall-clocks,
   total messages and final error, plus the peak host RSS / live
   device-buffer bytes from `tools.membuf_probe`.  `--workers N` shards
   plan construction over a fork pool (bitwise-identical output; a
   wall-clock lever on multi-core hosts only).

The FI profile (eps sentinel off, `fixed_ticks_scale=0.2`) is the
large-n configuration of record: convergence detection at 10^6 nodes
costs a full extra residual reduction per check and the paper's FI
variant is the one intended for known deployments.  `eps` here is only
the tick-budget scale parameter fed to `fi_ticks`.

    python -m benchmarks.large_n [--n 100000] [--smoke]

`--smoke` is the CI profile (n=20000, artifact `large_n_smoke`) wired
into `REPRO_BENCH_SMOKE=1 tools/ci.sh` and drift-gated by
`tools/check_artifacts.py --large-n-only`.  `gossip_trajectory` folds
any committed `large_n_*` artifacts into the BENCH_gossip.json entry.
"""
from __future__ import annotations

import os
import sys

import numpy as np

from repro.core import build_plan, execute_plan, random_geometric_graph
from repro.core.plan_cache import setup_plan

from .common import csv_line, enable_compile_cache, save_artifact, timed

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.membuf_probe import memory_report  # noqa: E402

OVERLAP_TOLERANCE = 0.15


def _execute_stats(plan, x0, *, eps, fixed_ticks_scale, seeds):
    res, dt = timed(
        execute_plan, plan, x0, eps=eps, seeds=seeds, weighted=True,
        fixed_ticks_scale=fixed_ticks_scale,
    )
    return res, dt


def overlap_check(overlap_n: int, *, eps: float, fixed_ticks_scale: float,
                  seed: int = 0) -> dict:
    """Execute reference-built vs vectorized-built plans at a size both
    can afford; return the message-count comparison."""
    g = random_geometric_graph(overlap_n, seed=1000 + overlap_n)
    x0 = np.random.default_rng(overlap_n).normal(0, 1, overlap_n)
    msgs = {}
    for method in ("reference", "vectorized"):
        plan = build_plan(g, seed=seed, method=method)
        res, _ = _execute_stats(
            plan, x0, eps=eps, fixed_ticks_scale=fixed_ticks_scale,
            seeds=(seed,),
        )
        msgs[method] = int(res.messages[0])
    ratio = msgs["vectorized"] / max(msgs["reference"], 1)
    return {
        "n": int(overlap_n),
        "messages": msgs,
        "ratio": float(ratio),
        "tolerance": OVERLAP_TOLERANCE,
        "ok": bool(abs(ratio - 1.0) <= OVERLAP_TOLERANCE),
    }


def default_cache_dir() -> str:
    """Benchmark-local plan cache (gitignored)."""
    return os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "artifacts", "plan_cache"
    )


def run(n: int = 100_000, overlap_n: int = 2000, trials: int = 1,
        eps: float = 1e-3, fixed_ticks_scale: float = 0.2,
        seed: int = 0, workers: int = 0,
        cache_dir: str | None = None,
        artifact: str | None = None) -> list[str]:
    artifact = artifact or f"large_n_{n}"
    cache_dir = cache_dir or default_cache_dir()
    overlap = overlap_check(
        overlap_n, eps=eps, fixed_ticks_scale=fixed_ticks_scale, seed=seed,
    ) if overlap_n else None

    # cold setup: streamed graph gen + plan build, forced fresh (the
    # store also primes the cache for the warm measurement)
    plan, cold_info = setup_plan(
        n=n, graph_seed=1000 + n, seed=seed, workers=workers,
        cache_dir=cache_dir, refresh=True,
    )
    graph_s = float(cold_info["graph_gen_s"])
    plan_s = float(cold_info["plan_build_s"].get("total", 0.0))
    cold_setup_s = graph_s + plan_s
    # warm setup: content-addressed cache hit, graph gen + build skipped
    warm_plan, warm_info = setup_plan(
        n=n, graph_seed=1000 + n, seed=seed, workers=workers,
        cache_dir=cache_dir,
    )
    warm_setup_s = float(warm_info["setup_s"])
    assert warm_info["cache"] == "hit", warm_info
    del warm_plan
    x0 = np.random.default_rng(n).normal(0, 1, n)
    seeds = tuple(seed + t for t in range(trials))
    res, cold_s = _execute_stats(
        plan, x0, eps=eps, fixed_ticks_scale=fixed_ticks_scale,
        seeds=seeds,
    )
    _, warm_s = _execute_stats(
        plan, x0, eps=eps, fixed_ticks_scale=fixed_ticks_scale,
        seeds=seeds,
    )
    payload = {
        "n": int(n),
        "trials": trials,
        "mode": "fixed_iterations",
        "eps": eps,
        "fixed_ticks_scale": fixed_ticks_scale,
        "graph_seed": 1000 + int(n),
        "levels": len(plan.levels),
        "workers": int(workers),
        "graph_gen_s": graph_s,
        "plan_build_s": dict(plan.build_seconds or {}),
        "setup": {
            "cold_s": float(cold_setup_s),
            "warm_s": float(warm_setup_s),
            "warm_over_cold": float(warm_setup_s / max(cold_setup_s, 1e-9)),
            "cache_key": warm_info["key"],
            "load_s": float(warm_info.get("load_s", 0.0)),
        },
        "wall_clock_s": {
            "graph": float(graph_s),
            "plan": plan_s,
            "setup_cold": float(cold_setup_s),
            "setup_warm": float(warm_setup_s),
            "execute_cold": float(cold_s),
            "execute_warm": float(warm_s),
        },
        "messages": [int(m) for m in np.asarray(res.messages)],
        "err": [float(e) for e in np.atleast_1d(res.error(x0))],
        "memory": memory_report(),
        "overlap": overlap,
    }
    save_artifact(artifact, payload)
    if overlap is not None and not overlap["ok"]:
        raise SystemExit(
            f"large_n: overlap parity FAILED at n={overlap_n}: "
            f"vectorized/reference message ratio {overlap['ratio']:.3f} "
            f"outside ±{OVERLAP_TOLERANCE:.0%}"
        )
    out = []
    mem = payload["memory"]
    out.append(csv_line(
        f"large_n/n{n}", cold_s * 1e6,
        f"msgs={payload['messages'][0]} err={payload['err'][0]:.2e} "
        f"graph={graph_s:.2f}s "
        f"plan={payload['plan_build_s'].get('total', 0.0):.2f}s "
        f"warm={warm_s:.2f}s "
        f"rss={mem['host_peak_rss_bytes'] / 2**30:.2f}GiB",
    ))
    out.append(csv_line(
        f"large_n/setup_n{n}", cold_setup_s * 1e6,
        f"cold={cold_setup_s:.2f}s warm={warm_setup_s:.3f}s "
        f"({payload['setup']['warm_over_cold']:.1%} of cold, cache hit) "
        f"workers={workers}",
    ))
    if overlap is not None:
        out.append(csv_line(
            "large_n/overlap_parity", 0.0,
            f"n={overlap_n} ratio={overlap['ratio']:.3f} "
            f"(vectorized vs reference plan, tol ±{OVERLAP_TOLERANCE:.0%})",
        ))
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--overlap-n", type=int, default=2000,
                    help="0 disables the dense-path overlap check")
    ap.add_argument("--trials", type=int, default=1)
    ap.add_argument("--eps", type=float, default=1e-3)
    ap.add_argument("--scale", type=float, default=0.2,
                    help="fixed_ticks_scale (FI tick budget)")
    ap.add_argument("--workers", type=int, default=0,
                    help="fork-pool width for plan construction "
                         "(bitwise-identical to serial; wall-clock only)")
    ap.add_argument("--cache-dir", default=None,
                    help="plan-cache directory "
                         "(default benchmarks/artifacts/plan_cache)")
    ap.add_argument("--artifact", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI profile: n=20000 -> artifact large_n_smoke")
    args = ap.parse_args()
    if args.smoke:
        args.n, args.artifact = 20_000, args.artifact or "large_n_smoke"
    enable_compile_cache()
    for line in run(
        n=args.n, overlap_n=args.overlap_n, trials=args.trials,
        eps=args.eps, fixed_ticks_scale=args.scale,
        workers=args.workers, cache_dir=args.cache_dir,
        artifact=args.artifact,
    ):
        print(line)
