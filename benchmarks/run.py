"""Benchmark harness — one entry per paper table/figure + system layers.

Prints ``name,us_per_call,derived`` CSV.  Profiles:
  default: reduced trial counts sized for a single-core CPU container;
  --full:  the paper's trial counts / sizes (longer).

Every figure benchmark exposes the same `run()` surface — `trials`,
`artifact` plus its own size knobs — so the harness dispatches them
from one profile table instead of special-casing each module.

The dry-run roofline cells are produced separately
(`python -m repro.launch.dryrun --all`, hours of XLA compile time) and
aggregated here if present.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale trials (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig3,roofline")
    args = ap.parse_args()
    keep = set(args.only.split(",")) if args.only else None

    # the sync suite lowers on 32 emulated host devices in a process of
    # its own; it runs to its end before this process imports JAX, so
    # the two never hold a device at once
    sync_proc = (_run_child("benchmarks.sync_collectives")
                 if keep is None or "sync" in keep else None)

    from repro.launch.compile_cache import enable_compile_cache

    from . import (
        fig2_levels, fig3_vs_path_averaging, fig4_cdf, fig5_failures,
        gossip_trajectory, kernel_bench, large_n, roofline, serve_bench,
        table1_node_utilization,
    )

    enable_compile_cache()
    # figure suites share one run() signature; each entry is
    # (module, default-profile kwargs, --full overrides)
    figures = {
        "fig2": (fig2_levels, dict(n=2000, trials=3),
                 dict(n=5000, trials=10)),
        "fig3": (fig3_vs_path_averaging,
                 dict(sizes=(500, 1000, 2000, 4000, 8000), trials=3),
                 dict(trials=10)),
        "fig4": (fig4_cdf, dict(n=2000), {}),
        "fig5": (fig5_failures, dict(n=2000, scenario_trials=3),
                 dict(scenario_trials=10)),
        "table1": (table1_node_utilization, dict(n=2000), dict(n=5000)),
    }

    def fig_suite(mod, base, full):
        kwargs = dict(base)
        if args.full:
            kwargs.update(full)
        return lambda: mod.run(**kwargs)

    suites = {name: fig_suite(*spec) for name, spec in figures.items()}
    suites.update({
        "kernels": kernel_bench.run,
        "sync": lambda: _child_lines(sync_proc),
        "roofline": roofline.run,
        "gossip": gossip_trajectory.run,
        "large_n": lambda: large_n.run(
            n=1_000_000 if args.full else 100_000
        ),
        "serve": serve_bench.run,
    })
    if keep is not None:
        suites = {k: v for k, v in suites.items() if k in keep}

    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        try:
            for line in fn():
                print(line, flush=True)
        except Exception as e:
            traceback.print_exc(file=sys.stderr)
            print(f"{name}/ERROR,0.0,{type(e).__name__}: {e}", flush=True)
            failed.append(name)
    if failed:
        raise SystemExit(f"failed suites: {', '.join(failed)}")


def _run_child(module: str) -> subprocess.CompletedProcess:
    """Run a benchmark that needs its own XLA device count in a fresh
    process on the CPU (the forced count must precede jax init)."""
    return subprocess.run(
        [sys.executable, "-m", module], capture_output=True, text=True,
        timeout=1800, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )


def _child_lines(proc: subprocess.CompletedProcess) -> list[str]:
    if proc.returncode != 0:
        raise RuntimeError(
            f"{proc.args[-1]} exited {proc.returncode}:\n"
            f"{proc.stderr[-3000:]}")
    return [l for l in proc.stdout.splitlines() if l.strip()]


if __name__ == "__main__":
    main()
