"""The system under test, driven from a configuration and a traffic mix.

This is the only module of the benchmark that imports the program
(`repro`, from the checkout's `src/`): the plan cache and `setup_plan`
for set-up, and `execute_plan` with the default `ExecOptions` for the
timed path.  Nothing the program builds reaches the reference.
"""
from __future__ import annotations

import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
PLAN_CACHE = os.path.join(ROOT, ".bench_cache", "plans")


def program():
    """The program's entry points; fails where the checkout has none."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"bench: no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.core as core
    from repro.launch.compile_cache import enable_compile_cache

    return core, enable_compile_cache


def setup_plan(cfg: dict):
    core, _ = program()
    plan, _info = core.setup_plan(
        n=cfg["n"], c=cfg["c"], graph_seed=cfg["graph_seed"], k=cfg["k"],
        a=cfg["a"], cell_max=cfg["cell_max"], seed=cfg["plan_seed"],
        rep_mode=cfg["rep_mode"], cache_dir=PLAN_CACHE)
    return plan


def options(traffic: dict, devices):
    """Default `ExecOptions`, with only the mesh set."""
    core, _ = program()
    mesh = None
    if traffic.get("mesh"):
        from jax.sharding import Mesh

        shape = traffic["mesh"]
        mesh = Mesh(np.array(devices).reshape(shape["trials"],
                                              shape["nodes"]),
                    ("trials", "nodes"))
    return core.ExecOptions(mesh=mesh)


def cadence(opts) -> dict:
    """The ε-oracle check cadence and tick cap per level that `opts`
    run with (the program's defaults): the replay follows them."""
    return {"check_every": opts.check_every,
            "max_ticks": opts.max_ticks_per_level}


def execute(plan, cfg: dict, x0, seeds, opts):
    core, _ = program()
    return core.execute_plan(
        plan, x0, eps=cfg["eps"], seeds=tuple(int(s) for s in seeds),
        weighted=cfg["weighted"], fixed_ticks_scale=cfg["fixed_ticks_scale"],
        options=opts)


def free(plan) -> None:
    """Drop the plan's compiled executors (and their device buffers)."""
    plan.exec_cache.clear()
