#!/usr/bin/env python3
"""Smoke run of the multiscale-gossip core across four TPU chips.

    python chip_smoke.py --chips 4

It runs only what exists across chips, with default `ExecOptions`:

* phase mesh: a million-node deployment (the large-n configuration of
  `benchmarks/large_n.py`: n=10^6, fixed iterations at
  `fixed_ticks_scale=0.2`) on a (trials=1, nodes=4) mesh against the
  same plan on one chip, bit for bit (x_final, messages, node sends,
  per-level ticks);
* phase sync: the training sync `execute_sync_sharded` against
  `execute_sync` for 4 replicas, one per chip.

What one chip runs is measured by the benchmark's cells
(`python3 -m bench.run`), each checked against a float64 replay.

Times printed on the way are one smoke run's wall clock, not metrics.
The last line of standard output is one JSON object naming the device,
printed only when every check passed.  Without four TPU chips it exits
non-zero before running anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro.core import ExecOptions, execute_plan, setup_plan  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

LARGE_N = 10**6


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def tpu_devices(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: {chips} chips asked for, {len(devs)} found")
    return devs


def log_levels(plan) -> None:
    for li, lp in enumerate(plan.levels):
        B, C = lp.node_mask.shape
        log(f"  level {li}: {lp.kind:7s} B={B} C={C} "
            f"max_hops={lp.max_hops}")


def timed_execute(plan, x0, options, **kw):
    """(result, cold seconds, warm seconds): the cold call compiles."""
    t0 = time.perf_counter()
    res = execute_plan(plan, x0, options=options, **kw)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    execute_plan(plan, x0, options=options, **kw)
    return res, cold, time.perf_counter() - t0


def bitwise_equal(a: np.ndarray, b: np.ndarray) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and bool(
        np.array_equal(a.view(np.uint8), b.view(np.uint8)))


def require_same(a, b, what: str) -> None:
    require(bitwise_equal(a.x_final, b.x_final), f"{what}: x_final differs")
    require(np.array_equal(a.messages, b.messages),
            f"{what}: messages {a.messages} != {b.messages}")
    require(np.array_equal(a.node_sends, b.node_sends),
            f"{what}: node_sends differ")
    require(np.array_equal(a.level_ticks, b.level_ticks),
            f"{what}: level ticks {a.level_ticks} != {b.level_ticks}")


def large_plan(n: int):
    t0 = time.perf_counter()
    plan, info = setup_plan(n=n, graph_seed=1000 + n, seed=0,
                            use_cache=False)
    log(f"  set-up: graph {info['graph_gen_s']:.3f} s, plan "
        f"{info['plan_build_s'].get('total', 0.0):.3f} s, total "
        f"{time.perf_counter() - t0:.3f} s")
    log_levels(plan)
    x0 = np.random.default_rng(n).normal(0.0, 1.0, n)
    return plan, x0


LARGE_KW = dict(eps=1e-3, fixed_ticks_scale=0.2, weighted=True, seeds=(0,))


def phase_mesh(devs, n: int = LARGE_N) -> None:
    """The large-n plan on a (trials=1, nodes=4) mesh vs one chip."""
    from jax.sharding import Mesh

    log(f"phase mesh: n={n} on a (trials=1, nodes=4) mesh vs one chip")
    plan, x0 = large_plan(n)
    mesh = Mesh(np.array(devs[:4]).reshape(1, 4), ("trials", "nodes"))
    sharded, cold, warm = timed_execute(
        plan, x0, ExecOptions(mesh=mesh), **LARGE_KW)
    log(f"  mesh: execute cold {cold:.3f} s, warm {warm:.3f} s")
    single, cold, warm = timed_execute(
        plan, x0, ExecOptions(), **LARGE_KW)
    log(f"  one chip: execute cold {cold:.3f} s, warm {warm:.3f} s")
    log(f"  error {float(sharded.error(x0)[0]):.3e}, "
        f"messages {sharded.messages.tolist()}")
    require_same(sharded, single, "mesh vs one chip")
    log("phase mesh: pass")


def phase_sync(devs, R: int = 4) -> None:
    """The training sync: shard_map executor vs the dense executor."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.dist import (
        SyncConfig, build_sync_plan, execute_sync, execute_sync_sharded,
        suggest_levels,
    )

    levels = suggest_levels(R)
    log(f"phase sync: multiscale, R={R}, levels {levels}, one replica "
        "per chip")
    mesh = jax.make_mesh((R,), ("replica",), devices=devs[:R])
    shapes = {"w1": (1024, 1024), "w2": (4096, 512), "w3": (65536,)}
    rng = np.random.default_rng(0)
    grads = {
        k: jax.device_put(
            rng.normal(0.0, 1.0, (R, *s)).astype(np.float32),
            NamedSharding(mesh, P("replica", *([None] * len(s)))))
        for k, s in shapes.items()
    }
    plan = build_sync_plan(SyncConfig("multiscale", levels=levels), R)
    dense = jax.jit(lambda g, s: execute_sync(plan, g, None, s)[0])
    sharded = jax.jit(
        lambda g, s: execute_sync_sharded(plan, g, None, s, mesh=mesh)[0])
    for step in (0, 1):
        a = dense(grads, jnp.int32(step))
        b = sharded(grads, jnp.int32(step))
        for k in shapes:
            np.testing.assert_allclose(
                np.asarray(a[k]), np.asarray(b[k]), rtol=2e-6, atol=2e-6,
                err_msg=f"phase sync step {step} leaf {k}")
    text = sharded.lower(grads, jnp.int32(0)).compile().as_text()
    log(f"  collectives in the shard_map executor: "
        f"{sum(text.count(op) for op in ('all-reduce', 'collective-permute'))}")
    log("phase sync: pass")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(4,), default=4,
                    help="chips to run across (four, the only mode)")
    args = ap.parse_args()
    devs = tpu_devices(args.chips)
    log(f"device: {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{enable_compile_cache()}")
    phase_mesh(devs)
    phase_sync(devs)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }}), flush=True)


if __name__ == "__main__":
    main()
