"""Collective-traffic comparison of the gradient-sync strategies — the
paper's message-complexity claim measured on compiled HLO (DESIGN §2).

Lowered on a 32-replica mesh (2 "pods" x 16) with a ~64 MB gradient
tree; for each strategy we count collective ops/bytes and the cross-pod
share.  Expected, mirroring the paper:
  * allreduce: one global all-reduce per leaf — every byte crosses pods;
  * hierarchical: grouped reduces — cross-pod bytes shrink to the
    top-level fusion only;
  * ring: many collective-permute rounds (flat gossip is chatty — the
    paper's slow baseline);
  * multiscale: permutes mostly INSIDE cells; only representative
    promotion crosses pods — the O(n^(1/3))-hop analogue.

Strategies lower through the plan/execute split (`build_sync_plan` +
`execute_sync`), including error-feedback-compressed and rotated
(randomized-cell) gossip variants.  The simulation exchanges dense f32
tensors; `total_bytes`/`by_kind` report the lowering as-is, which for
compressed/rotated variants includes compression-COMPUTE collectives
(the emulated top-k sort all-gathers rows; the rotation permutation
lowers as gathers) on top of the mixing payload.  The `wire_bytes`
column models what a packed wire format would actually carry: the base
strategy's mixing collective bytes x `compression.wire_fraction` (topk
ships (value, index) pairs, so fraction 0.125 keeps wire at 0.25x
dense; int8 is 1 byte per entry = 0.25x; rotation relabels neighbors
without changing traffic).  `modeled_wire_bytes` is the
device-independent `plan_wire_bytes` accounting used by the train-step
metric.

Cross-pod classification goes through `device_pod_map`: partition ids in
lowered replica_groups index the mesh device assignment (reshapes of the
replica axis remap them), so the raw `id // pod_size` heuristic is only
the fallback.

`--wallclock` additionally records the serialized-vs-overlapped
comparison: `serialized_ms` chains a stand-in backward compute into the
sync (the old pipeline — sync strictly after backward), `overlapped_ms`
runs the same compute and the sync of an INDEPENDENT (previous-step)
gradient buffer in one program (the async one-step pipeline,
`dist.async_sync`), both through the shard_map executor so the
collectives are scheduling-explicit; `overlap_delta_ms` is the
wall-clock the overlap reclaims.  Timed for the representative subset
`OVERLAP_TIMED` (exact baselines + both multiscale variants) — the
64-round flat ring is minutes of pure collective chatter per call on
the emulated mesh and adds nothing to the comparison.  On the emulated
host mesh the delta reflects scheduler behavior, not real interconnect
overlap — `wallclock_emulated` flags it.

Run standalone (sets its own device count): python -m benchmarks.sync_collectives
    --wallclock   additionally times the compiled sync on the available
                  devices (skips cleanly on single-device hosts)
"""
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"

import json
import time

import numpy as np


def run(wallclock: bool = False) -> list[str]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.dist import (
        CompressionConfig, SyncConfig, SyncFailureModel, build_sync_plan,
        execute_sync, execute_sync_sharded, plan_wire_bytes, suggest_levels,
        wire_fraction,
    )
    from repro.launch.hlo_analysis import collective_bytes, device_pod_map
    from .common import csv_line, load_artifact, save_artifact

    R = 32
    mesh = jax.make_mesh((R,), ("replica",))
    grads_abs = {
        "w1": jax.ShapeDtypeStruct((R, 1024, 1024), jnp.float32),
        "w2": jax.ShapeDtypeStruct((R, 4096, 512), jnp.float32),
        "w3": jax.ShapeDtypeStruct((R, 65536,), jnp.float32),
    }
    per_replica_bytes = sum(
        np.prod(a.shape[1:]) * 4 for a in grads_abs.values()
    )
    sh = {k: NamedSharding(mesh, P("replica", *([None] * (len(a.shape) - 1))))
          for k, a in grads_abs.items()}
    step_abs = jax.ShapeDtypeStruct((), jnp.int32)
    step_sh = NamedSharding(mesh, P())
    levels = suggest_levels(R)           # (4, 2, 4) for 32
    topk = CompressionConfig("topk", topk_fraction=0.125)  # 2x/entry -> 0.25x wire
    int8 = CompressionConfig("int8")
    strategies = {
        "allreduce": SyncConfig("allreduce"),
        "hierarchical": SyncConfig("hierarchical", levels=levels),
        "ring": SyncConfig("ring", rounds=(2 * R,)),
        "multiscale": SyncConfig("multiscale", levels=levels),
        "multiscale_exact": SyncConfig("multiscale", levels=levels,
                                       exact_fusion=True),
        "ring_int8": SyncConfig("ring", rounds=(2 * R,), compression=int8),
        "multiscale_topk": SyncConfig("multiscale", levels=levels,
                                      compression=topk),
        "multiscale_int8": SyncConfig("multiscale", levels=levels,
                                      compression=int8),
        "multiscale_rotated": SyncConfig("multiscale", levels=levels,
                                         rotation_period=4),
        # fault-tolerant variants (dist.failures / dist.robust): the same
        # lowering pipeline with failure injection + robust aggregation
        # fused into the executor — their extra collectives (mask
        # broadcasts, the trimmed-mean all-gather) are the measured cost
        # of the defense
        "multiscale_churn_survivor": SyncConfig(
            "multiscale", levels=levels, aggregation="survivor_weighted",
            failures=SyncFailureModel(churn_fraction=0.25, seed=0)),
        "multiscale_topk_churn": SyncConfig(
            "multiscale", levels=levels, compression=topk,
            failures=SyncFailureModel(churn_fraction=0.25, seed=0)),
        "allreduce_trimmed_byzantine": SyncConfig(
            "allreduce", aggregation="trimmed_mean",
            failures=SyncFailureModel(byzantine_fraction=0.125,
                                      byzantine_scale=10.0, seed=0)),
    }
    # serialized-vs-overlapped timing subset (see module docstring)
    OVERLAP_TIMED = {
        "allreduce", "hierarchical", "multiscale", "multiscale_exact",
    }
    # 16 replicas per "pod"; partition ids map through the assignment
    pod_of = device_pod_map(list(mesh.devices.flat), pod_size=16)
    can_time = jax.device_count() >= 2
    # standalone mode forces 32 emulated host devices — wallclock numbers
    # are then scheduling-emulation times, not real interconnect traffic;
    # label them so they are never read as hardware measurements
    emulated = "--xla_force_host_platform_device_count" in os.environ.get(
        "XLA_FLAGS", ""
    )
    grads = None
    if wallclock and can_time:  # identical for every strategy — build once
        grads = {
            k: jax.device_put(
                np.random.default_rng(0).normal(0, 1, a.shape).astype(
                    np.float32
                ),
                sh[k],
            )
            for k, a in grads_abs.items()
        }
        # stand-in backward for the serialized-vs-overlapped comparison:
        # a per-replica matmul chain, replica-sharded like the gradients
        act = jax.device_put(
            np.random.default_rng(1).normal(0, 1, (R, 128, 128)).astype(
                np.float32
            ),
            NamedSharding(mesh, P("replica", None, None)),
        )

        def backward_like(a):
            for _ in range(4):
                a = jnp.tanh(jnp.einsum("rij,rjk->rik", a, a) / 128.0)
            return a

        def time_compiled(fn, args, reps=3):
            jax.block_until_ready(fn(*args))  # warm-up / compile
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(fn(*args))
            return (time.perf_counter() - t0) * 1e3 / reps
    rows, lines = {}, []
    # dense-base mixing collectives per (strategy, levels, rounds,
    # exact_fusion): compressed/rotated variants inherit their base's
    # payload traffic for the wire_bytes model (iteration order puts
    # every base before its variants)
    base_bytes: dict = {}
    for name, cfg_s in strategies.items():
        plan = build_sync_plan(cfg_s, R)
        compressed = cfg_s.compression.scheme != "none"
        with jax.set_mesh(mesh):
            if compressed:  # residuals ride along as a second input pytree
                fn = lambda g, r, s, p=plan: execute_sync(p, g, r, s)
                jitted = jax.jit(fn, in_shardings=(sh, sh, step_sh),
                                 out_shardings=(sh, sh))
                abs_args = (grads_abs, grads_abs, step_abs)
            else:
                fn = lambda g, s, p=plan: execute_sync(p, g, None, s)[0]
                jitted = jax.jit(fn, in_shardings=(sh, step_sh),
                                 out_shardings=sh)
                abs_args = (grads_abs, step_abs)
            compiled = jitted.lower(*abs_args).compile()
        stats = collective_bytes(compiled.as_text(), pod_size=16, pod_of=pod_of)
        frac = wire_fraction(cfg_s.compression)
        key = (cfg_s.strategy, plan.levels, plan.rounds, plan.exact_fusion)
        if not compressed and not plan.rotated and not plan.faulty:
            base_bytes.setdefault(key, stats.total_bytes)
        # variants must follow their dense base in `strategies`: falling back
        # to the variant's own lowering would count compression-compute
        # collectives (top-k sort gathers, rotation gathers) as wire payload
        assert key in base_bytes, (
            f"{name}: dense base for {key} must be listed before its variants"
        )
        mixing_bytes = base_bytes[key]
        rows[name] = stats.asdict()
        rows[name]["bytes_per_replica_payload"] = float(per_replica_bytes)
        rows[name]["wire_fraction"] = frac
        rows[name]["wire_bytes"] = float(mixing_bytes) * frac
        rows[name]["modeled_wire_bytes"] = plan_wire_bytes(plan, grads_abs)
        rows[name]["compression"] = cfg_s.compression.scheme
        rows[name]["rotation_period"] = cfg_s.rotation_period
        rows[name]["aggregation"] = cfg_s.aggregation
        fm = cfg_s.failures
        rows[name]["failures"] = (
            "none" if fm is None else
            f"churn={fm.churn_fraction:g},straggler="
            f"{fm.straggler_fraction:g},byzantine={fm.byzantine_fraction:g}")
        lines.append(csv_line(
            f"sync/{name}", 0.0,
            f"coll_bytes={stats.total_bytes} "
            f"cross_pod={stats.cross_pod_bytes} "
            f"ops={stats.count} "
            f"xpod_frac={stats.cross_pod_bytes/max(stats.total_bytes,1):.2f} "
            f"wire_bytes={rows[name]['wire_bytes']:.0f} "
            f"wire_frac={frac:.3f} "
            f"agg={cfg_s.aggregation} "
            f"failures={rows[name]['failures']}",
        ))
        if wallclock and can_time:
            args = (grads, jnp.int32(0))
            if compressed:
                res = {k: jax.device_put(np.zeros(a.shape, np.float32), sh[k])
                       for k, a in grads_abs.items()}
                args = (grads, res, jnp.int32(0))
            jax.block_until_ready(compiled(*args))  # warm-up
            reps = 3
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(compiled(*args))
            ms = (time.perf_counter() - t0) * 1e3 / reps
            rows[name]["wallclock_ms"] = ms
            rows[name]["wallclock_emulated"] = emulated
            lines.append(csv_line(
                f"sync/{name}/wallclock", ms * 1e3,
                f"ms_per_sync={ms:.1f} devices={jax.device_count()} "
                f"emulated={emulated}",
            ))
        if wallclock and can_time and name in OVERLAP_TIMED:
            # serialized (backward then sync, data-dependent) vs
            # overlapped (backward plus the sync of an independent
            # previous-step buffer — the async one-step pipeline), both
            # through the shard_map executor
            def serialized_fn(g, a, s, p=plan):
                h = backward_like(a)
                # the sync input depends on the backward product
                g = jax.tree.map(
                    lambda x: x + jnp.tanh(jnp.mean(h)) * 1e-20, g
                )
                out, _ = execute_sync_sharded(p, g, None, s, mesh=mesh)
                return out, h

            def overlapped_fn(g, a, s, p=plan):
                out, _ = execute_sync_sharded(p, g, None, s, mesh=mesh)
                h = backward_like(a)
                return out, h

            args2 = (grads, act, jnp.int32(0))
            ser_ms = time_compiled(jax.jit(serialized_fn), args2)
            ovl_ms = time_compiled(jax.jit(overlapped_fn), args2)
            rows[name]["serialized_ms"] = ser_ms
            rows[name]["overlapped_ms"] = ovl_ms
            rows[name]["overlap_delta_ms"] = ser_ms - ovl_ms
            lines.append(csv_line(
                f"sync/{name}/overlap", ovl_ms * 1e3,
                f"serialized_ms={ser_ms:.1f} overlapped_ms={ovl_ms:.1f} "
                f"delta_ms={ser_ms - ovl_ms:.1f} emulated={emulated}",
            ))
    if wallclock and not can_time:
        lines.append(csv_line(
            "sync/wallclock", 0.0,
            f"SKIP: single-device host (devices={jax.device_count()})",
        ))
    payload = {"levels": list(levels), "rows": rows}
    if wallclock:
        payload["wallclock_devices"] = jax.device_count()
        payload["wallclock_emulated"] = emulated
    else:
        # lowering-only runs keep the last measured wall-clock data so a
        # default CI pass does not erase it from the tracked artifact
        prev = load_artifact("sync_collectives") or {}
        for k in ("wallclock_devices", "wallclock_emulated"):
            if k in prev:
                payload[k] = prev[k]
        for name, row in payload["rows"].items():
            old = prev.get("rows", {}).get(name, {})
            for k in ("wallclock_ms", "wallclock_emulated", "serialized_ms",
                      "overlapped_ms", "overlap_delta_ms"):
                if k in old:
                    row[k] = old[k]
    save_artifact("sync_collectives", payload)
    return lines


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--wallclock", action="store_true",
                    help="time compiled sync_gradients on available devices")
    for line in run(wallclock=ap.parse_args().wallclock):
        print(line)
