"""Asynchronous (overlapped) gradient synchronization.

The paper's analysis rests on an asynchronous time model: nodes gossip
without a global clock, and because most messages travel short
distances, communication proceeds concurrently with local work.  The
training-stack transplant of that idea is **one-step-delayed gradient
averaging** (`SyncConfig(overlap="one_step")`): step `t` applies the
*previous* step's mixed gradients while step `t`'s fresh gradients are
handed to the gossip executor — the mix has **no data dependency on the
current backward pass**, so the compiler is free to schedule the sync
collectives concurrently with backward compute.  The train state
carries a double-buffered `prev_grads` pytree (the in-flight
gradients); the error-feedback residual buffer rides along exactly as
in the serialized path, just one step late, so EF accounting stays
bitwise-conserving (`payload + residual` still reconstructs the
accumulator — see `dist.compression`).

Staleness correction: the delayed gradients are mixed under the
rotation index and applied under the learning rate of the step that
*produced* them (`step - 1`), so the overlapped trajectory is exactly
the serialized trajectory delayed by one step whenever the gradient
stream itself is step-independent — that is the equivalence contract
`tests/test_async_sync.py` pins down.  Warmup: at step 0 there is no
delayed gradient yet; the buffer starts at zeros, the mix is a no-op,
and the train step discards the (zero) update.

Two executors:

`async_execute_sync(plan, grads, prev_grads, residuals, step)`
    The functional pipeline stage: mixes `prev_grads` (rotation index
    `step - 1`), returns the mixed result, the new in-flight buffer
    (= `grads`), and the updated residuals.

`execute_sync_sharded(plan, grads, residuals, step, mesh=...)`
    The same mixing semantics expressed as explicit per-replica
    collectives under `jax.shard_map`: the replica axis is
    laid out over a mesh shaped like `plan.levels`, per-cell ring
    gossip is `ppermute` along one mesh axis, grouped fusion is `pmean`
    along one mesh axis, and dissemination is a masked-`psum`
    broadcast.  Unlike the GSPMD lowering of the dense executor, the
    collectives here are scheduling-explicit, which is what lets XLA
    interleave them with an independent backward dataflow branch.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .compression import compress, decompress, init_residual
from .failures import apply_payload_faults, replica_fault_masks
from .gossip_sync import execute_sync
from .plan import SyncPlan
from .robust import (
    masked_coordinate_median,
    masked_trimmed_mean,
    resolve_trim,
    survivor_weighted_fn,
)

__all__ = [
    "async_execute_sync",
    "execute_sync_sharded",
    "init_inflight",
]


def init_inflight(grads_like: Any) -> Any:
    """Zero in-flight gradient buffer (the second half of the double
    buffer) matching the gradient pytree."""
    return jax.tree.map(jnp.zeros_like, grads_like)


def async_execute_sync(
    plan: SyncPlan,
    grads: Any,
    prev_grads: Any,
    residuals: Optional[Any] = None,
    step: Any = 0,
    *,
    mesh: Optional[Mesh] = None,
    axis_name: str = "replica",
) -> tuple[Any, Any, Any]:
    """One stage of the overlapped sync pipeline.

    grads: the current step's fresh (clipped) gradients — NOT mixed yet;
        they become the new in-flight buffer.
    prev_grads: the previous step's gradients (in flight since last
        step; zeros at step 0).
    residuals / step: threaded to `execute_sync` as usual; the rotation
        schedule is indexed at `step - 1`, the sync index of the step
        that produced `prev_grads`.
    mesh: when given, the mix runs through `execute_sync_sharded` so the
        gossip lowers as explicit shard_map collectives.

    Returns (applied, new_prev_grads, new_residuals) where `applied` is
    `mix(prev_grads)` and `new_prev_grads` is `grads`.  `applied` has no
    data dependency on `grads`, which is the whole point: under jit the
    current backward and the previous step's gossip are independent
    dataflow branches.
    """
    sync_step = jnp.asarray(step, jnp.int32) - 1
    if mesh is not None:
        applied, new_residuals = execute_sync_sharded(
            plan, prev_grads, residuals, sync_step,
            mesh=mesh, axis_name=axis_name,
        )
    else:
        applied, new_residuals = execute_sync(
            plan, prev_grads, residuals, sync_step
        )
    return applied, grads, new_residuals


# ------------------------- shard_map executor -------------------------
#
# Axis layout: the replica axis is reshaped over a mesh of shape
# `plan.levels` (one named axis per hierarchy level, coarsest first), so
# level-l cells are exactly the programs sharing all mesh coordinates
# except axis l.  Gossip strategies then read as:
#   ring within a cell  -> ppermute +-1 along that level's axis
#   grouped fusion      -> pmean along that level's axis
#   dissemination       -> masked psum along the finer axes
# Flat strategies (allreduce / ring) use a single-axis mesh.

_AXIS_FMT = "gossip{}"


def _level_mesh(plan: SyncPlan, mesh: Mesh, axis_name: str) -> tuple[Mesh, tuple[str, ...]]:
    """Reshape the caller's replica axis into one mesh axis per level."""
    if axis_name not in mesh.shape:
        raise ValueError(
            f"mesh {mesh.shape} has no axis {axis_name!r} to shard replicas over"
        )
    if mesh.shape[axis_name] != plan.R:
        raise ValueError(
            f"mesh axis {axis_name!r} has {mesh.shape[axis_name]} devices but "
            f"the plan serves R={plan.R} replicas"
        )
    if len(mesh.shape) != 1:
        raise ValueError(
            f"execute_sync_sharded wants a dedicated 1-axis replica mesh, "
            f"got {dict(mesh.shape)}"
        )
    shape = plan.levels if plan.strategy in ("hierarchical", "multiscale") \
        else (plan.R,)
    names = tuple(_AXIS_FMT.format(i) for i in range(len(shape)))
    inner = Mesh(mesh.devices.reshape(shape), names,
                 axis_types=mesh.axis_types * len(names))
    return inner, names


def _ring_pairs(L: int, shift: int) -> list[tuple[int, int]]:
    """(src, dst) collective-permute pairs of a ring shift along one axis."""
    return [((i + shift) % L, i) for i in range(L)]


def _shard_ring_round(x, axis: str, L: int):
    """One doubly-stochastic ring round along a mesh axis — the
    collective form of gossip_sync._ring_round (same value order, so the
    result matches the dense roll-based round to f32 accuracy)."""
    up = lax.ppermute(x, axis, _ring_pairs(L, 1))
    dn = lax.ppermute(x, axis, _ring_pairs(L, -1))
    return (x + up + dn) / 3.0


def _shard_mix_axis(x, axis: str, L: int, rounds: int):
    if L == 1:
        return x
    return lax.fori_loop(
        0, rounds, lambda _, v: _shard_ring_round(v, axis, L), x
    )


def _shard_bcast_from_zero(x, axis: str):
    """Every program along `axis` adopts the value at index 0 (the
    representative slot) — dissemination as a masked-psum broadcast."""
    keep = (lax.axis_index(axis) == 0).astype(x.dtype)
    return lax.psum(x * keep, axis)


def _shard_strategy(plan: SyncPlan, names: tuple[str, ...]):
    """Per-program mixing body for one leaf (local shape (1, *payload))."""
    levels = plan.levels

    if plan.strategy == "allreduce":
        return lambda x: lax.pmean(x, names)

    if plan.strategy == "hierarchical" or (
        plan.strategy == "multiscale" and plan.exact_fusion
    ):
        # grouped-mean ladder: cell means at the finest scale, then
        # means-of-means up — uniform occupancy makes each pmean over a
        # coarser axis exactly the fusion of that level's cell means
        def ladder(x):
            for ax in reversed(names):
                x = lax.pmean(x, ax)
            return x
        return ladder

    if plan.strategy == "ring":
        return lambda x: _shard_mix_axis(x, names[0], plan.R, plan.rounds[0])

    # plain multiscale (Algorithm 1): per-cell ring gossip bottom-up;
    # programs whose finer coordinates are nonzero compute dead values
    # past their own level — dissemination overwrites every slot from
    # the representative plane, so no masking is needed
    def multiscale(x):
        for ax in range(len(levels) - 1, 0, -1):
            x = _shard_mix_axis(x, names[ax], levels[ax], plan.rounds[ax])
            # promotion is positional: the representative (cell member 0)
            # already lives on the axis-index-0 plane
        x = _shard_mix_axis(x, names[0], levels[0], plan.rounds[0])
        # down-pass: broadcast the representative value along the finer
        # axes in coarse-to-fine order (each pass extends the set of
        # coordinates holding their top-level cell's value)
        for ax in names[1:]:
            x = _shard_bcast_from_zero(x, ax)
        return x

    return multiscale


def _shard_rotate(fn, plan: SyncPlan, names: tuple[str, ...], step):
    """Rotation conjugation in collective form: route each program's
    value to its rotated slot, mix, route back.  `jnp.take(g, perm)` of
    the dense executor (slot s reads replica perm[s]) becomes ppermute
    pairs (perm[s] -> s); the scatter-back inverts them.  The step index
    picks the branch via lax.switch (ppermute pairs must be static)."""
    def branch(perm):
        fwd = [(int(perm[s]), s) for s in range(plan.R)]
        bwd = [(s, int(perm[s])) for s in range(plan.R)]
        def run(x):
            x = lax.ppermute(x, names, fwd)
            x = fn(x)
            return lax.ppermute(x, names, bwd)
        return run

    branches = [branch(p) for p in plan.rotation]
    idx = jnp.mod(jnp.asarray(step, jnp.int32), len(branches))
    return lambda x: lax.switch(idx, branches, x)


def execute_sync_sharded(
    plan: SyncPlan,
    grads: Any,
    residuals: Optional[Any] = None,
    step: Any = 0,
    *,
    mesh: Mesh,
    axis_name: str = "replica",
) -> tuple[Any, Any]:
    """`execute_sync` semantics as explicit shard_map collectives.

    grads: pytree with leading replica axis `plan.R`, sharded (or
        shardable) over `mesh`'s `axis_name`.  Compression happens
        per-program (each replica compresses its own row, exactly the
        per-replica semantics of the dense path); the mix lowers to
        ppermute / pmean / psum along the level axes.

    Returns (mixed_grads, new_residuals) like `execute_sync`.  Values
    match the dense executor to f32 accuracy (identical exchange
    sequences; fusion reductions may reassociate).
    """
    if plan.R == 1:
        return grads, residuals
    inner, names = _level_mesh(plan, mesh, axis_name)

    mix = _shard_strategy(plan, names)
    compressed = plan.compression.scheme != "none"
    if compressed and residuals is None:
        residuals = init_residual(grads)

    faulty = plan.faulty
    robust = plan.robust_consensus
    if robust:
        k_drop, k_trim = resolve_trim(plan.failures, plan.R)
    shape = tuple(inner.shape[n] for n in names)

    def _program_rid():
        # dense replica index of this program: the replica axis was
        # reshaped over `shape` row-major, so rid is the row-major
        # linearization of the program's mesh coordinates
        rid = jnp.int32(0)
        stride = 1
        for name, L in zip(reversed(names), reversed(shape)):
            rid = rid + lax.axis_index(name) * stride
            stride *= L
        return rid

    def _mix_body(payload, g, r, new_r, s):
        """Shared fault-injection + aggregation tail of both bodies.

        The fault masks are recomputed identically on every program
        (all inputs replicated, same (seed, step) fold as the dense
        executor), then indexed at this program's replica id — so the
        injected faults match the dense path bitwise for the same seed.
        """
        if faulty:
            faults = replica_fault_masks(plan.failures, plan.R, s)
            rid = _program_rid()
            dropped_i = faults.dropped[rid]
            byz_i = faults.byzantine[rid]
            live_i = faults.live[rid]
            if compressed:
                payload, new_r = apply_payload_faults(
                    payload, new_r, g, r, dropped_i, byz_i,
                    plan.failures.byzantine_scale,
                )
            else:
                payload, _ = apply_payload_faults(
                    payload, None, None, None, dropped_i, byz_i,
                    plan.failures.byzantine_scale,
                )

        if robust:
            dropped_full = (
                faults.dropped if faulty else jnp.zeros((plan.R,), bool)
            )

            def robust_fn(x):
                # gather the whole replica axis (names-order row-major
                # matches the dense replica ordering), reduce once —
                # the aggregate is a consensus value, identical on
                # every program
                full = lax.all_gather(x, names, axis=0, tiled=True)
                if plan.aggregation == "trimmed_mean":
                    agg = masked_trimmed_mean(
                        full, dropped_full, k_drop, k_trim
                    )
                else:
                    agg = masked_coordinate_median(full, dropped_full, k_drop)
                if faulty:
                    agg = jnp.where(dropped_i, jnp.zeros_like(agg), agg)
                return agg

            return jax.tree.map(robust_fn, payload), new_r

        fn = _shard_rotate(mix, plan, names, s) if plan.rotated else mix
        if faulty and plan.aggregation == "survivor_weighted":
            fn = survivor_weighted_fn(fn, live_i)
        out = jax.tree.map(fn, payload)
        if faulty:
            out = jax.tree.map(
                lambda m: jnp.where(live_i, m, jnp.zeros_like(m)), out
            )
        return out, new_r

    spec = P(names)      # leading replica axis over every level axis
    sspec = P()          # step index is replicated

    if compressed:
        def body(g, r, s):
            payload, new_r = compress(g, r, plan.compression)
            payload = decompress(payload, plan.compression)
            return _mix_body(payload, g, r, new_r, s)

        args, in_specs, out_specs = (grads, residuals), (spec, spec), (spec, spec)
    else:
        def body(g, s):
            mixed, _ = _mix_body(g, g, None, None, s)
            return mixed

        args, in_specs, out_specs = (grads,), (spec,), spec
    mixed = jax.shard_map(
        body, mesh=inner, in_specs=in_specs + (sspec,),
        out_specs=out_specs, check_vma=False,
    )
    s = jnp.asarray(step, jnp.int32)
    if AxisType.Explicit not in mesh.axis_types:
        out = mixed(*args, s)
    else:
        # the level axes are a reshape of the caller's replica axis: the
        # rows stay on their devices, only their sharding type changes
        with jax.sharding.use_abstract_mesh(inner.abstract_mesh):
            args = jax.sharding.reshard(args, NamedSharding(inner, spec))
            s = jax.sharding.reshard(s, NamedSharding(inner, sspec))
            out = mixed(*args, s)
        out = jax.sharding.reshard(out, NamedSharding(mesh, P(axis_name)))
    return out if compressed else (out, residuals)
