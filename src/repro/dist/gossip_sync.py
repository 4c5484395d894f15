"""Gradient synchronization strategies over a replica axis.

The paper's multiscale gossip (Algorithm 1), transplanted from wireless
sensor networks to decentralized data-parallel training: R parameter
replicas hold per-replica gradients (leading axis R on every pytree
leaf) mixed according to a static `SyncPlan` (`dist/plan.py`).

Plan/execute split (mirror of `core/plan.py` / `core/engine.py`): the
hierarchy, rounds, rotation schedule, and compression config are
resolved once by `build_sync_plan(SyncConfig, R)`; the compiled
`execute_sync(plan, grads, residuals, step)` then threads
compress -> rotate -> mix -> scatter-back with per-replica
error-feedback residuals through every strategy, and is the single
seam future async / shard_map overlap plugs into.  `sync_gradients` is
the one-shot convenience wrapper (no residual state across calls).

Strategies
----------
``allreduce``
    Exact global mean — the dense baseline every byte of which crosses
    the network diameter (one global all-reduce per leaf).
``hierarchical``
    Exact grouped fusion over the `levels` hierarchy: cell means at the
    finest scale, then means-of-means up to the root, broadcast back.
    Bitwise the same fixed point as allreduce, but lowering emits
    grouped collectives whose cross-pod share shrinks to the top-level
    fusion only.
``ring``
    Flat randomized-gossip analogue: `rounds` applications of the
    doubly-stochastic ring operator x <- (x + roll(x,+1) + roll(x,-1))/3
    along the replica axis.  Preserves the replica mean exactly; replica
    disagreement contracts by the ring's second eigenvalue per round
    (the paper's slow baseline — many cheap neighbor exchanges).
``multiscale``
    Algorithm 1 on the replica set.  Bottom-up over the recursive cells
    from `suggest_levels`: ring mixing inside every cell of a level in
    parallel, then promotion of one representative per cell to the next
    coarser level; after the coarsest level mixes, values disseminate
    back down the hierarchy (every replica adopts its top-level cell's
    representative value).  ``exact_fusion=True`` selects the paper's
    mass-weighted variant (§VII) where every fusion is the exact
    weighted cell mean, so the disseminated value is the global replica
    mean exactly; with the uniform occupancy this module enforces it
    evaluates as the hierarchical grouped-mean ladder.

Cross-cutting plan features (gossip strategies):

* **rotation** — `rotation_period > 0` cycles a precomputed table of
  replica permutations by sync step (the paper's randomized cells), so
  ring neighbors / cell membership change every step.  Conjugating a
  doubly-stochastic mix by a permutation is still doubly stochastic:
  the replica mean is untouched and exact_fusion stays exact.
* **compression** — a non-``none`` `CompressionConfig` mixes the
  as-transmitted payloads from `dist.compression` (error feedback:
  unsent mass stays in per-replica residuals and is re-injected next
  sync), so gossip competes on wire *bytes*, not just message counts.

Every strategy is a pure jittable function of the gradient pytree: on a
host-replicated array it is plain arithmetic; under a sharded
``("replica",)`` mesh the same code lowers to real collectives
(all-reduce for fusions, collective-permute for ring rolls), which
`launch.hlo_analysis.collective_bytes` classifies intra- vs cross-pod.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType

from .compression import compress, decompress, init_residual
from .failures import apply_payload_faults, replica_fault_masks
from .plan import STRATEGIES, SyncConfig, SyncPlan, build_sync_plan
from .robust import resolve_trim, survivor_weighted_fn, tree_robust_reduce

__all__ = [
    "SyncConfig",
    "SyncPlan",
    "build_sync_plan",
    "execute_sync",
    "sync_gradients",
    "STRATEGIES",
]


def execute_sync(
    plan: SyncPlan,
    grads: Any,
    residuals: Optional[Any] = None,
    step: Any = 0,
) -> tuple[Any, Any]:
    """Run one synchronization under a static plan.

    grads: pytree with leading replica axis `plan.R` on every leaf.
    residuals: error-feedback state (same pytree; required state when
        `plan.compression` is active — pass what the previous call
        returned, zeros via `compression.init_residual` at step 0).
        With compression off it is threaded through untouched.
    step: scalar sync index (traced or concrete) driving the rotation
        schedule; ignored by static plans.

    Returns (mixed_grads, new_residuals).  Jit with `plan` static (it is
    hashable); the compiled executor serves every step of a run.
    """
    R = plan.R
    leaves = jax.tree.leaves(grads)
    for leaf in leaves:
        if leaf.ndim < 1 or leaf.shape[0] != R:
            raise ValueError(
                f"every gradient leaf needs leading replica axis {R}, "
                f"got shape {leaf.shape}"
            )
    if R == 1:
        return grads, residuals
    mesh = _explicit_mesh(leaves)
    if mesh is None:
        return _execute_sync(plan, grads, residuals, step)
    # the strategies reshape, roll and gather the replica axis, which an
    # Explicit mesh axis cannot carry through those ops: mix with the
    # axes switched to Auto and hand back the callers' shardings
    spec = lambda a: jax.typeof(a).sharding.spec
    if residuals is None and plan.compression.scheme != "none":
        residuals = init_residual(grads)
    out = (jax.tree.map(spec, grads), jax.tree.map(spec, residuals))
    with jax.sharding.use_abstract_mesh(mesh):
        return jax.sharding.auto_axes(
            partial(_execute_sync, plan), out_sharding=out,
        )(grads, residuals, step)


def _explicit_mesh(leaves):
    """The abstract mesh of the first leaf typed with Explicit axes."""
    for leaf in leaves:
        mesh = jax.typeof(leaf).sharding.mesh
        if AxisType.Explicit in mesh.axis_types:
            return mesh
    return None


def _execute_sync(plan: SyncPlan, grads, residuals, step):
    R = plan.R
    if plan.compression.scheme != "none":
        if residuals is None:
            residuals = init_residual(grads)
        payload, new_residuals = compress(grads, residuals, plan.compression)
        payload = decompress(payload, plan.compression)
    else:
        payload, new_residuals = grads, residuals

    # Fault injection: dropped replicas transmit nothing (with EF
    # compression their whole accumulator stays in their residual —
    # bitwise mass conservation), Byzantine replicas transmit corrupted
    # payloads.  plan.failures is None (or inert) on the reliable path,
    # which stays bitwise-identical to a plan without the field.
    faults = None
    if plan.faulty:
        faults = replica_fault_masks(plan.failures, R, step)
        if plan.compression.scheme != "none":
            payload, new_residuals = apply_payload_faults(
                payload, new_residuals, grads, residuals,
                faults.dropped, faults.byzantine,
                plan.failures.byzantine_scale,
            )
        else:
            payload, _ = apply_payload_faults(
                payload, None, None, None,
                faults.dropped, faults.byzantine,
                plan.failures.byzantine_scale,
            )

    if plan.robust_consensus:
        # Consensus-style robust reduction replaces the strategy's own
        # mixing (and is invariant to the rotation permutation).
        k_drop, k_trim = resolve_trim(plan.failures, R)
        dropped = (
            faults.dropped if faults is not None
            else jnp.zeros((R,), bool)
        )
        mixed = tree_robust_reduce(
            plan.aggregation, payload, dropped, k_drop, k_trim
        )
        return mixed, new_residuals

    if plan.strategy == "allreduce":
        fn = _allreduce
    elif plan.strategy == "hierarchical":
        fn = lambda g: _hierarchical(g, plan.levels)
    elif plan.strategy == "ring":
        fn = lambda g: _ring(g, plan.rounds[0])
    else:  # multiscale
        fn = lambda g: _multiscale(
            g, plan.levels, plan.rounds, plan.exact_fusion
        )
    if plan.rotated:
        fn = _rotate(fn, plan, step)
    if faults is not None and plan.aggregation == "survivor_weighted":
        # weight-channel renormalization over live replicas, applied to
        # the (possibly rotation-conjugated) linear mixing operator
        fn = survivor_weighted_fn(fn, faults.live)
    mixed = jax.tree.map(fn, payload)
    if faults is not None:
        live = faults.live
        mixed = jax.tree.map(
            lambda m: jnp.where(
                live.reshape((R,) + (1,) * (m.ndim - 1)),
                m, jnp.zeros_like(m),
            ),
            mixed,
        )
    return mixed, new_residuals


def sync_gradients(grads: Any, cfg: SyncConfig, R: int) -> Any:
    """One-shot mix of a per-replica gradient pytree (leading axis R).

    Convenience wrapper over `build_sync_plan` + `execute_sync` for call
    sites without persistent state: residuals start at zero and the new
    residuals are dropped, so error-feedback compression only
    accumulates across calls when you hold the state yourself (the
    decentralized train step does).  Returns a pytree of the same
    structure/shapes.  Exact strategies leave every replica holding the
    global mean; gossip strategies bound the replica disagreement by
    the configured mixing rounds (the paper's eps) while staying inside
    the convex hull of the inputs.
    """
    mixed, _ = execute_sync(build_sync_plan(cfg, R), grads)
    return mixed


# ------------------------------ strategies ------------------------------


def _rotate(fn, plan: SyncPlan, step) -> Any:
    """Conjugate a mixing operator by the step's rotation permutation.

    Slot s of the mixed array holds replica perm[s]; the inverse table
    scatters slot values back to their home replicas, so the wrapped
    operator acts on a freshly shuffled cell assignment every step while
    output replica order stays fixed.
    """
    perms = jnp.asarray(plan.rotation, jnp.int32)
    invs = jnp.asarray(plan.rotation_inv, jnp.int32)
    idx = jnp.mod(jnp.asarray(step, jnp.int32), perms.shape[0])
    perm, inv = perms[idx], invs[idx]
    return lambda g: jnp.take(fn(jnp.take(g, perm, axis=0)), inv, axis=0)


def _allreduce(g: jax.Array) -> jax.Array:
    """Global mean over the replica axis, broadcast back to every replica."""
    return jnp.broadcast_to(jnp.mean(g, axis=0, keepdims=True), g.shape)


def _hierarchical(g: jax.Array, levels: tuple[int, ...]) -> jax.Array:
    """Grouped means finest-to-coarsest then broadcast back down.

    With uniform cell sizes (levels factor R exactly) the mean-of-means
    equals the global mean, so the result matches allreduce to float
    accuracy while lowering as a ladder of small-group collectives.
    """
    shape = g.shape
    x = g.reshape(levels + shape[1:])
    for ax in range(len(levels) - 1, -1, -1):
        x = jnp.mean(x, axis=ax, keepdims=True)
    return jnp.broadcast_to(x, levels + shape[1:]).reshape(shape)


def _ring_round(x: jax.Array) -> jax.Array:
    """One application of the doubly-stochastic ring operator on axis 0."""
    return (x + jnp.roll(x, 1, axis=0) + jnp.roll(x, -1, axis=0)) / 3.0


def _ring(g: jax.Array, rounds: int) -> jax.Array:
    """Flat neighbor gossip: `rounds` synchronized ring exchanges.

    Symmetric + doubly stochastic => the replica mean is invariant and
    disagreement contracts geometrically.  Under a replica-sharded mesh
    each roll is one collective-permute, so the lowered module makes the
    paper's point: flat gossip is chatty."""
    return lax.fori_loop(0, rounds, lambda _, x: _ring_round(x), g)


def _mix_level(x: jax.Array, axis: int, rounds: int) -> jax.Array:
    """Ring-mix all cells of one level in parallel along `axis`."""
    if x.shape[axis] == 1:
        return x
    moved = jnp.moveaxis(x, axis, 0)
    mixed = lax.fori_loop(0, rounds, lambda _, v: _ring_round(v), moved)
    return jnp.moveaxis(mixed, 0, axis)


def _multiscale(
    g: jax.Array,
    levels: tuple[int, ...],
    rounds: tuple[int, ...],
    exact_fusion: bool,
) -> jax.Array:
    """Algorithm 1 over the replica hierarchy.

    Axis layout after reshape: axis j hosts level-(j+1) cells; the last
    axis is the finest scale.  Bottom-up pass mixes within cells then
    promotes one representative per cell; top-level values disseminate
    back down by broadcast (the paper's n-message down-pass).
    """
    shape = g.shape
    payload = shape[1:]
    k = len(levels)
    if exact_fusion:
        # Mass-weighted variant: values travel as (w*x, w) pairs and every
        # fusion is the exact weighted cell mean.  resolved_levels enforces
        # uniform occupancy (prod(levels) == R), under which the weighted
        # fusion is identically the grouped-mean ladder — delegate rather
        # than carry a uniform weight channel; revisit when cells can be
        # partially occupied (time-varying replica topologies).
        return _hierarchical(g, levels)

    x = g.reshape(levels + payload)

    # Plain Algorithm 1: per-cell ring gossip, representative promotion.
    for ax in range(k - 1, 0, -1):
        x = _mix_level(x, ax, rounds[ax])
        # representative = cell member 0 after mixing (approx. cell mean)
        x = lax.index_in_dim(x, 0, axis=ax, keepdims=True)
    # coarsest level: representatives gossip on the top ring
    x = _mix_level(x, 0, rounds[0])
    # down-pass: every replica adopts its top-level cell's value
    return jnp.broadcast_to(x, levels + payload).reshape(shape)
