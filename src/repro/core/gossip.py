"""Randomized pairwise gossip (Boyd et al. [2]) — the black box used by
multiscale gossip (paper §III, Alg. 1 lines 9/15).

The engine is batched and fully jittable: B independent graphs (e.g. all
cells of one hierarchy level) gossip in lockstep, each with its own
convergence flag, so one `lax.while_loop` simulates a whole level.  The
asynchronous time model is standard: at each tick a uniformly random
node of each not-yet-converged graph wakes, picks a uniformly random
neighbor, and the pair averages.  Messages are counted per directed edge
so multi-hop overlay costs and per-node/relay attribution can be
computed afterwards.

Values may carry V channels (V=2 supports the mass-weighted variant,
where a pair (w*x, w) is averaged and the estimate is their ratio; the
paper's plain algorithm uses V=1).

Optional per-hop message loss (paper §VI-C-2): each single-hop
transmission of an exchange succeeds w.p. `loss_p`; a lost request
aborts the exchange, a lost reply leaves only the contacted node
updated (mass distortion — exactly the failure the paper analyzes).

Schedule / value split: every exchange decision depends only on
``(key, t)``, never on the values, so each `check_every` chunk first
presamples its full ``(T, B)`` exchange schedule in one batched RNG pass
(`core.schedule.sample_schedule` — usage and message accounting become
reductions over the presampled arrays), then
applies the pair list with `core.value_pass.pair_apply`: a scan whose
body is just two endpoint reads (one-hot selects over a cell's slots,
or gathers, by the level's shape), one average, and two conditional
writes.  Only the values are sequential, so only they pay for a scan.

Adjacency is CSR-addressed (`core.schedule.CsrGraphs`): one flat entry
per directed edge instead of ``(B, C, D)`` dense padding.  Usage is
counted one of two ways, the caller's static choice (`usage_count`):
per directed edge, in a flat ``(nnz+1,)`` buffer via a 1-D scatter on
the sampled `pos` field (``"edges"``), or per slot, in a ``(C, B)``
table of how many exchanges each slot was an endpoint of, by one-hot
adds over the chunk's ticks (``"slots"``, `slot_sends`): no scatter,
and all a caller needs that attributes sends to the endpoints alone.
`gossip_until` keeps the historical dense host API — it packs dense
inputs with `dense_to_csr` and scatters flat usage back to
``(B, C, D)`` for `GossipResult`.

Node sharding (`node_shard=(cols, ok)`): a shard owns columns `cols` of
the global batch (clipped duplicates masked by `ok`).  Each shard
samples the full global schedule — threefry streams have no prefix
property, so local draws would diverge from the unsharded run — and
slices its columns, making per-graph results bitwise independent of the
sharding.  Once a graph converges its exchanges freeze (writes become
identity, accounting masks to zero), so shards may run different
while-loop trip counts without affecting any output.

Layer scopes: every op `gossip_core` emits sits under one of the
`jax.named_scope`s in `LAYER_SCOPES` — ``schedule`` (sampling and its
perturbation), ``value_pass`` (the pair walk),
``accounting`` (usage, messages, ticks, cost counters) and
``convergence_check`` (the tolerance, the per-chunk ``err <= tol`` and
the chunk loop's own control); `core.engine` adds ``promote`` for the
moves between levels.  Scopes are HLO metadata (``op_name``), so they
name ops in a profiler trace and change nothing that runs.

`gossip_core` is the pure-JAX function (usable inside a larger jit /
vmap — the plan/execute engine in `core.engine` vmaps it over
Monte-Carlo trial seeds); `gossip_until` is the host-facing wrapper.

Shapes (static under jit):
  x         : (B, C, V)   node values, padded with 0
  adj       : CsrGraphs   start, degrees (C,B) / nbr, hops / n_nodes (B,)
  node_mask : (B, C)      live-node mask
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .medium import _TAG_RETX, _TAG_STRAGGLER, CostModel, FailureCtx
from .schedule import (
    CsrGraphs,
    dense_to_csr,
    flat_usage_to_dense,
    sample_schedule,
)
from .value_pass import pair_apply

__all__ = ["GossipResult", "gossip_core", "gossip_until", "batched_graphs",
           "slot_sends", "LAYER_SCOPES"]

LAYER_SCOPES = ("schedule", "value_pass", "accounting", "convergence_check",
                "promote")

_scope = jax.named_scope


@dataclasses.dataclass
class GossipResult:
    x: np.ndarray            # (B, C, V) final values
    ticks: np.ndarray        # (B,) exchanges attempted per graph
    converged: np.ndarray    # (B,) bool
    edge_usage: np.ndarray   # (B, C, D) int32: #exchanges initiated i->j
    messages: np.ndarray     # (B,) total single-hop transmissions

    @property
    def total_messages(self) -> int:
        return int(self.messages.sum())

    def estimates(self) -> np.ndarray:
        """(B, C) per-node estimates (ratio of channels if V == 2)."""
        if self.x.shape[-1] == 1:
            return self.x[..., 0]
        # channel 1 is a positive mass (node counts) in the weighted variant
        return self.x[..., 0] / np.maximum(self.x[..., 1], 1e-30)


def gossip_core(
    x0,
    adj: CsrGraphs,
    node_mask,
    eps,
    key,
    *,
    max_ticks: int,
    check_every: int,
    loss_p: Optional[float],
    node_shard=None,
    failure_ctx: Optional[FailureCtx] = None,
    cost_model: Optional[CostModel] = None,
    hop_cap: int = 1,
    usage_count: str = "edges",
):
    """Pure-JAX batched gossip loop; composable under jit and vmap.

    Returns (x, usage, msgs, done, ticks) where usage is, by the static
    `usage_count`, the flat ``(nnz+1,)`` per-directed-edge counter
    aligned with `adj` (``"edges"``) or the ``(C, B)`` int32 table of
    each slot's endpoint sends (``"slots"``, see `slot_sends`); with
    `cost_model` set, two extra per-graph arrays are appended —
    (retransmissions, congestion_pairs) — priced from the presampled
    schedule with RNG streams disjoint from the exchange streams, so
    x/usage/msgs/done/ticks are bitwise-independent of the cost model.
    `eps` and `max_ticks` may be traced scalars (the plan/execute engine
    passes them at runtime so eps-oracle and fixed-iteration runs share
    one compilation); `check_every` must be static (scan length).

    `failure_ctx` (a `medium.FailureCtx`) perturbs the presampled
    schedule — churned/regional nodes' exchanges vanish (a live
    initiator contacting a down partner wastes the forward leg),
    straggler exchanges fail w.p. 1 - straggler_success at full cost,
    Byzantine slots never apply updates.  This DOES change trajectory
    and accounting (that is the point).

    `node_shard=(cols, ok)` runs only the given global batch columns:
    `x0`/`node_mask` are the local ``(Bs, C, …)`` slices, sampling stays
    global (see module docstring), and the returned x/msgs/done/ticks
    are local while usage stays global-flat (adds land only at the
    shard's own edges), or, counted by slots, covers the shard's own
    columns.
    """
    if node_shard is not None and (
            failure_ctx is not None or cost_model is not None):
        raise ValueError(
            "failure scenarios / cost pricing are not supported on "
            "the (trials, nodes) mesh")
    with _scope("convergence_check"):
        # the barrier keeps the float mask one value: with a plan's mask
        # a constant, the TPU compiler otherwise folded it into a float
        # constant and copied that once more for the chunk loop of a
        # level whose value pass selects (17.5 MB more at n=10^6)
        live = jax.lax.optimization_barrier(
            node_mask.astype(x0.dtype))[..., None]    # (B, C, 1)
        denom = jnp.maximum(live.sum(1), 1.0)
        mean = (x0 * live).sum(1) / denom             # (B, V)
        x0_norm = jnp.sqrt(((x0 * live) ** 2).sum((1, 2)))
        tol = eps * jnp.maximum(x0_norm, 1e-30)

    def err(x):
        d = (x - mean[:, None, :]) * live
        return jnp.sqrt((d**2).sum((1, 2)))

    chunk = _presampled_chunk(
        adj, key, loss_p, check_every, err, tol,
        node_shard, failure_ctx, cost_model, hop_cap, usage_count,
    )

    def cond(carry):
        with _scope("convergence_check"):
            return (~jnp.all(carry[3])) & (carry[-1] < max_ticks)

    with _scope("accounting"):
        if usage_count == "slots":
            usage0 = jnp.zeros(x0.shape[1::-1], jnp.int32)   # (C, B)
        else:
            usage0 = jnp.zeros((adj.num_entries,), jnp.int32)
        msgs0 = jnp.zeros(x0.shape[:1], jnp.int32)
        ticks0 = jnp.zeros(x0.shape[:1], jnp.int32)
        if cost_model is not None:
            # per-graph cost accumulators: sampled extra attempts (int32,
            # exact) and concurrency pair counts (f32: a surcharge tally,
            # not an exact-accounting channel)
            extras = (jnp.zeros(x0.shape[:1], jnp.int32),
                      jnp.zeros(x0.shape[:1], jnp.float32))
        else:
            extras = ()
    with _scope("convergence_check"):
        done0 = err(x0) <= tol  # already-converged graphs (e.g. 1-node cells)
        carry = (x0, usage0, msgs0, done0, ticks0) + extras \
            + (jnp.array(0, jnp.int32),)
        out = jax.lax.while_loop(cond, chunk, carry)
    return out[:-1]  # drop the tick counter t0


def _presampled_chunk(adj, key, loss_p, check_every, err, tol,
                      node_shard=None, failure_ctx=None, cost_model=None,
                      hop_cap=1, usage_count="edges"):
    """Chunk body for the schedule/value split: one batched RNG pass for
    the whole chunk, accounting as reductions over it (usage as one
    scatter-add or, by slots, one-hot adds), then the value pass over
    the presampled pair list.

    `failure_ctx` perturbs the schedule before the value pass (scenario
    injection); `cost_model` adds pure reductions over the schedule
    (sampled retransmissions, concurrency pairs) whose RNG streams are
    folded from tags disjoint from every tick index, so the exchange
    draws — and therefore x/usage/msgs — are untouched.
    """
    cost_on = cost_model is not None
    sample_retx = (cost_on and cost_model.sample
                   and cost_model.retransmit_p < 1.0)
    track_cong = cost_on and cost_model.congestion_alpha > 0.0

    def chunk(carry):
        if cost_on:
            x, usage, msgs, done, ticks, retx, congp, t0 = carry
        else:
            x, usage, msgs, done, ticks, t0 = carry
        with _scope("schedule"):
            ts = t0 + jnp.arange(check_every)
            s = sample_schedule(ts, key, adj, loss_p, x.dtype)
            if node_shard is not None:
                cols, ok = node_shard
                s = type(s)(*(f[:, cols] for f in s))
                s = s._replace(valid=s.valid & ok[None, :])
            active = s.valid & ~done[None, :]  # done is frozen within a chunk
            if failure_ctx is None:
                attempt = active
                cost_t = s.cost
                upd_j = active & s.fwd_ok
                upd_i = upd_j & s.rep_ok
            else:
                fc = failure_ctx
                bcols = jnp.arange(active.shape[1])[None, :]
                when = ts[:, None]
                churn_now = when >= fc.churn_tick
                reg_now = (when >= fc.reg_t0) & (when < fc.reg_t1)
                down_i = (fc.churned[bcols, s.i] & churn_now) | (
                    fc.regional[bcols, s.i] & reg_now)
                down_j = (fc.churned[bcols, s.j] & churn_now) | (
                    fc.regional[bcols, s.j] & reg_now)
                attempt = active & ~down_i      # a down initiator never wakes
                delivered = attempt & ~down_j
                slow = fc.straggler[bcols, s.i] | fc.straggler[bcols, s.j]
                if fc.straggler_success < 1.0:
                    ku = jax.random.fold_in(
                        jax.random.fold_in(key, _TAG_STRAGGLER), t0)
                    u = jax.random.uniform(ku, active.shape)
                    delivered = delivered & (
                        ~slow | (u < fc.straggler_success))
                upd_j = delivered & s.fwd_ok & ~fc.byz[bcols, s.j]
                upd_i = delivered & s.fwd_ok & s.rep_ok & ~fc.byz[bcols, s.i]
                # a wasted contact of a down partner still transmits the
                # forward leg; straggler stalls burn the full exchange cost
                cost_t = jnp.where(attempt & ~down_j, s.cost, s.hops)
        with _scope("accounting"):
            if usage_count == "slots":
                usage = usage + slot_sends(s.i, s.j, attempt,
                                           usage.shape[0])
            else:
                usage = usage.at[s.pos].add(attempt.astype(jnp.int32))
            hops_t = jnp.where(attempt, cost_t, 0)
            msgs = msgs + hops_t.sum(0)
            if sample_retx:
                # iid Geometric(p) per single-hop transmission: extra
                # attempts per hop slot, masked to the hops actually sent.
                # The stream is fold_in(key, TAG) -> fold_in(., t0): tagged
                # before the tick fold, disjoint from exchange draws.
                kr = jax.random.fold_in(jax.random.fold_in(key, _TAG_RETX), t0)
                q = 1.0 - cost_model.retransmit_p
                u = jnp.maximum(
                    jax.random.uniform(kr, (*hops_t.shape, 2 * hop_cap)),
                    1e-12)
                g = jnp.floor(jnp.log(u) / jnp.log(q)).astype(jnp.int32)
                m = jnp.arange(2 * hop_cap)[None, None, :] < hops_t[..., None]
                retx = retx + jnp.where(m, g, 0).sum((0, 2))
            if track_cong:
                conc = attempt.sum(1)  # concurrent exchanges at each tick
                congp = congp + (
                    attempt * jnp.maximum(conc - 1, 0)[:, None]
                ).sum(0).astype(jnp.float32)
        with _scope("value_pass"):
            x = pair_apply(x, s.i, s.j, upd_i, upd_j)
        with _scope("accounting"):
            ticks = ticks + jnp.where(done, 0, check_every)
        with _scope("convergence_check"):
            done = done | (err(x) <= tol)
            t1 = t0 + check_every
        out = (x, usage, msgs, done, ticks)
        if cost_on:
            out = out + (retx, congp)
        return out + (t1,)

    return chunk


def slot_sends(i, j, attempt, C):
    """``(C, B)`` int32: how many of the ``(T, B)`` exchanges `i`–`j`
    that `attempt` marks had slot c of graph b as an endpoint, the
    initiator and the partner each counted once.  A one-hot
    compare-and-add over the C slots that XLA fuses into one reduction
    over the ticks: no scatter, and no ``(T, C, B)`` intermediate in
    memory.  Exact in int32."""
    c = jnp.arange(C, dtype=i.dtype)[None, :, None]
    ends = ((i[:, None, :] == c).astype(jnp.int32)
            + (j[:, None, :] == c).astype(jnp.int32))
    return jnp.sum(jnp.where(attempt[:, None, :], ends, 0), axis=0)


@partial(
    jax.jit,
    static_argnames=("max_ticks", "check_every", "loss_p"),
)
def _gossip_loop(
    x0,
    adj,
    node_mask,
    eps,
    key,
    max_ticks: int,
    check_every: int,
    loss_p: Optional[float],
):
    return gossip_core(
        x0, adj, node_mask, eps, key,
        max_ticks=max_ticks, check_every=check_every, loss_p=loss_p,
    )


def gossip_until(
    x0: np.ndarray,
    neighbors: np.ndarray,
    degrees: np.ndarray,
    n_nodes: np.ndarray,
    *,
    eps: float,
    seed: int = 0,
    edge_hops: Optional[np.ndarray] = None,
    node_mask: Optional[np.ndarray] = None,
    max_ticks: int = 2_000_000,
    check_every: int = 64,
    fixed_ticks: Optional[int] = None,
    loss_p: Optional[float] = None,
) -> GossipResult:
    """Run batched randomized gossip to eps-accuracy (or `fixed_ticks`).

    `fixed_ticks` implements the paper's fixed-iterations variant
    (MultiscaleGossipFI, §VI): exactly that many exchanges per graph, no
    convergence oracle.  Convergence is re-checked every `check_every`
    ticks, so up to that many extra exchanges can occur after the true
    crossing (convergence detection is not free in reality either).

    The host API stays dense — ``(B, C, D)`` padded neighbors in, dense
    `edge_usage` out; the CSR packing is internal.
    """
    x0 = np.asarray(x0)
    if x0.ndim == 2:
        x0 = x0[..., None]
    B, C, V = x0.shape
    D = neighbors.shape[2]
    if node_mask is None:
        node_mask = np.arange(C)[None, :] < np.asarray(n_nodes)[:, None]
    adj_np = dense_to_csr(neighbors, degrees, n_nodes, edge_hops)
    adj = jax.tree.map(jnp.asarray, adj_np)
    key = jax.random.PRNGKey(seed)
    if fixed_ticks is not None:
        eps_eff = -1.0  # negative tol: the oracle never fires
        check = max(1, min(check_every, int(fixed_ticks)))
        max_t = ((int(fixed_ticks) + check - 1) // check) * check
    else:
        eps_eff, max_t, check = float(eps), int(max_ticks), int(check_every)
    x, usage, msgs, done, ticks = _gossip_loop(
        jnp.asarray(x0, jnp.float32),
        adj,
        jnp.asarray(node_mask, bool),
        jnp.asarray(eps_eff, jnp.float32),
        key,
        max_ticks=max_t,
        check_every=check,
        loss_p=loss_p,
    )
    return GossipResult(
        x=np.asarray(x),
        ticks=np.asarray(ticks),
        converged=np.asarray(done),
        edge_usage=flat_usage_to_dense(np.asarray(usage), degrees, D),
        messages=np.asarray(msgs),
    )


def batched_graphs(
    graphs: list,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Pad a list of `rgg.Graph`-like (neighbors, degrees) into batch form.

    Returns (neighbors (B,C,D), degrees (B,C), n_nodes (B,), node_mask).
    """
    B = len(graphs)
    C = max(1, max(g.n for g in graphs))
    D = max(1, max(g.max_deg for g in graphs))
    neighbors = np.full((B, C, D), -1, np.int32)
    degrees = np.zeros((B, C), np.int32)
    n_nodes = np.zeros((B,), np.int32)
    for b, g in enumerate(graphs):
        neighbors[b, : g.n, : g.max_deg] = g.neighbors
        degrees[b, : g.n] = g.degrees
        n_nodes[b] = g.n
    node_mask = np.arange(C)[None, :] < n_nodes[:, None]
    return neighbors, degrees, n_nodes, node_mask
