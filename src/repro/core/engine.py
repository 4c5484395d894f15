"""Device-resident executor for a `HierarchyPlan` (the execute half of
the plan/execute simulation core).

One call runs all K levels of multiscale gossip end-to-end in a single
compiled JAX function: per-level batched gossip (`gossip_core`),
representative election (static, from the plan), Alg.-1 line-16
reweighting and value promotion as gathers/scatters, send attribution as
one scatter-add per level (a cell level's per-slot endpoint counts
through the slot's node id; an overlay level's flat per-edge counters
through the plan's route-incidence CSR), and the dissemination
down-pass as a gather — no host round-trips between levels.  Adjacency
is CSR end-to-end (flat per-directed-edge arrays from `LevelPlan`), so
device memory scales with edge count, not with ``B*C*max_deg``
padding.

The executor is `vmap`-ped over trial seeds, so `execute_plan(plan, x0,
seeds=[s0..sT])` simulates T independent Monte-Carlo trials in one
compiled call.  `mesh=` shards that computation over real hardware:

* a 1-axis mesh shard_maps the trial axis (trials are padded up to a
  device multiple and the padding discarded);
* a 2-axis mesh with axes named exactly ``("trials", "nodes")``
  additionally shards every level's graph batch over node blocks.  Each
  shard samples the full global exchange schedule (threefry streams
  have no prefix property) and slices its own columns, so per-trial
  results are bitwise-identical to the unsharded run; cross-shard
  traffic is a psum at each overlay promotion boundary (reps move
  between graphs exactly there) plus the final assembly — the gossip
  inner loops themselves run shard-local.

Every level runs the one value pass of `core.gossip`: the chunk's
presampled schedule walked by `core.value_pass.pair_apply`, whose
endpoint read (select or gather) follows from the level's shape.  A
choice of value pass would be a fork of the hot path for every change
to prove against; the shape already decides what a caller could.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import value_pass
from .events import record_duration, record_event
from .gossip import gossip_core
from .medium import (
    CostModel,
    FailureCtx,
    FailureModel,
    MediumCost,
    expected_retransmissions,
    failure_sets,
)
from .options import ExecOptions
from .plan import HierarchyPlan
from .schedule import CsrGraphs

__all__ = ["EngineResult", "execute_plan", "fi_ticks"]

_scope = jax.named_scope
_span = jax.profiler.TraceAnnotation

# Lighter XLA pipeline for the executor when it is compiled for the
# CPU: these are small scatter/gather loops where full optimization buys
# nothing measurable at runtime but more than doubles compile time.  The
# LLVM expensive-pass cut matters most: the executor's scatter bodies
# spend their compile budget in LLVM, not in HLO passes.  Other platforms
# compile with their defaults.
_CPU_COMPILER_OPTS = {
    "xla_backend_optimization_level": 0,
    "xla_llvm_disable_expensive_passes": True,
}


def fi_ticks(size: int, eps: float, scale: float, quadratic: bool) -> int:
    """Fixed-iterations budget (paper §VII): the theoretical
    epsilon-averaging-time bound for the worst-case graph size at the
    level — Theta(p^2 log 1/eps) ticks for p-node grids, Theta(p log
    1/eps) for the (near-complete) finest cells (Boyd et al. [2])."""
    ln = math.log(1.0 / eps)
    if quadratic:
        budget = 0.5 * size * size * ln
    else:
        budget = 4.0 * size * ln
    return max(32, math.ceil(scale * budget))


def trials_error(x_final: np.ndarray, x0: np.ndarray) -> np.ndarray:
    """(T,) relative error per trial (paper eq. 1); x0 may be (n,)
    shared or (T, n) per-trial."""
    x0 = np.asarray(x0)
    avg = x0.mean(axis=-1, keepdims=True)
    num = np.linalg.norm(x_final - avg, axis=-1)
    den = np.linalg.norm(np.broadcast_to(x0, x_final.shape), axis=-1)
    return num / den


@dataclasses.dataclass
class EngineResult:
    """Per-trial outputs of one vmapped plan execution (T trials)."""

    x_final: np.ndarray          # (T, n) estimates at every node
    messages: np.ndarray         # (T,) total single-hop transmissions
    node_sends: np.ndarray       # (T, n) transmissions attributed per node
    level_messages: np.ndarray   # (T, L) per executed level
    level_ticks: np.ndarray      # (T, L) max ticks over the level's graphs
    level_converged: np.ndarray  # (T, L) fraction of graphs converged
    edge_usage: list             # L flat (T, nnz+1) exchange counters in the
    #                              level's CSR layout (collect_usage=True
    #                              only; LevelPlan.dense_usage restores the
    #                              historical (B, C, D) view)
    cost: Optional[MediumCost] = None  # priced medium cost (CostModel runs)

    @property
    def trials(self) -> int:
        return int(self.x_final.shape[0])

    def error(self, x0: np.ndarray) -> np.ndarray:
        """(T,) relative error per trial; see `trials_error`."""
        return trials_error(self.x_final, x0)


def _usage_count(lp, collect_usage: bool) -> str:
    """How `gossip_core` counts a level's usage.  A cell level's sends
    go to the exchange's two endpoints alone, so unless the caller reads
    the flat per-edge counters each slot's endpoint count is all the
    attribution needs (``"slots"``); an overlay level's routes credit
    relay nodes per edge (``"edges"``)."""
    if lp.kind == "cells" and not collect_usage:
        return "slots"
    return "edges"


def _level_consts(lp, usage_count: str):
    c = {
        "adj": jax.tree.map(jnp.asarray, CsrGraphs.from_flat(
            lp.nbr_start, lp.nbr_flat, lp.hop_flat, lp.degrees, lp.n_nodes)),
        "node_mask": jnp.asarray(lp.node_mask, bool),
        "slot_node": jnp.asarray(lp.slot_node, jnp.int32),
    }
    if lp.kind == "cells":
        if usage_count == "edges":
            # per-flat-entry owner/partner global ids (sentinel = trash
            # slot n); a slot count attributes through slot_node
            c["row_node"] = jnp.asarray(lp.row_node, jnp.int32)
            c["partner_flat"] = jnp.asarray(lp.partner_flat, jnp.int32)
    else:
        for name in ("edge_pos_i", "edge_pos_j",
                     "inc_node", "inc_edge", "inc_count"):
            c[name] = jnp.asarray(getattr(lp, name), jnp.int32)
    if lp.rep_slot is not None:
        c["rep_slot"] = jnp.asarray(lp.rep_slot, jnp.int32)
        c["line16"] = jnp.asarray(lp.line16, jnp.float32)
        c["next_graph"] = jnp.asarray(lp.next_graph, jnp.int32)
        c["next_slot"] = jnp.asarray(lp.next_slot, jnp.int32)
    return c


def _failure_consts(plan, failures, maxt_levels, n):
    """Per-level `FailureCtx`s plus the dissemination freeze-out, from
    the host-drawn failure node sets mapped through each level's slot
    layout and static event windows.

    Event times are fractions of the FINEST level's tick budget (the
    finest level is where events fire); churned nodes stay down through
    every coarser level (churn_tick=0 there), and a regional outage
    persists into coarser levels only when its window extends past 1.0.

    Returns (ctxs, freeze): `freeze` is None or a dict with the (n,)
    mask of nodes that must NOT receive the dissemination down-pass —
    Byzantine nodes discard it, churned / permanently-out regional
    nodes never hear it — plus their (graph, slot) coordinates in the
    finest level, whose post-gossip value is exactly their frozen one.
    """
    sets = failure_sets(failures, n, coords=plan.graph.coords)
    maxt0 = int(maxt_levels[0])
    t0f, t1f = failures.regional_window
    reg_perm = t1f > 1.0
    ctxs = []
    for li, lp in enumerate(plan.levels):
        sn = np.asarray(lp.slot_node)
        valid = sn >= 0
        idx = np.clip(sn, 0, n - 1)
        if li == 0:
            churn_tick = int(round(failures.churn_time * maxt0))
            reg_t0 = int(round(t0f * maxt0))
            reg_t1 = maxt0 + 1 if reg_perm else int(round(t1f * maxt0))
        else:
            churn_tick = 0  # already-churned nodes stay down
            maxt = int(maxt_levels[li])
            reg_t0, reg_t1 = (0, maxt + 1) if reg_perm else (0, 0)
        ctxs.append(FailureCtx(
            churned=jnp.asarray(valid & sets["churned"][idx]),
            straggler=jnp.asarray(valid & sets["straggler"][idx]),
            byz=jnp.asarray(valid & sets["byz"][idx]),
            regional=jnp.asarray(valid & sets["regional"][idx]),
            churn_tick=churn_tick,
            reg_t0=reg_t0,
            reg_t1=reg_t1,
            straggler_success=(
                float(failures.straggler_success)
                if failures.straggler_fraction > 0 else 1.0),
        ))
    frozen = sets["byz"] | sets["churned"]
    if reg_perm:
        frozen = frozen | sets["regional"]
    freeze = None
    if plan.disseminate and frozen.any():
        sn0 = np.asarray(plan.levels[0].slot_node)
        b, c = np.nonzero(sn0 >= 0)
        ids = sn0[b, c].astype(np.int64)
        graph0 = np.zeros(n, np.int32)
        slot0 = np.zeros(n, np.int32)
        graph0[ids] = b.astype(np.int32)
        slot0[ids] = c.astype(np.int32)
        freeze = {
            "frozen": jnp.asarray(frozen),
            "graph0": jnp.asarray(graph0),
            "slot0": jnp.asarray(slot0),
        }
    return ctxs, freeze


def _price_levels(cost, plan, n, level_messages, messages, lretx, lcong):
    """Reduce the executor's per-graph cost counters into a `MediumCost`.

    `level_messages` is (T, L) int64; `lretx`/`lcong` are the L per-level
    (T, B) device counters (empty tuples when `cost` is None).  When the
    model is closed-form (``sample=False`` or ``retransmit_p == 1``) the
    sampled counters are ignored and the Geometric mean ``T*(1-p)/p`` is
    applied to the logical counts instead.  The dissemination down-pass
    (n extra logical transmissions, already in `messages`) is priced in
    expectation — there is no schedule to sample against.
    """
    if cost is None:
        return None
    p = cost.retransmit_p
    if cost.sample and p < 1.0:
        level_retx = np.stack(
            [np.asarray(r, np.int64)[:, : lp.num_graphs].sum(axis=1)
             for r, lp in zip(lretx, plan.levels)],
            axis=1,
        ).astype(np.float64)
    else:
        level_retx = expected_retransmissions(level_messages, p)
    level_cong = np.stack(
        [np.asarray(cg, np.float64)[:, : lp.num_graphs].sum(axis=1)
         for cg, lp in zip(lcong, plan.levels)],
        axis=1,
    )
    retx = level_retx.sum(axis=1)
    if plan.disseminate and p < 1.0:
        retx = retx + n * (1.0 - p) / p
    cong_e = cost.hop_energy * cost.congestion_alpha * level_cong
    congestion = cong_e.sum(axis=1)
    return MediumCost(
        transmissions=np.asarray(messages, np.float64),
        retransmissions=retx,
        congestion=congestion,
        energy=cost.hop_energy * (messages + retx) + congestion,
        level_energy=(
            cost.hop_energy * (level_messages + level_retx) + cong_e),
        model=cost,
    )


def execute_plan(
    plan: HierarchyPlan,
    x0: np.ndarray,
    *,
    eps: float = 1e-4,
    seeds: Sequence[int] = (0,),
    weighted: bool = False,
    fixed_ticks_scale: float = 0.0,
    options: Optional[ExecOptions] = None,
    failures: Optional[FailureModel] = None,
    cost: Optional[CostModel] = None,
) -> EngineResult:
    """Execute `plan` for T = len(seeds) independent trials in one
    compiled, vmapped call.

    x0 may be (n,) — shared across trials — or (T, n) per-trial.  Each
    seed drives one trial's exchange randomness; the plan (partition,
    election, routes) is shared, so trials differ only in gossip noise.

    `options` (an `ExecOptions`) selects mesh / check cadence / tick
    budget / usage read-out (the historical flat kwargs were
    removed after their deprecation window — a stale call now raises
    `TypeError`).  `failures` (a `FailureModel`) carries the paper's
    `loss_p` message-loss model plus the scenario fields (churn,
    stragglers, regional outage, Byzantine drops) that perturb the
    presampled schedule — scenario event times are fractions of the
    finest level's tick budget, so run scenarios in fixed-iterations
    mode.  `cost` (a `CostModel`) prices the schedule (energy,
    retransmissions, congestion) into `EngineResult.cost` WITHOUT
    perturbing the exchange trajectory: x / usage / messages are
    bitwise-identical with the cost model on or off.

    `options.mesh` shards the computation via shard_map: a 1-axis
    `jax.sharding.Mesh` shards the vmapped trial axis (T is padded up
    to a multiple of the mesh size with throwaway trials); a 2-axis
    mesh with axes named ``("trials", "nodes")`` also blocks every
    level's graph batch over the "nodes" axis, with psum halos only at
    promotion boundaries — per-trial results are bitwise-independent of
    the sharding either way.  The node-sharded path supports neither
    `collect_usage` (the flat usage buffer is deliberately never
    assembled globally) nor `failures` scenarios / `cost` pricing (their
    reductions are batch-global).

    `options.collect_usage` additionally returns the raw per-level flat
    exchange counters (for attribution audits); leave it off on the hot
    path.

    A profiler trace names the call's host steps:
    ``repro.execute_plan`` spans the call, ``.prepare`` everything up to
    and including the dispatch of the executor (option checks, trial
    keys, argument transfer, cache lookup), ``.build`` with its children
    ``.build.lower`` and ``.build.compile`` a cache miss, and
    ``.readback`` every device-to-host read and host reduction after
    the dispatch.  A cache miss also records the lowering and compile
    seconds as the `jax.monitoring` duration events
    ``/repro/core/executor_lower`` and ``/repro/core/executor_compile``,
    and per level the events ``/repro/core/schedule_lookup``, with how
    its schedule reads partners and hops (`CsrGraphs.lookup`),
    ``/repro/core/value_pass_lookup``, with how `pair_apply` reads a
    tick's endpoints in one node block (``path`` select or gather,
    `core.value_pass.value_read_path`), ``/repro/core/usage_count``,
    with how the level counts usage (``path`` slots, each slot's
    endpoint sends by one-hot adds, on a cell level unless
    `collect_usage` asks for the flat counters; else edges, the flat
    per-edge scatter),
    ``/repro/core/executor_consts``, with the ``bytes`` of the level's
    plan arrays baked into the executor as constants, and in
    fixed-iterations mode ``/repro/core/fixed_ticks``, with the level's
    tick budget (``ticks``, rounded up to the check cadence ``check``);
    `core.events.event_totals` keeps their running totals.
    On the device every executor op sits under a ``level_<i>`` (or
    ``final``) scope and one of `core.gossip.LAYER_SCOPES`.
    """
    with _span("repro.execute_plan"):
        with _span("repro.execute_plan.prepare"):
            options = options if options is not None else ExecOptions()
            fn, args = _executor(
                plan, x0, eps=eps, seeds=seeds, weighted=weighted,
                fixed_ticks_scale=fixed_ticks_scale, options=options,
                failures=failures, cost=cost)
            out = fn(*args)
        with _span("repro.execute_plan.readback"):
            return _readback(plan, out, len(seeds), cost)


def _executor(plan, x0, *, eps, seeds, weighted, fixed_ticks_scale,
              options, failures, cost):
    """The compiled executor for this call (from `plan.exec_cache`, or
    lowered and compiled on a miss) and its device arguments."""
    mesh, collect_usage = options.mesh, options.collect_usage
    check_every = options.check_every
    max_ticks_per_level = options.max_ticks_per_level
    if failures is not None and failures.heterogeneous:
        raise ValueError(
            "per-edge loss_p is closed-form pricing only — the trajectory "
            "engine needs a scalar; price heterogeneous links with "
            "level_edge_messages + price_edge_messages")
    if cost is not None and cost.heterogeneous:
        raise ValueError(
            "per-edge hop_energy is closed-form pricing only — price "
            "heterogeneous links with level_edge_messages + "
            "price_edge_messages")
    loss_p = failures.loss_p if failures is not None else None
    scenario = failures is not None and failures.has_scenario
    if scenario and fixed_ticks_scale <= 0:
        raise ValueError(
            "failure scenarios require fixed_ticks_scale > 0: scenario "
            "event times are fractions of the finest level's tick budget, "
            "which the eps-oracle mode leaves unbounded")
    platform = (mesh.devices.flat[0].platform if mesh is not None
                else jax.default_backend())
    n = plan.graph.n
    x0 = np.asarray(x0, np.float32)
    T = len(seeds)
    per_trial_x0 = x0.ndim == 2
    if per_trial_x0 and x0.shape[0] != T:
        raise ValueError(f"x0 leading dim {x0.shape[0]} != trials {T}")
    node_mesh = False
    if mesh is not None:
        if len(mesh.shape) == 2 and tuple(mesh.axis_names) == (
            "trials", "nodes",
        ):
            node_mesh = True
            if collect_usage:
                raise ValueError(
                    "collect_usage is not supported on the (trials, nodes) "
                    "mesh (flat usage stays shard-local)"
                )
            if scenario or cost is not None:
                raise ValueError(
                    "failure scenarios / cost pricing are not supported on "
                    "the (trials, nodes) mesh (their reductions are "
                    "batch-global)"
                )
        elif len(mesh.shape) != 1:
            raise ValueError(
                "execute_plan wants a 1-axis trial mesh or a 2-axis mesh "
                f"with axes ('trials', 'nodes'), got {dict(mesh.shape)}"
            )
    if mesh is None:
        pad = 0
    elif node_mesh:
        pad = (-T) % mesh.shape["trials"]
    else:
        pad = (-T) % mesh.devices.size
    nd = mesh.shape["nodes"] if node_mesh else 1
    V = 2 if weighted else 1
    L = len(plan.levels)
    K = plan.k

    # per-level loop config: eps / max_ticks are RUNTIME values (so the
    # eps-oracle and fixed-iterations modes share one compiled executor);
    # only the check cadence is static (scan length).
    eps_levels, maxt_levels, chk_levels = [], [], []
    for lp in plan.levels:
        if fixed_ticks_scale > 0:
            fixed = fi_ticks(
                int(lp.n_nodes.max()), eps, fixed_ticks_scale,
                quadratic=(lp.kind == "overlay"),
            )
            chk = max(1, min(check_every, fixed))
            eps_levels.append(-1.0)  # negative tol: the oracle never fires
            maxt_levels.append(((fixed + chk - 1) // chk) * chk)
            chk_levels.append(chk)
        else:
            eps_levels.append(float(eps))
            maxt_levels.append(int(max_ticks_per_level))
            chk_levels.append(int(check_every))
    counts = [_usage_count(lp, collect_usage) for lp in plan.levels]
    # filled only when the executor must be (re)traced: a cache hit never
    # touches the plan's big constant arrays again.  fail_ctxs holds the
    # per-level scenario flags (slot-mapped failure sets + static event
    # windows), freeze_c the dissemination freeze-out; both are filled
    # alongside consts.
    consts: list = []
    fail_ctxs: list = []
    freeze_c: list = []

    def _shard_cols(B):
        """This shard's contiguous block of the B graphs: clipped column
        ids plus the realness mask (clipped duplicates sample masked-out
        schedules, so they contribute nothing anywhere)."""
        Bs = -(-B // nd)
        sidx = jax.lax.axis_index("nodes") * Bs + jnp.arange(Bs)
        return jnp.minimum(sidx, B - 1), sidx < B, sidx

    def _run(x0_row, key, eps_arr, maxt_arr):
        with _scope("level_0"), _scope("accounting"):
            node_sends = jnp.zeros(n + 1, jnp.int32)  # slot n swallows padding
        lvl_msgs, lvl_ticks, lvl_conv, usages = [], [], [], []
        lvl_retx, lvl_cong = [], []
        xb = None
        frozen_vals = None
        for li, (lp, c, chk, count) in enumerate(
                zip(plan.levels, consts, chk_levels, counts)):
            with _scope(f"level_{li}"):
                B = lp.num_graphs
                with _scope("schedule"):
                    if node_mesh:
                        cols, ok, _ = _shard_cols(B)
                        mask = c["node_mask"][cols] & ok[:, None]
                        shard = (cols, ok)
                    else:
                        cols, ok, shard = slice(None), None, None
                        mask = c["node_mask"]
                    level_key = jax.random.fold_in(key, li)
                with _scope("convergence_check"):
                    eps_l, maxt_l = eps_arr[li], maxt_arr[li]
                with _scope("promote"):
                    if lp.kind == "cells":
                        slots = jnp.clip(c["slot_node"][cols], 0)
                        vals = jnp.where(mask, x0_row[slots], 0.0)
                        if weighted:
                            w = mask.astype(jnp.float32)
                            xb_loc = jnp.stack([vals * w, w], axis=-1)
                        else:
                            xb_loc = vals[..., None]
                    else:
                        # promotion left xb global (the psum halo); take
                        # our block
                        xb_loc = xb[cols] if node_mesh else xb
                out = gossip_core(
                    xb_loc, c["adj"], mask,
                    eps_l, level_key,
                    max_ticks=maxt_l, check_every=chk, loss_p=loss_p,
                    node_shard=shard,
                    failure_ctx=fail_ctxs[li] if scenario else None,
                    cost_model=cost, hop_cap=max(1, int(lp.max_hops)),
                    usage_count=count,
                )
                if cost is not None:
                    x, usage, msgs, done, ticks, retx_l, cong_l = out
                    lvl_retx.append(retx_l)
                    lvl_cong.append(cong_l)
                else:
                    x, usage, msgs, done, ticks = out
                # per-graph counters stay int32 on device; they are summed
                # on the host in int64 (jnp.sum would wrap without x64)
                lvl_msgs.append(msgs)
                with _scope("accounting"):
                    if node_mesh:
                        lvl_ticks.append(jax.lax.pmax(ticks.max(), "nodes"))
                        lvl_conv.append(
                            jax.lax.psum((done & ok).sum(), "nodes") / B
                        )
                    else:
                        lvl_ticks.append(ticks.max())
                        lvl_conv.append(done.mean())
                if collect_usage:
                    usages.append(usage)
                # a frozen node's own post-gossip value at the finest level
                # is its value for the rest of the run: snapshot it before
                # promotion for the dissemination freeze-out
                if li == 0 and scenario and freeze_c \
                        and freeze_c[0] is not None:
                    with _scope("promote"):
                        fz = freeze_c[0]
                        e0 = (x[..., 0] if V == 1
                              else x[..., 0] / jnp.maximum(x[..., 1], 1e-30))
                        frozen_vals = e0[fz["graph0"], fz["slot0"]]
                # attribution: one scatter-add per level, of the slot
                # counts through the node ids promotion read x0 by, or of
                # the flat counter through the plan CSR.  A padding slot
                # is never an endpoint (i < n_nodes, j a neighbour), so
                # clipping sends its zero count to node 0; reusing the
                # clipped ids keeps one folded constant where a second
                # index array would add another (17.55 MB at n=10^6).
                # Under node sharding `usage` is the shard's partial
                # counter (both directed entries of an overlay edge live
                # in one graph, hence one shard), so the partial
                # node_sends just psum at the end.
                with _scope("accounting"):
                    if count == "slots":
                        node_sends = node_sends.at[slots].add(usage.T)
                    elif lp.kind == "cells":
                        node_sends = node_sends.at[c["row_node"]].add(usage)
                        node_sends = node_sends.at[
                            c["partner_flat"]].add(usage)
                    else:
                        usage_e = (usage[c["edge_pos_i"]]
                                   + usage[c["edge_pos_j"]])
                        node_sends = node_sends.at[c["inc_node"]].add(
                            usage_e[c["inc_edge"]] * c["inc_count"]
                        )
                # promotion (gathers; Alg.1 line 16 on the finest level)
                if lp.rep_slot is not None:
                    with _scope("promote"):
                        Bl = x.shape[0]
                        v = x[jnp.arange(Bl), c["rep_slot"][cols]]  # (Bl, V)
                        if weighted:
                            v = v * c["adj"].n_nodes[cols, None].astype(
                                jnp.float32)
                        else:
                            v = v * c["line16"][cols, None]
                        B2, C2 = plan.levels[li + 1].node_mask.shape
                        if node_mesh:
                            # reps hop shards here: scatter into a
                            # trash-rowed global buffer and psum the halo
                            # over node blocks
                            tg = jnp.where(ok, c["next_graph"][cols], B2)
                            full = jnp.zeros(
                                (B2 + 1, C2, V), jnp.float32).at[
                                    tg, c["next_slot"][cols]
                            ].set(jnp.where(ok[:, None], v, 0.0))
                            xb = jax.lax.psum(full, "nodes")[:B2]
                        else:
                            xb = jnp.zeros((B2, C2, V), jnp.float32).at[
                                c["next_graph"], c["next_slot"]
                            ].set(v)
        # after the last level: the final estimate, the dissemination
        # down-pass and the per-node counters' read-out
        with _scope("final"), _scope("promote"):
            est = (x[..., 0] if V == 1
                   else x[..., 0] / jnp.maximum(x[..., 1], 1e-30))
            if node_mesh:
                BL, CL = plan.levels[-1].node_mask.shape
                cols, ok, sidx = _shard_cols(BL)
                tg = jnp.where(ok, sidx, BL)
                full = jnp.zeros((BL + 1, CL), jnp.float32).at[tg].set(
                    jnp.where(ok[:, None], est, 0.0)
                )
                est = jax.lax.psum(full, "nodes")[:BL]
            x_final = est[plan.final_graph, plan.final_slot]
            # Byzantine nodes discard the down-pass; churned / permanently
            # regional-out nodes never hear it — they keep their frozen
            # value
            if frozen_vals is not None:
                x_final = jnp.where(freeze_c[0]["frozen"], frozen_vals,
                                    x_final)
        with _scope("final"), _scope("accounting"):
            node_sends = node_sends[:n]
            if node_mesh:
                node_sends = jax.lax.psum(node_sends, "nodes")
            if plan.disseminate:
                node_sends = node_sends + 1  # the n-message down-pass
            return (
                x_final, node_sends,
                tuple(lvl_msgs), jnp.stack(lvl_ticks), jnp.stack(lvl_conv),
                tuple(usages), tuple(lvl_retx), tuple(lvl_cong),
            )

    # throwaway padding trials bring T up to a mesh-device multiple
    pad_seeds = tuple(seeds) + tuple(seeds[:1]) * pad
    keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in pad_seeds])
    if per_trial_x0 and pad:
        x0 = np.concatenate([x0, np.repeat(x0[:1], pad, axis=0)], axis=0)
    args = (
        jnp.asarray(x0),
        keys,
        jnp.asarray(eps_levels, jnp.float32),
        jnp.asarray(maxt_levels, jnp.int32),
    )
    cache_key = (
        platform, T, per_trial_x0, weighted, failures, cost, mesh,
        tuple(chk_levels), collect_usage,
        # scenario event ticks are baked into the trace as constants
        # derived from maxt_levels (see _failure_consts), so executors
        # traced for different tick budgets must not collide
        tuple(maxt_levels) if scenario else None,
    )
    fn = plan.exec_cache.get(cache_key)
    if fn is None:
        with _span("repro.execute_plan.build"):
            t0 = time.perf_counter()
            with _span("repro.execute_plan.build.lower"):
                consts.extend(_level_consts(lp, count)
                              for lp, count in zip(plan.levels, counts))
                if scenario:
                    ctxs, freeze = _failure_consts(
                        plan, failures, maxt_levels, n)
                    fail_ctxs.extend(ctxs)
                    freeze_c.append(freeze)
                run_v = _over_trials(_run, T, per_trial_x0, mesh, node_mesh,
                                     len(plan.levels))
                lowered = jax.jit(run_v).lower(*args)
            t1 = time.perf_counter()
            with _span("repro.execute_plan.build.compile"):
                opts = _CPU_COMPILER_OPTS if platform == "cpu" else None
                fn = lowered.compile(compiler_options=opts)
            t2 = time.perf_counter()
        record_duration("/repro/core/executor_lower", t1 - t0)
        record_duration("/repro/core/executor_compile", t2 - t1)
        for li, c in enumerate(consts):
            record_event("/repro/core/schedule_lookup", level=li,
                         **c["adj"].lookup)
            B, C = plan.levels[li].node_mask.shape
            record_event("/repro/core/value_pass_lookup", level=li,
                         path=value_pass.value_read_path(-(-B // nd), C))
            record_event("/repro/core/usage_count", level=li,
                         path=counts[li])
            record_event("/repro/core/executor_consts", level=li,
                         bytes=sum(a.nbytes for a in jax.tree.leaves(c)))
            if fixed_ticks_scale > 0:
                record_event("/repro/core/fixed_ticks", level=li,
                             ticks=maxt_levels[li], check=chk_levels[li])
        plan.exec_cache[cache_key] = fn
    return fn, args


def _over_trials(run, T, per_trial_x0, mesh, node_mesh, num_levels):
    """`run` (one trial) over the call's T trials: vmapped, and
    shard_mapped over `mesh` when there is one."""
    if T == 1 and mesh is None:
        # single-trial fast path: vmap's batching trace roughly
        # doubles trace time and XLA pays for size-1 batch dims on
        # every op — run the trial unbatched and re-add the trial
        # axis on the way out (per-trial results are independent of
        # the batching, see test_trials_vmap_matches_sequential)
        def run_v(x0_, keys_, eps_, maxt_):
            out = run(x0_[0] if per_trial_x0 else x0_, keys_[0],
                      eps_, maxt_)
            return jax.tree_util.tree_map(lambda a: a[None], out)
        return run_v
    run_v = jax.vmap(run, in_axes=(0 if per_trial_x0 else None, 0, None, None))
    if mesh is None:
        return run_v
    from jax.sharding import PartitionSpec as P

    if node_mesh:
        Pt = P("trials")
        return jax.shard_map(
            run_v, mesh=mesh,
            in_specs=(Pt if per_trial_x0 else P(), Pt, P(), P()),
            out_specs=(
                Pt, Pt,
                tuple(P("trials", "nodes") for _ in range(num_levels)),
                Pt, Pt, (), (), (),
            ),
            check_vma=False,
        )
    (axis,) = mesh.axis_names
    return jax.shard_map(
        run_v, mesh=mesh,
        in_specs=(P(axis) if per_trial_x0 else P(), P(axis), P(), P()),
        out_specs=P(axis), check_vma=False,
    )


def _readback(plan, out, T, cost) -> EngineResult:
    """The executor's device outputs as host arrays: padding trials
    dropped, per-graph int32 counters reduced in int64, cost priced."""
    n = plan.graph.n
    xf, sends, lm, lt, lc, usages, lretx, lcong = out
    if xf.shape[0] > T:
        xf, sends, lt, lc = xf[:T], sends[:T], lt[:T], lc[:T]
        lm = tuple(m[:T] for m in lm)
        usages = tuple(u[:T] for u in usages)
        lretx = tuple(r[:T] for r in lretx)
        lcong = tuple(cg[:T] for cg in lcong)
    # host-side int64 reduction of the per-graph int32 counters (under
    # node sharding the per-level column count is nd*ceil(B/nd) with
    # zero-contribution duplicates — slice to the true B before summing)
    level_messages = np.stack(
        [np.asarray(m, np.int64)[:, : lp.num_graphs].sum(axis=1)
         for m, lp in zip(lm, plan.levels)],
        axis=1,
    )
    messages = level_messages.sum(axis=1)
    if plan.disseminate:
        messages = messages + n
    return EngineResult(
        x_final=np.asarray(xf),
        messages=messages,
        node_sends=np.asarray(sends, np.int64),
        level_messages=level_messages,
        level_ticks=np.asarray(lt, np.int64),
        level_converged=np.asarray(lc, np.float64),
        edge_usage=[np.asarray(u) for u in usages],
        cost=_price_levels(
            cost, plan, n, level_messages, messages, lretx, lcong),
    )
