"""Presampled exchange schedules for randomized gossip.

Every exchange decision in the asynchronous gossip model — which node
wakes, which neighbor it draws, whether each hop of the request/reply
survives, what the exchange costs — depends only on ``(key, t)``, never
on the node values; only the pair-average recursion itself is
sequential.  (Boyd et al. [2] and the paper's §VII fixed-iterations
analysis both treat the exchange sequence as an i.i.d. schedule for
exactly this reason.)  This module exploits that split:

* `sample_tick` is the sampling half of one gossip tick — the draws
  of one tick, in order, so a per-tick scan that samples as it goes
  (the tests' oracle) sees the same exchanges bit for bit;
* `sample_schedule` vmaps it over a whole `check_every` chunk of tick
  indices: one batched RNG pass produces the full ``(T, B)`` schedule
  (waking node, neighbor slot, partner, per-hop loss outcomes, hop
  cost) at once.  `jax.vmap` does not change threefry's per-key
  streams, so the presampled schedule is bit-identical to T sequential
  `sample_tick` calls.

Adjacency is CSR-addressed (`CsrGraphs`): the ``(B, C, D)`` dense
padded arrays of the historical path wasted O(B*C*D) memory on the
degree spread; a sampled tick carries `pos`, the flat index of the drawn
directed edge in the level's CSR order (one entry per directed edge plus
a single trailing sentinel), so per-edge usage counters live in a flat
``(nnz+1,)`` buffer and counting them is a 1-D scatter-add.  The lookups
that turn a draw into a degree, a row start, a partner and a hop count
are compare-and-selects over the drawn graph's slots, not gathers, but
for the partner of a level with wide rows (see `CsrGraphs`).

The value half — applying the presampled pair list to ``(B, C, V)``
cell state — lives in `core.value_pass`.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "CsrGraphs",
    "ExchangeSchedule",
    "dense_to_csr",
    "flat_usage_to_dense",
    "sample_tick",
    "sample_schedule",
]


# The partner (and a varying hop count) is read by the select while one
# row of the level's padded neighbour lists, C*D_max entries, is at most
# this long; a longer row keeps the flat CSR gather at `pos`.  On a TPU
# v5e the select beats the gather 3-25x up to rows of 4096 once B fills
# the lanes, and ties it for a few graphs; a select costs a row's length
# per lookup on the CPU, so wide rows, which plans build only for a few
# graphs (large cells, flat graphs), keep the gather.
ROW_SELECT_MAX = 1024


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["start", "degrees", "n_nodes", "nbr", "hops"],
    meta_fields=["num_entries", "hop"],
)
@dataclasses.dataclass(frozen=True)
class CsrGraphs:
    """CSR adjacency for a batch of B padded graphs, laid out for the
    schedule's lookups.

    Rows are the ``B*C`` (graph, slot) pairs in row-major order; row
    ``(b, c)`` owns flat entries ``start : start + degree``.  One
    trailing sentinel entry (neighbor 0, hops 1) keeps the flat layout
    non-empty and gives empty rows an in-bounds target — a draw against
    a zero-degree row is already marked invalid by the schedule, so the
    garbage neighbor is never applied.

    Every draw is looked up within its own graph b, so the per-slot
    tables are slot-major, ``(C, B)``: the graph axis lies on the TPU's
    lanes and `sample_tick` reads them by a one-hot select over the slot
    axis.  The partner table `nbr` is laid out by the level's shape
    (`from_flat`): where a row of padded neighbour lists fits
    `ROW_SELECT_MAX`, it is the ``(C*D, B)`` lists themselves, entry
    ``(c*D + d, b)`` the d-th neighbour of slot c (D the level's largest
    degree), read by the same select; otherwise it is the flat
    ``(nnz+1,)`` CSR array, gathered at `pos`.  `hops` takes `nbr`'s
    layout, or is None when every flat entry, the sentinel included,
    routes over the same `hop` hops.  `num_entries` is the flat length,
    nnz + 1.
    """

    start: jax.Array              # (C, B) int32 flat offset of each row
    degrees: jax.Array            # (C, B) int32
    n_nodes: jax.Array            # (B,) int32
    nbr: jax.Array                # (C*D, B) rows, or (nnz+1,) flat
    hops: Optional[jax.Array]     # like nbr; None when uniform
    num_entries: int
    hop: Optional[int]            # the uniform hop count, else None

    @classmethod
    def from_flat(cls, start, nbr, hops, degrees, n_nodes) -> "CsrGraphs":
        """Host-side tables from the flat CSR arrays: ``(B, C)`` row
        starts and degrees, ``(nnz+1,)`` neighbours and hops with the
        trailing sentinel.  Empty rows' first padded entry holds the
        flat entry at their start, so the select reads the very value a
        gather of the flat arrays at `pos` would (neighbours and hops are
        bitwise equal)."""
        start = np.asarray(start, np.int32)
        degrees = np.asarray(degrees, np.int32)
        nbr = np.asarray(nbr, np.int32)
        hops = np.asarray(hops, np.int32)
        B, C = degrees.shape
        E = int(nbr.shape[0])
        D = max(1, int(degrees.max(initial=0)))
        uniform = bool((hops == hops[0]).all())
        if C * D <= ROW_SELECT_MAX:
            d = np.arange(D)
            idx = np.minimum(start[..., None] + d, E - 1)
            keep = d < np.maximum(degrees, 1)[..., None]

            def rows(flat):
                r = np.where(keep, flat[idx], 0).transpose(1, 2, 0)
                return _narrow(r.reshape(C * D, B))

            nbr_t = rows(nbr)
            hops_t = None if uniform else rows(hops)
        else:
            nbr_t, hops_t = nbr, None if uniform else hops
        return cls(
            start=np.ascontiguousarray(start.T),
            degrees=np.ascontiguousarray(degrees.T),
            n_nodes=np.asarray(n_nodes, np.int32),
            nbr=nbr_t, hops=hops_t, num_entries=E,
            hop=int(hops[0]) if uniform else None,
        )

    @property
    def lookup(self) -> dict:
        """How `sample_tick` reads the partner (``path``) and the hop
        count (``hops``) of a draw."""
        return {"path": "select" if self.nbr.ndim == 2 else "gather",
                "hops": "table" if self.hop is None else "const"}


def _narrow(a: np.ndarray) -> np.ndarray:
    """Non-negative integers in the narrowest signed integer type."""
    top = int(a.max(initial=0))
    return a.astype(np.int8 if top < 2**7 else
                    np.int16 if top < 2**15 else np.int32)


def dense_to_csr(neighbors, degrees, n_nodes, edge_hops=None) -> CsrGraphs:
    """Pack ``(B, C, D)`` padded adjacency into a host-side `CsrGraphs`.

    Entry order within a row is the dense row order (slots < degree), so
    a jidx drawn uniformly in [0, deg) addresses the same neighbor in
    both layouts — the CSR schedule is draw-for-draw identical to the
    dense one.
    """
    neighbors = np.asarray(neighbors)
    degrees = np.asarray(degrees, np.int32)
    B, C, D = neighbors.shape
    if edge_hops is None:
        edge_hops = np.ones((B, C, D), np.int32)
    keep = np.arange(D)[None, None, :] < degrees[:, :, None]
    cs = np.concatenate([[0], np.cumsum(degrees.ravel(), dtype=np.int64)])
    start = cs[:-1].reshape(B, C)
    nbr = np.concatenate([neighbors[keep].astype(np.int32), [0]])
    hops = np.concatenate([np.asarray(edge_hops)[keep].astype(np.int32), [1]])
    return CsrGraphs.from_flat(start, nbr, hops, degrees, n_nodes)


def flat_usage_to_dense(usage, degrees, D=None) -> np.ndarray:
    """Scatter flat ``(nnz+1,)`` usage counters back to ``(B, C, D)``.

    The host-side inverse of the CSR layout; padding slots get 0, the
    sentinel entry is dropped.
    """
    usage = np.asarray(usage)
    degrees = np.asarray(degrees, np.int64)
    B, C = degrees.shape
    if D is None:
        D = max(1, int(degrees.max(initial=0)))
    nnz = int(degrees.sum())
    deg_flat = degrees.ravel()
    row = np.repeat(np.arange(B * C), deg_flat)
    col = np.arange(nnz) - np.repeat(
        np.concatenate([[0], np.cumsum(deg_flat)])[:-1], deg_flat
    )
    out = np.zeros((B * C, D), usage.dtype)
    out[row, col] = usage[:nnz]
    return out.reshape(B, C, D)


class ExchangeSchedule(NamedTuple):
    """Value-independent draws for a block of gossip ticks.

    Leading axis is the tick index within the chunk (absent for a
    single `sample_tick`); all fields are per-graph ``(…, B)``.
    `valid` excludes the per-chunk `done` freeze, which is the caller's
    to apply (it is constant within a chunk): ``active = valid & ~done``.
    """

    i: jax.Array       # waking node
    jidx: jax.Array    # neighbor slot drawn at i
    j: jax.Array       # contacted node (garbage when not `valid`)
    valid: jax.Array   # bool: i has neighbors
    fwd_ok: jax.Array  # bool: request delivered over every hop
    rep_ok: jax.Array  # bool: reply delivered over every hop
    cost: jax.Array    # int32 single-hop transmissions if the tick is active
    pos: jax.Array     # int32 flat CSR index of the drawn directed edge
    hops: jax.Array    # int32 routing hops of the drawn directed edge


def truncated_failure_hops(u, p, h):
    """Hops transmitted for a message over h hops with per-hop success p.

    Successes before first failure: S = floor(log u / log p); delivered
    iff S >= h (transmits h); else transmits S + 1.  Returns
    (delivered, hops_transmitted).
    """
    s = jnp.where(p < 1.0, jnp.floor(jnp.log(u) / jnp.log(jnp.maximum(p, 1e-12))), jnp.inf)
    delivered = s >= h
    return delivered, jnp.where(delivered, h, s + 1.0).astype(jnp.int32)


def sample_tick(
    t,
    key,
    adj: CsrGraphs,
    loss_p: Optional[float],
    dtype=jnp.float32,
) -> ExchangeSchedule:
    """Draw one tick's exchange decisions for all B graphs.

    This is the sampling half of one tick: a per-tick scan that calls
    it as it goes draws exactly the presampled schedule, bit for bit;
    each draw's degree, row start, partner and hops are read within its
    own graph (see `CsrGraphs`).  Draws are over the
    global batch: a node-sharded caller samples the full ``(B,)``
    schedule and slices its columns, which keeps every shard's draws
    bit-identical to the unsharded run (threefry streams have no prefix
    property, so sampling only local columns would diverge).
    """
    C, B = adj.degrees.shape
    kt = jax.random.fold_in(key, t)
    ki, kj, kf, kr = jax.random.split(kt, 4)
    # pick a waking node per graph (uniform over live nodes)
    u = jax.random.uniform(ki, (B,))
    i = jnp.minimum((u * adj.n_nodes).astype(jnp.int32), adj.n_nodes - 1)
    deg_i = _select(adj.degrees, i)
    v = jax.random.uniform(kj, (B,))
    jidx = jnp.minimum((v * deg_i).astype(jnp.int32), jnp.maximum(deg_i - 1, 0))
    pos = _select(adj.start, i) + jidx
    if adj.nbr.ndim == 2:  # the drawn row's padded neighbour list
        k = i * (adj.nbr.shape[0] // C) + jidx

        def read(table):
            return _select(table, k)
    else:
        def read(table):
            return table[pos]
    j = read(adj.nbr)
    hops = (jnp.full((B,), adj.hop, jnp.int32) if adj.hops is None
            else read(adj.hops))
    valid = deg_i > 0  # compact rows: deg>0 iff the slot holds a real edge

    if loss_p is None:
        fwd_ok = jnp.ones((B,), bool)
        rep_ok = jnp.ones((B,), bool)
        cost = 2 * hops
    else:
        p = jnp.asarray(loss_p, dtype)
        fwd_ok, fwd_hops = truncated_failure_hops(
            jax.random.uniform(kf, (B,)), p, hops
        )
        rep_ok, rep_hops = truncated_failure_hops(
            jax.random.uniform(kr, (B,)), p, hops
        )
        cost = fwd_hops + jnp.where(fwd_ok, rep_hops, 0)
    return ExchangeSchedule(
        i=i, jidx=jidx, j=j, valid=valid,
        fwd_ok=fwd_ok, rep_ok=rep_ok, cost=cost, pos=pos, hops=hops,
    )


def _select(table, k):
    """``table[k[b], b]`` for a slot-major ``(R, B)`` table: a one-hot
    compare-and-select over R that XLA fuses into one reduction — no
    gather, and no ``(…, R, B)`` intermediate in memory."""
    hit = k[None, :] == jnp.arange(table.shape[0], dtype=k.dtype)[:, None]
    return jnp.sum(jnp.where(hit, table, 0), axis=0, dtype=jnp.int32)


def sample_schedule(
    ts,
    key,
    adj: CsrGraphs,
    loss_p: Optional[float],
    dtype=jnp.float32,
) -> ExchangeSchedule:
    """Presample a whole chunk: one batched RNG pass over tick indices
    `ts` producing an `ExchangeSchedule` with leading axis len(ts)."""

    def one(t):
        return sample_tick(t, key, adj, loss_p, dtype)

    return jax.vmap(one)(ts)
