"""Plain reference for a multiscale-gossip deployment.

It replays, one tick at a time on the host, the same randomized
exchanges the simulation core draws (paper §III: at each tick a uniform
live node of every unconverged graph wakes, draws a uniform neighbour,
and the pair averages), level by level up the hierarchy, with the
mass-weighted promotion and the dissemination down-pass, and counts
every single-hop transmission.  Values are kept in float64; the
exchange decisions come from the trial seed alone:

    key   = PRNGKey(seed)            per trial
    key_l = fold_in(key, level)      per level
    k_t   = fold_in(key_l, tick)     per tick
    k_i, k_j, _, _ = split(k_t, 4)
    u = uniform(k_i, (B,)),  v = uniform(k_j, (B,))
    i = min(floor(u * n_nodes), n_nodes - 1)
    j = the floor(v * deg(i))-th neighbour of i

The random bits are `jax.random`'s; every other step is numpy here.
The reference imports nothing of the program and takes nothing it has
made: the hierarchy it replays over (`Hierarchy`: cells, overlay grids,
routes, representatives) is built from the configuration alone by
`bench.hierarchy`.

In ε-oracle mode a graph stops at the first check (every `check_every`
ticks) at which its error is within ε of its start norm.  Whether that
happened is a float32 decision in the configuration (its state dtype),
so the replay carries a float32 shadow of the values and takes each
decision from it; the float64 values are what is compared.  The control
(`dtype=bfloat16`) runs the same replay with bfloat16 values.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional, Sequence

import numpy as np

__all__ = ["Hierarchy", "Level", "Replay", "fi_budget", "jax_uniforms",
           "level_schedule", "replay", "compare", "failed_trials"]


@dataclasses.dataclass
class Level:
    """One level of the hierarchy as plain arrays (B graphs, C slots)."""

    kind: str                 # "cells" | "overlay"
    start: np.ndarray         # (B, C) CSR row offsets
    nbr: np.ndarray           # (nnz+1,) neighbour slot of each directed edge
    hops: np.ndarray          # (nnz+1,) single-hop transmissions per leg
    degrees: np.ndarray       # (B, C)
    n_nodes: np.ndarray       # (B,)
    node_mask: np.ndarray     # (B, C) bool
    slot_node: np.ndarray     # (B, C) global node id, -1 padding
    # who transmits for one use of a directed edge (cells) ...
    row_node: Optional[np.ndarray] = None
    partner_flat: Optional[np.ndarray] = None
    # ... or of an undirected overlay edge, along its route (overlay)
    edge_pos_i: Optional[np.ndarray] = None
    edge_pos_j: Optional[np.ndarray] = None
    inc_node: Optional[np.ndarray] = None
    inc_edge: Optional[np.ndarray] = None
    inc_count: Optional[np.ndarray] = None
    # promotion to the next level (None on the last)
    rep_slot: Optional[np.ndarray] = None
    line16: Optional[np.ndarray] = None
    next_graph: Optional[np.ndarray] = None
    next_slot: Optional[np.ndarray] = None


@dataclasses.dataclass
class Hierarchy:
    n: int
    levels: list
    final_graph: np.ndarray   # (n,) where each node reads its estimate
    final_slot: np.ndarray
    disseminate: bool         # the down-pass costs one message per node


@dataclasses.dataclass
class Replay:
    """Per-trial outputs of the replay, S trials."""

    x_final: np.ndarray       # (S, n) estimate at every node
    messages: np.ndarray      # (S,) int64
    node_sends: np.ndarray    # (S, n) int64
    level_ticks: np.ndarray   # (S, L) int64, max over the level's graphs


def fi_budget(size: int, eps: float, scale: float, quadratic: bool) -> int:
    """Fixed-iterations tick budget (paper §VII): `scale` times the
    ε-averaging bound of the level's largest graph, p^2/2 ln(1/ε) for a
    p-node grid and 4 p ln(1/ε) for a near-complete cell, at least 32."""
    ln = math.log(1.0 / eps)
    bound = 0.5 * size * size * ln if quadratic else 4.0 * size * ln
    return max(32, math.ceil(scale * bound))


def level_schedule(lv: Level, eps: float, fixed_ticks_scale: float,
                   check_every: int, max_ticks: int):
    """(tolerance factor, tick cap, check cadence) of one level."""
    if fixed_ticks_scale > 0:
        fixed = fi_budget(int(lv.n_nodes.max()), eps, fixed_ticks_scale,
                          quadratic=lv.kind == "overlay")
        chk = max(1, min(check_every, fixed))
        return -1.0, -(-fixed // chk) * chk, chk
    return eps, max_ticks, check_every


@functools.lru_cache(maxsize=None)
def _uniform_fn(chk: int, B: int):
    import jax
    import jax.numpy as jnp

    def per_trial(key_l, t0):
        def per_tick(t):
            k_i, k_j, _, _ = jax.random.split(jax.random.fold_in(key_l, t), 4)
            return (jax.random.uniform(k_i, (B,)),
                    jax.random.uniform(k_j, (B,)))
        return jax.vmap(per_tick)(t0 + jnp.arange(chk, dtype=jnp.int32))

    return jax.jit(jax.vmap(per_trial, in_axes=(0, None)))


def jax_uniforms(seeds: Sequence[int]) -> Callable:
    """Draw function for `replay`: (level, t0, chk, B) -> u, v of shape
    (S, chk, B), float32, from `jax.random`'s threefry streams."""
    import jax
    import jax.numpy as jnp

    keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    level_keys = {}

    def draw(level: int, t0: int, chk: int, B: int):
        if level not in level_keys:
            level_keys[level] = jax.vmap(
                lambda k: jax.random.fold_in(k, level))(keys)
        u, v = _uniform_fn(chk, B)(level_keys[level], jnp.int32(t0))
        return np.asarray(u), np.asarray(v)

    return draw


def _pair_average(x, sidx, bidx, i, j, act, half):
    """Tick: i and j of every (trial, graph) average where `act`;
    partner written first, then initiator."""
    xi = x[sidx, bidx, i]
    xj = x[sidx, bidx, j]
    avg = ((xi + xj) * half).astype(x.dtype)
    a = act[..., None]
    x[sidx, bidx, j] = np.where(a, avg, xj)
    x[sidx, bidx, i] = np.where(a, avg, xi)


def _err32(xs, mean, live):
    d = (xs - mean[:, :, None, :]) * live
    return np.sqrt((d * d).sum(axis=(2, 3), dtype=np.float32))


def replay(h: Hierarchy, x0: np.ndarray, seeds: Sequence[int], *,
           weighted: bool, eps: float, fixed_ticks_scale: float,
           check_every: int, max_ticks: int, draw: Callable,
           dtype=np.float64) -> Replay:
    """Replay S = len(seeds) trials of the deployment from node values
    `x0` (n,).  `check_every` and `max_ticks` are the cadence of the
    checks and the tick cap of a level in ε-oracle mode.  `dtype` is the
    value precision (float64 for the reference, bfloat16 for the
    control)."""
    dt = np.dtype(dtype)
    half = dt.type(0.5)
    f32 = np.float32
    S, n = len(seeds), h.n
    V = 2 if weighted else 1
    sidx = np.arange(S)[:, None]
    oracle = fixed_ticks_scale <= 0
    node_sends = np.zeros((S, n + 1), np.int64)
    messages = np.zeros(S, np.int64)
    level_ticks = []
    xb = xs_b = None
    for li, lv in enumerate(h.levels):
        B, C = lv.node_mask.shape
        if (lv.n_nodes < 1).any():
            raise ValueError(f"level {li} has an empty graph")
        bidx = np.arange(B)[None, :]
        mask = lv.node_mask
        if lv.kind == "cells":
            vals = np.where(mask, x0[np.clip(lv.slot_node, 0, None)], 0.0)
            w = mask.astype(np.float64)
            chans = [vals * w, w] if weighted else [vals]
            x = np.broadcast_to(np.stack(chans, -1), (S, B, C, V)).astype(dt)
            xs = np.broadcast_to(np.stack(chans, -1),
                                 (S, B, C, V)).astype(f32)
        else:
            x, xs = xb, xs_b
        tol_eps, max_t, chk = level_schedule(
            lv, eps, fixed_ticks_scale, check_every, max_ticks)
        live = mask.astype(f32)[None, :, :, None]
        denom = np.maximum(live.sum(axis=2), f32(1.0))
        mean = (xs * live).sum(axis=2) / denom
        x0_norm = np.sqrt(((xs * live) ** 2).sum(axis=(2, 3)))
        tol = f32(tol_eps) * np.maximum(x0_norm, f32(1e-30))
        done = (_err32(xs, mean, live) <= tol) if oracle else \
            np.zeros((S, B), bool)
        nnz1 = lv.nbr.shape[0]
        usage = np.zeros(S * nnz1, np.int64)
        msgs = np.zeros((S, B), np.int64)
        ticks = np.zeros((S, B), np.int64)
        nn = lv.n_nodes.astype(np.int32)
        t0 = 0
        while t0 < max_t and not done.all():
            u, v = draw(li, t0, chk, B)                     # (S, chk, B)
            i = np.minimum((u * nn.astype(f32)).astype(np.int32), nn - 1)
            deg = lv.degrees[np.arange(B)[None, None, :], i]
            jidx = np.minimum((v * deg.astype(f32)).astype(np.int32),
                              np.maximum(deg - 1, 0))
            pos = lv.start[np.arange(B)[None, None, :], i] + jidx
            j = lv.nbr[pos]
            act = (deg > 0) & ~done[:, None, :]
            flat = pos + (np.arange(S) * nnz1)[:, None, None]
            usage += np.bincount(flat[act], minlength=S * nnz1)
            msgs += np.where(act, 2 * lv.hops[pos], 0).sum(axis=1)
            for t in range(chk):
                _pair_average(x, sidx, bidx, i[:, t], j[:, t], act[:, t],
                              half)
                if oracle:
                    _pair_average(xs, sidx, bidx, i[:, t], j[:, t],
                                  act[:, t], f32(0.5))
            ticks += np.where(done, 0, chk)
            if oracle:
                done = done | (_err32(xs, mean, live) <= tol)
            t0 += chk
        level_ticks.append(ticks.max(axis=1))
        messages += msgs.sum(axis=1)
        usage = usage.reshape(S, nnz1)
        for s in range(S):
            if lv.kind == "cells":
                sends = (np.bincount(lv.row_node, usage[s], n + 1)
                         + np.bincount(lv.partner_flat, usage[s], n + 1))
            else:
                use_e = usage[s, lv.edge_pos_i] + usage[s, lv.edge_pos_j]
                sends = np.bincount(
                    lv.inc_node, use_e[lv.inc_edge] * lv.inc_count, n + 1)
            node_sends[s] += np.rint(sends).astype(np.int64)
        if lv.rep_slot is not None:
            nxt = h.levels[li + 1].node_mask.shape
            factor = lv.n_nodes if weighted else lv.line16
            xb = np.zeros((S, *nxt, V), dt)
            xs_b = np.zeros((S, *nxt, V), f32)
            rep = x[sidx, bidx, lv.rep_slot[None, :]]
            xb[:, lv.next_graph, lv.next_slot] = (
                rep * factor.astype(dt)[None, :, None]).astype(dt)
            rep32 = xs[sidx, bidx, lv.rep_slot[None, :]]
            xs_b[:, lv.next_graph, lv.next_slot] = (
                rep32 * factor.astype(f32)[None, :, None])
    x64 = x.astype(np.float64)
    est = (x64[..., 0] / np.maximum(x64[..., 1], 1e-30) if weighted
           else x64[..., 0])
    extra = 1 if h.disseminate else 0
    return Replay(
        x_final=est[:, h.final_graph, h.final_slot],
        messages=messages + extra * n,
        node_sends=node_sends[:, :n] + extra,
        level_ticks=np.stack(level_ticks, axis=1),
    )


def compare(x_final, messages, node_sends, level_ticks, ref: Replay,
            x0: np.ndarray) -> dict:
    """The numbers `correct` is decided on.

    x_gap: the largest gap between a node's value and the reference's,
      over every node of every compared trial, in units of the rms of x0.
    count_diff: how many of the integer outputs (messages, per-level
      ticks, per-node sends) differ from the reference's.
    """
    scale = float(np.sqrt(np.mean(np.square(x0, dtype=np.float64))))
    gap = np.abs(np.asarray(x_final, np.float64) - ref.x_final)
    x_gap = float(gap.max()) / scale if np.isfinite(gap).all() else math.inf
    count_diff = int(
        (np.asarray(messages) != ref.messages).sum()
        + (np.asarray(level_ticks) != ref.level_ticks).sum()
        + (np.asarray(node_sends) != ref.node_sends).sum())
    return {"x_gap": x_gap, "count_diff": count_diff}


def failed_trials(x_final, messages, node_sends, level_ticks, ref: Replay,
                  x0: np.ndarray, limits: dict) -> int:
    """How many of the compared trials break a limit."""
    return sum(
        any(not v <= limits[k] for k, v in compare(
            x_final[s:s + 1], messages[s:s + 1], node_sends[s:s + 1],
            level_ticks[s:s + 1],
            Replay(ref.x_final[s:s + 1], ref.messages[s:s + 1],
                   ref.node_sends[s:s + 1], ref.level_ticks[s:s + 1]),
            x0).items())
        for s in range(len(messages)))
