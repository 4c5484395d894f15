"""The library's chunk against the per-tick oracle (`per_tick_oracle`).

The schedule/value split must be invisible to the simulation: gossip
with the presampled schedule and the pair walk of `core.value_pass` is
BITWISE-identical to the sequential sample-and-apply scan (x,
edge_usage, messages, ticks — including the `loss_p` failure path),
whichever endpoint read the value pass takes.
"""
import numpy as np
import pytest

import per_tick_oracle as oracle
from repro.core import (
    ExecOptions,
    FailureModel,
    batched_graphs,
    build_plan,
    execute_plan,
    gossip,
    gossip_until,
    random_geometric_graph,
    sample_schedule,
    sample_tick,
    value_pass,
)
from repro.core.value_pass import pair_apply


@pytest.fixture(params=["select", "gather"])
def forced_read(request, monkeypatch):
    """Every value pass reads its endpoints by one path, whatever the
    shape; `gossip_until` traces its loop anew, so the force holds."""
    path = request.param
    monkeypatch.setattr(value_pass, "value_read_path", lambda B, C: path)
    monkeypatch.setattr(gossip, "_gossip_loop", oracle.fresh_gossip_loop())
    return path


def _ring(n):
    class G:
        pass

    g = G()
    g.n = n
    g.max_deg = 2
    g.neighbors = np.stack(
        [(np.arange(n) - 1) % n, (np.arange(n) + 1) % n], axis=1
    ).astype(np.int32)
    g.degrees = np.full(n, 2, np.int32)
    return g


def _gossip_args(n=48, seed=0):
    g = _ring(n)
    x0 = np.random.default_rng(seed).normal(0, 1, n).astype(np.float32)[None]
    return (x0, g.neighbors[None], g.degrees[None], np.array([n], np.int32))


def _assert_int_parity(a, b):
    np.testing.assert_array_equal(a.edge_usage, b.edge_usage)
    np.testing.assert_array_equal(a.messages, b.messages)
    np.testing.assert_array_equal(a.ticks, b.ticks)
    np.testing.assert_array_equal(a.converged, b.converged)


# ------------------------ gossip-loop parity ---------------------------


def test_presampled_bitwise_eps_oracle(forced_read):
    args = _gossip_args(seed=1)
    legacy = oracle.gossip_until_per_tick(*args, eps=1e-4, seed=3)
    new = gossip_until(*args, eps=1e-4, seed=3)
    np.testing.assert_array_equal(legacy.x, new.x)
    _assert_int_parity(legacy, new)


def test_presampled_parity_fixed_ticks_loss(forced_read):
    """The paper's failure path: fixed budget, per-hop loss."""
    args = _gossip_args(seed=2)
    kw = dict(eps=-1.0, seed=7, fixed_ticks=384, loss_p=0.8)
    legacy = oracle.gossip_until_per_tick(*args, **kw)
    new = gossip_until(*args, **kw)
    _assert_int_parity(legacy, new)
    np.testing.assert_array_equal(legacy.x, new.x)


def test_presampled_parity_batched_weighted(forced_read):
    gs = [_ring(8), _ring(24), _ring(40)]
    neighbors, degrees, n_nodes, mask = batched_graphs(gs)
    rng = np.random.default_rng(5)
    x = np.where(mask, rng.normal(0, 1, mask.shape), 0.0)
    w = np.where(mask, rng.uniform(0.5, 2.0, mask.shape), 0.0)
    x0 = np.stack([x * w, w], axis=-1).astype(np.float32)
    legacy = oracle.gossip_until_per_tick(
        x0, neighbors, degrees, n_nodes, eps=1e-4, seed=9)
    new = gossip_until(x0, neighbors, degrees, n_nodes, eps=1e-4, seed=9)
    np.testing.assert_array_equal(legacy.x, new.x)
    _assert_int_parity(legacy, new)


# -------------------------- schedule pass ------------------------------


def test_sample_schedule_matches_sample_tick():
    import jax
    import jax.numpy as jnp

    from repro.core import dense_to_csr

    g = _ring(16)
    key = jax.random.PRNGKey(4)
    adj_np = dense_to_csr(
        g.neighbors[None], g.degrees[None], np.array([16], np.int32)
    )
    adj = jax.tree.map(jnp.asarray, adj_np)
    ts = jnp.arange(10, 42)
    sched = sample_schedule(ts, key, adj, 0.7)
    for idx, t in enumerate(np.asarray(ts)):
        one = sample_tick(jnp.int32(t), key, adj, 0.7)
        for field, batch in zip(one._fields, sched):
            np.testing.assert_array_equal(
                np.asarray(batch[idx]), np.asarray(getattr(one, field)),
                err_msg=f"t={t} field={field}",
            )


# ------------------- lookups: select against gather --------------------

# dense batches: (B, C, D_max, hops); every batch has rows of degree 0
# and ends with an edgeless graph, whose draws all land on the trailing
# sentinel; rows of padded neighbour lists (C*D_max) from 6 to 2352, on
# both sides of `ROW_SELECT_MAX`
_LOOKUP_CASES = {
    "cells": (6, 8, 7, "ones"),
    "overlay": (9, 4, 3, "vary"),
    "all-twos": (3, 5, 4, "twos"),    # the sentinel's 1 varies
    "edgeless": (2, 6, 0, "ones"),
    "at-128": (3, 16, 8, "vary"),
    "wide": (4, 13, 12, "ones"),
    "wide-hops": (2, 16, 15, "vary"),
    "big-cells": (3, 49, 48, "ones"),
    "big-cells-hops": (2, 40, 39, "vary"),
}


def _dense_batch(B, C, D, hops, seed):
    rng = np.random.default_rng(seed)
    n_nodes = rng.integers(1, C + 1, B).astype(np.int32)
    n_nodes[-1] = C
    degrees = np.where(np.arange(C)[None, :] < n_nodes[:, None],
                       rng.integers(0, D + 1, (B, C)), 0).astype(np.int32)
    degrees[:, 0] = 0  # slot 0 of every graph is isolated
    degrees[0, -1] = D
    degrees[-1] = 0
    neighbors = (rng.integers(0, 1 << 20, (B, C, max(D, 1)))
                 % n_nodes[:, None, None]).astype(np.int32)
    edge_hops = (rng.integers(1, 6, neighbors.shape) if hops == "vary" else
                 np.full(neighbors.shape, 2 if hops == "twos" else 1))
    return neighbors, degrees, n_nodes, edge_hops.astype(np.int32)


def _gather_schedule(ts, key, neighbors, degrees, n_nodes, edge_hops,
                     loss_p):
    """The lookups as gathers into the flat CSR arrays (``(B, C)`` row
    starts and degrees, ``(nnz+1,)`` neighbours and hops with the
    trailing sentinel), with `sample_tick`'s draws: the oracle."""
    import jax
    import jax.numpy as jnp

    from repro.core.schedule import ExchangeSchedule, truncated_failure_hops

    B, C, D = neighbors.shape
    keep = np.arange(D)[None, None, :] < degrees[:, :, None]
    start = jnp.asarray(np.concatenate(
        [[0], np.cumsum(degrees.ravel())])[:-1].reshape(B, C), jnp.int32)
    nbr = jnp.asarray(np.concatenate([neighbors[keep], [0]]), jnp.int32)
    hop_flat = jnp.asarray(np.concatenate([edge_hops[keep], [1]]), jnp.int32)
    deg, nn = jnp.asarray(degrees), jnp.asarray(n_nodes)

    def tick(t):
        kt = jax.random.fold_in(key, t)
        ki, kj, kf, kr = jax.random.split(kt, 4)
        u = jax.random.uniform(ki, (B,))
        i = jnp.minimum((u * nn).astype(jnp.int32), nn - 1)
        deg_i = jnp.take_along_axis(deg, i[:, None], axis=1)[:, 0]
        v = jax.random.uniform(kj, (B,))
        jidx = jnp.minimum((v * deg_i).astype(jnp.int32),
                           jnp.maximum(deg_i - 1, 0))
        pos = start[jnp.arange(B), i] + jidx
        hops = hop_flat[pos]
        if loss_p is None:
            fwd_ok = rep_ok = jnp.ones((B,), bool)
            cost = 2 * hops
        else:
            p = jnp.asarray(loss_p, jnp.float32)
            fwd_ok, fh = truncated_failure_hops(
                jax.random.uniform(kf, (B,)), p, hops)
            rep_ok, rh = truncated_failure_hops(
                jax.random.uniform(kr, (B,)), p, hops)
            cost = fh + jnp.where(fwd_ok, rh, 0)
        return ExchangeSchedule(
            i=i, jidx=jidx, j=nbr[pos], valid=deg_i > 0, fwd_ok=fwd_ok,
            rep_ok=rep_ok, cost=cost, pos=pos, hops=hops)

    return jax.vmap(tick)(ts)


@pytest.mark.parametrize("loss_p", [None, 0.7], ids=["lossless", "loss"])
@pytest.mark.parametrize("case", list(_LOOKUP_CASES))
def test_select_lookups_match_gather(case, loss_p):
    """The schedule's lookups — by one-hot selects over the drawn
    graph's slots, or by the flat gather past `ROW_SELECT_MAX` — give
    the gathers' integers bit for bit, garbage partners of invalid
    draws included; the select path lowers with no gather at all."""
    import jax
    import jax.numpy as jnp

    from repro.core import dense_to_csr
    from repro.core.schedule import ROW_SELECT_MAX

    B, C, D, hops = _LOOKUP_CASES[case]
    path = "select" if C * max(D, 1) <= ROW_SELECT_MAX else "gather"
    dense = _dense_batch(B, C, D, hops, seed=B * C + D)
    adj_np = dense_to_csr(*dense)
    uniform = hops == "ones"
    assert adj_np.lookup == {"path": path,
                             "hops": "const" if uniform else "table"}
    assert adj_np.nbr.shape == ((C * max(D, 1), B) if path == "select"
                                else (int(dense[1].sum()) + 1,))
    adj = jax.tree.map(jnp.asarray, adj_np)
    key = jax.random.PRNGKey(B + C + D)
    ts = jnp.arange(7, 7 + 96)
    got = jax.jit(sample_schedule, static_argnums=3)(ts, key, adj, loss_p)
    want = _gather_schedule(ts, key, *dense, loss_p)
    for field in want._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)), np.asarray(getattr(want, field)),
            err_msg=field)
    assert not np.asarray(got.valid)[:, -1].any()   # the edgeless graph
    np.testing.assert_array_equal(np.asarray(got.pos)[:, -1],
                                  adj.num_entries - 1)
    hlo = jax.jit(sample_schedule, static_argnums=3).lower(
        ts, key, adj, loss_p).as_text()
    assert ("gather" in hlo) == (path == "gather")


# (B, C, V, T): both sides of `value_read_path`'s rule, a one-slot cell
# among them; every case has ticks with i == j, all-masked ticks and
# -0.0 among the values
_VALUE_READ_CASES = [
    (875, 8, 2, 48), (337, 13, 1, 24), (128, 32, 2, 16), (300, 1, 2, 8),
    (16, 100, 2, 16), (3, 13, 2, 64), (4, 49, 2, 32), (1, 100, 2, 64),
    (7, 5, 1, 32), (16, 101, 1, 8),
]


def _value_case(B, C, V, T):
    """x, i, j, upd_i, upd_j for one of `_VALUE_READ_CASES`."""
    import jax.numpy as jnp

    rng = np.random.default_rng(B * C + V * T)
    x = rng.normal(size=(B, C, V)).astype(np.float32)
    x[rng.uniform(size=x.shape) < 0.1] = -0.0
    x[B // 2, :, 0] = -0.0     # averages of -0.0 stay -0.0, a sum's do not
    i = rng.integers(0, C, (T, B)).astype(np.int32)
    j = rng.integers(0, C, (T, B)).astype(np.int32)
    j[::3] = i[::3]                                  # i == j
    ui = rng.uniform(size=(T, B)) < 0.8
    uj = rng.uniform(size=(T, B)) < 0.9
    ui[1::5] = uj[1::5] = False                      # all-masked ticks
    return [jnp.asarray(a) for a in (x, i, j, ui, uj)]


@pytest.mark.parametrize("B,C,V,T", _VALUE_READ_CASES)
def test_pair_apply_matches_sequential_updates(B, C, V, T):
    """The value pass gives the bits of the same pair list applied tick
    by tick with scatters, signs of zero included."""
    args = _value_case(B, C, V, T)
    want = np.asarray(oracle.sequential_pair_apply(*args)).view(np.uint32)
    got = np.asarray(pair_apply(*args)).view(np.uint32)
    np.testing.assert_array_equal(got, want)
    assert (want == np.float32(-0.0).view(np.uint32)).any()


@pytest.mark.parametrize("B,C,V,T", _VALUE_READ_CASES)
def test_value_read_paths_give_the_same_bits(B, C, V, T, monkeypatch):
    """`pair_apply` reads x[i], x[j] by a select over a cell's slots or
    by gathers, as `value_read_path` picks from the shape: either path,
    forced, gives the rule's result bit for bit, signs of zero
    included."""
    args = _value_case(B, C, V, T)
    want = np.asarray(pair_apply(*args)).view(np.uint32)
    for path in ("select", "gather"):
        monkeypatch.setattr(value_pass, "value_read_path", lambda B, C: path)
        got = np.asarray(pair_apply(*args)).view(np.uint32)
        np.testing.assert_array_equal(got, want, err_msg=path)
    assert (want == np.float32(-0.0).view(np.uint32)).any()


def test_pair_apply_noop_when_masked(forced_read):
    import jax.numpy as jnp

    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 9, 1)),
                    jnp.float32)
    i = jnp.zeros((12, 2), jnp.int32)
    off = jnp.zeros((12, 2), bool)
    got = pair_apply(x, i, i, off, off)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))


@pytest.mark.parametrize("B,C,path", [
    (337_504, 13, "select"), (16, 100, "select"),
    (1, 100, "gather"), (15, 8, "gather"), (4, 49, "gather"),
    (16, 101, "gather"),
])
def test_value_read_path_rule(B, C, path):
    assert value_pass.value_read_path(B, C) == path


# --------------------------- engine parity -----------------------------


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["plain", "weighted"])
@pytest.mark.parametrize("mode", ["eps", "fi-loss"])
def test_engine_presampled_matches_per_tick(mode, weighted, monkeypatch):
    """The whole executor, every level, with the per-tick oracle swapped
    in for the chunk body: the same bits and counts.  Each run builds
    its own plan, so no executor traced before the swap is reused."""
    g = random_geometric_graph(120, seed=5)
    x0 = np.random.default_rng(2).normal(0, 1, 120)
    kw = dict(eps=1e-4, seeds=(0, 1), weighted=weighted,
              options=ExecOptions(collect_usage=True))
    if mode == "fi-loss":
        kw.update(eps=1e-3, fixed_ticks_scale=0.3,
                  failures=FailureModel(loss_p=0.8))
    new = execute_plan(build_plan(g, seed=0), x0, **kw)
    monkeypatch.setattr(gossip, "_presampled_chunk", oracle.per_tick_chunk)
    legacy = execute_plan(build_plan(g, seed=0), x0, **kw)
    np.testing.assert_array_equal(legacy.x_final, new.x_final)
    np.testing.assert_array_equal(legacy.messages, new.messages)
    np.testing.assert_array_equal(legacy.node_sends, new.node_sends)
    np.testing.assert_array_equal(legacy.level_ticks, new.level_ticks)
    np.testing.assert_array_equal(legacy.level_messages, new.level_messages)
    for a, b in zip(legacy.edge_usage, new.edge_usage, strict=True):
        np.testing.assert_array_equal(a, b)


def test_engine_single_device_mesh_matches_unsharded():
    import jax
    from jax.sharding import Mesh

    g = random_geometric_graph(90, seed=7)
    x0 = np.random.default_rng(4).normal(0, 1, 90)
    plan = build_plan(g, seed=0)
    mesh = Mesh(np.array(jax.devices()), ("trials",))
    sharded = execute_plan(
        plan, x0, eps=1e-4, seeds=(0, 1, 2), weighted=True,
        options=ExecOptions(mesh=mesh),
    )
    dense = execute_plan(plan, x0, eps=1e-4, seeds=(0, 1, 2), weighted=True)
    np.testing.assert_array_equal(sharded.x_final, dense.x_final)
    np.testing.assert_array_equal(sharded.messages, dense.messages)
    np.testing.assert_array_equal(sharded.node_sends, dense.node_sends)


def test_engine_mesh_rejects_multi_axis():
    import jax
    from jax.sharding import Mesh

    g = random_geometric_graph(30, seed=8)
    plan = build_plan(g, seed=0)
    mesh = Mesh(np.array(jax.devices()).reshape(1, 1), ("a", "b"))
    with pytest.raises(ValueError):
        execute_plan(
            plan, np.zeros(30), seeds=(0,), options=ExecOptions(mesh=mesh)
        )
