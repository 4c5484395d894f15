"""Plan/execute core: batched-router parity with the scalar router,
CSR attribution parity with the legacy dict-based crawl, slot counts
against the flat per-edge counters, trial vmapping
consistency, per-trial mass conservation, and the removed options."""
import numpy as np
import pytest

from repro.core import (
    CostModel,
    ExecOptions,
    FailureModel,
    Graph,
    batched_greedy_routes,
    batched_routes_to_nodes,
    build_plan,
    execute_plan,
    gossip_until,
    greedy_route,
    multiscale_gossip,
    random_geometric_graph,
    route_to_node,
)
from repro.core.plan import overlay_node_sends


# --------------------------- routing parity ----------------------------


def test_batched_greedy_matches_scalar(rgg500):
    rng = np.random.default_rng(0)
    E = 50
    srcs = rng.integers(500, size=E)
    targets = rng.uniform(0, 1, (E, 2))
    br = batched_greedy_routes(rgg500, srcs, targets)
    for e in range(E):
        r = greedy_route(rgg500, int(srcs[e]), targets[e])
        assert br.hops[e] == r.hops
        np.testing.assert_array_equal(br.nodes[e, : r.hops + 1], r.nodes)
        assert (br.nodes[e, r.hops + 1 :] == -1).all()


def test_batched_route_to_nodes_matches_scalar(rgg500):
    rng = np.random.default_rng(1)
    pairs = rng.integers(500, size=(60, 2))
    br = batched_routes_to_nodes(rgg500, pairs)
    for e, (u, v) in enumerate(pairs):
        r = route_to_node(rgg500, int(u), int(v))
        assert br.hops[e] == r.hops
        np.testing.assert_array_equal(br.nodes[e, : r.hops + 1], r.nodes)
        assert br.greedy_ok[e] == r.greedy_ok
        assert br.nodes[e, 0] == u and br.nodes[e, br.hops[e]] == v


def _dead_end_graph() -> Graph:
    """A hook shape where greedy routing from node 0 toward node 4 gets
    stuck at a local minimizer, forcing the BFS fallback."""
    coords = np.array([
        [0.10, 0.50],   # 0: source
        [0.10, 0.20],   # 1: detour, farther from 4 than 0 is
        [0.45, 0.10],   # 2
        [0.80, 0.20],   # 3
        [0.80, 0.50],   # 4: destination (no direct link 0-4)
        [0.30, 0.52],   # 5: bait — closer to 4 than 0, but a dead end
    ])
    pairs = np.array([[0, 1], [1, 2], [2, 3], [3, 4], [0, 5]], np.int32)
    return Graph.from_pairs(coords, pairs, radius=0.4)


def test_batched_bfs_fallback_matches_scalar():
    g = _dead_end_graph()
    scalar = route_to_node(g, 0, 4)
    assert not scalar.greedy_ok  # the construction forces the fallback
    br = batched_routes_to_nodes(g, np.array([[0, 4], [4, 0], [1, 3]]))
    assert not br.greedy_ok[0]
    for e, (u, v) in enumerate([(0, 4), (4, 0), (1, 3)]):
        r = route_to_node(g, u, v)
        assert br.hops[e] == r.hops
        np.testing.assert_array_equal(br.nodes[e, : r.hops + 1], r.nodes)


# ------------------------- attribution parity --------------------------


def _legacy_overlay_sends(lp, usage, n):
    """The pre-refactor dict crawl: map (node, slot) -> edge via list
    scans, then add the full route send profile per recorded exchange."""
    E = len(lp.edge_b)
    node_sends = np.zeros(n, np.int64)
    for e in range(E):
        b = int(lp.edge_b[e])
        route = lp.routes.route(e)
        uses = int(usage[b, lp.edge_i[e], lp.edge_si[e]]) + int(
            usage[b, lp.edge_j[e], lp.edge_sj[e]]
        )
        node_sends += uses * route.send_counts(n)
    return node_sends


def test_csr_attribution_matches_legacy_dict(rgg500, x0_500):
    plan = build_plan(rgg500, seed=0)
    res = execute_plan(
        plan, x0_500, eps=1e-4, seeds=(0,), weighted=True,
        options=ExecOptions(collect_usage=True),
    )
    overlay_total = np.zeros(500, np.int64)
    checked = 0
    for li, lp in enumerate(plan.levels):
        if lp.kind != "overlay":
            continue
        usage = res.edge_usage[li][0]          # flat (nnz+1,) counters
        csr = overlay_node_sends(lp, usage, 500)
        legacy = _legacy_overlay_sends(lp, lp.dense_usage(usage), 500)
        np.testing.assert_array_equal(csr, legacy)
        overlay_total += csr
        checked += 1
    assert checked >= 1
    # full-run cross-check: engine node_sends == overlay CSR + base-level
    # (initiator+partner) counts + the dissemination send
    base = plan.levels[0]
    usage0 = base.dense_usage(res.edge_usage[0][0])
    base_sends = np.zeros(500, np.int64)
    for b in range(base.num_graphs):
        ids = base.slot_node[b][base.slot_node[b] >= 0]
        u = usage0[b, : len(ids)]
        base_sends[ids] += u.sum(axis=1)
        nbr = base.neighbors[b, : len(ids)]
        valid = nbr >= 0
        np.add.at(base_sends, ids[nbr[valid]], u[valid])
    expect = base_sends + overlay_total + (1 if plan.disseminate else 0)
    np.testing.assert_array_equal(res.node_sends[0], expect)


# ----------------------- slot counts vs flat usage ----------------------


@pytest.fixture(scope="module")
def plan500(rgg500):
    return build_plan(rgg500, seed=0)


FI = dict(eps=1e-3, fixed_ticks_scale=0.3)


@pytest.mark.parametrize("kw", [
    dict(eps=1e-4, seeds=(0,)),
    dict(eps=1e-4, seeds=(0, 1, 2), weighted=True),
    dict(FI, seeds=(4,), weighted=True),
    dict(FI, seeds=(4, 5, 6)),
    dict(FI, seeds=(7, 8, 9), weighted=True,
         failures=FailureModel(loss_p=0.8)),
    dict(FI, seeds=(1, 2), weighted=True,
         failures=FailureModel(churn_fraction=0.2, churn_time=0.3,
                               straggler_fraction=0.1, seed=5)),
    dict(eps=1e-4, seeds=(3,), weighted=True,
         cost=CostModel(retransmit_p=0.7, congestion_alpha=0.2)),
], ids=["eps-V1-T1", "eps-V2-T3", "fi-V2-T1", "fi-V1-T3", "fi-loss-T3",
        "fi-scenario-T2", "eps-cost-T1"])
def test_slot_counts_match_flat_usage(plan500, x0_500, kw):
    """A cell level's default count (each slot's endpoint sends, by
    one-hot adds) against the flat per-edge counter that
    `collect_usage` keeps: the same bits in every output."""
    slots = execute_plan(plan500, x0_500, **kw)
    flat = execute_plan(plan500, x0_500,
                        options=ExecOptions(collect_usage=True), **kw)
    assert slots.edge_usage == [] and len(flat.edge_usage) == \
        len(plan500.levels)
    for name in ("node_sends", "messages", "level_messages", "level_ticks",
                 "x_final"):
        np.testing.assert_array_equal(getattr(slots, name),
                                      getattr(flat, name), err_msg=name)
    if "cost" in kw:
        for name in ("retransmissions", "congestion", "energy"):
            np.testing.assert_array_equal(getattr(slots.cost, name),
                                          getattr(flat.cost, name))


# --------------------------- trial vmapping ----------------------------


def test_trials_vmap_matches_sequential(rgg500, x0_500):
    plan = build_plan(rgg500, seed=0)
    batched = multiscale_gossip(
        rgg500, x0_500, eps=1e-4, seed=0, weighted=True, trials=3, plan=plan
    )
    assert batched.trials == 3
    for t in range(3):
        single = multiscale_gossip(
            rgg500, x0_500, eps=1e-4, seed=t, weighted=True, plan=plan
        )
        assert int(batched.messages[t]) == single.messages
        np.testing.assert_array_equal(batched.node_sends[t], single.node_sends)
        np.testing.assert_allclose(
            batched.x_final[t], single.x_final, rtol=1e-5, atol=1e-6
        )
    errs = batched.error(x0_500)
    assert errs.shape == (3,)


def test_trial_conservation_weighted(rgg500, x0_500):
    res = multiscale_gossip(
        rgg500, x0_500, eps=1e-5, seed=0, weighted=True, trials=3
    )
    target = 500 * float(np.mean(x0_500))
    for t in range(3):
        # exact-mass fusion: sum(x_final) ~= n * mean(x0) per trial
        assert abs(float(res.x_final[t].sum()) - target) <= 0.5
        assert res.error(x0_500)[t] <= 20 * 1e-5


def test_trials_accounting_per_trial(rgg500, x0_500):
    res = multiscale_gossip(
        rgg500, x0_500, eps=1e-4, seed=3, weighted=True, trials=2
    )
    for t in range(2):
        assert res.node_sends[t].sum() == res.messages[t]


# ------------------------- removed options ------------------------------


@pytest.mark.parametrize("name,value", [
    ("backend", "pallas"), ("schedule", "per_tick"), ("interpret", True),
], ids=["backend", "schedule", "interpret"])
def test_removed_exec_options_fail_loudly(name, value):
    """The executor has one value pass: a stale choice of it is a
    TypeError, in the options and in the host gossip API alike."""
    with pytest.raises(TypeError):
        ExecOptions(**{name: value})
    with pytest.raises(TypeError):
        gossip_until(np.zeros((1, 2)), np.zeros((1, 2, 1), np.int32),
                     np.ones((1, 2), np.int32), np.array([2]), eps=1e-3,
                     **{name: value})


def test_single_level_plan_counts_reps():
    # n <= cell_max => K == 1: no promotion, but the per-cell election
    # still happens and is counted (legacy Alg. 1 behavior)
    g = random_geometric_graph(6, seed=0)
    x0 = np.random.default_rng(0).normal(0, 1, 6)
    res = multiscale_gossip(g, x0, eps=1e-4, seed=0)
    assert res.partition.k == 1
    assert res.rep_counts.sum() > 0
    assert res.rep_counts.max() <= res.partition.k
    assert res.error(x0) <= 1e-3
