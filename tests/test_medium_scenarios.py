"""Wireless-medium cost model + failure-scenario matrix + the unified
ExecOptions/FailureModel/CostModel API (core.medium / core.options /
core.scenarios).

The load-bearing invariants:

* cost pricing is a pure reduction over the presampled schedule — the
  exchange trajectory (x / usage / messages) is bitwise-identical with
  the CostModel on or off;
* sampled Geometric retransmissions agree with the closed form
  ``T * (1-p)/p`` in expectation, and the closed-form mode returns it
  exactly;
* hop-distance pricing matches the independent route-incidence total
  ``sum(usage * 2 * hops)`` computed from the plan CSR;
* the deprecated flat kwargs warn and produce bitwise-identical
  EngineResults to the options=/failures= call form;
* scenarios perturb the replayed schedule in the physically sensible
  direction (churn reduces messages, Byzantine nodes keep their values).
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import (
    CostModel,
    ExecOptions,
    FailureModel,
    build_plan,
    execute_plan,
    expected_retransmissions,
    multiscale_gossip,
    price_messages,
    level_edge_messages,
    price_edge_messages,
    random_geometric_graph,
    route_edge_transmissions,
    run_scenario_matrix,
    scenario_matrix,
)
from repro.core.medium import failure_sets

N = 160
SEEDS = (0, 1)


@pytest.fixture(scope="module")
def setup():
    g = random_geometric_graph(N, seed=5)
    plan = build_plan(g, k=2, seed=0)
    x0 = np.random.default_rng(2).normal(0, 1, N).astype(np.float32)
    return g, plan, x0


def _run(plan, x0, **kw):
    kw.setdefault("eps", 1e-3)
    kw.setdefault("seeds", SEEDS)
    kw.setdefault("fixed_ticks_scale", 0.25)
    return execute_plan(plan, x0, **kw)


def test_cost_pricing_is_bitwise_neutral(setup):
    g, plan, x0 = setup
    base = _run(plan, x0, options=ExecOptions(collect_usage=True))
    priced = _run(
        plan, x0, options=ExecOptions(collect_usage=True),
        cost=CostModel(retransmit_p=0.7, congestion_alpha=0.2),
    )
    assert np.array_equal(base.x_final, priced.x_final)
    assert np.array_equal(base.messages, priced.messages)
    assert np.array_equal(base.node_sends, priced.node_sends)
    for u0, u1 in zip(base.edge_usage, priced.edge_usage):
        assert np.array_equal(u0, u1)
    assert base.cost is None
    assert priced.cost is not None
    assert np.array_equal(
        priced.cost.transmissions, base.messages.astype(np.float64))


def test_sampled_retransmissions_match_geometric_mean(setup):
    g, plan, x0 = setup
    p = 0.6
    # many trials, one schedule each: the per-trial sampled extras
    # should concentrate on T*(1-p)/p within a few percent
    seeds = tuple(range(24))
    res = _run(plan, x0, seeds=seeds, cost=CostModel(retransmit_p=p))
    want = expected_retransmissions(res.messages, p)
    got = res.cost.retransmissions
    assert np.all(got >= 0)
    rel = abs(got.mean() - want.mean()) / want.mean()
    assert rel < 0.05, (got.mean(), want.mean())


def test_closed_form_mode_is_exact(setup):
    g, plan, x0 = setup
    p = 0.8
    res = _run(plan, x0, cost=CostModel(retransmit_p=p, sample=False))
    np.testing.assert_allclose(
        res.cost.retransmissions,
        expected_retransmissions(res.messages, p),
    )
    # energy identity: hop_energy * (logical + retx) with no congestion
    np.testing.assert_allclose(
        res.cost.energy,
        res.cost.transmissions + res.cost.retransmissions,
    )


def test_hop_pricing_matches_route_incidence_totals(setup):
    """The engine's logical message count IS the route-priced total:
    sum over directed-edge slots of usage * 2 * hops (forward + reply
    legs), independently recomputed from the plan CSR."""
    g, plan, x0 = setup
    res = _run(plan, x0, options=ExecOptions(collect_usage=True))
    for li, (lp, usage) in enumerate(zip(plan.levels, res.edge_usage)):
        hops = np.asarray(lp.hop_flat, np.int64)
        for t in range(len(SEEDS)):
            priced = int((usage[t].astype(np.int64) * 2 * hops).sum())
            assert priced == int(res.level_messages[t, li]), (li, t)


def test_congestion_counts_concurrent_pairs(setup):
    """congestion_alpha scales a pure tally: doubling alpha doubles the
    congestion term and nothing else."""
    g, plan, x0 = setup
    a = _run(plan, x0, cost=CostModel(congestion_alpha=0.1, sample=False))
    b = _run(plan, x0, cost=CostModel(congestion_alpha=0.2, sample=False))
    np.testing.assert_allclose(2 * a.cost.congestion, b.cost.congestion)
    np.testing.assert_allclose(
        b.cost.energy - a.cost.energy, a.cost.congestion)


def test_price_messages_supersedes_handshake_cost():
    from repro.core import handshake_cost

    msgs = 10_000
    p = 0.5
    rng_a = np.random.default_rng(7)
    rng_b = np.random.default_rng(7)
    legacy = handshake_cost(msgs, p, rng_a)
    c = price_messages(msgs, CostModel(retransmit_p=p), rng_b)
    assert int(c.physical_transmissions[0]) == legacy
    exact = price_messages(msgs, CostModel(retransmit_p=p, sample=False))
    assert float(exact.retransmissions[0]) == msgs * (1 - p) / p


def test_legacy_flat_kwargs_removed(setup):
    """PR 9's one-release deprecation window has expired: the flat
    execute kwargs are gone, and a stale call fails loudly as a
    TypeError instead of silently warning."""
    g, plan, x0 = setup
    with pytest.raises(TypeError):
        _run(plan, x0, backend="lax", check_every=32)
    with pytest.raises(TypeError):
        _run(plan, x0, loss_p=0.9)
    with pytest.raises(TypeError):
        multiscale_gossip(
            g, x0, eps=1e-3, seed=0, trials=2, plan=plan, backend="lax",
        )


def test_multiscale_gossip_threads_options(setup):
    """options= reaches the engine: the explicit default matches the
    no-options call bitwise."""
    g, plan, x0 = setup
    new = multiscale_gossip(
        g, x0, eps=1e-3, seed=0, trials=2, plan=plan,
        options=ExecOptions(check_every=64),
    )
    default = multiscale_gossip(g, x0, eps=1e-3, seed=0, trials=2, plan=plan)
    assert np.array_equal(new.x_final, default.x_final)
    assert np.array_equal(new.messages, default.messages)


def test_churn_reduces_messages_and_degrades_error(setup):
    g, plan, x0 = setup
    base = _run(plan, x0)
    churned = _run(
        plan, x0,
        failures=FailureModel(churn_fraction=0.25, churn_time=0.25),
    )
    assert np.all(churned.messages < base.messages)
    assert churned.error(x0).mean() > base.error(x0).mean()


def test_byzantine_nodes_keep_initial_values(setup):
    """drop_fraction nodes never apply updates: their final estimate is
    exactly their initial value (V=1: the raw x0 entry)."""
    g, plan, x0 = setup
    fm = FailureModel(drop_fraction=0.2, seed=3)
    res = _run(plan, x0, failures=fm)
    byz = failure_sets(fm, N)["byz"]
    assert byz.sum() > 0
    # unweighted runs promote raw values, so a frozen node stays at x0
    np.testing.assert_array_equal(
        res.x_final[:, byz], np.broadcast_to(x0[byz], (len(SEEDS),
                                                       int(byz.sum()))))


def test_failure_sets_draw_order_is_stable():
    """Adding one scenario field must not reshuffle another's node set."""
    a = failure_sets(FailureModel(churn_fraction=0.2), 200)
    b = failure_sets(
        FailureModel(churn_fraction=0.2, drop_fraction=0.1), 200)
    np.testing.assert_array_equal(a["churned"], b["churned"])


def test_scenario_matrix_smoke(setup):
    g, plan, x0 = setup
    res = run_scenario_matrix(
        g, x0, scenario_matrix(), eps=1e-3, trials=2, seed=0,
        fixed_ticks_scale=0.25, plan=plan,
        cost=CostModel(retransmit_p=0.9),
    )
    names = [r.scenario.name for r in res]
    assert names == ["baseline", "churn", "stragglers", "regional",
                     "byzantine"]
    by = {r.scenario.name: r for r in res}
    for r in res:
        assert r.errors.shape == (2,)
        assert r.cost is not None and np.all(r.cost.energy > 0)
    # events hurt: every scenario is at least as bad as the baseline
    assert by["churn"].err_mean > by["baseline"].err_mean
    assert by["byzantine"].err_mean > by["baseline"].err_mean
    # eps-oracle mode rejects (event times are budget fractions)
    with pytest.raises(ValueError, match="fixed_ticks_scale"):
        run_scenario_matrix(g, x0, fixed_ticks_scale=0.0, plan=plan)


def test_scenario_executor_cache_keys_on_tick_budget(setup):
    """Scenario event ticks are baked into the trace as constants
    derived from maxt_levels, so a plan whose executor cache was primed
    at one fixed_ticks_scale must retrace — not silently reuse stale
    event times — when replayed at another budget (regression: the
    cache key used to omit maxt_levels)."""
    g, plan, x0 = setup
    fm = FailureModel(churn_fraction=0.25, churn_time=0.25)
    # fresh plan: the ground truth for the full-budget scenario run
    fresh = build_plan(g, k=2, seed=0)
    want = _run(fresh, x0, fixed_ticks_scale=1.0, failures=fm)
    # primed plan: a quarter-budget run populates the executor cache
    # with event ticks scaled to ITS maxt_levels first
    _run(plan, x0, fixed_ticks_scale=0.25, failures=fm)
    got = _run(plan, x0, fixed_ticks_scale=1.0, failures=fm)
    assert np.array_equal(want.x_final, got.x_final)
    assert np.array_equal(want.messages, got.messages)


def test_scenarios_reject_eps_oracle_mode(setup):
    """execute_plan itself (not just run_scenario_matrix) rejects
    scenario FailureModels in eps-oracle mode, where event times become
    fractions of the unbounded max_ticks_per_level cap and the scenario
    silently degenerates to the reliable run."""
    g, plan, x0 = setup
    with pytest.raises(ValueError, match="fixed_ticks_scale"):
        _run(plan, x0, fixed_ticks_scale=0.0,
             failures=FailureModel(churn_fraction=0.1))
    # loss_p alone is the legacy trajectory-level model, not a scenario:
    # it stays valid in eps-oracle mode
    _run(plan, x0, fixed_ticks_scale=0.0, eps=1e-2,
         failures=FailureModel(loss_p=0.9))


def test_price_messages_requires_rng_when_sampling():
    with pytest.raises(ValueError, match="rng"):
        price_messages(100, CostModel(retransmit_p=0.5))
    # no draws happen at p=1 or with sample=False: rng stays optional
    assert price_messages(
        100, CostModel(retransmit_p=1.0)).retransmissions[0] == 0.0
    price_messages(100, CostModel(retransmit_p=0.5, sample=False))


def test_regional_window_coerced_and_validated():
    # lists (natural from JSON configs) coerce to a hashable tuple
    fm = FailureModel(regional_radius=0.2, regional_window=[0.25, 0.75])
    assert fm.regional_window == (0.25, 0.75)
    hash(fm)
    with pytest.raises(ValueError, match="regional_window"):
        FailureModel(regional_window=(0.75, 0.25))
    with pytest.raises(ValueError, match="regional_window"):
        FailureModel(regional_window=(-0.1, 0.5))
    with pytest.raises(ValueError, match="regional_window"):
        FailureModel(regional_window=(0.25,))


# ----------------- heterogeneous per-link loss/energy ------------------


def _overlay_edge_messages(plan, res, trial=0):
    """(lp, per-edge logical transmissions) for every overlay level."""
    out = []
    for li, lp in enumerate(plan.levels):
        if lp.kind != "overlay":
            continue
        out.append((lp, level_edge_messages(lp, res.edge_usage[li][trial])))
    assert out
    return out


def test_route_edge_transmissions_is_two_hops(setup):
    """The incidence scatter independently reproduces 2 * route hops
    per exchange (endpoints once, relays twice)."""
    g, plan, x0 = setup
    for lp in plan.levels:
        if lp.kind != "overlay":
            continue
        tx = route_edge_transmissions(lp)
        hops = np.asarray(lp.hop_flat, np.int64)[lp.edge_pos_i]
        np.testing.assert_array_equal(tx, 2 * hops)


def test_per_edge_messages_sum_to_level_total(setup):
    """Summing the per-edge breakdown recovers the engine's per-level
    logical message count exactly."""
    g, plan, x0 = setup
    res = _run(plan, x0, options=ExecOptions(collect_usage=True))
    for li, lp in enumerate(plan.levels):
        if lp.kind != "overlay":
            continue
        for t in range(len(SEEDS)):
            em = level_edge_messages(lp, res.edge_usage[li][t])
            assert int(em.sum()) == int(res.level_messages[t, li]), (li, t)


def test_per_edge_pricing_constant_tuple_matches_scalar(setup):
    """Parity: a constant per-edge tuple prices identically to the
    scalar model, and both match the homogeneous `price_messages` path
    on the summed count (with loss folded into the delivery p)."""
    g, plan, x0 = setup
    res = _run(plan, x0, options=ExecOptions(collect_usage=True))
    lp, em = _overlay_edge_messages(plan, res)[0]
    E = len(em)
    hop, retx_p, loss = 1.5, 0.8, 0.9
    scalar = price_edge_messages(
        em, CostModel(hop_energy=hop, retransmit_p=retx_p, sample=False),
        FailureModel(loss_p=loss),
    )
    tupled = price_edge_messages(
        em,
        CostModel(hop_energy=(hop,) * E, retransmit_p=retx_p, sample=False),
        FailureModel(loss_p=(loss,) * E),
    )
    np.testing.assert_allclose(tupled.energy, scalar.energy)
    np.testing.assert_allclose(tupled.retransmissions, scalar.retransmissions)
    np.testing.assert_array_equal(tupled.transmissions, scalar.transmissions)
    homo = price_messages(
        int(em.sum()),
        CostModel(hop_energy=hop, retransmit_p=retx_p * loss, sample=False),
    )
    np.testing.assert_allclose(scalar.energy, homo.energy, rtol=1e-12)


def test_per_edge_heterogeneity_is_local(setup):
    """Doubling ONE edge's hop_energy adds exactly that edge's base
    energy — per-edge pricing is a local, decomposable sum."""
    g, plan, x0 = setup
    res = _run(plan, x0, options=ExecOptions(collect_usage=True))
    lp, em = _overlay_edge_messages(plan, res)[0]
    e = int(np.argmax(em))
    assert em[e] > 0
    base = price_edge_messages(
        em, CostModel(hop_energy=(1.0,) * len(em), sample=False))
    he = [1.0] * len(em)
    he[e] = 2.0
    bumped = price_edge_messages(
        em, CostModel(hop_energy=tuple(he), sample=False))
    np.testing.assert_allclose(
        bumped.energy - base.energy, base.level_energy[:, e])


def test_heterogeneous_models_are_closed_form_only(setup):
    """Per-edge tuples coerce/hash like regional_window, but every
    schedule-level consumer rejects them with a pointer at the
    closed-form path."""
    g, plan, x0 = setup
    fm = FailureModel(loss_p=[0.9, 0.8])        # list coerces to tuple
    cm = CostModel(hop_energy=[1.0, 2.0], sample=False)
    assert fm.loss_p == (0.9, 0.8) and fm.heterogeneous
    assert cm.hop_energy == (1.0, 2.0) and cm.heterogeneous
    hash((fm, cm))
    with pytest.raises(ValueError, match="price_edge_messages"):
        _run(plan, x0, failures=fm)
    with pytest.raises(ValueError, match="price_edge_messages"):
        _run(plan, x0, cost=cm)
    with pytest.raises(ValueError, match="price_edge_messages"):
        price_messages(100, cm)
    # per-edge sampling has no schedule: sample=True models are rejected
    with pytest.raises(ValueError, match="sample"):
        price_edge_messages(
            np.ones(2, np.int64), CostModel(hop_energy=(1.0, 2.0),
                                            retransmit_p=0.5))
    # entry validation mirrors the scalar paths
    with pytest.raises(ValueError, match="loss_p"):
        FailureModel(loss_p=(0.9, 0.0))
    with pytest.raises(ValueError):
        CostModel(hop_energy=(1.0, -2.0))
    with pytest.raises(ValueError, match="edges"):
        price_edge_messages(
            np.ones(3, np.int64),
            CostModel(hop_energy=(1.0, 2.0), sample=False))


def test_dataclass_validation():
    with pytest.raises(ValueError):
        CostModel(retransmit_p=0.0)
    with pytest.raises(ValueError):
        CostModel(hop_energy=-1.0)
    with pytest.raises(ValueError):
        FailureModel(churn_fraction=1.5)
    with pytest.raises(ValueError):
        FailureModel(loss_p=0.0)
    with pytest.raises(ValueError):
        ExecOptions(check_every=0)
    # all three are hashable (compiled-executor cache keys)
    hash((ExecOptions(), FailureModel(), CostModel()))
