"""Gossip engine invariants: mass conservation, convergence, message
accounting, failure semantics."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import batched_graphs, gossip_until, random_geometric_graph


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


def test_mass_conservation_reliable():
    g = random_geometric_graph(200, seed=2)
    x0 = np.random.default_rng(0).normal(0, 1, 200).astype(np.float32)
    res = gossip_until(
        x0[None, :], g.neighbors[None], g.degrees[None],
        np.array([200], np.int32), eps=1e-5, seed=0,
    )
    assert res.converged.all()
    # pairwise averaging preserves the sum exactly (up to f32 rounding)
    np.testing.assert_allclose(res.x[0, :, 0].sum(), x0.sum(), rtol=1e-4, atol=1e-3)


def test_convergence_to_mean():
    g = random_geometric_graph(300, seed=3)
    x0 = np.random.default_rng(1).normal(0, 1, 300).astype(np.float32)
    res = gossip_until(
        x0[None, :], g.neighbors[None], g.degrees[None],
        np.array([300], np.int32), eps=1e-4, seed=1,
    )
    est = res.estimates()[0]
    assert np.linalg.norm(est - x0.mean()) <= 1.1e-4 * np.linalg.norm(x0) + 1e-5


def test_batched_independent_convergence():
    gs = [_ring(8), _ring(32), _ring(64)]
    neighbors, degrees, n_nodes, mask = batched_graphs(gs)
    rng = np.random.default_rng(2)
    x0 = np.where(mask, rng.normal(0, 1, mask.shape), 0.0).astype(np.float32)
    res = gossip_until(x0, neighbors, degrees, n_nodes, eps=1e-3, seed=2)
    assert res.converged.all()
    # smaller rings must not pay for the biggest ring's convergence
    assert res.ticks[0] <= res.ticks[2]
    for b, g in enumerate(gs):
        m = x0[b, : g.n].mean()
        d = res.x[b, : g.n, 0] - m
        assert np.linalg.norm(d) <= 1.1e-3 * np.linalg.norm(x0[b, : g.n]) + 1e-6


def test_message_accounting_matches_usage():
    g = _ring(16)
    x0 = np.random.default_rng(3).normal(0, 1, 16).astype(np.float32)
    hops = np.full((1, 16, 2), 3, np.int32)
    res = gossip_until(
        x0[None], g.neighbors[None], g.degrees[None],
        np.array([16], np.int32), eps=1e-3, seed=3, edge_hops=hops,
    )
    assert res.messages[0] == 2 * 3 * res.edge_usage[0].sum()


def test_fixed_ticks_exact_budget():
    g = _ring(16)
    x0 = np.random.default_rng(4).normal(0, 1, 16).astype(np.float32)
    res = gossip_until(
        x0[None], g.neighbors[None], g.degrees[None],
        np.array([16], np.int32), eps=1e-3, seed=4, fixed_ticks=100,
    )
    # budget padded up to the check_every multiple
    assert res.ticks[0] >= 100
    assert res.edge_usage[0].sum() == res.ticks[0]


def test_weighted_channels_ratio():
    g = _ring(32)
    rng = np.random.default_rng(5)
    x = rng.normal(0, 1, 32).astype(np.float32)
    w = rng.uniform(0.5, 2.0, 32).astype(np.float32)
    x0 = np.stack([x * w, w], axis=-1)
    res = gossip_until(
        x0[None], g.neighbors[None], g.degrees[None],
        np.array([32], np.int32), eps=1e-6, seed=5,
    )
    est = res.estimates()[0]
    expected = (x * w).sum() / w.sum()
    np.testing.assert_allclose(est, expected, rtol=1e-3, atol=1e-5)


def test_loss_p_one_equals_reliable():
    g = _ring(24)
    x0 = np.random.default_rng(6).normal(0, 1, 24).astype(np.float32)[None]
    a = gossip_until(
        x0, g.neighbors[None], g.degrees[None], np.array([24], np.int32),
        eps=1e-4, seed=6,
    )
    b = gossip_until(
        x0, g.neighbors[None], g.degrees[None], np.array([24], np.int32),
        eps=1e-4, seed=6, loss_p=1.0,
    )
    np.testing.assert_array_equal(a.x, b.x)
    assert a.messages[0] == b.messages[0]


def test_loss_model_distorts_mass():
    g = _ring(64)
    x0 = np.random.default_rng(7).normal(0, 1, 64).astype(np.float32)
    res = gossip_until(
        x0[None], g.neighbors[None], g.degrees[None],
        np.array([64], np.int32), eps=1e-6, seed=7, loss_p=0.5,
        fixed_ticks=2000,
    )
    # under heavy loss the sum drifts (paper §VI-C-2: signal energy lost)
    assert abs(res.x[0, :, 0].sum() - x0.sum()) > 1e-4
    # and each exchange costs at most the reliable 2 hops
    assert res.messages[0] <= 2 * res.edge_usage[0].sum()


@given(seed=st.integers(0, 2**16), n=st.sampled_from([5, 17, 40]))
@settings(max_examples=10)
def test_property_mass_conserved(seed, n):
    g = _ring(n)
    rng = np.random.default_rng(seed)
    x0 = rng.normal(0, 1, n).astype(np.float32)
    res = gossip_until(
        x0[None], g.neighbors[None], g.degrees[None],
        np.array([n], np.int32), eps=-1.0, seed=seed, fixed_ticks=256,
    )
    np.testing.assert_allclose(
        res.x[0, :, 0].sum(), x0.sum(), rtol=2e-4, atol=2e-3
    )


@given(seed=st.integers(0, 2**16))
@settings(max_examples=10)
def test_property_values_stay_in_convex_hull(seed):
    n = 20
    g = _ring(n)
    rng = np.random.default_rng(seed)
    x0 = rng.normal(0, 1, n).astype(np.float32)
    res = gossip_until(
        x0[None], g.neighbors[None], g.degrees[None],
        np.array([n], np.int32), eps=-1.0, seed=seed, fixed_ticks=128,
    )
    x = res.x[0, :, 0]
    assert x.min() >= x0.min() - 1e-5 and x.max() <= x0.max() + 1e-5
