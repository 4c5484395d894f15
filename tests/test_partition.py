"""Partition invariants (paper §III, §V)."""
import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core import auto_levels, build_partition, random_geometric_graph


def test_auto_levels_matches_paper_regime():
    # paper §VI-A: ~4-5 levels suffice for n = 5000
    assert auto_levels(5000) in (4, 5, 6)
    # slow growth: Theta(log log n)
    assert auto_levels(100) <= auto_levels(10_000) <= auto_levels(10_000_000)
    assert auto_levels(10_000_000) - auto_levels(100) <= 4
    assert auto_levels(5) == 1  # tiny network: single level


def test_sides_multiplicative_and_refining():
    p = build_partition(5000)
    assert p.sides[0] == 1
    for a, b in zip(p.sides, p.sides[1:]):
        assert b % a == 0 and b // a >= 2  # strict refinement


def test_cell_of_tiles_unit_square():
    p = build_partition(2000)
    coords = np.random.default_rng(0).uniform(0, 1, (2000, 2))
    for level in range(1, p.k + 1):
        c = p.cell_of(coords, level)
        assert c.min() >= 0 and c.max() < p.num_cells(level)
    # boundary coordinates clamp into range
    edge = np.array([[0.0, 0.0], [1.0, 1.0], [0.5, 1.0]])
    c = p.cell_of(edge, p.k)
    assert (c >= 0).all() and (c < p.num_cells(p.k)).all()


def test_parent_consistency():
    p = build_partition(3000)
    coords = np.random.default_rng(1).uniform(0, 1, (1000, 2))
    for level in range(2, p.k + 1):
        child = p.cell_of(coords, level)
        parent = p.cell_of(coords, level - 1)
        assert (p.parent_cell(level, child) == parent).all()


def test_child_grid_edges_adjacent_same_parent():
    p = build_partition(3000)
    for j in range(1, p.k):
        s = p.sides[j]  # child side
        edges = p.child_grid_edges(j)
        assert len(edges)
        r_u, c_u = edges[:, 0] // s, edges[:, 0] % s
        r_v, c_v = edges[:, 1] // s, edges[:, 1] % s
        manhattan = np.abs(r_u - r_v) + np.abs(c_u - c_v)
        assert (manhattan == 1).all()  # N/S/E/W adjacency
        assert (
            p.parent_cell(j + 1, edges[:, 0]) == p.parent_cell(j + 1, edges[:, 1])
        ).all()


def test_cell_centers_inside_cells():
    p = build_partition(1500)
    cells = np.arange(p.num_cells(p.k))
    centers = p.cell_center(p.k, cells)
    assert (p.cell_of(centers, p.k) == cells).all()


@given(
    n=st.integers(min_value=10, max_value=500_000),
    a=st.floats(min_value=0.55, max_value=0.8),
)
def test_partition_properties(n, a):
    p = build_partition(n, a=a)
    assert p.k >= 1 and p.sides[0] == 1
    # finest cells stay small (bounded occupancy, paper Thm 1 part 2);
    # rounding of split factors makes this approximate
    assert p.expected_cell_size(p.k) <= 4 * 8.0
    # and never degenerate below a fraction of a node on average
    assert p.expected_cell_size(p.k) > 0.1


def test_paper_scaling_of_finest_cells():
    # subnetworks at scale j hold O(n^((2/3)^j)) nodes: check the finest
    # level against the closed form within rounding slack
    for n in (1000, 5000, 20000):
        p = build_partition(n)
        expected = n ** ((2.0 / 3.0) ** (p.k - 1))
        assert p.expected_cell_size(p.k) <= 6 * expected
