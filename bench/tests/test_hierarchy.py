"""The reference's own hierarchy against the program's plan.

`bench.hierarchy` builds every table the replay uses from the
configuration alone.  Where the two agree table for table, a fault in
the program's planning (cells, neighbour order, grids, routes,
representatives, promotion or read-out) would show here by name before
it shows as a count or value gap in a run.

    python -m pytest bench/tests
"""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import deploy, hierarchy, run

# (n, graph_seed, c): the paper's field, the large-n field's shape at
# small n, and sparse fields whose greedy routes fall back to
# breadth-first paths and whose cells and grids come apart
FIELDS = [(300, 7, 3.0), (2000, 100, 3.0), (3000, 1003000, 3.0),
          (3000, 6, 0.5), (3000, 9, 0.4)]

TABLES = {"start": "nbr_start", "nbr": "nbr_flat", "hops": "hop_flat",
          "degrees": "degrees", "n_nodes": "n_nodes",
          "node_mask": "node_mask", "slot_node": "slot_node",
          "row_node": "row_node", "partner_flat": "partner_flat",
          "edge_pos_i": "edge_pos_i", "edge_pos_j": "edge_pos_j",
          "inc_node": "inc_node", "inc_edge": "inc_edge",
          "inc_count": "inc_count", "rep_slot": "rep_slot",
          "line16": "line16", "next_graph": "next_graph",
          "next_slot": "next_slot"}


@pytest.mark.parametrize("n,graph_seed,c", FIELDS)
def test_hierarchy_matches_the_program_plan(monkeypatch, tmp_path, n,
                                            graph_seed, c):
    monkeypatch.setattr(deploy, "PLAN_CACHE", str(tmp_path))
    with open(os.path.join(run.BENCH, "configs", "rgg2k-paper.json")) as f:
        cfg = json.load(f)
    cfg.update(n=n, graph_seed=graph_seed, c=c)
    h = hierarchy.build(cfg)
    plan = deploy.setup_plan(cfg)
    assert len(h.levels) == len(plan.levels)
    for li, (mine, theirs) in enumerate(zip(h.levels, plan.levels)):
        assert mine.kind == theirs.kind
        for field, attr in TABLES.items():
            a, b = getattr(mine, field), getattr(theirs, attr)
            if a is None or b is None:
                assert a is None and b is None, (li, field)
            else:
                np.testing.assert_array_equal(a, b, err_msg=f"level {li} "
                                              f"{field}")
    np.testing.assert_array_equal(h.final_graph, plan.final_graph)
    np.testing.assert_array_equal(h.final_slot, plan.final_slot)
    assert h.disseminate == plan.disseminate


def test_levels_of_the_deployments():
    assert hierarchy.levels_of(2000, None, 2 / 3, 8.0) == [1, 4, 8, 16, 32]
    assert hierarchy.levels_of(10**6, None, 2 / 3, 8.0) == \
        [1, 10, 50, 150, 300, 600]
