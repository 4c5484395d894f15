"""Vectorized (CSR fast-path) plan builder vs the reference builder.

`build_plan(method="reference")` keeps the historical per-cell /
per-group loop construction; `method="vectorized"` (the default) is the
large-n rewrite.  The two must be BITWISE-interchangeable: every level's
CSR arrays, routes, and election outcomes identical — and therefore the
executed simulation (messages, usage counters, x) identical too,
whichever endpoint read the value pass takes.
"""
import numpy as np
import pytest

from repro.core import ExecOptions, build_plan, execute_plan, value_pass
from repro.core.plan import PLAN_METHODS

_LP_ARRAY_FIELDS = (
    "nbr_start", "nbr_flat", "hop_flat", "degrees", "n_nodes", "node_mask",
    "slot_node", "row_node", "partner_flat", "edge_b", "edge_i", "edge_si",
    "edge_j", "edge_sj", "edge_pos_i", "edge_pos_j", "inc_node", "inc_edge",
    "inc_count", "rep_slot", "rep_node", "line16", "next_graph", "next_slot",
)


def _plans(rgg500):
    return {m: build_plan(rgg500, seed=0, method=m) for m in PLAN_METHODS}


def test_plan_methods_bitwise_identical(rgg500):
    plans = _plans(rgg500)
    ref, vec = plans["reference"], plans["vectorized"]
    assert len(ref.levels) == len(vec.levels)
    for lr, lv in zip(ref.levels, vec.levels):
        assert (lr.level, lr.kind, lr.max_hops, lr.max_deg) == \
               (lv.level, lv.kind, lv.max_hops, lv.max_deg)
        for f in _LP_ARRAY_FIELDS:
            a, b = getattr(lr, f), getattr(lv, f)
            if a is None or b is None:
                assert a is b, (lr.level, f)
                continue
            np.testing.assert_array_equal(a, b, err_msg=f"L{lr.level}.{f}")
        if lr.routes is None:
            assert lv.routes is None
        else:
            np.testing.assert_array_equal(lr.routes.nodes, lv.routes.nodes)
            np.testing.assert_array_equal(lr.routes.hops, lv.routes.hops)
    np.testing.assert_array_equal(ref.rep_counts, vec.rep_counts)
    assert ref.disconnected_cells == vec.disconnected_cells
    np.testing.assert_array_equal(ref.final_graph, vec.final_graph)
    np.testing.assert_array_equal(ref.final_slot, vec.final_slot)
    # build_seconds carries the per-stage breakdown on both paths
    for plan in plans.values():
        assert set(plan.build_seconds) >= {
            "partition", "cells", "overlay", "routes", "incidence", "total"
        }


@pytest.mark.parametrize("read", ["lax", "gather"])
def test_plan_methods_execute_identically(rgg500, x0_500, read, monkeypatch):
    """fig3-sized end-to-end: messages, flat usage counters, and x are
    identical between the two builders under the presampled engine,
    with each level's endpoint read as its shape picks (``lax``) or
    forced to the gather."""
    if read == "gather":
        monkeypatch.setattr(value_pass, "value_read_path",
                            lambda B, C: "gather")
    plans = _plans(rgg500)
    results = {
        m: execute_plan(
            p, x0_500, eps=1e-4, seeds=(0,), weighted=True,
            options=ExecOptions(collect_usage=True),
        )
        for m, p in plans.items()
    }
    ref, vec = results["reference"], results["vectorized"]
    np.testing.assert_array_equal(ref.messages, vec.messages)
    np.testing.assert_array_equal(ref.x_final, vec.x_final)
    np.testing.assert_array_equal(ref.node_sends, vec.node_sends)
    np.testing.assert_array_equal(ref.level_ticks, vec.level_ticks)
    for ur, uv in zip(ref.edge_usage, vec.edge_usage):
        np.testing.assert_array_equal(ur, uv)


def test_plan_method_validation(rgg500):
    with pytest.raises(ValueError):
        build_plan(rgg500, seed=0, method="dense")
