"""The million-node fixed-iterations deployment (`rgg1m-fixed`).

* the reference's hierarchy against the program's plan at the
  deployment's own size, n=10^6, table for table (about a minute and
  3 GB on the host; the other cases run at n=3000);
* its `x_gap` limit: the control (the reference replayed in bfloat16)
  fails it and the program passes it, and a planted fault fails a run;
* the three set-up readers of its cell: a number from the program's
  event totals, nothing (and no error) from a program without them.

    python -m pytest bench/tests/test_rgg1m_fixed.py
"""
from __future__ import annotations

import json
import os
import types

import jax
import numpy as np
import pytest

from bench import control, deploy, hierarchy, run
from bench.tests.helpers import run_main, tiny_cell
from bench.tests.test_correctness import (_broken, _count_altered,
                                          _unchanged, _value_altered)
from bench.tests.test_hierarchy import TABLES

READERS = ("setup_lower_s", "setup_compile_s", "executor_const_mb")


def _config() -> dict:
    with open(os.path.join(run.BENCH, "configs", "rgg1m-fixed.json")) as f:
        return json.load(f)


def _cell() -> dict:
    """The cell at n=3000 on the deployment's field shape."""
    cell = tiny_cell("rgg1m-fixed", "t1", 3000, 1)
    cell["config"]["graph_seed"] = 1003000
    return cell


def test_hierarchy_matches_the_program_plan_at_a_million(monkeypatch,
                                                         tmp_path):
    monkeypatch.setattr(deploy, "PLAN_CACHE", str(tmp_path))
    cfg = _config()
    assert cfg["n"] == 10**6
    h = hierarchy.build(cfg)
    plan = deploy.setup_plan(cfg)
    assert [lv.node_mask.shape for lv in h.levels] == [
        (337504, 13), (89996, 4), (22500, 4), (2500, 9), (100, 25),
        (1, 100)]
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


def test_control_fails_and_program_passes(monkeypatch, tmp_path):
    monkeypatch.setattr(deploy, "PLAN_CACHE", str(tmp_path / "plans"))
    cell = _cell()
    limits = cell["config"]["limits"]
    assert limits == _config()["limits"]
    for row in control.readings(cell, [5, 6, 7], jax.devices()[:1]):
        assert row["program"]["x_gap"] <= limits["x_gap"]
        assert row["program"]["count_diff"] == 0
        assert row["control"]["x_gap"] > limits["x_gap"]


@pytest.mark.parametrize("fault", [_unchanged, _value_altered,
                                   _count_altered])
def test_fault_makes_run_incorrect(monkeypatch, tmp_path, fault):
    _broken(monkeypatch, fault)
    out = run_main(monkeypatch, tmp_path, _cell())
    assert out["correct"] is False
    assert out["failed"] > 0


def test_readers_read_the_programs_event_totals(monkeypatch, tmp_path):
    cell = _cell()
    assert [m["name"] for m in cell["per_layer"]] == list(READERS)
    out = run_main(monkeypatch, tmp_path, cell, trace=1)
    assert out["correct"] is True
    assert list(out["metrics"]) == list(READERS)
    for name in READERS:
        assert out["metrics"][name]["value"] > 0


def test_readers_read_nothing_from_a_program_without_totals(monkeypatch,
                                                            tmp_path):
    core, enable = deploy.program()
    stub = types.SimpleNamespace(
        **{k: getattr(core, k) for k in dir(core)
           if not k.startswith("_") and k != "event_totals"})
    monkeypatch.setattr(deploy, "program", lambda: (stub, enable))
    for name in READERS:
        assert run.reader("layers", name)({}) is None
    out = run_main(monkeypatch, tmp_path, _cell(), trace=1)
    assert out["correct"] is True
    assert out["metrics"] == {}
