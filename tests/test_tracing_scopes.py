"""The executor's own names for its layers: every op the executor's
HLO carries an ``op_name`` for sits under one ``level_<i>`` (or
``final``) scope and one of `LAYER_SCOPES`; a profiler trace of
`execute_plan` holds its host spans; a cache miss records one lowering
and one compile event and one schedule-lookup event per level, a hit
none."""
import collections
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.core import build_plan, execute_plan, setup_plan, value_pass
from repro.core import random_geometric_graph
from repro.core.gossip import LAYER_SCOPES

_OP = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = \S+ ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^(?:\w+\()*|\)*$")   # vmap(level_0) -> level_0
_LEVEL = re.compile(r"level_\d+|final")


@pytest.fixture(scope="module")
def plan300():
    return build_plan(random_geometric_graph(300, seed=7), seed=0)


@pytest.fixture(scope="module")
def x300():
    return np.random.default_rng(0).standard_normal(300).astype(np.float32)


def _scopes(op_name: str) -> tuple:
    """(levels, layers) named on an op's name stack, outermost first."""
    parts = [_WRAPPED.sub("", p) for p in op_name.split("/")]
    return ([p for p in parts if _LEVEL.fullmatch(p)],
            [p for p in parts if p in LAYER_SCOPES])


def test_scopes_read_through_transform_wrappers():
    levels, layers = _scopes(
        "jit(_run)/vmap(level_2)/convergence_check/while/body/"
        "value_pass/while/body/closed_call/eq")
    assert levels == ["level_2"]
    assert layers == ["convergence_check", "value_pass"]
    assert _scopes("jit(run_v)/add") == ([], [])


@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weighted"])
@pytest.mark.parametrize("trials", [1, 3], ids=["T1", "T3"])
@pytest.mark.parametrize("read", ["lax", "gather"])
def test_every_executor_op_carries_one_layer_scope(plan300, x300, read,
                                                   trials, weighted,
                                                   monkeypatch):
    """Each level's endpoint read as its shape picks (``lax``), or every
    level's forced to the gather."""
    if read == "gather":
        monkeypatch.setattr(value_pass, "value_read_path",
                            lambda B, C: "gather")
    plan300.exec_cache.clear()
    execute_plan(plan300, x300, eps=1e-3, seeds=tuple(range(trials)),
                 weighted=weighted)
    (fn,) = plan300.exec_cache.values()
    per_layer, unscoped, traced = collections.Counter(), [], 0
    for line in fn.as_text().splitlines():
        op = _OP.match(line)
        name = _OP_NAME.search(line)
        # parameters, tuple plumbing and what XLA makes itself (copies,
        # constants, rewritten reductions) carry no traced name
        if not op or not name or not name.group(1).startswith("jit("):
            continue
        traced += 1
        levels, layers = _scopes(name.group(1))
        if levels and layers:
            per_layer[layers[-1]] += 1
        else:
            unscoped.append(f"{op.group(1)}: {name.group(1)}")
    assert traced > 1000
    assert unscoped == []
    assert set(per_layer) == set(LAYER_SCOPES)
    plan300.exec_cache.clear()


def _host_spans(trace_dir) -> list:
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(e.start_ns, e.end_ns, e.name)
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


@pytest.mark.parametrize("cached", [True, False], ids=["hit", "miss"])
def test_a_trace_of_one_call_holds_its_host_spans(plan300, x300, tmp_path,
                                                  cached):
    plan300.exec_cache.clear()
    if cached:
        execute_plan(plan300, x300, eps=1e-3, seeds=(1,))
    jax.profiler.start_trace(str(tmp_path))
    try:
        execute_plan(plan300, x300, eps=1e-3, seeds=(2,))
    finally:
        jax.profiler.stop_trace()
    spans = collections.defaultdict(list)
    for s, e, name in _host_spans(tmp_path):
        if name.startswith("repro.execute_plan"):
            spans[name].append((s, e))
    build = {"repro.execute_plan.build", "repro.execute_plan.build.lower",
             "repro.execute_plan.build.compile"}
    expected = {"repro.execute_plan", "repro.execute_plan.prepare",
                "repro.execute_plan.readback"}
    assert set(spans) == (expected if cached else expected | build)
    assert all(len(v) == 1 for v in spans.values())
    (call,) = spans["repro.execute_plan"]
    (prep,) = spans["repro.execute_plan.prepare"]
    (back,) = spans["repro.execute_plan.readback"]
    assert call[0] <= prep[0] <= prep[1] <= back[0] <= back[1] <= call[1]
    if not cached:
        (lower,) = spans["repro.execute_plan.build.lower"]
        (comp,) = spans["repro.execute_plan.build.compile"]
        assert prep[0] <= lower[0] <= lower[1] <= comp[0] <= comp[1] <= prep[1]


def test_a_cache_miss_records_one_lower_and_one_compile_event(plan300, x300):
    events = []

    def listen(event, secs, **kw):
        if event.startswith("/repro/core/"):
            events.append((event, secs))

    plan300.exec_cache.clear()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        execute_plan(plan300, x300, eps=1e-3, seeds=(1, 2))
        miss = list(events)
        execute_plan(plan300, x300, eps=1e-3, seeds=(3, 4))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert [e for e, _ in miss] == ["/repro/core/executor_lower",
                                    "/repro/core/executor_compile"]
    assert all(secs > 0 for _, secs in miss)
    assert events == miss          # the hit recorded nothing


def test_a_build_records_each_levels_schedule_lookup():
    """The paper's deployment (n=2000, five levels): every level reads
    partners by the select; hop counts are uniform on the cells and the
    first overlay."""
    plan, _ = setup_plan(n=2000, c=3.0, graph_seed=100, a=2 / 3,
                         cell_max=8.0, seed=0, rep_mode="random",
                         use_cache=False)
    x0 = np.random.default_rng(1).standard_normal(2000).astype(np.float32)
    events = []

    def listen(event, **kw):
        if event == "/repro/core/schedule_lookup":
            events.append(kw)

    jax.monitoring.register_event_listener(listen)
    try:
        execute_plan(plan, x0, eps=1e-2, seeds=(1,))
        miss = list(events)
        execute_plan(plan, x0, eps=1e-2, seeds=(2,))
    finally:
        jax.monitoring.unregister_event_listener(listen)
    hops = ["const", "const", "table", "table", "table"]
    assert miss == [{"level": li, "path": "select", "hops": h}
                    for li, h in enumerate(hops)]
    assert events == miss          # the hit recorded nothing
