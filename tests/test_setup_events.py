"""What set-up and an executor build record, and the fixed-iterations
run they set up.

`setup_plan` names its steps as profiler spans and duration events; an
executor build records, per level, the bytes of plan arrays it bakes in
as constants and, in fixed-iterations (FI) mode, the level's tick
budget; `event_totals()` keeps the running totals.  Recording changes
nothing the run computes: FI results are pinned bitwise, and the
million-node deployment's field shape at n=3000 matches the plain
float64 reference (`bench.reference` over `bench.hierarchy`)."""
import collections
import glob
import hashlib
import json
import os

import jax
import numpy as np
import pytest

from repro.core import (ExecOptions, build_plan, event_totals, execute_plan,
                        random_geometric_graph, setup_plan)
from repro.core.engine import _level_consts, _usage_count, fi_ticks

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def plan300():
    return build_plan(random_geometric_graph(300, seed=7), seed=0)


@pytest.fixture(scope="module")
def x300():
    return np.random.default_rng(0).standard_normal(300).astype(np.float32)


class _Events:
    """The `/repro/core/` events recorded inside the block: durations as
    [(name, secs)], others as [(name, attributes)]."""

    def __enter__(self):
        self.durations, self.events = [], []

        def on_duration(event, secs, **kw):
            if event.startswith("/repro/core/"):
                self.durations.append((event, secs))

        def on_event(event, **kw):
            if event.startswith("/repro/core/"):
                self.events.append((event, kw))

        self._listeners = (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def __exit__(self, *exc):
        on_duration, on_event = self._listeners
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)

    def named(self, name) -> list:
        return [kw for e, kw in self.events if e == name]


def _setup_spans(trace_dir) -> collections.Counter:
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return collections.Counter(
        e.name for plane in pd.planes if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
        if e.name.startswith("repro.setup_plan"))


@pytest.mark.parametrize("mode,events,spans", [
    ("miss", ["graph_build", "plan_build", "plan_store"],
     ["", ".load", ".graph", ".plan", ".store"]),
    ("hit", ["plan_load"], ["", ".load"]),
    ("off", ["graph_build", "plan_build"], ["", ".graph", ".plan"]),
])
def test_setup_plan_records_its_steps(tmp_path, mode, events, spans):
    kw = dict(n=300, graph_seed=7, cache_dir=str(tmp_path / "plans"),
              use_cache=mode != "off")
    if mode == "hit":
        setup_plan(**kw)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        with _Events() as rec:
            _, info = setup_plan(**kw)
    finally:
        jax.profiler.stop_trace()
    assert info["cache"] == mode
    assert [e for e, _ in rec.durations] == \
        [f"/repro/core/{e}" for e in events]
    assert all(secs > 0 for _, secs in rec.durations)
    assert _setup_spans(tmp_path / "trace") == \
        collections.Counter(f"repro.setup_plan{s}" for s in spans)


@pytest.mark.parametrize("scale", [0.2, 0.0], ids=["fixed", "eps"])
def test_an_fi_build_records_each_levels_fixed_ticks(plan300, x300, scale):
    plan300.exec_cache.clear()
    with _Events() as rec:
        execute_plan(plan300, x300, eps=1e-3, seeds=(1,), weighted=True,
                     fixed_ticks_scale=scale)
        miss = rec.named("/repro/core/fixed_ticks")
        res = execute_plan(plan300, x300, eps=1e-3, seeds=(2,),
                           weighted=True, fixed_ticks_scale=scale)
    assert rec.named("/repro/core/fixed_ticks") == miss   # a hit: none
    if scale == 0.0:
        assert miss == []
        return
    check_every = ExecOptions().check_every
    want = []
    for li, lp in enumerate(plan300.levels):
        fixed = fi_ticks(int(lp.n_nodes.max()), 1e-3, scale,
                         quadratic=lp.kind == "overlay")
        chk = min(check_every, fixed)
        want.append({"level": li, "ticks": -(-fixed // chk) * chk,
                     "check": chk})
    assert miss == want
    np.testing.assert_array_equal(res.level_ticks[0],
                                  [w["ticks"] for w in want])


def test_a_build_records_each_levels_constant_bytes(plan300, x300):
    plan300.exec_cache.clear()
    with _Events() as rec:
        execute_plan(plan300, x300, eps=1e-3, seeds=(1, 2))
        miss = rec.named("/repro/core/executor_consts")
        execute_plan(plan300, x300, eps=1e-3, seeds=(3, 4))
    want = [{"level": li, "bytes": sum(
        a.nbytes for a in jax.tree.leaves(
            _level_consts(lp, _usage_count(lp, False))))}
        for li, lp in enumerate(plan300.levels)]
    assert miss == want
    assert all(w["bytes"] > 0 for w in want)
    assert rec.named("/repro/core/executor_consts") == miss


def test_event_totals_sum_durations_and_bytes(plan300, x300, tmp_path):
    plan300.exec_cache.clear()
    before = event_totals()
    with _Events() as rec:
        setup_plan(n=300, graph_seed=7, cache_dir=str(tmp_path))
        execute_plan(plan300, x300, eps=1e-3, seeds=(1,))
        execute_plan(plan300, x300, eps=1e-3, seeds=(1,),
                     fixed_ticks_scale=0.2)
    after = event_totals()
    delta = {k: v - before.get(k, 0) for k, v in after.items()
             if v != before.get(k, 0)}
    secs = collections.defaultdict(float)
    for name, s in rec.durations:
        secs[name] += s
    nbytes = collections.defaultdict(int)
    for name, kw in rec.events:
        if "bytes" in kw:
            nbytes[name] += kw["bytes"]
    assert set(secs) == {"/repro/core/graph_build", "/repro/core/plan_build",
                         "/repro/core/plan_store",
                         "/repro/core/executor_lower",
                         "/repro/core/executor_compile"}
    assert set(nbytes) == {"/repro/core/executor_consts"}
    assert set(delta) == set(secs) | set(nbytes)
    for name, s in secs.items():
        assert delta[name] == pytest.approx(s)
    # two executors (eps and FI) of the same plan
    assert delta["/repro/core/executor_consts"] == \
        nbytes["/repro/core/executor_consts"]
    assert len(rec.named("/repro/core/executor_consts")) == \
        2 * len(plan300.levels)


def test_a_build_records_each_levels_value_pass_lookup():
    """The paper's deployment (n=2000, five levels: 875 cells of 8
    slots, then 256, 64 and 16 cells of 4 and one of 16): one event per
    level on a build, none on a hit, with the path `value_read_path`
    picks for the level's shape."""
    from repro.core.value_pass import value_read_path

    plan, _ = setup_plan(n=2000, c=3.0, graph_seed=100, a=2 / 3,
                         cell_max=8.0, seed=0, rep_mode="random",
                         use_cache=False)
    x0 = np.random.default_rng(1).standard_normal(2000).astype(np.float32)
    with _Events() as rec:
        execute_plan(plan, x0, eps=1e-2, seeds=(1, 2))
        miss = rec.named("/repro/core/value_pass_lookup")
        execute_plan(plan, x0, eps=1e-2, seeds=(3, 4))
    shapes = [lp.node_mask.shape for lp in plan.levels]
    assert shapes == [(875, 8), (256, 4), (64, 4), (16, 4), (1, 16)]
    paths = ["select", "select", "select", "select", "gather"]
    assert [value_read_path(*bc) for bc in shapes] == paths
    assert miss == [{"level": li, "path": p} for li, p in enumerate(paths)]
    assert rec.named("/repro/core/value_pass_lookup") == miss   # a hit


@pytest.mark.parametrize("collect_usage", [False, True],
                         ids=["default", "collect_usage"])
def test_a_build_records_each_levels_usage_count(plan300, x300,
                                                 collect_usage):
    """One event per level on a build, none on a hit: the cell level
    counts by slots and the overlay levels by edges, or every level by
    edges where the flat counters are read.  A slot count leaves the
    cell level's per-edge owner and partner ids out of the program."""
    plan300.exec_cache.clear()
    opts = ExecOptions(collect_usage=collect_usage)
    with _Events() as rec:
        execute_plan(plan300, x300, eps=1e-3, seeds=(1,), options=opts)
        miss = rec.named("/repro/core/usage_count")
        consts = rec.named("/repro/core/executor_consts")
        execute_plan(plan300, x300, eps=1e-3, seeds=(2,), options=opts)
    kinds = [lp.kind for lp in plan300.levels]
    assert kinds == ["cells", "overlay", "overlay", "overlay"]
    paths = ["edges"] * len(kinds) if collect_usage else \
        ["slots", "edges", "edges", "edges"]
    assert miss == [{"level": li, "path": p} for li, p in enumerate(paths)]
    assert rec.named("/repro/core/usage_count") == miss   # a hit
    base = plan300.levels[0]
    flat_ids = base.row_node.nbytes + base.partner_flat.nbytes
    assert flat_ids > 0
    edges0 = sum(a.nbytes for a in jax.tree.leaves(
        _level_consts(base, "edges")))
    assert consts[0]["bytes"] == (edges0 if collect_usage
                                  else edges0 - flat_ids)


def _digest(a: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


# FI results of the n=300 plan as computed before set-up and build
# events were recorded: the events change no value and no count
PINNED = {
    1: ("4abcbc469908579d", [9780], [[34, 32, 32, 56]], "109563b7433f6b1a"),
    2: ("593d9397bc8568b6", [9780, 9794],
        [[34, 32, 32, 56], [34, 32, 32, 56]], "cff6567645ff0ca9"),
}


@pytest.mark.parametrize("trials", [1, 2], ids=["T1", "T2"])
def test_fi_results_are_pinned_bitwise(plan300, x300, trials):
    res = execute_plan(plan300, x300, eps=1e-3,
                       seeds=tuple(range(11, 11 + trials)), weighted=True,
                       fixed_ticks_scale=0.2)
    x_final, messages, ticks, sends = PINNED[trials]
    assert res.messages.tolist() == messages
    assert res.level_ticks.tolist() == ticks
    assert _digest(res.node_sends) == sends
    assert _digest(res.x_final) == x_final


@pytest.fixture(scope="module")
def field3000():
    """The million-node deployment's configuration at n=3000: its plan,
    the reference's hierarchy built from the configuration alone, and
    the configuration."""
    from bench import hierarchy

    with open(os.path.join(ROOT, "bench", "configs",
                           "rgg1m-fixed.json")) as f:
        cfg = json.load(f)
    cfg.update(n=3000, graph_seed=1003000)
    plan, _ = setup_plan(
        n=cfg["n"], c=cfg["c"], graph_seed=cfg["graph_seed"], k=cfg["k"],
        a=cfg["a"], cell_max=cfg["cell_max"], seed=cfg["plan_seed"],
        rep_mode=cfg["rep_mode"], use_cache=False)
    return plan, hierarchy.build(cfg), cfg


@pytest.mark.parametrize("seed", [3000001501, 3000001502])
def test_fi_matches_the_plain_reference(field3000, seed):
    from bench import reference

    plan, h, cfg = field3000
    rng = np.random.default_rng(seed)
    x0 = rng.standard_normal(cfg["n"]).astype(np.float32)
    trial_seeds = [int(rng.integers(0, 2**31 - 1))]
    opts = ExecOptions()
    res = execute_plan(plan, x0, eps=cfg["eps"], seeds=trial_seeds,
                       weighted=cfg["weighted"],
                       fixed_ticks_scale=cfg["fixed_ticks_scale"],
                       options=opts)
    ref = reference.replay(
        h, x0, trial_seeds, weighted=cfg["weighted"], eps=cfg["eps"],
        fixed_ticks_scale=cfg["fixed_ticks_scale"],
        check_every=opts.check_every, max_ticks=opts.max_ticks_per_level,
        draw=reference.jax_uniforms(trial_seeds))
    np.testing.assert_array_equal(res.messages, ref.messages)
    np.testing.assert_array_equal(res.level_ticks, ref.level_ticks)
    np.testing.assert_array_equal(res.node_sends, ref.node_sends)
    numbers = reference.compare(res.x_final, res.messages, res.node_sends,
                                res.level_ticks, ref, x0)
    assert numbers == {"x_gap": numbers["x_gap"], "count_diff": 0}
    assert numbers["x_gap"] <= cfg["limits"]["x_gap"]
