"""The program's names read back from a trace (`bench.scopes`), the nine
readers built on them, and `bench.profile_cell` on the CPU.

    python -m pytest bench/tests
"""
from __future__ import annotations

import gzip
import os

import jax
import pytest

from bench import deploy, profile_cell, run
from bench import scopes as S
from bench import trace as T
from bench.tests.helpers import tiny_cell

DATA = os.path.join(os.path.dirname(__file__), "data")
PREP, BACK = S.SPANS
LOWER, COMPILE = S.EVENTS
VP = "jit(run_v)/level_1/convergence_check/while/body/value_pass/while/body"


def _run_dict():
    return {
        "events_s": {LOWER: 2.5, COMPILE: 1.25},
        "trace": {
            "call_s": [0.5, 1.0], "trials": 20,
            "layer_s": {0: {"schedule": 0.02, "value_pass": 0.04,
                            "accounting": 0.01, "convergence_check": 0.03,
                            "promote": 0.002},
                        1: {"schedule": 0.03, "value_pass": 0.01}},
            "span_host_s": {PREP: [0.003, 0.005], BACK: [0.01, 0.02]},
        },
    }


@pytest.mark.parametrize("name,expected", [
    ("schedule_ms_per_trial", 1e3 * 0.03 / 20),      # the busiest chip
    ("value_pass_ms_per_trial", 1e3 * 0.04 / 20),
    ("accounting_ms_per_trial", 1e3 * 0.01 / 20),
    ("check_ms_per_trial", 1e3 * 0.03 / 20),
    ("promote_ms_per_trial", 1e3 * 0.002 / 20),
    ("prepare_ms_per_call", 1e3 * 0.008 / 2),
    ("readback_ms_per_call", 1e3 * 0.03 / 2),
    ("executor_lower_s", 2.5),
    ("executor_compile_s", 1.25),
])
def test_reader_arithmetic(name, expected):
    assert run.reader("layers", name)(_run_dict()) == pytest.approx(expected)


NINE = ["schedule_ms_per_trial", "value_pass_ms_per_trial",
        "accounting_ms_per_trial", "check_ms_per_trial",
        "promote_ms_per_trial", "prepare_ms_per_call",
        "readback_ms_per_call", "executor_lower_s", "executor_compile_s"]


@pytest.mark.parametrize("name", NINE)
def test_readers_read_nothing_from_a_program_without_names(name):
    # what `bench.run` hands its readers, and what a program without
    # scopes, spans and events leaves in the keys they read
    bare = {"trace": {"call_s": [0.5], "trials": 10, "busy_s": {0: 0.1}}}
    empty = {"events_s": {}, "trace": {"call_s": [0.5], "trials": 10,
                                       "layer_s": {0: {}},
                                       "span_host_s": {}}}
    reader = run.reader("layers", name)
    assert reader(bare) is None and reader(empty) is None


def test_scope_of_reads_the_innermost_level_and_layer():
    assert S.scope_of(f"{VP}/select_n") == ("level_1", "value_pass")
    assert S.scope_of("jit(_run)/vmap(level_3)/promote/scatter") == \
        ("level_3", "promote")
    assert S.scope_of("jit(run_v)/final/accounting/add") == \
        ("final", "accounting")
    assert S.scope_of("jit(_threefry_seed)/concatenate") == (None, None)
    assert S.scope_of(None) == (None, None)


def test_an_op_without_a_name_takes_what_its_nested_ops_share():
    chunk = "jit(run_v)/level_1/convergence_check/while/body"
    ev = [
        (0.0, 10.0, "while.7", None),                  # the chunk loop
        (1.0, 2.0, "fusion.1", f"{chunk}/schedule/add"),
        (3.0, 8.0, "while.9", None),                   # the tick scan
        (4.0, 5.0, "fusion.2", f"{VP}/select_n"),
        (6.0, 7.0, "fusion.3", f"{VP}/select_n"),
        (9.0, 9.5, "and.4", f"{chunk}/convergence_check/and"),
        (11.0, 12.0, "copy.5", None),                  # nothing inside
        (12.0, 12.5, "fusion.6", "jit(_threefry_seed)/concatenate"),
    ]
    got = {name: (round(sec, 9), S.scope_of(path))
           for sec, path, name, _ in S.attribute(ev)}
    assert got == {
        "while.7": (3.5, ("level_1", "convergence_check")),
        "fusion.1": (1.0, ("level_1", "schedule")),
        "while.9": (3.0, ("level_1", "value_pass")),
        "fusion.2": (1.0, ("level_1", "value_pass")),
        "fusion.3": (1.0, ("level_1", "value_pass")),
        "and.4": (0.5, ("level_1", "convergence_check")),
        "copy.5": (1.0, (None, None)),
        "fusion.6": (0.5, (None, None)),
    }
    assert sum(v[0] for v in got.values()) == pytest.approx(11.5)


HLO = """HloModule jit_run_v

%fused_computation.49 (param_0: s32[8], param_1: s32[4]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  %reshape.2 = s32[4]{0} reshape(%param_1), metadata={op_name="jit(run_v)/level_0/convergence_check/while/body/accounting/convert_element_type"}
  ROOT %scatter.86 = s32[8]{0} scatter(%param_0, %reshape.2)
}

%fused_computation.50 (param_0: f32[8]) -> f32[8] {
  ROOT %copy.3 = f32[8]{0} copy(%param_0)
}

ENTRY %main.1 (p: s32[8]) -> s32[8] {
  %fusion.503 = s32[8]{0} fusion(%p, %q), kind=kCustom, calls=%fused_computation.49
  %fusion.504 = f32[8]{0} fusion(%r), kind=kLoop, calls=%fused_computation.50
  ROOT %add.1 = s32[8]{0} add(%fusion.503, %p), metadata={op_name="jit(run_v)/final/accounting/add"}
}
"""


def test_a_fusion_xla_made_in_a_loop_takes_its_fused_names():
    chunk = "jit(run_v)/level_0/convergence_check/while"
    assert S.fused_paths(HLO) == {
        "fusion.503": f"{chunk}/body/accounting/convert_element_type"}
    sc = S.Scoped(modules={0: T.merge([(0.0, 3.0)])},
                  ops={0: [(0.0, 1.0, "fusion.503", chunk),
                           (1.0, 2.0, "fusion.504", chunk),
                           (2.0, 3.0, "add.1", None)]},
                  spans=[])
    by_name = {n: s for n, s in S.reduce(sc, [0], [(0.0, 3.0)],
                                         hlo=HLO)["named_ops"]}
    # the loop's own name stands where nothing named was fused in, and
    # an op without any name stays unscoped
    assert by_name == pytest.approx({
        "level_0/accounting:fusion.503": 1.0,
        "level_0/convergence_check:fusion.504": 1.0,
        "unscoped:add.1": 1.0})
    no_hlo = S.reduce(sc, [0], [(0.0, 3.0)])["layer_s"][0]
    assert no_hlo == pytest.approx({"convergence_check": 2.0})


def _scoped():
    chunk = "jit(run_v)/level_0/convergence_check/while/body"
    return S.Scoped(
        modules={0: T.merge([(1.0, 3.0), (4.0, 9.0), (13.0, 14.0)])},
        ops={0: [(1.0, 3.0, "fusion.1", f"{chunk}/schedule/add"),
                 (4.0, 9.0, "while.2", None),
                 (4.0, 8.0, "fusion.3", f"{chunk}/value_pass/select_n"),
                 (8.2, 8.8, "and.6", f"{chunk}/convergence_check/and"),
                 (13.0, 14.0, "fusion.4", "jit(run_v)/final/promote/gather"),
                 (20.0, 21.0, "fusion.5", f"{chunk}/schedule/add")]},
        spans=[(0.0, 10.0, "bench.call", "python"),
               (0.5, 4.5, PREP, "python"),
               (4.5, 9.8, BACK, "python"),
               (3.2, 3.8, "$array.py:631 _value", "python"),
               (11.5, 15.0, "bench.call", "python"),
               (11.5, 12.5, PREP, "python"),
               (12.5, 15.0, BACK, "python")])


def test_reduce_splits_device_time_by_layer_and_host_time_by_span():
    red = S.reduce(_scoped(), [0], [(0.0, 10.0), (11.5, 15.0)])
    # fusion.5 ran after the window; while.2 is its chunk loop's control
    assert red["layer_s"] == {0: pytest.approx(
        {"schedule": 2.0, "value_pass": 4.0, "convergence_check": 1.0,
         "promote": 1.0})}
    assert red["level_layer_s"][0]["final/promote"] == pytest.approx(1.0)
    assert red["unscoped_s"] == {0: 0.0}
    busy = {PREP: [4.0 - 2.5, 1.0 - 0.0], BACK: [5.3 - 4.5, 2.5 - 1.0]}
    for span, host in busy.items():
        assert red["span_host_s"][span] == pytest.approx(host)
    assert red["named_ops"][0] == ["level_0/value_pass:fusion.3",
                                   pytest.approx(4.0)]
    assert dict(red["named_gaps"]) == pytest.approx({
        "outside the benchmark's spans": 4.0,          # between the calls
        f"bench.call / {PREP}": 1.0,
        f"bench.call / {PREP} / $array.py:631 _value": 1.0,
        f"bench.call / {BACK}": 1.0,
    })


@pytest.fixture(scope="module")
def unscoped(tmp_path_factory):
    """A recording of a program with no scopes or spans of its own."""
    path = tmp_path_factory.mktemp("trace") / "two_calls.xplane.pb"
    with gzip.open(os.path.join(DATA, "two_calls.xplane.pb.gz")) as src:
        path.write_bytes(src.read())
    return str(path)


def test_a_trace_without_the_programs_names_reads_nothing(unscoped):
    sc = S.load_xplane(unscoped)
    tr = T.load_xplane(unscoped)
    spans = T.bench_spans(tr, "bench.call")
    red = S.reduce(sc, [0], spans)
    assert red["layer_s"] == {0: {}} and red["span_host_s"] == {}
    # the proto and ProfileData read the same device time
    assert (abs(sc.modules[0] - tr.modules[0]) < 1e-8).all()
    assert red["unscoped_s"][0] == pytest.approx(sum(tr.ops[0].values()),
                                                 rel=1e-4)
    r = {"trace": {**T.reduce(tr, [0], spans), **red, "trials": 2}}
    for name in NINE[:7]:
        assert run.reader("layers", name)(r) is None


def test_profile_cell_on_the_cpu(monkeypatch, tmp_path):
    monkeypatch.setattr(deploy, "PLAN_CACHE", str(tmp_path / "plans"))
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(profile_cell, "COST_DIR", str(tmp_path / "cost"))
    cell = tiny_cell("rgg2k-paper", "t10", n=300, check_trials=8)
    save = tmp_path / "window"
    out = profile_cell.profile(cell, 3000000001, jax.devices()[:1],
                               save=str(save), cost_seconds=0.2)
    m = out["metrics"]
    assert set(NINE) <= set(m)
    # no TPU plane in a CPU trace: the device layers read nothing, the
    # host spans and build events do
    assert all(m[k] is None for k in NINE[:5])
    assert m["prepare_ms_per_call"] > 0 and m["readback_ms_per_call"] > 0
    assert m["executor_lower_s"] > 0 and m["executor_compile_s"] > 0
    assert m["executor_lower_s"] + m["executor_compile_s"] < \
        m["compile_s"] + 1.0
    assert gzip.open(f"{save}.xplane.pb.gz").read(4)
    assert "HloModule" in gzip.open(f"{save}.hlo.txt.gz", "rt").read(200)
    assert set(out["profiler_cost_trials_per_s"]) == {"off", "on",
                                                      "off_again"}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Two `bench.call`s of `rgg2k-paper.eps.t1` recorded on a TPU v5
    lite with the program's names (`bench.profile_cell --save-trace`),
    and the executor's compiled HLO."""
    path = tmp_path_factory.mktemp("trace") / "t1_scoped.xplane.pb"
    with gzip.open(os.path.join(DATA, "t1_scoped.xplane.pb.gz")) as src:
        path.write_bytes(src.read())
    with gzip.open(os.path.join(DATA, "t1_scoped.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    tr = T.load_xplane(str(path))
    spans = T.bench_spans(tr, "bench.call")
    return (S.load_xplane(str(path)), T.reduce(tr, [0], spans), spans, hlo)


def test_recorded_layers_hold_the_device_time(recorded):
    sc, base, spans, hlo = recorded
    red = S.reduce(sc, [0], spans, hlo)
    layers = red["layer_s"][0]
    assert set(layers) == set(S.LAYERS)
    busy = base["busy_s"][0]
    assert sum(layers.values()) + red["unscoped_s"][0] == \
        pytest.approx(busy, rel=0.01)
    assert sum(layers.values()) >= 0.99 * busy
    # the numbers as recorded (TPU v5 lite), in seconds over two trials
    assert layers == pytest.approx(
        {"schedule": 8.040e-3, "value_pass": 12.857e-3,
         "accounting": 2.637e-3, "convergence_check": 0.1564e-3,
         "promote": 0.3037e-3}, rel=1e-3)
    assert red["level_layer_s"][0]["level_4/value_pass"] == \
        pytest.approx(7.063e-3, rel=1e-3)


def test_recorded_whiles_without_a_name_take_their_bodies_scope(recorded):
    sc = recorded[0]
    got = {name: S.scope_of(path)
           for _, path, name, _ in S.attribute(sc.ops[0])
           if name.startswith("while")}
    # XLA's rewritten loops carry no tf_op: each level's chunk loop is
    # the check's control, its tick scan the value pass
    for li, (chunk, scan) in enumerate([(67, 77), (69, 78), (72, 79),
                                        (74, 80), (76, 81)]):
        assert got[f"while.{chunk}"] == (f"level_{li}", "convergence_check")
        assert got[f"while.{scan}"] == (f"level_{li}", "value_pass")
    assert all(path is None for *_, name, path in sc.ops[0]
               if name in ("while.67", "while.81"))


def test_recorded_host_spans_hold_the_host_time(recorded):
    sc, base, spans, hlo = recorded
    red = S.reduce(sc, [0], spans, hlo)
    host = [s - b for s, b in zip(base["call_s"], base["call_busy_s"])]
    prep, back = (red["span_host_s"][k] for k in S.SPANS)
    assert len(prep) == len(back) == len(spans) == 2
    assert sum(prep) + sum(back) >= 0.9 * sum(host)
    assert sum(prep) + sum(back) <= sum(host)
    assert prep == pytest.approx([2.904e-3, 2.015e-3], rel=1e-3)
    assert back == pytest.approx([6.473e-3, 6.528e-3], rel=1e-3)


def test_recorded_breakdown_names_levels_layers_and_spans(recorded):
    sc, base, spans, hlo = recorded
    red = S.reduce(sc, [0], spans, hlo)
    assert red["named_ops"][0][0] == "level_4/value_pass:while.81"
    for name, _ in red["named_ops"]:
        level, layer = name.split(":")[0].split("/")
        assert (level, layer) in {(f"level_{i}", y) for i in range(5)
                                  for y in S.LAYERS}
    # the benchmark's own breakdown times the same ops (it reads whole
    # nanoseconds and the whole trace, this picoseconds and the window)
    assert [s for _, s in red["named_ops"]] == pytest.approx(
        [s for _, s in base["device_ops"]], rel=5e-3)
    assert [op.split(":")[1] for op, _ in red["named_ops"]] == \
        [op for op, _ in base["device_ops"]]
    # gaps inside a call name the program's span; one lies in the
    # benchmark's own code around `execute_plan`
    labels = [g for g, _ in red["named_gaps"]]
    assert all(g.startswith("bench.call / ") for g in labels)
    assert sum("/ repro.execute_plan." in g for g in labels) >= 8
