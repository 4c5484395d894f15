"""The trace reduction, on hand-made intervals and on a small trace
recorded on a TPU v5 lite: two `bench.call`-annotated `execute_plan`
calls of one ε-oracle trial at n=300, gzipped.

    python -m pytest bench/tests
"""
from __future__ import annotations

import gzip
import os

import numpy as np
import pytest

from bench import trace as T

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "two_calls.xplane.pb.gz")


def test_merge_is_a_union():
    m = T.merge([(5, 6), (0, 2), (1, 3), (3, 4), (7, 7.5)])
    assert m.tolist() == [[0, 4], [5, 6], [7, 7.5]]


def test_busy_and_gaps_partition_a_window():
    m = T.merge([(1, 2), (3, 5), (8, 12)])
    lo, hi = 0.5, 10.0
    gaps = T.gaps_within(m, lo, hi)
    assert gaps == [(0.5, 1), (2, 3), (5, 8)]
    assert T.busy_within(m, lo, hi) == pytest.approx(1 + 2 + 2)
    assert T.busy_within(m, lo, hi) + sum(e - s for s, e in gaps) == \
        pytest.approx(hi - lo)


def test_self_times_subtract_nested_ops():
    ev = [(0.0, 10.0, "while"), (1.0, 3.0, "fusion"), (4.0, 5.0, "fusion"),
          (4.2, 4.7, "copy"), (11.0, 12.0, "fusion")]
    st = T.self_times(ev)
    assert st == pytest.approx({"while": 7.0, "fusion": 2.0 + 0.5 + 1.0,
                                "copy": 0.5})
    assert sum(st.values()) == pytest.approx(11.0)


def test_op_name_is_the_hlo_name():
    assert T.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        "fusion.12"
    assert T.op_name("while.3") == "while.3"


def test_label_names_the_bench_span_and_the_innermost_host_span():
    tr = T.Trace(modules={}, ops={}, spans=[
        (0.0, 10.0, "bench.window", "python"),
        (1.0, 4.0, "bench.call", "python"),
        (1.5, 3.5, "execute", "python"),
        (2.0, 3.0, "D2H", "worker"),
    ])
    assert T.label(tr, 2.5) == "bench.call / D2H"
    assert T.label(tr, 5.0) == "bench.window"
    assert T.label(tr, 11.0) == "outside the benchmark's spans"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "two_calls.xplane.pb"
    with gzip.open(FIXTURE, "rb") as src:
        path.write_bytes(src.read())
    return T.load_xplane(str(path))


def test_recorded_trace_has_one_chip_and_two_calls(recorded):
    assert list(recorded.modules) == [0]
    assert len(T.bench_spans(recorded, "bench.call")) == 2


def test_recorded_trace_reduction(recorded):
    spans = T.bench_spans(recorded, "bench.call")
    red = T.reduce(recorded, [0], spans)
    lo, hi = spans[0][0], spans[-1][1]
    assert red["window_s"] == pytest.approx(hi - lo)
    assert list(red["busy_s"]) == [0]
    busy = red["busy_s"][0]
    assert 0 < busy < red["window_s"]
    # the window is busy time plus idle gaps, and the calls' busy time
    # is the window's (nothing ran on the chip between the calls)
    idle = sum(e - s for s, e in T.gaps_within(recorded.modules[0], lo, hi))
    assert busy + idle == pytest.approx(red["window_s"])
    assert sum(red["call_busy_s"]) == pytest.approx(busy)
    for s, b in zip(red["call_s"], red["call_busy_s"]):
        assert 0 < b < s
    # op self times add up to the module time: nothing counted twice
    total_ops = sum(recorded.ops[0].values())
    modules = float(np.sum(recorded.modules[0][:, 1]
                           - recorded.modules[0][:, 0]))
    assert total_ops == pytest.approx(modules, rel=0.02)
    # the numbers as recorded (TPU v5 lite)
    assert busy == pytest.approx(3.33444e-3, rel=1e-4)
    assert red["window_s"] == pytest.approx(21.385179e-3, rel=1e-6)
    assert red["device_ops"][0][0] == "while.65"
    assert red["device_ops"] == sorted(red["device_ops"],
                                       key=lambda kv: -kv[1])
    assert all(g[0].startswith("bench.") for g in red["idle_gaps"])


def test_a_chip_without_a_plane_is_an_error(recorded):
    spans = T.bench_spans(recorded, "bench.call")
    with pytest.raises(RuntimeError, match="no device plane"):
        T.reduce(recorded, [0, 1], spans)
    # off a TPU (the harness's CPU runs) the chip is left out instead
    red = T.reduce(recorded, [0, 1], spans, every_chip=False)
    assert list(red["busy_s"]) == [0]
