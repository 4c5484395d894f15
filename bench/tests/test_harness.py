"""The harness on the CPU: metric arithmetic, the result line, and the
refusal to run without a TPU.

    python -m pytest bench/tests
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.tests.helpers import run_main, tiny_cell

ROOT = run.ROOT
REQUIRED = ["correct", "attempted", "failed", "metrics", "device"]


def _run_dict():
    calls = [{"start": 10.0, "end": 10.5, "trials": 10},
             {"start": 10.5, "end": 11.5, "trials": 10},
             {"start": 11.5, "end": 12.0, "trials": 10}]
    return {
        "calls": calls, "setup_s": 7.25, "plan_s": 1.5,
        "first_call_s": 9.0, "memory_peak_bytes": 2_500_000_000,
        "trace": {"call_s": [0.5, 1.0, 0.5], "call_busy_s": [0.4, 0.7, 0.3],
                  "busy_s": {0: 1.4, 1: 1.0}, "window_s": 2.0,
                  "trials": 30},
    }


@pytest.mark.parametrize("kind,name,expected", [
    ("metrics", "trials_per_s", 30 / 2.0),
    ("metrics", "call_s_p95", 0.95),
    ("metrics", "peak_hbm_gb", 2.5),
    ("metrics", "setup_s", 7.25),
    ("layers", "plan_s", 1.5),
    ("layers", "compile_s", 9.0 - 0.5),
    ("layers", "host_ms_per_call", 1e3 * (0.1 + 0.3 + 0.2) / 3),
    ("layers", "device_ms_per_trial", 1e3 * 1.4 / 30),
    ("layers", "device_idle_share", 100 * (1 - 1.2 / 2.0)),
])
def test_metric_arithmetic(kind, name, expected):
    assert run.reader(kind, name)(_run_dict()) == pytest.approx(expected)


@pytest.mark.parametrize("name", ["host_ms_per_call", "device_ms_per_trial",
                                  "device_idle_share"])
def test_device_readers_read_nothing_without_a_device_plane(name):
    d = _run_dict()
    d["trace"]["busy_s"] = {}
    assert run.reader("layers", name)(d) is None


def test_every_metric_has_its_reader():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for kind, key in (("metrics", "end_to_end"), ("layers", "per_layer")):
        for m in bench[key]:
            assert callable(run.reader(kind, m["name"]))


@pytest.mark.parametrize("trace", [0, 1])
def test_result_line(monkeypatch, tmp_path, trace):
    cell = tiny_cell("rgg2k-paper", "t10", n=300, check_trials=8)
    out = run_main(monkeypatch, tmp_path, cell, trace=trace)
    keys = list(out)
    assert keys[:5] == REQUIRED
    assert keys[5:] == (["breakdown", "check"] if trace else ["check"])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 10 and out["attempted"] % 10 == 0
    assert out["device"]["count"] == 1
    want = [m["name"] for m in cell["per_layer" if trace else "end_to_end"]]
    got = list(out["metrics"])
    if trace:
        # the CPU trace has no TPU plane: the device readers read nothing
        want = [m for m in want if m in ("plan_s", "compile_s")]
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert {"busy_s", "window_s"} <= set(out["device"])
    assert got == want
    assert set(out["check"]) == {"x_gap", "count_diff"}
    for v in out["check"].values():
        assert v["value"] <= v["limit"]


def _bench(args, cwd, **env):
    return subprocess.run(
        [sys.executable, "-m", "bench.run", *args], cwd=cwd,
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_exits_without_a_tpu():
    p = _bench(["--workload", "rgg2k-paper.eps.t10", "--seed", "1",
                "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_exits_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench(["--workload", "rgg2k-paper.eps.t10", "--seed", "1",
                "--seconds", "1", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
