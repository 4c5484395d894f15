"""The comparison that decides `correct`, shown to fail.

* the control: the reference replayed with bfloat16 values (the
  precision below the configuration's float32 state) in the program's
  place fails the limit, while the program passes it;
* the faults the cells can have, each planted under a run of the
  harness: a call that returns its state unchanged, half of a batch of
  trials left out, an answer altered where it is produced, and (on four
  virtual CPU devices) the exchange between chips left out.

    python -m pytest bench/tests
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import control, deploy, run
from bench.tests.helpers import run_main, tiny_cell

CELLS = [("rgg2k-paper", "t10", 300, 8), ("rgg1m-fi", "t1", 3000, 1)]


@pytest.mark.parametrize("config,traffic,n,k", CELLS)
def test_control_fails_and_program_passes(monkeypatch, tmp_path, config,
                                          traffic, n, k):
    import jax

    monkeypatch.setattr(deploy, "PLAN_CACHE", str(tmp_path / "plans"))
    cell = tiny_cell(config, traffic, n, k)
    limits = cell["config"]["limits"]
    for row in control.readings(cell, [5, 6, 7], jax.devices()[:1]):
        assert row["program"]["x_gap"] <= limits["x_gap"]
        assert row["program"]["count_diff"] == 0
        assert row["control"]["x_gap"] > limits["x_gap"]


def _broken(monkeypatch, fault):
    execute = deploy.execute

    def wrapped(plan, cfg, x0, seeds, opts):
        res = execute(plan, cfg, x0, seeds, opts)
        return fault(res, np.asarray(x0))

    monkeypatch.setattr(deploy, "execute", wrapped)


def _unchanged(res, x0):
    return dataclasses.replace(
        res, x_final=np.broadcast_to(x0, res.x_final.shape).copy())


def _half_left_out(res, x0):
    T = res.x_final.shape[0]
    keep = np.arange(T) % max(1, T // 2)
    return dataclasses.replace(
        res, x_final=res.x_final[keep], messages=res.messages[keep],
        node_sends=res.node_sends[keep], level_ticks=res.level_ticks[keep])


def _value_altered(res, x0):
    x = res.x_final.copy()
    x[:, 0] += 1e-3 * np.sqrt(np.mean(np.square(x0)))
    return dataclasses.replace(res, x_final=x)


def _count_altered(res, x0):
    return dataclasses.replace(res, messages=res.messages + 1)


# half of a batch exists only where a call runs several trials
FAULTS = [(f, cell) for cell in CELLS
          for f in (_unchanged, _value_altered, _count_altered)] + [
    (_half_left_out, CELLS[0])]


@pytest.mark.parametrize("fault,cell", FAULTS)
def test_fault_makes_run_incorrect(monkeypatch, tmp_path, fault, cell):
    _broken(monkeypatch, fault)
    out = run_main(monkeypatch, tmp_path, tiny_cell(*cell))
    assert out["correct"] is False
    assert out["failed"] > 0
    assert any(v["value"] > v["limit"] for v in out["check"].values())


MESH_SCRIPT = r"""
import json, sys
import jax
from bench import deploy, run
from bench.tests.helpers import tiny_cell

deploy.PLAN_CACHE = sys.argv[1]
run.TRACE_DIR = sys.argv[1] + "/trace"
if sys.argv[2] == "broken":
    jax.lax.psum = lambda x, axis_name, **kw: x
cell = tiny_cell("rgg1m-fi", "t1.mesh4", 3000, 1, chips=4)
out = run.run_cell(cell, 3000000007, 0.5, False, jax.devices()[:4])
print(json.dumps({"correct": out["correct"], "check": out["check"]}))
"""


@pytest.mark.parametrize("mode", ["sound", "broken"])
def test_mesh_exchange_left_out(tmp_path, mode):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, "-c", MESH_SCRIPT, str(tmp_path),
                        mode], cwd=run.ROOT, capture_output=True, text=True,
                       env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is (mode == "sound"), out
