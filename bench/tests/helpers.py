"""A tiny deployment of each configuration, run on the CPU."""
from __future__ import annotations

import io
import json
import os
from contextlib import redirect_stdout

import jax

from bench import deploy, run


def tiny_cell(config: str, traffic: str, n: int, check_trials: int,
              chips: int = 1) -> dict:
    """A cell of configuration and traffic files at n nodes, reporting
    the metrics `BENCHMARK.json` gives every cell of that pair."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    pair = {w["name"] for w in bench["workloads"]
            if (w["config"], w["traffic"]) == (config, traffic)}

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or pair & set(m["workloads"])]

    with open(os.path.join(run.BENCH, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(run.BENCH, "traffic", f"{traffic}.json")) as f:
        traf = json.load(f)
    cfg.update(n=n, graph_seed=7, check_trials=check_trials)
    return {"name": f"{config}.{traffic}", "chips": chips, "config": cfg,
            "traffic": traf, "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def run_main(monkeypatch, tmp_path, cell: dict, *, seconds: float = 0.5,
             trace: int = 0, seed: int = 3000000001) -> dict:
    """`bench.run.main` on the CPU, with the look for a chip skipped;
    returns the parsed last line of standard output."""
    monkeypatch.setattr(deploy, "PLAN_CACHE", str(tmp_path / "plans"))
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(run, "load_cell", lambda name: cell)
    monkeypatch.setattr(run, "tpu_devices", lambda c: jax.devices()[:c])
    monkeypatch.setattr(deploy, "program", _no_cache(deploy.program))
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", cell["name"], "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)])
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


def _no_cache(program):
    """The program's entry points, with the compile cache left off."""
    def wrapped():
        core, _ = program()
        return core, lambda: None
    return wrapped
