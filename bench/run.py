"""Run one cell of the chip benchmark.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (`BENCHMARK.json` -> `workloads`) names a configuration
(`bench/configs/<name>.json`: one deployment of the gossip core) and a
traffic mix (`bench/traffic/<name>.json`: trials per call and mesh).
`--seed` makes the node values x0 and the stream of trial seeds.

Set-up builds or loads the plan, loads or compiles the executor and
makes one warm call.  Then `execute_plan` runs in a closed loop, one
batch of fresh trials per call, for `--seconds` (`--trace 0`, the
end-to-end metrics) or for a traced window of `TRACE_CALLS` calls
(`--trace 1`, the per-layer metrics from the profiler trace).  After
the window a sample of the window's trials, drawn from the seed, is
replayed by the float64 reference (`bench.reference`) over the
hierarchy that `bench.hierarchy` builds from the configuration, and
compared.

The last line of standard output is one JSON object; the numbers
compared, with their limits, are the last lines of standard error and
the last key of that object.  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero having run nothing.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from . import deploy  # noqa: E402
from . import hierarchy  # noqa: E402
from . import reference  # noqa: E402
from . import trace as tracing  # noqa: E402

BENCH = deploy.BENCH
ROOT = deploy.ROOT
TRACE_DIR = os.path.join(ROOT, ".bench_cache", "trace")
SEED_LIMIT = 2**31 - 1   # trial seeds are PRNGKey seeds
TRACE_CALLS = 2          # calls in the traced window


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's workload entry, configuration, traffic and metrics."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    w = work[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(metrics):
        return [m for m in metrics
                if name in m.get("workloads", [name])]

    return {
        "name": name,
        "chips": int(w["chips"]),
        "config": _json(os.path.join(root, cfg_entry["file"])),
        "traffic": _json(os.path.join(BENCH, "traffic",
                                      f"{w['traffic']}.json")),
        "end_to_end": mine(bench["end_to_end"]),
        "per_layer": mine(bench["per_layer"]),
    }


def reader(kind: str, name: str):
    """`read(run)` of metric `name` from `bench/<kind>/<name>.py`."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def tpu_devices(chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell asks for {chips} chips, JAX "
                         f"found {len(devs)}; nothing was run")
    return devs[:chips]


class Cell:
    """One cell's deployment, inputs and closed-loop caller."""

    def __init__(self, cell: dict, seed: int, devices):
        self.cfg, self.traffic = cell["config"], cell["traffic"]
        self.devices = devices
        rng = np.random.default_rng(seed)
        self.x0 = rng.standard_normal(self.cfg["n"]).astype(np.float32)
        self._trial_rng = rng
        self.plan = None

    def setup(self) -> dict:
        t = time.perf_counter()
        self.plan = deploy.setup_plan(self.cfg)
        plan_s = time.perf_counter() - t
        self.opts = deploy.options(self.traffic, self.devices)
        first = self.call()
        return {"plan_s": plan_s, "first_call_s": first["end"] - first["start"]}

    def call(self) -> dict:
        seeds = self._trial_rng.integers(
            0, SEED_LIMIT, self.traffic["trials_per_call"])
        start = time.perf_counter()
        res = deploy.execute(self.plan, self.cfg, self.x0, seeds, self.opts)
        end = time.perf_counter()
        return {"start": start, "end": end, "trials": len(seeds),
                "seeds": seeds, "result": res}

    def peak_bytes(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)


def compile_counter():
    """Counts backend compiles from the moment it is made."""
    import jax

    count = [0]

    def listen(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return count


def timed_window(cell: Cell, seconds: float) -> list:
    calls = [cell.call()]
    while calls[-1]["end"] - calls[0]["start"] < seconds:
        calls.append(cell.call())
    return calls


def traced_window(cell: Cell) -> tuple:
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    calls = []
    jax.profiler.start_trace(TRACE_DIR)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(TRACE_CALLS):
                with jax.profiler.TraceAnnotation("bench.call"):
                    calls.append(cell.call())
    finally:
        jax.profiler.stop_trace()
    tr = tracing.load(TRACE_DIR)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    spans = tracing.bench_spans(tr, "bench.call")
    if len(spans) != len(calls):
        raise RuntimeError(f"trace holds {len(spans)} call spans for "
                           f"{len(calls)} calls")
    chips = [d.id for d in cell.devices]
    red = tracing.reduce(tr, chips, spans,
                         every_chip=cell.devices[0].platform == "tpu")
    red["trials"] = sum(c["trials"] for c in calls)
    return calls, red


def sample(calls: list, k: int, seed: int) -> tuple:
    """k of the window's trials, drawn from the seed: the program's
    outputs for them and their trial seeds.  Drops the other results."""
    pairs = [(ci, ti) for ci, c in enumerate(calls)
             for ti in range(c["trials"])]
    pick = np.sort(np.random.default_rng([seed, 2]).choice(
        len(pairs), min(k, len(pairs)), replace=False))
    chosen = [pairs[p] for p in pick]
    out = {f: np.stack([getattr(calls[ci]["result"], f)[ti]
                        for ci, ti in chosen])
           for f in ("x_final", "messages", "node_sends", "level_ticks")}
    seeds = [int(calls[ci]["seeds"][ti]) for ci, ti in chosen]
    for c in calls:
        c.pop("result", None)
    return out, seeds


def replay(cell: Cell, h, seeds, dtype=np.float64):
    cfg = cell.cfg
    return reference.replay(
        h, cell.x0, seeds, weighted=cfg["weighted"], eps=cfg["eps"],
        fixed_ticks_scale=cfg["fixed_ticks_scale"],
        draw=reference.jax_uniforms(seeds), dtype=dtype,
        **deploy.cadence(cell.opts))


def check(cell: Cell, calls: list, seed: int) -> tuple:
    """Replay a sample of the window's trials with the reference, once
    the program's executors are freed, and compare every node's value
    and every count.  Returns (numbers, trials that broke a limit)."""
    out, seeds = sample(calls, cell.cfg["check_trials"], seed)
    deploy.free(cell.plan)
    cell.plan = None
    gc.collect()
    ref = replay(cell, hierarchy.build(cell.cfg), seeds)
    args = (out["x_final"], out["messages"], out["node_sends"],
            out["level_ticks"], ref, cell.x0)
    return (reference.compare(*args),
            reference.failed_trials(*args, cell.cfg["limits"]))


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             devices, t0: float = _T0) -> dict:
    """Set up, run the window, check; the result line as a dict."""
    c = Cell(cell, seed, devices)
    run = c.setup()
    compiles = compile_counter()
    run["setup_s"] = time.perf_counter() - t0
    if trace:
        calls, run["trace"] = traced_window(c)
    else:
        calls = timed_window(c, seconds)
    log(f"bench: {len(calls)} calls, {compiles[0]} compiles in the window")
    run["calls"] = calls
    run["memory_peak_bytes"] = c.peak_bytes()
    metrics = {}
    for m in cell["per_layer" if trace else "end_to_end"]:
        value = reader("layers" if trace else "metrics", m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    numbers, failed_trials = check(c, calls, seed)
    limits = c.cfg["limits"]
    failed = [k for k, v in numbers.items() if not v <= limits[k]]
    dev = devices[0]
    result = {
        "correct": not failed,
        "attempted": sum(x["trials"] for x in calls),
        "failed": failed_trials,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": run["memory_peak_bytes"]},
    }
    if trace:
        tr = run["trace"]
        result["device"]["busy_s"] = (
            sum(tr["busy_s"].values()) / len(tr["busy_s"])
            if tr["busy_s"] else 0.0)
        result["device"]["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["check"] = {k: {"value": v, "limit": limits[k]}
                       for k, v in numbers.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    _, enable_compile_cache = deploy.program()
    devices = tpu_devices(cell["chips"])
    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    for k, v in result["check"].items():
        log(f"check {k} = {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
