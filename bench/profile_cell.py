"""Profile one cell of the chip benchmark with the program's own names.

    python3 -m bench.profile_cell --workload <name> --seed <n>
        [--save-trace PREFIX] [--cost-seconds S]

Set-up as `bench.run` makes it, with the executor's build events
(`/repro/core/executor_*`) recorded from the start; then a traced
window of `run.TRACE_CALLS` calls, reduced twice: by `bench.trace` for
the benchmark's per-layer metrics, and by `bench.scopes` (with the
executor's compiled HLO) for the device time of each layer scope, the
host time of each `repro.execute_plan.*` span and the breakdown named by
level, layer and span.  Every reader under `bench/layers/` is applied
to the result, so the benchmark's own per-layer metrics and those read
from the program's names come out of the same window.  Nothing is
checked against the reference.

`--save-trace PREFIX` writes the window's trace to
`PREFIX.xplane.pb.gz` and the executor's compiled HLO to
`PREFIX.hlo.txt.gz`.
`--cost-seconds S` then measures what a running profiler session costs:
trials per second over closed-loop windows of S seconds without a
session, with one, and without one again.

The last line of standard output is one JSON object.  Like `bench.run`
it exits non-zero having run nothing without a TPU.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
from collections import defaultdict

from . import deploy
from . import run
from . import scopes
from . import trace as tracing

COST_DIR = os.path.join(run.ROOT, ".bench_cache", "cost_trace")


def event_recorder() -> dict:
    """Seconds of the program's duration events, summed by name from
    the moment it is made."""
    import jax

    seen = defaultdict(float)

    def listen(event, secs, **kw):
        if event.startswith("/repro/"):
            seen[event] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    return seen


def traced_window(cell: run.Cell, save: str | None = None) -> dict:
    """`run.traced_window`, keeping the trace and the executor's HLO
    for `bench.scopes`."""
    import jax

    shutil.rmtree(run.TRACE_DIR, ignore_errors=True)
    calls = []
    jax.profiler.start_trace(run.TRACE_DIR)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(run.TRACE_CALLS):
                with jax.profiler.TraceAnnotation("bench.call"):
                    calls.append(cell.call())
    finally:
        jax.profiler.stop_trace()
    path = tracing.find_xplane(run.TRACE_DIR)
    tr = tracing.load_xplane(path)
    spans = tracing.bench_spans(tr, "bench.call")
    if len(spans) != len(calls):
        raise RuntimeError(f"trace holds {len(spans)} call spans for "
                           f"{len(calls)} calls")
    chips = [d.id for d in cell.devices]
    red = tracing.reduce(tr, chips, spans,
                         every_chip=cell.devices[0].platform == "tpu")
    (executor,) = cell.plan.exec_cache.values()
    hlo = executor.as_text()
    red.update(scopes.reduce(scopes.load_xplane(path), chips, spans, hlo))
    red["trials"] = sum(c["trials"] for c in calls)
    if save:
        with open(path, "rb") as src, \
                gzip.open(f"{save}.xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
        with gzip.open(f"{save}.hlo.txt.gz", "wt") as dst:
            dst.write(hlo)
    shutil.rmtree(run.TRACE_DIR, ignore_errors=True)
    return red


def profiler_cost(cell: run.Cell, seconds: float) -> dict:
    """Trials per second without, with and again without a profiler
    session running."""
    import jax

    out = {}
    for label in ("off", "on", "off_again"):
        if label == "on":
            shutil.rmtree(COST_DIR, ignore_errors=True)
            jax.profiler.start_trace(COST_DIR)
        calls = run.timed_window(cell, seconds)
        if label == "on":
            jax.profiler.stop_trace()
            shutil.rmtree(COST_DIR, ignore_errors=True)
        span = calls[-1]["end"] - calls[0]["start"]
        out[label] = sum(c["trials"] for c in calls) / span
    return out


def breakdown_ms(red: dict) -> dict:
    """Device ms per trial by level and layer on the busiest chip, and
    what no layer scope holds."""
    if not red["busy_s"]:
        return {}
    chip = max(red["busy_s"], key=red["busy_s"].get)
    per = 1e3 / red["trials"]
    return {
        "chip": chip,
        "level_layer_ms_per_trial": {
            k: v * per for k, v in sorted(red["level_layer_s"][chip].items())},
        "unscoped_ms_per_trial": red["unscoped_s"][chip] * per,
    }


def profile(cell: dict, seed: int, devices, save=None, cost_seconds=0.0):
    events = event_recorder()
    c = run.Cell(cell, seed, devices)
    r = c.setup()
    r["events_s"] = dict(events)
    r["trace"] = traced_window(c, save)
    readers = sorted(os.path.basename(p)[:-3] for p in
                     glob.glob(os.path.join(run.BENCH, "layers", "*.py")))
    metrics = {name: run.reader("layers", name)(r) for name in readers}
    out = {
        "metrics": metrics,
        "by_level": breakdown_ms(r["trace"]),
        "breakdown": {"device_ops": r["trace"]["named_ops"],
                      "idle_gaps": r["trace"]["named_gaps"]},
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
    }
    m = metrics
    layers = [m.get(f"{k}_ms_per_trial") for k in
              ("schedule", "value_pass", "accounting", "check", "promote")]
    if None not in layers and m.get("device_ms_per_trial"):
        out["layers_share_of_device"] = sum(layers) / m["device_ms_per_trial"]
    spans = [m.get("prepare_ms_per_call"), m.get("readback_ms_per_call")]
    if None not in spans and m.get("host_ms_per_call"):
        out["spans_share_of_host"] = sum(spans) / m["host_ms_per_call"]
    if cost_seconds > 0:
        out["profiler_cost_trials_per_s"] = profiler_cost(c, cost_seconds)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--save-trace", default=None)
    ap.add_argument("--cost-seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    _, enable_compile_cache = deploy.program()
    devices = run.tpu_devices(cell["chips"])
    enable_compile_cache()
    out = profile(cell, args.seed, devices, args.save_trace,
                  args.cost_seconds)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
