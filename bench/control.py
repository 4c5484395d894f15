"""Readings that the limits of `correct` are set from, for one cell.

    python3 -m bench.control --workload <name> --seeds 11 12 13 ...

In one process (set-up once), for each seed: the cell's inputs from
that seed, as many calls at the cell's own load as a run compares
trials from, the same sample of trials as a run draws, and then

* `program`: the numbers `bench.run` compares (x_gap, count_diff),
  the program against the float64 reference: the lower readings;
* `control`: the same numbers for the reference itself replayed with
  bfloat16 values (the precision below the configuration's float32
  state) put in the program's place: the upper readings.

One JSON line per seed on standard output.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import ml_dtypes

from . import deploy, hierarchy, reference
from .run import Cell, load_cell, replay, sample, tpu_devices


def readings(cell: dict, seeds, devices, controls: int = 0) -> list:
    """Per seed: the program's numbers, and for the first `controls`
    seeds (all, where 0) the control's."""
    c = Cell(cell, seeds[0], devices)
    c.setup()
    k = c.cfg["check_trials"]
    calls_needed = -(-k // cell["traffic"]["trials_per_call"])
    h = hierarchy.build(c.cfg)
    rows = []
    controls = controls or len(seeds)
    for n, seed in enumerate(seeds):
        fresh = Cell(cell, seed, devices)
        fresh.plan, fresh.opts = c.plan, c.opts
        calls = [fresh.call() for _ in range(calls_needed)]
        out, trial_seeds = sample(calls, k, seed)
        gc.collect()
        ref = replay(fresh, h, trial_seeds)
        row = {"seed": seed, "trials": len(trial_seeds),
               "program": reference.compare(
                   out["x_final"], out["messages"], out["node_sends"],
                   out["level_ticks"], ref, fresh.x0)}
        if n < controls:
            low = replay(fresh, h, trial_seeds, dtype=ml_dtypes.bfloat16)
            row["control"] = reference.compare(
                low.x_final, low.messages, low.node_sends, low.level_ticks,
                ref, fresh.x0)
        rows.append(row)
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=0,
                    help="replay the control for the first N seeds only")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    _, enable_compile_cache = deploy.program()
    devices = tpu_devices(cell["chips"])
    enable_compile_cache()
    rows = readings(cell, args.seeds, devices, args.controls)
    for key in rows[0]["program"]:
        lo = max(r["program"][key] for r in rows)
        up = min(r["control"][key] for r in rows if "control" in r)
        print(f"{key}: program max {lo!r}, control min {up!r}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
