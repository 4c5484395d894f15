"""Shared benchmark helpers: trial running, CSV/JSON artifact output.

CPU-budget note (DESIGN.md §8): the paper's experiments average 10-25
trials on graphs up to 8000 nodes; on this single-core container the
default benchmark profile uses 3 trials and the same size range, with
`--full` restoring the paper's trial counts.  Scaling-law fits still
span >= 1 decade of n.

Trial-vmapping note: the multiscale benchmarks run all trials of one
configuration in a single compiled vmapped call (`multiscale_gossip(...,
trials=T)`); artifacts record `wall_clock_s` per algorithm.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.launch.compile_cache import enable_compile_cache

ARTIFACTS = os.path.join(os.path.dirname(__file__), "artifacts")

def _tuple_arg(elem):
    def parse(s):
        return tuple(elem(x) for x in s.split(","))
    return parse


def bench_cli(run_fn, argv=None) -> None:
    """Uniform standalone CLI for `python -m benchmarks.figX`.

    Builds argparse flags from `run_fn`'s keyword defaults, so every
    figure benchmark exposes the same surface (--trials, --artifact,
    plus its own numeric knobs) without each
    module hand-rolling a parser.  Tuple defaults parse as
    comma-separated lists (e.g. ``--sizes 500,1000``).
    """
    import argparse
    import inspect

    ap = argparse.ArgumentParser(description=run_fn.__module__)
    for name, p in inspect.signature(run_fn).parameters.items():
        d = p.default
        if d is inspect.Parameter.empty or d is None:
            continue
        flag = f"--{name.replace('_', '-')}"
        if isinstance(d, bool):
            ap.add_argument(flag, action=argparse.BooleanOptionalAction,
                            default=d)
        elif isinstance(d, tuple):
            ap.add_argument(flag, type=_tuple_arg(type(d[0])), default=d,
                            metavar=",".join(str(x) for x in d[:2]) + ",…")
        elif isinstance(d, (int, float, str)):
            ap.add_argument(flag, type=type(d), default=d)
    args = vars(ap.parse_args(argv))
    enable_compile_cache()
    for line in run_fn(**{k: v for k, v in args.items() if v is not None}):
        print(line)


def timed(fn, *args, **kwargs):
    """(result, seconds) of one call — wall-clock for artifact payloads."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


def save_artifact(name: str, payload: dict) -> str:
    os.makedirs(ARTIFACTS, exist_ok=True)
    path = os.path.join(ARTIFACTS, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


def load_artifact(name: str):
    path = os.path.join(ARTIFACTS, f"{name}.json")
    if not os.path.exists(path):
        return None
    return json.load(open(path))


def csv_line(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.seconds = time.time() - self.t0
