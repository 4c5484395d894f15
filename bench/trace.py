"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A trace (`*.xplane.pb`) holds one plane per TPU chip (`/device:TPU:<id>`)
whose `XLA Modules` line has one event per program execution and whose
`XLA Ops` line has one event per HLO operation, and one host plane
(`/host:CPU`) whose lines hold the host threads' spans, the benchmark's
own `TraceAnnotation`s among them.  Both are on one clock, in ns.

* busy: the union of a chip's module intervals, so time in which some
  program ran on the chip;
* idle gaps: the holes in that union inside a window, each labelled
  with the benchmark span and the innermost host span it fell in;
* busy inside a span: the union clipped to the span;
* top ops: self time per HLO op name (an op's time less that of the
  ops nested in it, as a while loop's body is), averaged over chips.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

import numpy as np

__all__ = ["Trace", "load", "load_xplane", "merge", "busy_within",
           "gaps_within", "op_name", "self_times", "find_xplane", "reduce"]

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OP = re.compile(r"^%?([^\s=]+)")


@dataclasses.dataclass
class Trace:
    """What the reduction needs of one trace, in seconds on one clock."""

    modules: dict        # chip id -> (N, 2) merged busy intervals
    ops: dict            # chip id -> {op name: seconds}
    spans: list          # host spans: (start, end, name, thread)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def op_name(event_name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name


def merge(intervals) -> np.ndarray:
    """Union of (start, end) intervals as sorted disjoint (N, 2)."""
    iv = np.asarray(sorted(intervals), np.float64).reshape(-1, 2)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64).reshape(-1, 2)


def busy_within(merged: np.ndarray, lo: float, hi: float) -> float:
    if not len(merged):
        return 0.0
    s = np.clip(merged[:, 0], lo, hi)
    e = np.clip(merged[:, 1], lo, hi)
    return float((e - s).sum())


def gaps_within(merged: np.ndarray, lo: float, hi: float) -> list:
    """Idle (start, end) intervals of `merged` inside [lo, hi]."""
    out, cur = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def self_times(events) -> dict:
    """{name: seconds} of (start, end, name) events, each less the time
    of the events nested inside it."""
    out = defaultdict(float)
    stack = []                      # [start, end, name, nested seconds]

    def close(item):
        out[item[2]] += (item[1] - item[0]) - item[3]

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([s, e, name, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    modules, ops, spans = {}, {}, []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            chip = int(m.group(1))
            ivs, op_events = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    ivs = [(e.start_ns * 1e-9, e.end_ns * 1e-9)
                           for e in line.events]
                elif line.name == "XLA Ops":
                    op_events = [(e.start_ns * 1e-9, e.end_ns * 1e-9,
                                  op_name(e.name)) for e in line.events]
            modules[chip] = merge(ivs)
            ops[chip] = self_times(op_events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        spans.append((e.start_ns * 1e-9, e.end_ns * 1e-9,
                                      e.name, line.name))
    return Trace(modules=modules, ops=ops, spans=spans)


def load(trace_dir: str) -> Trace:
    return load_xplane(find_xplane(trace_dir))


def bench_spans(trace: Trace, name: str) -> list:
    """(start, end) of the benchmark's own spans called `name`."""
    return sorted((s, e) for s, e, n, _ in trace.spans if n == name)


def label(trace: Trace, t: float, prefix: str = "bench.") -> str:
    """The benchmark span and the innermost other host span at time t."""
    outer, inner = "outside the benchmark's spans", None
    best_o = best_i = np.inf
    for s, e, name, _ in trace.spans:
        if s <= t <= e:
            if name.startswith(prefix):
                if e - s < best_o:
                    outer, best_o = name, e - s
            elif e - s < best_i:
                inner, best_i = name, e - s
    return outer if inner is None else f"{outer} / {inner}"


def reduce(trace: Trace, chips, call_spans: list, every_chip: bool = True,
           top: int = 10) -> dict:
    """Device numbers over the window from the first call's start to the
    last call's end.  A chip the trace has no plane for is an error where
    `every_chip`; otherwise (a trace taken off a TPU) it is left out."""
    missing = [c for c in chips if c not in trace.modules]
    if missing and every_chip:
        raise RuntimeError(f"the trace has no device plane for chips "
                           f"{missing}")
    lo, hi = call_spans[0][0], call_spans[-1][1]
    chips = [c for c in chips if c in trace.modules]
    busy = {c: busy_within(trace.modules[c], lo, hi) for c in chips}
    in_call = [max((busy_within(trace.modules[c], s, e) for c in chips),
                   default=None) for s, e in call_spans]
    per_op = defaultdict(float)
    for c in chips:
        for name, sec in trace.ops[c].items():
            per_op[name] += sec / len(chips)
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    for c in chips:
        for s, e in gaps_within(trace.modules[c], lo, hi):
            where = label(trace, 0.5 * (s + e))
            if len(chips) > 1:
                where = f"{where} (chip {c})"
            gaps.append((where, float(e - s)))
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": hi - lo,
        "busy_s": busy,
        "call_s": [e - s for s, e in call_spans],
        "call_busy_s": in_call,
        "device_ops": [[n, s] for n, s in device_ops],
        "idle_gaps": [[n, s] for n, s in gaps[:top]],
    }
