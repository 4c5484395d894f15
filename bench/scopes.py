"""The program's own names in a profiler trace.

The executor names its work (`repro.core.engine`): every device op sits
under a ``level_<i>`` (or ``final``) scope and one of five layer scopes,
and `execute_plan` opens host spans ``repro.execute_plan`` with children
``.prepare``, ``.build`` (``.build.lower``, ``.build.compile``) and
``.readback``.  This module reads both back from a trace:

* an op's scopes come from its ``tf_op`` stat, the JAX name stack, for
  example ``jit(run_v)/level_4/convergence_check/while/body/value_pass/
  ...``; its layer is the innermost layer scope on that path and its
  level the innermost level scope.  `jax.profiler.ProfileData` does not
  expose the stat, so the `XSpace` proto is decoded with the generated
  `xplane_pb2` of the installed TensorFlow package, loaded by file
  (it needs only `google.protobuf`; TensorFlow is never imported);
* an op without a ``tf_op`` (XLA rewrites a ``while`` and drops its
  metadata) takes the deepest path that every op nested inside it
  shares, so a scan's loop control goes to the layer that holds the
  scan and the chunk loop's own control to ``convergence_check``; one
  with nothing named inside stays unscoped;
* an op XLA made inside a loop carries no name of its own, and the
  profiler gives it the loop's (a path ending in ``while``).  Where it
  is a fusion and the executor's compiled HLO is at hand, it takes the
  deepest path that the named instructions fused into it share: a
  vmapped usage scatter-add that XLA rewrites comes out as an unnamed
  ``scatter`` fused with its named operands, and so goes to
  ``accounting``, not to the chunk loop;
* a span's host time is its length less the device busy time inside it
  (busiest chip), as `host_ms_per_call` is for a whole call.

Times are op self times (an op less the ops nested in it), clipped to
the window from the first call's start to the last call's end, in
seconds on the trace's one clock.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import os
import re
from collections import defaultdict

import numpy as np

from . import trace as tracing

__all__ = ["LAYERS", "SPANS", "EVENTS", "Scoped", "load_xplane",
           "scope_of", "fused_paths", "attribute", "span_label", "reduce"]

LAYERS = ("schedule", "value_pass", "accounting", "convergence_check",
          "promote")
SPANS = ("repro.execute_plan.prepare", "repro.execute_plan.readback")
EVENTS = ("/repro/core/executor_lower", "/repro/core/executor_compile")

_WRAPPED = re.compile(r"^(?:\w+\()*|\)*$")   # vmap(level_0) -> level_0
_LEVEL = re.compile(r"level_\d+|final")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@dataclasses.dataclass
class Scoped:
    """What the reduction needs of one trace, in seconds on one clock."""

    modules: dict        # chip id -> (N, 2) merged busy intervals
    ops: dict            # chip id -> [(start, end, hlo op name, path|None)]
    spans: list          # host spans: (start, end, name, thread)


@functools.lru_cache(maxsize=1)
def _xplane_pb2():
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise RuntimeError("reading op scopes needs the xplane_pb2 of the "
                           "installed tensorflow package; none found")
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _stat_str(stat, names: dict) -> str:
    if stat.HasField("str_value"):
        return stat.str_value
    if stat.HasField("ref_value"):
        return names.get(stat.ref_value, "")
    return ""


def load_xplane(path: str) -> Scoped:
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    modules, ops, spans = {}, {}, []
    for plane in space.planes:
        device = tracing._DEVICE.match(plane.name)
        if not device and plane.name != "/host:CPU":
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        meta = {}
        for k, em in plane.event_metadata.items():
            path_ = next((_stat_str(s, stat_names) for s in em.stats
                          if s.metadata_id in tf_op), "")
            if ":" in path_:
                path_ = path_.rsplit(":", 1)[0]
            meta[k] = (em.name, path_ or None)

        def events(line):
            for e in line.events:
                s = (line.timestamp_ns + e.offset_ps * 1e-3) * 1e-9
                yield s, s + e.duration_ps * 1e-12, meta[e.metadata_id]

        if device:
            chip = int(device.group(1))
            ivs, op_events = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    ivs = [(s, e) for s, e, _ in events(line)]
                elif line.name == "XLA Ops":
                    op_events = [(s, e, tracing.op_name(name), p)
                                 for s, e, (name, p) in events(line)]
            modules[chip] = tracing.merge(ivs)
            ops[chip] = op_events
        else:
            for line in plane.lines:
                spans.extend((s, e, name, line.name)
                             for s, e, (name, _) in events(line) if e > s)
    return Scoped(modules=modules, ops=ops, spans=spans)


def scope_of(path) -> tuple:
    """(level, layer) of an op's name stack: the innermost of each on
    it, None where it names none."""
    level = layer = None
    for part in (path or "").split("/"):
        part = _WRAPPED.sub("", part)
        if _LEVEL.fullmatch(part):
            level = part
        elif part in LAYERS:
            layer = part
    return level, layer


def fused_paths(hlo_text: str) -> dict:
    """{fusion name: the deepest path that the named instructions of its
    fused computation share} from a compiled module's HLO text."""
    named, calls, comp = defaultdict(list), {}, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name = _OP_NAME.search(line)
        if name and name.group(1).startswith("jit("):
            named[comp].append(name.group(1))
        if " fusion(" in line and (c := _CALLS.search(line)):
            calls[m.group(1)] = c.group(1)
    return {f: _common(named[c]) for f, c in calls.items() if named[c]}


def _loop_own(path) -> bool:
    return path is None or _WRAPPED.sub("", path.rsplit("/", 1)[-1]) == \
        "while"


def _common(paths) -> str | None:
    split = [p.split("/") for p in paths]
    if not split:
        return None
    out = []
    for parts in zip(*split):
        if any(p != parts[0] for p in parts):
            break
        out.append(parts[0])
    return "/".join(out) or None


def attribute(op_events) -> list:
    """[(self seconds, path, hlo op name, start)] of (start, end, name,
    path) op events; an op without a path takes the deepest path that
    every named op nested inside it shares."""
    nodes = [{"ev": ev, "kids": [], "nested": 0.0}
             for ev in sorted(op_events, key=lambda ev: (ev[0], -ev[1]))]
    stack = []
    for node in nodes:
        s, e = node["ev"][0], node["ev"][1]
        while stack and stack[-1]["ev"][1] <= s:
            stack.pop()
        if stack:
            stack[-1]["kids"].append(node)
            stack[-1]["nested"] += e - s
        stack.append(node)
    out = []
    for node in reversed(nodes):       # every op after the ops inside it
        s, e, name, path = node["ev"]
        if path is None:
            path = _common([k["path"] for k in node["kids"]
                            if k["path"] is not None])
        node["path"] = path
        out.append((e - s - node["nested"], path, name, s))
    return out


def span_label(scoped: Scoped, t: float) -> str:
    """The benchmark span, the innermost `repro.` span and the innermost
    other host span at time t."""
    best = {"bench.": (np.inf, "outside the benchmark's spans"),
            "repro.": (np.inf, None), "": (np.inf, None)}
    for s, e, name, _ in scoped.spans:
        if s <= t <= e:
            kind = ("bench." if name.startswith("bench.") else
                    "repro." if name.startswith("repro.") else "")
            if e - s < best[kind][0]:
                best[kind] = (e - s, name)
    return " / ".join(n for _, n in best.values() if n is not None)


def reduce(scoped: Scoped, chips, call_spans: list, hlo: str | None = None,
           top: int = 10) -> dict:
    """Device time by layer and by level, host time by program span,
    and the named breakdown, over the window from the first call's
    start to the last call's end (chips without a plane left out).
    `hlo` is the executor's compiled HLO text, for the fusions XLA
    made inside a loop."""
    lo, hi = call_spans[0][0], call_spans[-1][1]
    chips = [c for c in chips if c in scoped.modules]
    fused = fused_paths(hlo) if hlo else {}
    layer_s, level_layer_s, unscoped_s = {}, {}, {}
    per_op = defaultdict(float)
    for c in chips:
        ops = [(s, e, name, fused[name] if _loop_own(path) and name in fused
                and not name.startswith("while") else path)
               for s, e, name, path in scoped.ops[c]]
        lay, lev, un = defaultdict(float), defaultdict(float), 0.0
        for sec, path, name, start in attribute(ops):
            if not lo <= start <= hi:
                continue
            level, layer = scope_of(path)
            if layer is None:
                un += sec
                key = f"unscoped:{name}"
            else:
                lay[layer] += sec
                lev[f"{level or '-'}/{layer}"] += sec
                key = f"{level or '-'}/{layer}:{name}"
            per_op[key] += sec / len(chips)
        layer_s[c], level_layer_s[c], unscoped_s[c] = dict(lay), dict(lev), un
    span_host_s = defaultdict(list)
    for s, e, name, _ in sorted(scoped.spans):
        if name.startswith("repro.") and lo <= 0.5 * (s + e) <= hi:
            busy = max((tracing.busy_within(scoped.modules[c], s, e)
                        for c in chips), default=0.0)
            span_host_s[name].append(e - s - busy)
    gaps = []
    for c in chips:
        for s, e in tracing.gaps_within(scoped.modules[c], lo, hi):
            where = span_label(scoped, 0.5 * (s + e))
            if len(chips) > 1:
                where = f"{where} (chip {c})"
            gaps.append((where, float(e - s)))
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "layer_s": layer_s,
        "level_layer_s": level_layer_s,
        "unscoped_s": unscoped_s,
        "span_host_s": dict(span_host_s),
        "named_ops": [[n, s] for n, s in ops],
        "named_gaps": [[n, s] for n, s in gaps[:top]],
    }


def layer_ms_per_trial(run: dict, layer: str):
    """Op self time under `layer` on the busiest chip over the traced
    window, per trial, in ms; None where no op of the trace has it."""
    tr = run["trace"]
    per_chip = [s[layer] for s in tr.get("layer_s", {}).values()
                if layer in s]
    if not per_chip:
        return None
    return 1e3 * max(per_chip) / tr["trials"]


def span_ms_per_call(run: dict, span: str):
    """Host time of the program span `span` (its length less the device
    busy time inside it), summed over the window and divided by its
    calls, in ms; None where the trace has no such span."""
    tr = run["trace"]
    host = tr.get("span_host_s", {}).get(span)
    if not host:
        return None
    return 1e3 * sum(host) / len(tr["call_s"])


def event_s(run: dict, event: str):
    """Seconds of the program's duration event `event` in set-up; None
    where the program recorded none."""
    return run.get("events_s", {}).get(event)
