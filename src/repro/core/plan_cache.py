"""Content-addressed persistent cache for built `HierarchyPlan`s.

A plan is a pure function of (graph spec, partition config, routing
params, plan seed, builder version): hash those into a key, pickle the
built plan under it, and warm runs — repeated fig sweeps, CI smokes,
`benchmarks/large_n.py` — skip both graph generation and plan
construction entirely (the plan embeds its graph).

Key design:

* the spec is canonical JSON over plain scalars — seeded graphs hash
  their (kind, n, c, seed, method) recipe; externally built graphs hash
  a sha256 digest of coords + CSR adjacency instead;
* `PLAN_CACHE_VERSION` is baked into every key: bump it whenever the
  builder's output layout changes and all old entries silently miss
  (versioned invalidation — no migration code);
* `workers` is deliberately NOT part of the key — the parallel build is
  bitwise-identical to the serial one, so it must hit the same entry;
* writes are atomic (tmp file + rename), safe under concurrent runs;
* a hit is bitwise-equal to a fresh build (asserted by
  tests/test_plan_cache.py).

The default cache directory is `$REPRO_PLAN_CACHE` or
`~/.cache/repro/plan_cache`; benchmarks point it at
`benchmarks/artifacts/plan_cache` (gitignored).
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
import time
from typing import Any, Optional

import jax
import numpy as np

from .events import record_duration
from .plan import HierarchyPlan, build_plan
from .rgg import Graph, random_geometric_graph

__all__ = [
    "PLAN_CACHE_VERSION",
    "default_cache_dir",
    "graph_spec",
    "graph_digest_spec",
    "plan_key",
    "load_plan",
    "store_plan",
    "setup_plan",
]

# bump on any change to plan layout or builder semantics; stale entries
# then miss by construction
PLAN_CACHE_VERSION = 1

_span = jax.profiler.TraceAnnotation


def default_cache_dir() -> str:
    env = os.environ.get("REPRO_PLAN_CACHE")
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "plan_cache"
    )


def _digest_arrays(*arrays: np.ndarray) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def graph_spec(
    n: int, *, c: float = 3.0, seed: int = 0,
    radius: Optional[float] = None,
) -> dict:
    """Spec for a seeded `random_geometric_graph` — hashes the recipe,
    not the arrays, so the warm path can skip generation entirely.  The
    builder `method`/`chunk` are excluded: every builder produces the
    same Graph (bitwise, tested)."""
    return {
        "kind": "rgg",
        "n": int(n),
        "c": float(c),
        "seed": int(seed),
        "radius": None if radius is None else float(radius),
    }


def graph_digest_spec(g: Graph) -> dict:
    """Spec for an externally built graph: content digest of coords +
    CSR adjacency."""
    return {
        "kind": "digest",
        "n": g.n,
        "radius": float(g.radius),
        "sha256": _digest_arrays(
            g.coords, g.nbr_start, g.nbr_flat, g.degrees
        ),
    }


def plan_key(
    graph: dict,
    *,
    k: Optional[int] = None,
    a: float = 2.0 / 3.0,
    cell_max: float = 8.0,
    seed: int = 0,
    rep_mode: str = "random",
) -> str:
    """Content hash of everything a build depends on (except `workers`,
    which cannot change the output)."""
    spec = {
        "version": PLAN_CACHE_VERSION,
        "graph": graph,
        "plan": {
            "k": None if k is None else int(k),
            "a": float(a),
            "cell_max": float(cell_max),
            "seed": int(seed),
            "rep_mode": str(rep_mode),
        },
    }
    blob = json.dumps(spec, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def _entry_path(cache_dir: str, key: str) -> str:
    return os.path.join(cache_dir, f"{key}.plan.pkl")


def load_plan(key: str, cache_dir: Optional[str] = None) -> Optional[HierarchyPlan]:
    """Return the cached plan for `key`, or None on a miss (absent,
    unreadable, or a key mismatch from a hash collision / truncation)."""
    path = _entry_path(cache_dir or default_cache_dir(), key)
    try:
        with open(path, "rb") as f:
            payload = pickle.load(f)
    except (OSError, pickle.UnpicklingError, EOFError, AttributeError):
        return None
    if not isinstance(payload, dict) or payload.get("key") != key:
        return None
    return payload.get("plan")


def store_plan(
    key: str, plan: HierarchyPlan, cache_dir: Optional[str] = None
) -> str:
    """Atomically persist `plan` under `key`; returns the entry path."""
    cache_dir = cache_dir or default_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    path = _entry_path(cache_dir, key)
    payload = {"key": key, "version": PLAN_CACHE_VERSION, "plan": plan}
    fd, tmp = tempfile.mkstemp(dir=cache_dir, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            pickle.dump(payload, f, protocol=5)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


def setup_plan(
    n: Optional[int] = None,
    *,
    g: Optional[Graph] = None,
    c: float = 3.0,
    graph_seed: int = 0,
    radius: Optional[float] = None,
    graph_method: str = "bucket",
    k: Optional[int] = None,
    a: float = 2.0 / 3.0,
    cell_max: float = 8.0,
    seed: int = 0,
    rep_mode: str = "random",
    workers: int = 0,
    cache_dir: Optional[str] = None,
    use_cache: bool = True,
    refresh: bool = False,
) -> tuple[HierarchyPlan, dict]:
    """End-to-end cached setup: graph generation + plan build, skipped
    wholesale on a cache hit (the plan embeds its graph).

    Pass either `n` (+ graph params, the seeded-RGG recipe) or a
    prebuilt `g` (hashed by content).  Returns `(plan, info)` where
    info records {cache: "hit"|"miss"|"off", key, graph_gen_s,
    plan_build_s, load_s | store_s, setup_s}.  `refresh=True` forces a
    rebuild (and re-store) even if an entry exists — the benchmark's
    cold path.

    A profiler trace holds the span ``repro.setup_plan`` with children
    ``.load`` (a cache lookup), ``.graph``, ``.plan`` and ``.store`` (the
    steps of a miss).  A hit records the duration event
    ``/repro/core/plan_load``; a miss records ``/repro/core/graph_build``
    (seeded graphs only), ``/repro/core/plan_build`` and, with the cache
    on, ``/repro/core/plan_store`` (`core.events`).
    """
    if (n is None) == (g is None):
        raise ValueError("pass exactly one of n= or g=")
    with _span("repro.setup_plan"):
        t_all = time.perf_counter()
        if g is None:
            gspec = graph_spec(n, c=c, seed=graph_seed, radius=radius)
        else:
            gspec = graph_digest_spec(g)
        key = plan_key(
            gspec, k=k, a=a, cell_max=cell_max, seed=seed,
            rep_mode=rep_mode,
        )
        info: dict[str, Any] = {"key": key, "graph_gen_s": 0.0}
        if use_cache and not refresh:
            t0 = time.perf_counter()
            with _span("repro.setup_plan.load"):
                plan = load_plan(key, cache_dir=cache_dir)
            if plan is not None:
                load_s = time.perf_counter() - t0
                record_duration("/repro/core/plan_load", load_s)
                info.update(
                    cache="hit",
                    load_s=round(load_s, 6),
                    plan_build_s=dict(plan.build_seconds or {}),
                    setup_s=round(time.perf_counter() - t_all, 6),
                )
                return plan, info
        if g is None:
            t0 = time.perf_counter()
            with _span("repro.setup_plan.graph"):
                g = random_geometric_graph(
                    n, c=c, seed=graph_seed, radius=radius,
                    method=graph_method,
                )
            graph_s = time.perf_counter() - t0
            record_duration("/repro/core/graph_build", graph_s)
            info["graph_gen_s"] = round(graph_s, 6)
        t0 = time.perf_counter()
        with _span("repro.setup_plan.plan"):
            plan = build_plan(
                g, k=k, a=a, cell_max=cell_max, seed=seed,
                rep_mode=rep_mode, workers=workers,
            )
        record_duration("/repro/core/plan_build", time.perf_counter() - t0)
        info["plan_build_s"] = dict(plan.build_seconds or {})
        if use_cache:
            t0 = time.perf_counter()
            with _span("repro.setup_plan.store"):
                store_plan(key, plan, cache_dir=cache_dir)
            store_s = time.perf_counter() - t0
            record_duration("/repro/core/plan_store", store_s)
            info["store_s"] = round(store_s, 6)
            info["cache"] = "miss"
        else:
            info["cache"] = "off"
        info["setup_s"] = round(time.perf_counter() - t_all, 6)
        return plan, info
