"""Memory probes: gossip plan+execute footprint and the model-cell
HLO-buffer dry run.

Gossip mode (importable; used by the large-n benchmark smoke) reports
the peak host RSS and live device-buffer bytes for building and
executing a `HierarchyPlan` at a given n:

  PYTHONPATH=src python tools/membuf_probe.py --gossip-n 100000

`--graph-only` restricts the probe to graph generation (the streamed
bucket builder's peak RSS, no plan build or execute):

  PYTHONPATH=src python tools/membuf_probe.py --gossip-n 1000000 \
      --graph-only [--chunk 8000] [--graph-method bucket|reference]

Model mode compiles a (reduced-depth) cell and lists the largest
per-device HLO buffers — the working tool behind the §Perf memory
iterations.  It forces a 512-device host platform, so it runs as a
fresh process only (never import-triggered):

  PYTHONPATH=src python tools/membuf_probe.py --arch grok-1-314b \
      --shape train_4k --unit "attn" --layers 1 [--top 15]
"""
from __future__ import annotations

import argparse
import re
import sys

SHAPE_RE = re.compile(r"^\s*%?\S+ = ([a-z0-9]+)\[([\d,]+)\]")


# --------------------------- gossip probes -----------------------------


def host_peak_rss_bytes() -> int:
    """Peak resident set size of this process so far, in bytes.

    `ru_maxrss` is KiB on Linux and bytes on macOS; normalize to bytes.
    """
    import resource

    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    if sys.platform != "darwin":
        peak *= 1024
    return int(peak)


def device_live_bytes() -> int:
    """Total bytes of live (committed) jax device buffers right now."""
    import jax

    return int(sum(int(a.nbytes) for a in jax.live_arrays()))


def memory_report() -> dict:
    """Snapshot both probes — call after the work being measured."""
    return {
        "host_peak_rss_bytes": host_peak_rss_bytes(),
        "device_live_bytes": device_live_bytes(),
    }


def gossip_memory_report(
    n: int,
    *,
    seed: int = 0,
    eps: float = 1e-3,
    fixed_ticks_scale: float = 0.2,
    trials: int = 1,
    method: str = "vectorized",
) -> dict:
    """Build and execute a multiscale plan at size `n`, reporting the
    peak host RSS and live device-buffer bytes alongside the
    `build_seconds` breakdown.  Defaults mirror the large-n benchmark
    profile (fixed-iterations mode, one trial).
    """
    import numpy as np

    from repro.core import build_plan, execute_plan, random_geometric_graph

    g = random_geometric_graph(n, seed=1000 + n)
    x0 = np.random.default_rng(n).normal(0, 1, n)
    plan = build_plan(g, seed=seed, method=method)
    res = execute_plan(
        plan, x0, eps=eps, seeds=tuple(seed + t for t in range(trials)),
        weighted=True, fixed_ticks_scale=fixed_ticks_scale,
    )
    report = memory_report()
    report.update(
        n=int(n),
        levels=len(plan.levels),
        plan_build_s=dict(plan.build_seconds or {}),
        messages=[int(m) for m in np.asarray(res.messages)],
        err=[float(e) for e in np.atleast_1d(res.error(x0))],
    )
    return report


def graph_gen_memory_report(
    n: int,
    *,
    seed: int | None = None,
    method: str = "bucket",
    chunk: int | None = None,
) -> dict:
    """Peak host RSS of graph generation ALONE at size `n` — the probe
    behind the streamed bucket builder's O(chunk + nnz) memory claim
    (the old cKDTree + dense-padded path peaked on the `(n, max_deg)`
    intermediate instead).  `seed` defaults to the benchmark convention
    `1000 + n`."""
    import time

    from repro.core import random_geometric_graph

    kw = {} if chunk is None else {"chunk": chunk}
    t0 = time.perf_counter()
    g = random_geometric_graph(
        n, seed=(1000 + n) if seed is None else seed, method=method, **kw
    )
    dt = time.perf_counter() - t0
    return {
        "n": int(n),
        "method": method,
        "chunk": chunk,
        "nnz": int(g.nnz),
        "graph_gen_s": float(dt),
        "host_peak_rss_bytes": host_peak_rss_bytes(),
    }


# ---------------------------- model probe ------------------------------


def probe(arch, shape, unit=None, layers=None, top=15, multi_pod=False):
    import dataclasses
    from collections import Counter

    import jax

    from repro.configs import get_config
    from repro.launch.hlo_analysis import DTYPE_BYTES
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import build_cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    changes = {}
    if unit:
        changes["block_unit"] = tuple(unit.split(","))
    if layers:
        changes["num_layers"] = layers
        if cfg.encoder_layers:
            changes["encoder_layers"] = min(cfg.encoder_layers, layers)
    if changes:
        cfg = dataclasses.replace(cfg, **changes)
    cell = build_cell(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        c = (
            jax.jit(cell.fn, in_shardings=cell.in_shardings,
                    out_shardings=cell.out_shardings,
                    donate_argnums=cell.donate)
            .lower(*cell.args_abs)
            .compile()
        )
    ma = c.memory_analysis()
    print(f"{arch} {shape} layers={cfg.num_layers} unit={cfg.block_unit}: "
          f"args={ma.argument_size_in_bytes/2**30:.2f}GiB "
          f"temp={ma.temp_size_in_bytes/2**30:.2f}GiB")
    sizes = Counter()
    for line in c.as_text().splitlines():
        m = SHAPE_RE.match(line)
        if m and m.group(1) in DTYPE_BYTES:
            n = 1
            for d in m.group(2).split(","):
                n *= int(d)
            sizes[(m.group(1), m.group(2))] += 1
    items = sorted(
        sizes.items(),
        key=lambda kv: -DTYPE_BYTES[kv[0][0]]
        * eval(kv[0][1].replace(",", "*")),
    )
    shown = 0
    for (dt, dims), cnt in items:
        n = 1
        for d in dims.split(","):
            n *= int(d)
        b = n * DTYPE_BYTES[dt]
        if b < 2**27:
            break
        print(f"  {dt}[{dims}] x{cnt}  {b/2**30:.2f}GiB each")
        shown += 1
        if shown >= top:
            break


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--gossip-n", type=int, default=None,
                    help="probe the gossip plan+execute path at this n "
                         "instead of compiling a model cell")
    ap.add_argument("--graph-only", action="store_true",
                    help="with --gossip-n: probe graph generation alone "
                         "(the streamed builder's RSS, no plan/execute)")
    ap.add_argument("--graph-method", default="bucket",
                    help="graph builder for --graph-only (bucket|reference)")
    ap.add_argument("--chunk", type=int, default=None,
                    help="bucket-builder chunk size for --graph-only")
    ap.add_argument("--scale", type=float, default=0.2,
                    help="fixed_ticks_scale for the gossip probe")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--unit", default=None)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--multi-pod", action="store_true")
    a = ap.parse_args()
    if a.gossip_n is not None:
        import json

        if a.graph_only:
            rep = graph_gen_memory_report(
                a.gossip_n, method=a.graph_method, chunk=a.chunk
            )
            rss = rep["host_peak_rss_bytes"] / 2**30
            print(f"graph n={a.gossip_n} ({rep['method']}): "
                  f"peak_rss={rss:.2f}GiB nnz={rep['nnz']} "
                  f"gen={rep['graph_gen_s']:.2f}s")
            print(json.dumps(rep, indent=1))
        else:
            rep = gossip_memory_report(a.gossip_n, fixed_ticks_scale=a.scale)
            rss = rep["host_peak_rss_bytes"] / 2**30
            dev = rep["device_live_bytes"] / 2**20
            print(f"gossip n={a.gossip_n}: peak_rss={rss:.2f}GiB "
                  f"device_live={dev:.1f}MiB "
                  f"build={rep['plan_build_s'].get('total', 0.0):.2f}s")
            print(json.dumps(rep, indent=1))
    else:
        if a.arch is None:
            ap.error("--arch is required without --gossip-n")
        # the model probe compiles against a production-sized mesh;
        # the 512-device host forcing must precede the first jax import
        import os

        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=512"
        )
        probe(a.arch, a.shape, a.unit, a.layers, a.top, a.multi_pod)
