import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run (deliverable (e)).

For every (architecture x input shape x mesh) cell:
  1. lower + compile the FULL-DEPTH production step (scan-over-layers)
     -> proof the sharding config is coherent and the memory fits
        (compiled.memory_analysis());
  2. lower + compile 1-unit and 2-unit UNROLLED depth variants per
     distinct layer group -> loop-aware per-step totals for flops,
     bytes, and collective bytes (XLA cost_analysis counts while-loop
     bodies once; see hlo_analysis.secant_totals);
  3. emit a JSON artifact under benchmarks/artifacts/dryrun/ with the
     roofline terms (197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s/link ICI).

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import numpy as np

from repro.configs.registry import ARCH_IDS, SHAPES, cell_is_runnable, get_config
from repro.launch.hlo_analysis import CollectiveStats, collective_bytes
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell

HW = {
    "peak_flops_per_chip": 197e12,   # bf16 TFLOP/s (TPU v5e)
    "hbm_bw_per_chip": 819e9,        # B/s
    "ici_bw_per_link": 50e9,         # B/s
}
ARTIFACT_DIR = os.path.join("benchmarks", "artifacts", "dryrun")


# ------------------------- analytic model flops ------------------------


def active_params(cfg) -> tuple[int, int]:
    """(total, active) parameter counts; active replaces each MoE
    layer's E experts by the K routed ones."""
    from repro.models import Transformer

    total = Transformer(cfg).num_params
    if not cfg.num_experts:
        return total, total
    n_moe_layers = sum(1 for k in cfg.layer_kinds() if k in ("attn", "local"))
    per_expert = 3 * cfg.d_model * cfg.d_ff
    moe_total = n_moe_layers * cfg.num_experts * per_expert
    moe_active = n_moe_layers * cfg.experts_per_token * per_expert
    return total, total - moe_total + moe_active


def model_flops(cfg, shape_name: str) -> float:
    S, B, mode = SHAPES[shape_name]
    _, n_active = active_params(cfg)
    tokens = B * S if mode in ("train", "prefill") else B
    if mode == "train":
        return 6.0 * n_active * tokens
    return 2.0 * n_active * tokens


# ------------------------------ compiles -------------------------------


def _compile(cfg, shape_name, mesh, model_axis=16):
    cell = build_cell(cfg, shape_name, mesh, model_axis=model_axis)
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            cell.fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate,
        )
        lowered = jitted.lower(*cell.args_abs)
        compiled = lowered.compile()
    return cell, lowered, compiled


def _cost_record(compiled, pod_size: int) -> dict:
    ca = compiled.cost_analysis() or {}
    text = compiled.as_text()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "collectives": collective_bytes(text, pod_size=pod_size),
    }


def _depth_variants(cfg):
    """[(variant_1u_cfg, variant_2u_cfg, repeats)] per distinct group."""
    out = []
    for unit, repeats in cfg.scan_groups():
        changes = dict(block_unit=unit, scan_unroll=True)
        enc = cfg.encoder_layers
        v1 = dataclasses.replace(
            cfg, num_layers=len(unit), encoder_layers=min(enc, 1) if enc else 0,
            **changes,
        )
        v2 = dataclasses.replace(
            cfg, num_layers=2 * len(unit),
            encoder_layers=min(enc, 2) if enc else 0, **changes,
        )
        out.append((v1, v2, repeats))
    return out


def loop_aware_totals(cfg, shape_name, mesh, pod_size) -> dict:
    """Exact per-step totals via the secant method over depth variants.

    For whisper the encoder scales with the variants too (enc repeats ==
    decoder repeats for the assigned config), so the unit includes one
    encoder layer and the extrapolation stays exact.
    """
    variants = _depth_variants(cfg)
    stem = None
    total = {"flops": 0.0, "bytes": 0.0, "collectives": CollectiveStats()}
    for i, (v1, v2, repeats) in enumerate(variants):
        _, _, c1 = _compile(v1, shape_name, mesh)
        r1 = _cost_record(c1, pod_size)
        _, _, c2 = _compile(v2, shape_name, mesh)
        r2 = _cost_record(c2, pod_size)
        unit = {
            "flops": r2["flops"] - r1["flops"],
            "bytes": r2["bytes"] - r1["bytes"],
            "collectives": r2["collectives"] - r1["collectives"],
        }
        if stem is None:
            stem = {
                "flops": r1["flops"] - unit["flops"],
                "bytes": r1["bytes"] - unit["bytes"],
                "collectives": r1["collectives"] - unit["collectives"],
            }
        total["flops"] += repeats * unit["flops"]
        total["bytes"] += repeats * unit["bytes"]
        total["collectives"] = total["collectives"] + unit["collectives"].scaled(repeats)
    total["flops"] += stem["flops"]
    total["bytes"] += stem["bytes"]
    total["collectives"] = total["collectives"] + stem["collectives"]
    return total


def roofline_terms(totals: dict, chips: int) -> dict:
    """totals are PER-DEVICE module costs (XLA analyzes the SPMD
    partition); x chips = fleet totals, then the assignment's formulas."""
    # clamp tiny negative secant wiggles (variant-dependent stem patterns)
    flops_global = max(totals["flops"], 0.0) * chips
    bytes_global = max(totals["bytes"], 0.0) * chips
    coll = totals["collectives"]
    coll.total_bytes = max(coll.total_bytes, 0)
    coll.cross_pod_bytes = max(coll.cross_pod_bytes, 0)
    compute_s = flops_global / (chips * HW["peak_flops_per_chip"])
    memory_s = bytes_global / (chips * HW["hbm_bw_per_chip"])
    collective_s = coll.total_bytes / (chips * HW["ici_bw_per_link"])
    dominant = max(
        ("compute", compute_s), ("memory", memory_s), ("collective", collective_s),
        key=lambda kv: kv[1],
    )[0]
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": dominant,
        "hlo_flops_global": flops_global,
        "hlo_bytes_global": bytes_global,
        "collective_bytes": coll.total_bytes,
        "cross_pod_bytes": coll.cross_pod_bytes,
        "collectives_by_kind": coll.by_kind,
    }


# -------------------------------- cells --------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = ARTIFACT_DIR, with_roofline: bool = True) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(
        out_dir, f"{arch}__{shape_name}__{mesh_name}.json".replace("/", "_")
    )
    cfg = get_config(arch)
    runnable, reason = cell_is_runnable(cfg, shape_name)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "multi_pod": multi_pod, "status": "skip", "reason": reason,
    }
    if not runnable:
        json.dump(rec, open(out_path, "w"), indent=1)
        return rec
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = int(np.prod(list(mesh.shape.values())))
        pod_size = 256
        cell, lowered, compiled = _compile(cfg, shape_name, mesh)
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "peak_bytes": int(
                ma.argument_size_in_bytes + ma.temp_size_in_bytes
            ),
            "fits_16GiB": bool(
                ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16 * 2**30
            ),
        }
        full_coll = collective_bytes(compiled.as_text(), pod_size=pod_size)
        rec.update(
            status="ok",
            compile_seconds=round(time.time() - t0, 1),
            chips=chips,
            mode=cell.mode,
            num_params=cell.meta["num_params"],
            memory=mem,
            fulldepth_collectives_once=full_coll.asdict(),  # body counted once
        )
        if with_roofline:
            totals = loop_aware_totals(cfg, shape_name, mesh, pod_size)
            terms = roofline_terms(totals, chips)
            mf = model_flops(cfg, shape_name)
            terms["model_flops"] = mf
            terms["model_flops_ratio"] = (
                mf / terms["hlo_flops_global"] if terms["hlo_flops_global"] else 0.0
            )
            rec["roofline"] = {
                k: (v if not isinstance(v, dict) else v)
                for k, v in terms.items()
            }
    except Exception as e:  # record the failure — dry-run bugs are bugs
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    json.dump(rec, open(out_path, "w"), indent=1, default=str)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-roofline", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    args = ap.parse_args()

    cells = []
    archs = list(ARCH_IDS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cells.append((arch, shape, mp))
    for arch, shape, mp in cells:
        rec = run_cell(arch, shape, mp, out_dir=args.out,
                       with_roofline=not args.no_roofline)
        status = rec["status"]
        extra = ""
        if status == "ok":
            extra = (
                f" mem={rec['memory']['peak_bytes']/2**30:.1f}GiB"
                f" fits={rec['memory']['fits_16GiB']}"
            )
            if "roofline" in rec:
                r = rec["roofline"]
                extra += (
                    f" dom={r['dominant']}"
                    f" c={r['compute_s']*1e3:.1f}ms"
                    f" m={r['memory_s']*1e3:.1f}ms"
                    f" x={r['collective_s']*1e3:.1f}ms"
                )
        elif status == "error":
            extra = " " + rec["error"][:120]
        elif status == "skip":
            extra = " " + rec["reason"]
        print(f"[{status:5s}] {arch} {shape} "
              f"{'multi' if mp else 'single'}{extra}", flush=True)


if __name__ == "__main__":
    main()
