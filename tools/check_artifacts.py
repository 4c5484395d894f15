#!/usr/bin/env python
"""Benchmark-regression gate over the committed artifacts.

Re-runs the fig2 smoke (same n / eps / seeds as the committed run —
the benchmark is deterministic, so honest drift comes from algorithm
changes, not noise) and compares per-level-count message means against
`benchmarks/artifacts/fig2_levels.json` within a relative tolerance.
The same gate then covers the fig3 smoke: the committed artifact
`fig3_smoke_lax` is re-run at its recorded n / eps / trials and the
per-algorithm message means are compared within the same tolerance (wall-clocks are machine-dependent
and NOT gated).  Artifact drift then fails CI loudly instead of being
silently committed the next time someone regenerates the artifacts.

Fresh runs are written to scratch artifacts (`*_check`) so the
committed files are never clobbered by a drifting run — regenerating a
committed artifact on purpose stays an explicit
`python -m benchmarks.run --only fig2` / `REPRO_BENCH_SMOKE=1
tools/ci.sh`.

    python tools/check_artifacts.py [--tolerance 0.15] [--trials N]
                                    [--skip-fig3]

Exit status: 0 when every row is within tolerance, 1 on drift or a
missing committed artifact.  Wired into tools/ci.sh as the benchmark
smoke gate.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

COMMITTED = "fig2_levels"
SCRATCH = "fig2_levels_check"
FIG3 = "fig3_smoke_lax"
LARGE_N = "large_n_smoke"
FIG5 = "fig5_smoke"
ROBUST_TRAIN = "robust_train_smoke"
# minimum absolute graph_gen_s drift (seconds) that counts as real: the
# smoke builds in ~0.2s, where scheduler noise alone exceeds 15%
GRAPH_GEN_FLOOR_S = 0.5


def check_fig3(tolerance: float) -> list[str]:
    """Gate the fig3 smoke message counts."""
    from benchmarks import fig3_vs_path_averaging
    from benchmarks.common import load_artifact

    name = FIG3
    committed = load_artifact(name)
    if committed is None:
        return [f"  {name}: committed artifact benchmarks/artifacts/"
                f"{name}.json is missing; run REPRO_BENCH_SMOKE=1 "
                f"tools/ci.sh and commit the result"]
    sizes = tuple(sorted({
        int(n) for rows in committed["summary"].values() for n in rows
    }))
    print(f"check_artifacts: re-running fig3 smoke (sizes={sizes}, "
          f"trials={committed['trials']}, eps={committed['eps']}) against "
          f"{name} (tolerance ±{tolerance:.0%})")
    fig3_vs_path_averaging.run(
        sizes=sizes, trials=int(committed["trials"]),
        eps=float(committed["eps"]), artifact=f"{name}_check",
    )
    fresh = load_artifact(f"{name}_check")
    failures = []
    for algo, rows in committed["summary"].items():
        for n, rec in rows.items():
            want = float(rec["messages_mean"])
            got_rec = fresh["summary"].get(algo, {}).get(
                n, fresh["summary"].get(algo, {}).get(str(n)))
            if got_rec is None:
                failures.append(
                    f"  {name} {algo}@n{n}: missing from the fresh run")
                continue
            got = float(got_rec["messages_mean"])
            rel = abs(got - want) / max(want, 1.0)
            status = "ok" if rel <= tolerance else "DRIFT"
            print(f"  {algo}@n{n}: committed={want:.0f} "
                  f"fresh={got:.0f} rel={rel:+.1%} [{status}]")
            if rel > tolerance:
                failures.append(
                    f"  {name} {algo}@n{n}: messages_mean drifted "
                    f"{rel:.1%} (committed {want:.0f} -> fresh {got:.0f},"
                    f" tolerance {tolerance:.0%})")
    return failures


def check_fig5(tolerance: float) -> list[str]:
    """Gate the fig5 failure-scenario smoke: achieved error and priced
    medium cost (energy) per scenario, plus the loss-model error, must
    stay within tolerance of the committed `fig5_smoke` artifact.

    The smoke is deterministic (shared plan, fixed gossip and
    failure-injection seeds), so drift means the scenario replay or the
    cost pricing changed — exactly what this gate is for.
    """
    from benchmarks import fig5_failures
    from benchmarks.common import load_artifact

    committed = load_artifact(FIG5)
    if committed is None:
        return [
            f"  {FIG5}: committed artifact benchmarks/artifacts/{FIG5}.json "
            f"is missing; run `python -m benchmarks.fig5_failures --n 300 "
            f"--trials 2 --scenario-trials 2 --ps 0.7,1.0 "
            f"--artifact {FIG5}` and commit the result"
        ]
    sm = committed.get("scenario_matrix") or {}
    sc_committed = sm.get("scenarios") or {}
    if len(sc_committed) < 4:
        return [
            f"  {FIG5}: committed artifact has {len(sc_committed)} "
            "scenarios; the gate wants the >=4-scenario matrix — "
            "regenerate with --scenario-trials > 0"
        ]
    ps = tuple(float(p) for p in committed["handshake"])
    print(f"check_artifacts: re-running fig5 smoke "
          f"(n={committed['n']}, trials={committed['trials']}, "
          f"eps={committed['eps']}, scenarios={sorted(sc_committed)}) "
          f"against {FIG5} (tolerance ±{tolerance:.0%})")
    fig5_failures.run(
        n=int(committed["n"]), eps=float(committed["eps"]), ps=ps,
        trials=int(committed["trials"]),
        scenario_trials=int(sm["trials"]),
        scenario_scale=float(sm["fixed_ticks_scale"]),
        scenario_retransmit_p=float(sm["retransmit_p"]),
        artifact=f"{FIG5}_check",
    )
    fresh = load_artifact(f"{FIG5}_check")
    failures = []

    def gate(label, want, got, floor):
        rel = abs(got - want) / max(abs(want), floor)
        status = "ok" if rel <= tolerance else "DRIFT"
        print(f"  {label}: committed={want:.4g} fresh={got:.4g} "
              f"rel={rel:+.1%} [{status}]")
        if rel > tolerance:
            failures.append(
                f"  {FIG5} {label}: drifted {rel:.1%} "
                f"(committed {want:.4g} -> fresh {got:.4g}, "
                f"tolerance {tolerance:.0%})")

    fresh_sc = (fresh.get("scenario_matrix") or {}).get("scenarios") or {}
    for name, rec in sc_committed.items():
        got = fresh_sc.get(name)
        if got is None:
            failures.append(f"  {FIG5} scenario {name}: missing from the "
                            "fresh run")
            continue
        # error floor 1e-3: a reliable baseline converges to ~0 where
        # relative drift is meaningless noise on an already-passing run
        gate(f"scenario/{name}/err", float(rec["err_mean"]),
             float(got["err_mean"]), 1e-3)
        gate(f"scenario/{name}/energy", float(rec["energy_mean"]),
             float(got["energy_mean"]), 1.0)
    lm_want = committed["loss_model"]["multiscale"]
    lm_got = fresh["loss_model"]["multiscale"]
    gate("loss_model/ms_err", float(lm_want["err"]), float(lm_got["err"]),
         1e-3)
    return failures


def _run_robust_train(num_steps: int, artifact: str) -> dict:
    """Run the robust-training scenario smoke (tiny model, R=8,
    reliable baseline + churn+Byzantine with the trimmed-mean defense)
    and persist the summary metrics as `artifact`.  Deterministic:
    fixed model init, fixed synthetic stream, fixed failure seed."""
    import jax

    from benchmarks.common import save_artifact
    from repro.data import SyntheticLM
    from repro.dist import SyncConfig, SyncFailureModel
    from repro.models import Transformer
    from repro.models.config import ModelConfig
    from repro.optim import sgdm
    from repro.train import TrainScenario, run_train_scenarios

    R = 8
    cfg = ModelConfig(
        name="robust-gate", family="dense", num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
        remat=False, dtype="float32",
    )
    model = Transformer(cfg, model_axis=1)
    params = model.init(jax.random.PRNGKey(0))
    data = SyntheticLM(cfg.vocab_size, seq_len=32, global_batch=R * 2, seed=7)
    scenarios = [
        TrainScenario("baseline", None, "mean", "reliable replicas"),
        TrainScenario(
            "churn_byzantine",
            SyncFailureModel(churn_fraction=0.125, byzantine_fraction=0.125,
                             byzantine_scale=10.0, seed=4),
            "trimmed_mean",
            "12.5% churn + 12.5% Byzantine (x10), trimmed-mean defense",
        ),
    ]
    res = run_train_scenarios(
        cfg, sgdm(), lambda s: 1e-2, SyncConfig("multiscale"), R,
        params, data, scenarios, num_steps=num_steps,
    )
    payload = {
        "R": R, "num_steps": num_steps,
        "scenarios": {
            r.scenario.name: {
                "final_loss": r.final_loss,
                "loss_drop": r.loss_drop,
                "survivor_error_final": r.survivor_error_final,
                "effective_replica_fraction_mean":
                    r.effective_replica_fraction_mean,
                "rejected_gradients_total": r.rejected_gradients_total,
            }
            for r in res
        },
    }
    save_artifact(artifact, payload)
    return payload


def check_robust_train(tolerance: float) -> list[str]:
    """Gate the robust-training smoke: per-scenario final loss,
    survivor consensus error (floor 1e-3 — a reliable baseline sits at
    ~0 where relative drift is noise), effective replica fraction, and
    rejected-gradient totals vs the committed `robust_train_smoke`
    artifact.  Drift means the failure injection, robust reduction, or
    degradation metrics changed."""
    from benchmarks.common import load_artifact

    committed = load_artifact(ROBUST_TRAIN)
    if committed is None:
        return [
            f"  {ROBUST_TRAIN}: committed artifact benchmarks/artifacts/"
            f"{ROBUST_TRAIN}.json is missing; run `python "
            f"tools/check_artifacts.py --robust-train-regen` and commit "
            f"the result"
        ]
    print(f"check_artifacts: re-running robust-train smoke "
          f"(R={committed['R']}, steps={committed['num_steps']}, "
          f"scenarios={sorted(committed['scenarios'])}) against "
          f"{ROBUST_TRAIN} (tolerance ±{tolerance:.0%})")
    fresh = _run_robust_train(
        int(committed["num_steps"]), f"{ROBUST_TRAIN}_check")
    failures = []

    def gate(label, want, got, floor):
        rel = abs(got - want) / max(abs(want), floor)
        status = "ok" if rel <= tolerance else "DRIFT"
        print(f"  {label}: committed={want:.4g} fresh={got:.4g} "
              f"rel={rel:+.1%} [{status}]")
        if rel > tolerance:
            failures.append(
                f"  {ROBUST_TRAIN} {label}: drifted {rel:.1%} "
                f"(committed {want:.4g} -> fresh {got:.4g}, "
                f"tolerance {tolerance:.0%})")

    floors = {
        "final_loss": 1.0,
        "loss_drop": 0.1,
        "survivor_error_final": 1e-3,
        "effective_replica_fraction_mean": 1e-2,
        "rejected_gradients_total": 1.0,
    }
    for name, rec in committed["scenarios"].items():
        got = fresh["scenarios"].get(name)
        if got is None:
            failures.append(
                f"  {ROBUST_TRAIN} scenario {name}: missing from the "
                "fresh run")
            continue
        for metric, floor in floors.items():
            gate(f"scenario/{name}/{metric}", float(rec[metric]),
                 float(got[metric]), floor)
    return failures


def check_large_n(tolerance: float) -> list[str]:
    """Gate the large-n CSR-path smoke (n=20k FI run) message count.

    Re-runs `benchmarks.large_n --smoke` at the committed artifact's
    exact profile into a scratch artifact and compares total messages.
    The run itself also re-asserts the reference-vs-vectorized overlap
    parity at n=2000, so plan-builder drift fails here too.
    """
    from benchmarks import large_n
    from benchmarks.common import load_artifact

    committed = load_artifact(LARGE_N)
    if committed is None:
        return [
            f"  {LARGE_N}: committed artifact benchmarks/artifacts/"
            f"{LARGE_N}.json is missing; run "
            f"`python -m benchmarks.large_n --smoke` and commit the result"
        ]
    overlap_n = int((committed.get("overlap") or {}).get("n", 2000))
    print(f"check_artifacts: re-running large-n smoke "
          f"(n={committed['n']}, scale={committed['fixed_ticks_scale']}, "
          f"overlap_n={overlap_n}) against "
          f"{LARGE_N} (tolerance ±{tolerance:.0%})")
    try:
        large_n.run(
            n=int(committed["n"]), overlap_n=overlap_n,
            trials=int(committed["trials"]), eps=float(committed["eps"]),
            fixed_ticks_scale=float(committed["fixed_ticks_scale"]),
            artifact=f"{LARGE_N}_check",
        )
    except SystemExit as e:  # overlap-parity failure inside the benchmark
        return [f"  {LARGE_N}: {e}"]
    fresh = load_artifact(f"{LARGE_N}_check")
    failures = []
    want = float(committed["messages"][0])
    got = float(fresh["messages"][0])
    rel = abs(got - want) / max(want, 1.0)
    status = "ok" if rel <= tolerance else "DRIFT"
    print(f"  large_n@n{committed['n']}: committed={want:.0f} "
          f"fresh={got:.0f} rel={rel:+.1%} [{status}]")
    if rel > tolerance:
        failures.append(
            f"  {LARGE_N}@n{committed['n']}: messages drifted {rel:.1%} "
            f"(committed {want:.0f} -> fresh {got:.0f}, "
            f"tolerance {tolerance:.0%})")
    # graph_gen_s gate: the streamed builder's wall clock at the smoke
    # size, ±tolerance but with an absolute floor — sub-second timings
    # jitter tens of percent with host load, so only a drift that is
    # ALSO >= the floor in absolute seconds is a real builder regression
    want_g = committed.get("graph_gen_s")
    if want_g is not None:
        got_g = float(fresh["graph_gen_s"])
        want_g = float(want_g)
        rel_g = abs(got_g - want_g) / max(want_g, 1e-9)
        abs_g = abs(got_g - want_g)
        bad = rel_g > tolerance and abs_g >= GRAPH_GEN_FLOOR_S
        status = "DRIFT" if bad else "ok"
        print(f"  large_n@n{committed['n']}: graph_gen_s "
              f"committed={want_g:.3f}s fresh={got_g:.3f}s rel={rel_g:+.1%} "
              f"[{status}]")
        if bad:
            failures.append(
                f"  {LARGE_N}@n{committed['n']}: graph_gen_s drifted "
                f"{rel_g:.1%} ({want_g:.3f}s -> {got_g:.3f}s, tolerance "
                f"{tolerance:.0%} with {GRAPH_GEN_FLOOR_S}s floor)")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tolerance", type=float, default=0.15,
                    help="max relative drift of messages_mean per level count")
    ap.add_argument("--trials", type=int, default=None,
                    help="override trial count of the fresh run (defaults "
                         "to 3, the committed profile)")
    ap.add_argument("--skip-fig3", action="store_true",
                    help="gate only the fig2 artifact")
    ap.add_argument("--fig5", action="store_true",
                    help="also gate the fig5 failure-scenario smoke "
                         "(error + priced cost per scenario vs the "
                         "committed fig5_smoke artifact)")
    ap.add_argument("--fig5-only", action="store_true",
                    help="gate ONLY the fig5 failure-scenario smoke")
    ap.add_argument("--large-n", action="store_true",
                    help="also gate the large-n smoke (n=20k FI run; "
                         "slower, run under REPRO_BENCH_SMOKE=1)")
    ap.add_argument("--large-n-only", action="store_true",
                    help="gate ONLY the large-n smoke")
    ap.add_argument("--robust-train-only", action="store_true",
                    help="gate ONLY the robust-training scenario smoke "
                         "(survivor consensus error / loss / degradation "
                         "metrics vs the committed robust_train_smoke)")
    ap.add_argument("--robust-train-regen", action="store_true",
                    help="regenerate the committed robust_train_smoke "
                         "artifact in place (8 steps) and exit")
    args = ap.parse_args()

    from benchmarks import fig2_levels
    from benchmarks.common import load_artifact

    if args.robust_train_regen:
        _run_robust_train(8, ROBUST_TRAIN)
        print(f"check_artifacts: regenerated benchmarks/artifacts/"
              f"{ROBUST_TRAIN}.json — review and commit it")
        return 0

    if args.robust_train_only:
        failures = check_robust_train(args.tolerance)
        if failures:
            print("check_artifacts: FAIL — robust-train smoke drifted from "
                  "the committed artifact:")
            print("\n".join(failures))
            print("If the drift is intentional (algorithm change), "
                  "regenerate and commit: python tools/check_artifacts.py "
                  "--robust-train-regen")
            return 1
        print(f"check_artifacts: OK — robust-train smoke within "
              f"±{args.tolerance:.0%} of the committed artifact")
        return 0

    if args.large_n_only:
        failures = check_large_n(args.tolerance)
        if failures:
            print("check_artifacts: FAIL — large-n smoke drifted from the "
                  "committed artifact:")
            print("\n".join(failures))
            print("If the drift is intentional (algorithm change), "
                  "regenerate and commit: python -m benchmarks.large_n "
                  "--smoke")
            return 1
        print(f"check_artifacts: OK — large-n smoke within "
              f"±{args.tolerance:.0%} of the committed artifact")
        return 0

    if args.fig5_only:
        failures = check_fig5(args.tolerance)
        if failures:
            print("check_artifacts: FAIL — fig5 scenario smoke drifted from "
                  "the committed artifact:")
            print("\n".join(failures))
            print("If the drift is intentional (algorithm change), "
                  "regenerate and commit: python -m benchmarks.fig5_failures"
                  " --n 300 --trials 2 --scenario-trials 2 --ps 0.7,1.0 "
                  f"--artifact {FIG5}")
            return 1
        print(f"check_artifacts: OK — fig5 scenario smoke within "
              f"±{args.tolerance:.0%} of the committed artifact")
        return 0

    committed = load_artifact(COMMITTED)
    if committed is None:
        print(f"check_artifacts: FAIL — committed artifact "
              f"benchmarks/artifacts/{COMMITTED}.json is missing; run "
              f"`python -m benchmarks.run --only fig2` and commit the result")
        return 1

    ks = sorted(int(k) for k in committed["rows"])
    trials = args.trials if args.trials is not None else 3
    print(f"check_artifacts: re-running fig2 smoke "
          f"(n={committed['n']}, eps={committed['eps']}, trials={trials}, "
          f"k={ks[0]}..{ks[-1]}) against the committed artifact "
          f"(tolerance ±{args.tolerance:.0%})")
    fig2_levels.run(
        n=int(committed["n"]), trials=trials, eps=float(committed["eps"]),
        max_k=ks[-1], artifact=SCRATCH,
    )
    fresh = load_artifact(SCRATCH)

    failures = []
    for k in ks:
        want = float(committed["rows"][str(k)]["messages_mean"])
        got_row = fresh["rows"].get(k, fresh["rows"].get(str(k)))
        if got_row is None:
            failures.append(f"  k={k}: missing from the fresh run")
            continue
        got = float(got_row["messages_mean"])
        rel = abs(got - want) / max(want, 1.0)
        status = "ok" if rel <= args.tolerance else "DRIFT"
        print(f"  k={k}: committed={want:.0f} fresh={got:.0f} "
              f"rel={rel:+.1%} [{status}]")
        if rel > args.tolerance:
            failures.append(
                f"  k={k}: messages_mean drifted {rel:.1%} "
                f"(committed {want:.0f} -> fresh {got:.0f}, "
                f"tolerance {args.tolerance:.0%})"
            )

    if not args.skip_fig3:
        failures += check_fig3(args.tolerance)
    if args.fig5:
        failures += check_fig5(args.tolerance)
    if args.large_n:
        failures += check_large_n(args.tolerance)

    if failures:
        print("check_artifacts: FAIL — per-algorithm message counts drifted "
              "from the committed artifacts:")
        print("\n".join(failures))
        print("If the drift is intentional (algorithm change), regenerate "
              "and commit the artifacts: python -m benchmarks.run --only "
              "fig2 and REPRO_BENCH_SMOKE=1 tools/ci.sh for the fig3 smokes")
        return 1
    gated = "fig2" if args.skip_fig3 else "fig2 + fig3 smoke"
    if args.fig5:
        gated += " + fig5 scenario smoke"
    print(f"check_artifacts: OK — {gated} message counts within "
          f"±{args.tolerance:.0%} of the committed artifacts")
    return 0


if __name__ == "__main__":
    sys.exit(main())
