#!/usr/bin/env bash
# CI entry point: tier-1 test suite + a fast benchmark smoke gated by the
# artifact-regression check.
#
#   tools/ci.sh                     # tier-1 (-m "not slow") + fig2/fig3
#                                   #   smokes + fig5 scenario-matrix
#                                   #   smoke through
#                                   #   tools/check_artifacts.py (±15%
#                                   #   message-count / error / priced-
#                                   #   cost gate vs the committed
#                                   #   artifacts)
#   tools/ci.sh --no-bench          # tests only
#   tools/ci.sh --bench-only        # gate + smokes only (CI job 2: the
#                                   #   tier1 job already ran the tests)
#   REPRO_BENCH_SMOKE=1 tools/ci.sh # + large-n CSR-path smoke gate
#                                   #   (tools/check_artifacts.py
#                                   #   --large-n-only: n=20k FI re-run
#                                   #   ±15% vs the committed
#                                   #   large_n_smoke artifact, incl.
#                                   #   the reference-vs-vectorized
#                                   #   plan-builder overlap parity)
#                                   # + fig3 device-resident smoke
#                                   #   (n=500, trials=1, its own
#                                   #   fig3_smoke_lax artifact), then
#                                   #   an entry appended to the
#                                   #   BENCH_gossip.json perf trajectory
#                                   # + compressed decentralized-train smoke
#                                   #   (2 steps, topk+rotation, multiscale,
#                                   #   R=8) and an async-overlap train
#                                   #   smoke (one-step-delayed averaging)
#                                   # + serving-fleet smoke (16 replicas,
#                                   #   p2c-from-gossip vs oracle vs random)
#                                   #   and a BENCH_serve.json trajectory
#                                   #   entry (fleet + paged-decode tok/s)
#                                   # + robust-train smoke (R=8, churn +
#                                   #   Byzantine, trimmed_mean + topk) and
#                                   #   the robust_train_smoke drift gate
#                                   #   (tools/check_artifacts.py
#                                   #   --robust-train-only: survivor
#                                   #   consensus error / loss / degradation
#                                   #   metrics ±15%)
#
# The slow tier (multi-device subprocess + vmap-parity tests) is
# NOT run here — .github/workflows/ci.yml's second job runs `-m slow`.
# A bare `python -m pytest -x -q` still runs both tiers.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${1:-}" != "--bench-only" ]]; then
    echo "== tier-1 tests (-m 'not slow') =="
    python -m pytest -x -q -m "not slow"
fi

if [[ "${1:-}" != "--no-bench" ]]; then
    echo "== benchmark smoke + artifact-regression gate (fig2 + fig3 + fig5 scenarios) =="
    # --fig5: re-runs the failure-scenario matrix smoke (n=300, 5
    # scenarios: baseline/churn/stragglers/regional/byzantine) and gates
    # achieved error + priced medium cost ±15% vs the committed
    # fig5_smoke artifact
    python tools/check_artifacts.py --fig5
fi

if [[ "${REPRO_BENCH_SMOKE:-0}" == "1" ]]; then
    # a scratch artifact name: the smoke must not clobber the full-run
    # artifact
    echo "== benchmark smoke (fig3 n=500 trials=1) =="
    python -m benchmarks.fig3_vs_path_averaging --sizes 500 --trials 1 \
        --artifact fig3_smoke_lax
    echo "== large-n smoke gate (n=20k FI, CSR path, ±15% vs committed) =="
    python tools/check_artifacts.py --large-n-only
    echo "== gossip perf trajectory (BENCH_gossip.json) =="
    python -m benchmarks.gossip_trajectory --label "ci smoke"
    echo "== compressed decentralized-train smoke (R=8, topk, multiscale) =="
    python examples/decentralized_consensus.py --strategy multiscale \
        --compress topk --rotate 4 --replicas 8 --steps 2
    echo "== async-overlap decentralized-train smoke (R=8, one_step) =="
    python examples/decentralized_consensus.py --strategy multiscale \
        --overlap --replicas 8 --steps 3
    echo "== serving-fleet smoke (16 replicas, 3 routers) + BENCH_serve.json =="
    python examples/serve_fleet.py --replicas 16 --ticks 120
    python -m benchmarks.serve_bench --label "ci smoke"
    echo "== robust-train smoke (R=8, churn+byzantine, trimmed_mean, topk) =="
    python examples/robust_training.py --replicas 8 --steps 8 \
        --churn 0.25 --byzantine 0.125 --aggregation trimmed_mean \
        --compress topk
    echo "== robust-train drift gate (survivor consensus error vs committed) =="
    python tools/check_artifacts.py --robust-train-only
fi

echo "CI OK"
