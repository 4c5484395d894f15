"""Chip benchmark of the multiscale-gossip simulation core.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

`BENCHMARK.json` at the root of the checkout names the cells; each
configuration (`configs/`), traffic mix (`traffic/`) and metric
(`metrics/` end to end, `layers/` per layer) is a file of its own that
the harness finds by name.
"""
