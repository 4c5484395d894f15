"""Execution options for the plan/execute simulation core.

`ExecOptions` is the single static (hashable) config surface for HOW a
plan is executed — sharding mesh, convergence check cadence, tick
budget, usage read-out — mirroring the dist layer's `SyncConfig` →
`SyncPlan` pattern.  WHAT is simulated stays in positional/semantic
arguments (`eps`, `seeds`, `weighted`, `fixed_ticks_scale`) and the two
sibling dataclasses `FailureModel` / `CostModel` (`core.medium`).

The value pass is not an option: the executor has one, the presampled
schedule walked by `core.value_pass.pair_apply`, which picks its own
endpoint read from each level's shape, so there is nothing left for a
caller to choose.  An unknown keyword fails with `TypeError`, as do the
flat execute kwargs (``mesh=``, ``loss_p=``, ...): `execute_plan` /
`multiscale_gossip` take `options=ExecOptions(...)` and
`failures=FailureModel(...)` only.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

__all__ = ["ExecOptions"]


@dataclasses.dataclass(frozen=True)
class ExecOptions:
    """Static (hashable) description of how to execute a plan.

    mesh: optional `jax.sharding.Mesh` — 1-axis shards the trial axis;
        a 2-axis ``("trials", "nodes")`` mesh also blocks node batches.
    check_every: convergence-oracle cadence (static scan length).
    max_ticks_per_level: per-level tick budget in eps-oracle mode.
    collect_usage: also return the raw per-level flat exchange
        counters (attribution audits; off on the hot path).
    """

    mesh: Optional[Any] = None
    check_every: int = 64
    max_ticks_per_level: int = 2_000_000
    collect_usage: bool = False

    def __post_init__(self):
        if self.check_every < 1:
            raise ValueError("check_every must be >= 1")
