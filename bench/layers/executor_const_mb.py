"""Plan arrays baked into the executor as constants: the `bytes` of the
program's `/repro/core/executor_consts` events (one per level of each
executor built), from its own running totals
(`repro.core.event_totals()`), in MB.  None where the program keeps no
totals."""
from bench import deploy


def read(run):
    core, _ = deploy.program()
    if not hasattr(core, "event_totals"):
        return None
    total = core.event_totals().get("/repro/core/executor_consts")
    return None if total is None else total / 1e6
