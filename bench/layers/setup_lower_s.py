"""Executor lowering in set-up: seconds of the program's
`/repro/core/executor_lower` events, from its own running totals
(`repro.core.event_totals()`), read after the traced window, in which
nothing is built.  None where the program keeps no totals."""
from bench import deploy


def read(run):
    core, _ = deploy.program()
    if not hasattr(core, "event_totals"):
        return None
    return core.event_totals().get("/repro/core/executor_lower")
