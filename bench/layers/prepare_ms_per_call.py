"""The host call path before dispatch: the program's
`repro.execute_plan.prepare` span less the device busy time inside it,
mean per traced call, in ms (`bench.scopes`)."""
from bench import scopes


def read(run):
    return scopes.span_ms_per_call(run, "repro.execute_plan.prepare")
