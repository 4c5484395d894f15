"""Set-up layer: seconds in `setup_plan` (graph and hierarchy, or their
load from the plan cache), host clock around the call."""


def read(run):
    return run["plan_s"]
