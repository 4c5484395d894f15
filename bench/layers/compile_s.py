"""Executor compile layer: the first (warm-up) call's wall time minus the
median traced call's, so lowering, compiling or loading the executor
from the compile cache, and first-execution costs."""
import statistics


def read(run):
    return run["first_call_s"] - statistics.median(run["trace"]["call_s"])
