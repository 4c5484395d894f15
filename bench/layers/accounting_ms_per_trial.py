"""Usage and message accounting on the device: op self time under the
executor's `accounting` scope on the busiest chip over the traced
window, per trial, in ms (`bench.scopes`)."""
from bench import scopes


def read(run):
    return scopes.layer_ms_per_trial(run, "accounting")
