"""The convergence check and the chunk loop's own control on the device: op
self time under the executor's `convergence_check` scope on the busiest
chip over the traced window, per trial, in ms (`bench.scopes`)."""
from bench import scopes


def read(run):
    return scopes.layer_ms_per_trial(run, "convergence_check")
