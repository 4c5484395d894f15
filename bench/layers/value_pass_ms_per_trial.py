"""The value pass on the device (the pair-average backend): op self time
under the executor's `value_pass` scope on the busiest chip over the
traced window, per trial, in ms (`bench.scopes`)."""
from bench import scopes


def read(run):
    return scopes.layer_ms_per_trial(run, "value_pass")
