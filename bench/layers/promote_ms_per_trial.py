"""Promotion between levels and the dissemination down-pass on the device:
op self time under the executor's `promote` scope on the busiest chip
over the traced window, per trial, in ms (`bench.scopes`)."""
from bench import scopes


def read(run):
    return scopes.layer_ms_per_trial(run, "promote")
