"""Executor compile (or compile-cache load) in set-up: seconds of the
program's `/repro/core/executor_compile` duration events
(`jax.monitoring`) between process start and the first timed call."""
from bench import scopes


def read(run):
    return scopes.event_s(run, "/repro/core/executor_compile")
