"""Shared test configuration.

NOTE: XLA_FLAGS / device-count forcing is deliberately NOT set here —
smoke tests and benchmarks must see the real single CPU device.  The
multi-device distribution tests spawn subprocesses that set
XLA_FLAGS=--xla_force_host_platform_device_count=<N> before importing
jax (see tests/test_dist_multidevice.py).
"""
import numpy as np
import pytest

from hypothesis import HealthCheck, settings

# Keep property tests small and undeadlined.
settings.register_profile(
    "ci",
    max_examples=15,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
)
settings.load_profile("ci")


# ---- test tiering (markers registered in pyproject.toml) ----
# `slow`: the multi-device subprocess tests (each spawns a fresh
# interpreter with 8 emulated devices) and the vmap-/backend-parity
# tests that re-run the simulation engine several times.  Everything
# else is `tier1`.  tools/ci.sh runs `-m "not slow"`; the CI workflow's
# second job runs `-m slow`; a bare pytest invocation runs both tiers.
SLOW_FILES = {"test_dist_multidevice.py"}
SLOW_TESTS = {
    "test_trials_vmap_matches_sequential",
    "test_engine_single_device_mesh_matches_unsharded",
    "test_plan_methods_execute_identically",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        base = item.name.split("[")[0]
        if item.path.name in SLOW_FILES or base in SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
        else:
            item.add_marker(pytest.mark.tier1)


@pytest.fixture(scope="session")
def rgg500():
    from repro.core import random_geometric_graph

    return random_geometric_graph(500, seed=7)


@pytest.fixture(scope="session")
def x0_500():
    return np.random.default_rng(3).normal(0.0, 1.0, 500)
