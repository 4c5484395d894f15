"""Compile-only rehearsals of the value pass, the cell-mixing kernel,
the schedule's lookups and the slot count for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what the CPU and the
Pallas interpreter cannot: block shapes that break the (8, 128) tiling
rule, primitives Mosaic cannot lower, gathers or scatters left in a
program meant to have none, scratch memory.  Nothing runs.

Shapes come from `setup_plan(n=10**6, graph_seed=1001000)`: its six
levels, from the finest cells (B=337,504 graphs of C=13 nodes) to the
single top overlay of C=100 nodes, with V=2 (the weighted variant) and
T=64 ticks per chunk.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *shapes):
    import jax

    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_cell_mixing_compiles_for_v5e(one_chip):
    import jax
    import jax.numpy as jnp

    from repro.kernels.cell_mixing import cell_mixing

    B, C, V = 2_500, 9, 2
    w = jax.ShapeDtypeStruct((B, C, C), jnp.float32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((B, C, V), jnp.float32, sharding=one_chip)
    text = _compile_text(
        lambda w, x: cell_mixing(w, x, use_pallas=True, interpret=False),
        w, x,
    )
    assert "tpu_custom_call" in text


def _level_tables(B, C, D, hops):
    """Host tables with a level's shape: every row of degree 1 but one of
    degree D, every hop 1 or ("table") one of 3."""
    import numpy as np

    from repro.core.schedule import CsrGraphs

    degrees = np.ones((B, C), np.int32)
    degrees[0] = D
    start = np.concatenate([[0], np.cumsum(degrees.ravel())])[:-1]
    nnz1 = int(degrees.sum()) + 1
    hop_flat = np.ones(nnz1, np.int32)
    hop_flat[0] = 3 if hops == "table" else 1
    return CsrGraphs.from_flat(start.reshape(B, C), np.zeros(nnz1, np.int32),
                               hop_flat, degrees, np.full(B, C, np.int32))


# (trials, B, C, D_max, hops): the n=2000 cells of the paper's
# deployment with a ten-trial batch, the six levels at n=10^6, then big
# cells whose rows pass `ROW_SELECT_MAX`
@pytest.mark.parametrize("trials,B,C,D,hops", [
    (10, 875, 8, 7, "const"),
    (1, 337_504, 13, 12, "const"),
    (1, 89_996, 4, 2, "const"),
    (1, 22_500, 4, 2, "table"),
    (1, 2_500, 9, 4, "table"),
    (1, 100, 25, 4, "table"),
    (1, 1, 100, 4, "table"),
    (2, 4, 49, 48, "table"),
])
def test_sample_schedule_compiles_for_v5e(one_chip, trials, B, C, D, hops):
    """The schedule's lookups at a level's shape: a select level lowers
    to no gather, and its one-hot compares stay fused (scratch memory no
    more than four (T, trials, B) int32 arrays)."""
    import jax
    import jax.numpy as jnp

    from repro.core.schedule import ROW_SELECT_MAX, sample_schedule

    T = 64
    path = "select" if C * D <= ROW_SELECT_MAX else "gather"
    adj = _level_tables(B, C, D, hops)
    assert adj.lookup == {"path": path, "hops": hops}
    adj = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), adj)
    keys = jax.ShapeDtypeStruct((trials, 2), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(lambda adj, keys: jax.vmap(
        lambda k: sample_schedule(jnp.arange(T), k, adj, None))(keys)
    ).lower(adj, keys).compile()
    assert ("gather" in compiled.as_text()) == (path == "gather")
    scratch = compiled.memory_analysis().temp_size_in_bytes
    assert scratch <= 4 * T * trials * B * 4


# (trials, B, C): the six levels at n=10^6, the paper's cells under its
# ten-trial batch and wide cells of a k=2 plan
@pytest.mark.parametrize("trials,B,C", [
    (1, 337_504, 13), (1, 89_996, 4), (1, 22_500, 4), (1, 2_500, 9),
    (1, 100, 25), (1, 1, 100), (10, 875, 8), (1, 4, 49),
])
def test_value_pass_compiles_for_v5e(one_chip, trials, B, C):
    """The value pass lowers to the read `value_read_path` picks: a
    select level keeps no gather after compiling and its select chain
    stays fused (scratch memory no more than one copy of the state, and
    1 MiB besides for the gather's buffers at a few cells)."""
    import jax
    import jax.numpy as jnp

    from repro.core.value_pass import pair_apply, value_read_path

    T, V = 64, 2
    path = value_read_path(B, C)
    x = jax.ShapeDtypeStruct((trials, B, C, V), jnp.float32,
                             sharding=one_chip)
    ij = jax.ShapeDtypeStruct((trials, T, B), jnp.int32, sharding=one_chip)
    upd = jax.ShapeDtypeStruct((trials, T, B), jnp.bool_, sharding=one_chip)
    fn = jax.vmap(pair_apply)
    if trials == 1:
        def fn(x, i, j, ui, uj):
            return pair_apply(x[0], i[0], j[0], ui[0], uj[0])[None]
    lowered = jax.jit(fn).lower(x, ij, ij, upd, upd)
    assert ("gather" in lowered.as_text()) == (path == "gather")
    compiled = lowered.compile()
    if path == "select":
        assert " gather(" not in compiled.as_text()
    scratch = compiled.memory_analysis().temp_size_in_bytes
    assert scratch <= trials * B * C * V * 4 + 2**20


# (trials, B, C): the finest cells at n=10^6 and the paper's cells under
# its ten-trial batch
@pytest.mark.parametrize("trials,B,C", [(1, 337_504, 13), (10, 875, 8)])
def test_slot_count_compiles_for_v5e(one_chip, trials, B, C):
    """A cell level's usage count over a 64-tick chunk lowers to no
    scatter and keeps its one-hot compares fused: scratch memory no
    more than one (trials, C, B) int32 table, and 1 MiB besides."""
    import jax
    import jax.numpy as jnp

    from repro.core.gossip import slot_sends

    T = 64
    ij = jax.ShapeDtypeStruct((trials, T, B), jnp.int32, sharding=one_chip)
    att = jax.ShapeDtypeStruct((trials, T, B), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(jax.vmap(
        lambda i, j, a: slot_sends(i, j, a, C))).lower(ij, ij, att).compile()
    assert "scatter" not in compiled.as_text()
    scratch = compiled.memory_analysis().temp_size_in_bytes
    assert scratch <= trials * C * B * 4 + 2**20


def test_the_executor_holds_each_mask_as_floats_once(one_chip, monkeypatch):
    """The paper's deployment (n=2000, one trial) compiled as
    `execute_plan` builds it, every plan array a constant: each level's
    node mask is in the program as floats at most once.  The compiler
    had copied that constant once more for the chunk loop of each level
    whose value pass selects (17.5 MB more device memory at n=10^6)."""
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import engine, setup_plan

    plan, _ = setup_plan(n=2000, c=3.0, graph_seed=100, a=2 / 3,
                         cell_max=8.0, seed=0, rep_mode="random",
                         use_cache=False)
    built = {}

    class Built(Exception):
        pass

    def over_trials(*args, **kwargs):
        built["run"] = over_trials.orig(*args, **kwargs)
        raise Built

    over_trials.orig = engine._over_trials
    monkeypatch.setattr(engine, "_over_trials", over_trials)
    x0 = np.zeros(2000, np.float32)
    with pytest.raises(Built):
        engine.execute_plan(plan, x0, eps=1e-4, seeds=(1,), weighted=True)
    L = len(plan.levels)
    text = _compile_text(
        built["run"],
        jax.ShapeDtypeStruct((2000,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((1, 2), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((L,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((L,), jnp.int32, sharding=one_chip),
    )
    for lp in plan.levels:
        B, C = lp.node_mask.shape
        assert len(re.findall(rf"f32\[{B},{C}\]\S* constant\(", text)) <= 1
