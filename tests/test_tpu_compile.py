"""Compile-only rehearsals of the value-pass kernels and the schedule's
lookups for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what the Pallas
interpreter cannot: block shapes that break the (8, 128) tiling rule,
primitives Mosaic cannot lower, VMEM/SMEM budgets.  Nothing runs.

Shapes come from `setup_plan(n=10**6, graph_seed=1001000)`: the finest
cells (B=337,504 graphs of C=13 nodes), the B=100, C=25 overlay and the
single top overlay of C=100 nodes, with V=2 (the weighted variant) and
T=64 ticks per chunk; the schedule's lookups compile at all six of its
levels.

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


@pytest.mark.parametrize("B,C", [(337_504, 13), (100, 25), (1, 100)])
def test_pair_apply_compiles_for_v5e(one_chip, B, C):
    import jax
    import jax.numpy as jnp

    from repro.kernels.pair_apply import pair_apply

    T, V = 64, 2
    x = jax.ShapeDtypeStruct((B, C, V), jnp.float32, sharding=one_chip)
    ij = jax.ShapeDtypeStruct((T, B), jnp.int32, sharding=one_chip)
    upd = jax.ShapeDtypeStruct((T, B), jnp.bool_, sharding=one_chip)
    text = _compile_text(
        lambda x, i, j, ui, uj: pair_apply(
            x, i, j, ui, uj, use_pallas=True, interpret=False),
        x, ij, ij, upd, upd,
    )
    assert "tpu_custom_call" in text


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
