"""Compile-only rehearsals of the value-pass kernels for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what the Pallas
interpreter cannot: block shapes that break the (8, 128) tiling rule,
primitives Mosaic cannot lower, VMEM/SMEM budgets.  Nothing runs.

Shapes come from `setup_plan(n=10**6, graph_seed=1001000)`: the finest
cells (B=337,504 graphs of C=13 nodes), the B=100, C=25 overlay and the
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
