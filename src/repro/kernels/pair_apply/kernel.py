"""Pallas TPU kernel: walk a presampled gossip schedule in VMEM.

The simulation hot path applies a `check_every`-tick presampled pair
list to the (B, C, V) cell state.  Doing that with XLA scatters keeps
the state in HBM and round-trips it twice per tick; here cell state is
loaded into VMEM once per kernel call and the whole schedule is walked
on-chip — two dynamic row slices, one VPU average, and two dynamic row
updates per tick, with the final state written back once.

State residence is TILED: the grid runs over blocks of `block_b` cells,
so only one ``(block_b, C_pad, V_pad)`` state block and its
``(block_b, T)`` schedule slice are resident at a time — large-n levels
(tens of thousands of cells) stream through VMEM instead of assuming
the whole batch fits.  The schedule rides in as blocked SMEM inputs
(NOT whole-array scalar prefetch, which would have to hold all ``B*T``
indices in SMEM at once and overflows at large B); the loop's dynamic
row indices must live in SMEM on TPU.

Per-program working set: ``block_b * C_pad * V_pad * 4`` bytes of VMEM
for each of x/out plus ``4 * block_b * T`` int32 SMEM words — the
caller (ops.pair_apply) sizes `block_b` to keep both far inside budget.

Arithmetic per cell is the exact f32 op sequence of the jnp oracle
(`ref.pair_apply_ref`) and cells never interact, so the kernel is
bitwise-interchangeable with the lax backend for every block size
rather than merely allclose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pair_apply_pallas"]


def _pair_apply_kernel(
    i_ref, j_ref, ui_ref, uj_ref, x_ref, o_ref, *, ticks: int, cells: int
):
    # the schedule is walked on the output block itself: rows are read
    # and written through the ref, so each tick moves two rows, never
    # the whole (C_pad, V_pad) cell
    o_ref[...] = x_ref[...]

    def cell_body(l, carry):
        def tick(t, carry):
            it = i_ref[l, t]
            jt = j_ref[l, t]
            xi = o_ref[l, pl.ds(it, 1), :]               # (1, V_pad)
            xj = o_ref[l, pl.ds(jt, 1), :]
            avg = 0.5 * (xi + xj)

            # partner row first, then initiator — the oracle's write order
            @pl.when(uj_ref[l, t] > 0)
            def _():
                o_ref[l, pl.ds(jt, 1), :] = avg

            @pl.when(ui_ref[l, t] > 0)
            def _():
                o_ref[l, pl.ds(it, 1), :] = avg

            return carry

        return jax.lax.fori_loop(0, ticks, tick, carry)

    jax.lax.fori_loop(0, cells, cell_body, 0)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def pair_apply_pallas(
    x: jax.Array,
    i: jax.Array,
    j: jax.Array,
    upd_i: jax.Array,
    upd_j: jax.Array,
    *,
    block_b: int,
    interpret: bool = False,
) -> jax.Array:
    """Apply a (B, T) presampled schedule to (B, C_pad, V_pad) state,
    `block_b` cells per grid step.

    The caller (ops.pair_apply) is responsible for sublane/lane
    alignment (C_pad multiple of 8, V_pad multiple of 128), for a
    `block_b` that is a multiple of 8 or B itself (the (8, 128) rule of
    the SMEM schedule tiles), for padding B up to a `block_b` multiple
    (padded cells carry an all-masked schedule, so their rows pass
    through untouched), and for transposing the schedule to graph-major
    (B, T) int32.  `x` is donated to the output.
    """
    B, C, V = x.shape
    T = i.shape[1]
    assert i.shape == j.shape == upd_i.shape == upd_j.shape == (B, T)
    assert B % block_b == 0, (B, block_b)
    sched_spec = pl.BlockSpec(
        (block_b, T), lambda g: (g, 0), memory_space=pltpu.SMEM
    )
    return pl.pallas_call(
        functools.partial(_pair_apply_kernel, ticks=T, cells=block_b),
        grid=(B // block_b,),
        in_specs=[
            sched_spec, sched_spec, sched_spec, sched_spec,
            pl.BlockSpec((block_b, C, V), lambda g: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, C, V), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        input_output_aliases={4: 0},
        interpret=interpret,
    )(i, j, upd_i, upd_j, x)
