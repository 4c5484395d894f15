"""Public op for applying presampled gossip schedules: alignment
padding, cell-block tiling, schedule layout, and the Pallas-vs-oracle
dispatch.

`use_pallas=True` always runs the kernel: compiled for the TPU, or in
the Pallas interpreter with `interpret=True` (the only way it runs on
other backends).  `use_pallas=False` takes the jnp oracle — the scan
the lax backend uses, bitwise-identical to the kernel's op sequence,
so backend choice never changes simulation results.

`block_b` controls how many cells are resident per grid step (see
kernel.py).  The default sizes the block so the state tile stays
within ~512 KiB of VMEM and the four schedule tiles within ~128 KiB of
SMEM, rounded down to a multiple of 8 (or the whole batch) so the
SMEM schedule tiles obey the TPU's (8, 128) block rule — large-n
levels stream through in blocks, tiny fig3-scale levels still run as
a single block.  Results are bitwise-independent of the block size
(cells never interact).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import pair_apply_pallas
from .ref import pair_apply_ref

__all__ = ["pair_apply"]

_VMEM_BLOCK_BYTES = 512 * 1024
_SMEM_BLOCK_BYTES = 128 * 1024


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def _auto_block(B: int, Cp: int, Vp: int, T: int) -> int:
    """Cells per grid step: the whole batch when it fits the budgets,
    else the largest multiple of 8 that does (at least 8)."""
    cap = min(_VMEM_BLOCK_BYTES // (Cp * Vp * 4),
              _SMEM_BLOCK_BYTES // (4 * T * 4))
    return B if B <= cap else max(8, cap // 8 * 8)


@functools.partial(
    jax.jit, static_argnames=("use_pallas", "interpret", "block_b")
)
def pair_apply(
    x: jax.Array,
    i: jax.Array,
    j: jax.Array,
    upd_i: jax.Array,
    upd_j: jax.Array,
    *,
    use_pallas: bool = True,
    interpret: bool = False,
    block_b: Optional[int] = None,
) -> jax.Array:
    """Walk a (T, B) presampled exchange schedule over (B, C, V) state.

    See `ref.pair_apply_ref` for argument semantics.  Inputs may be
    unaligned; the Pallas path pads C to 8 sublanes / V to 128 lanes,
    pads B up to a `block_b` multiple (padded cells get an all-masked
    schedule, i.e. pure pass-through), transposes the schedule to
    graph-major SMEM layout, and crops the result back.
    """
    if not use_pallas:
        return pair_apply_ref(x, i, j, upd_i, upd_j)
    B, C, V = x.shape
    T = i.shape[0]
    Cp, Vp = _round_up(C, 8), _round_up(V, 128)
    bb = block_b if block_b is not None else _auto_block(B, Cp, Vp, T)
    bb = max(1, min(bb, B))
    Bp = _round_up(B, bb)
    xp = jnp.pad(x, ((0, Bp - B), (0, Cp - C), (0, Vp - V)))

    def prep(a):  # (T, B) -> graph-major (Bp, T) int32
        return jnp.pad(a.astype(jnp.int32), ((0, 0), (0, Bp - B))).T

    y = pair_apply_pallas(
        xp, prep(i), prep(j), prep(upd_i), prep(upd_j),
        block_b=bb, interpret=interpret,
    )
    return y[:B, :C, :V]
