"""The value pass: a chunk's presampled pair list applied to cell state.

The schedule decides every exchange from ``(key, t)`` alone, so the
values are the only sequential part of gossip: tick by tick, read the
two endpoints, average them, and write the partner row, then the
initiator row, where the exchange updates them.  `pair_apply` is that
recursion as one `lax.scan` over the chunk's ticks, with nothing else
in its body.

The endpoints x[i], x[j] are read by a one-hot select over a cell's
slots where there are at least VALUE_SELECT_MIN_B cells and a cell has
at most VALUE_SELECT_MAX_C slots; elsewhere by XLA gathers.  A gather's
output puts V on the TPU's lanes and drags the loop carry out of its
cells-on-lanes layout; a select reads all C slots of an endpoint but
keeps the cells on the lanes.  On a TPU v5e (64-tick calls, V=2) the
select won at every B >= 16 and C <= 100 swept: about even at B=16,
1.2-2x at B of 64-256, 3-40x from B=2,500 on (18 ms against 341 ms at
337,504 cells of 13).  It tied at B=4 and lost at B=1 from C=8 on (5x
slower at C=100).  On the CPU a select costs C reads an endpoint, so
cells wider than the sweep keep the gather.  Both reads give the same
bits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["pair_apply", "value_read_path"]


VALUE_SELECT_MIN_B = 16
VALUE_SELECT_MAX_C = 100


def value_read_path(B: int, C: int) -> str:
    """How `pair_apply` reads the endpoints of B cells of C slots:
    ``"select"`` or ``"gather"``."""
    if B >= VALUE_SELECT_MIN_B and C <= VALUE_SELECT_MAX_C:
        return "select"
    return "gather"


def _select_slot(x, k):
    """``x[b, k[b]]`` by a chain of selects over the C slots: exact, bit
    for bit and -0.0 included (a masked sum would turn -0.0 into +0.0).
    A k outside [0, C) reads slot 0, where a gather would clamp or wrap;
    the schedule writes no row at such a k, so the state is the same."""
    out = x[:, 0]
    for c in range(1, x.shape[1]):
        out = jnp.where((k == c)[:, None], x[:, c], out)
    return out


def pair_apply(x, i, j, upd_i, upd_j):
    """Apply a presampled pair list to batched cell state.

    Args:
      x: (B, C, V) node values.
      i, j: (T, B) int32 exchange pairs (j already clipped to >= 0).
      upd_i, upd_j: (T, B) bool — whether the initiator / partner row
        actually updates at that tick (schedule validity, per-chunk
        done freeze, and per-hop loss outcomes already folded in).
    Returns (B, C, V) state after the T ticks, in order.  The endpoints
    are read as `value_read_path` says; both paths give the same bits.
    """
    B, C, V = x.shape
    bidx = jnp.arange(B)
    slots = jnp.arange(C)[None, :]
    if value_read_path(B, C) == "select":
        read = _select_slot
    else:
        def read(x, k):
            return x[bidx, k]

    def tick(x, sched):
        it, jt, ui, uj = sched
        xi = read(x, it)
        xj = read(x, jt)
        avg = 0.5 * (xi + xj)
        # row writes as one-hot masked selects, not scatters: the written
        # value is the identical float either way (no arithmetic on the
        # pass-through lanes), but XLA compiles a select orders of
        # magnitude faster than a scatter and vectorizes it better on
        # CPU.  Partner row first, then initiator.
        oh_j = (slots == jt[:, None]) & uj[:, None]
        oh_i = (slots == it[:, None]) & ui[:, None]
        x = jnp.where(oh_j[..., None], avg[:, None, :], x)
        x = jnp.where(oh_i[..., None], avg[:, None, :], x)
        return x, None

    x, _ = jax.lax.scan(tick, x, (i, j, upd_i, upd_j))
    return x
