"""Public ops for batched cell mixing: padding helpers, mixing-matrix
construction (Metropolis-Hastings weights — symmetric doubly stochastic,
the standard synchronous-gossip mixing choice), and the jitted entry
point that dispatches Pallas vs the jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import cell_mixing_pallas
from .ref import cell_mixing_ref

__all__ = ["mixing_matrix", "pad_mixing", "cell_mixing"]


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def mixing_matrix(
    neighbors: np.ndarray, degrees: np.ndarray, n_nodes: np.ndarray
) -> np.ndarray:
    """Batched Metropolis-Hastings mixing matrices from padded adjacency.

    W_ij = 1 / (1 + max(d_i, d_j)) for edges, W_ii = 1 - sum_j W_ij,
    identity on padding rows — symmetric, doubly stochastic, with the
    same fixed point (the average) as asynchronous pairwise gossip.
    """
    B, C, D = neighbors.shape
    w = np.zeros((B, C, C), np.float32)
    for b in range(B):
        for i in range(int(n_nodes[b])):
            for s in range(int(degrees[b, i])):
                j = int(neighbors[b, i, s])
                w[b, i, j] = 1.0 / (1.0 + max(degrees[b, i], degrees[b, j]))
        row = w[b].sum(axis=1)
        np.fill_diagonal(w[b], 1.0 - row)
    return w


def pad_mixing(w: jax.Array | np.ndarray, x: jax.Array | np.ndarray,
               m_mult: int = 8, d_mult: int = 128):
    """Pad (B, m, m) W with identity and (B, m, d) x with zeros so m is a
    multiple of `m_mult` and d of `d_mult` (MXU/lane alignment)."""
    B, m, d = x.shape
    mp, dp = _round_up(m, m_mult), _round_up(d, d_mult)
    if mp != m:
        w = jnp.pad(jnp.asarray(w), ((0, 0), (0, mp - m), (0, mp - m)))
        eye_pad = jnp.zeros((B, mp, mp), w.dtype).at[
            :, jnp.arange(m, mp), jnp.arange(m, mp)
        ].set(1.0)
        w = w + eye_pad
        x = jnp.pad(jnp.asarray(x), ((0, 0), (0, mp - m), (0, 0)))
    if dp != d:
        x = jnp.pad(jnp.asarray(x), ((0, 0), (0, 0), (0, dp - d)))
    return w, x, (m, d)


@functools.partial(
    jax.jit, static_argnames=("rounds", "use_pallas", "interpret", "block_d")
)
def cell_mixing(
    w: jax.Array,
    x: jax.Array,
    *,
    rounds: int = 1,
    use_pallas: bool = True,
    interpret: bool = False,
    block_d: int = 512,
) -> jax.Array:
    """Apply `rounds` synchronous gossip rounds per cell: W[b]^R @ x[b].

    Inputs may be unaligned; they are identity/zero padded, mixed, and
    cropped back.  `use_pallas=False` selects the pure-jnp oracle;
    `use_pallas=True` runs the kernel, in the Pallas interpreter when
    `interpret=True`.
    """
    wp, xp, (m, d) = pad_mixing(w, x)
    if use_pallas:
        bd = min(block_d, xp.shape[2])
        y = cell_mixing_pallas(wp, xp, rounds=rounds, block_d=bd, interpret=interpret)
    else:
        y = cell_mixing_ref(wp, xp, rounds=rounds)
    return y[:, :m, :d]
