"""Pallas TPU kernel: RWKV-6 (Finch) wkv recurrence.

Per head of width N, with data-dependent per-channel decay w_t and a
current-token bonus u:

    y_t = (S_{t-1} + (u * k_t) v_t^T)^T r_t
    S_t = diag(w_t) S_{t-1} + k_t v_t^T

The (N, N) state S stays resident in VMEM scratch for the whole
sequence; time is streamed in blocks along the innermost ("arbitrary")
grid axis, so HBM traffic is O(T*N) instead of the O(T*N^2) a naive
scan materializing states would need.

Grid: (B*H, T/bt).  VMEM per program (N=64..128, bt=256, fp32):
state N^2 + 4 input blocks bt*N + out bt*N ~ 0.4-0.7 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rwkv6_pallas"]


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_ref, *, block_t):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0].astype(jnp.float32)          # (N,) current-token bonus

    def step(t, _):
        r = r_ref[0, t].astype(jnp.float32)   # (N,)
        k = k_ref[0, t].astype(jnp.float32)
        v = v_ref[0, t].astype(jnp.float32)
        w = w_ref[0, t].astype(jnp.float32)
        s = s_ref[...]                        # (N, N) keys x values
        kv = k[:, None] * v[None, :]          # (N, N)
        y = jnp.sum((s + u[:, None] * kv) * r[:, None], axis=0)
        o_ref[0, t] = y.astype(o_ref.dtype)
        s_ref[...] = w[:, None] * s + kv
        return 0

    jax.lax.fori_loop(0, block_t, step, 0)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def rwkv6_pallas(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    *,
    block_t: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """r/k/v/w: (BH, T, N); u: (BH, N) per-head bonus. Returns (BH, T, N).

    T must be a multiple of block_t (ops.py pads with w=1, k=0 so padded
    steps neither decay nor write the state).
    """
    BH, T, N = r.shape
    assert T % block_t == 0, (T, block_t)
    grid = (BH, T // block_t)
    blk = pl.BlockSpec((1, block_t, N), lambda b, t: (b, t, 0))
    return pl.pallas_call(
        functools.partial(_wkv_kernel, block_t=block_t),
        grid=grid,
        in_specs=[blk, blk, blk, blk, pl.BlockSpec((1, N), lambda b, t: (b, 0))],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((BH, T, N), r.dtype),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, w, u)
