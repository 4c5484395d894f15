"""Pallas TPU kernel: blocked flash attention (online softmax).

Supports the attention variants the assigned architectures need:
  * causal masking (decoder LMs)
  * sliding-window locality (gemma2 local layers, recurrentgemma)
  * logit soft-capping (gemma2)
  * GQA: Hq query heads read Hq/Hkv-grouped KV heads via the BlockSpec
    index map — KV blocks are never materialized per-query-head.

Grid: (B, Hq, Sq/bq, Sk/bk); the innermost axis streams KV blocks while
(m, l, acc) running statistics live in VMEM scratch, so scores are
never materialized in HBM — the O(S^2) term exists only as compute.
VMEM per program (bq=bk=512, D=128, fp32): q/k/v blocks ~0.8 MiB +
acc/stats ~0.5 MiB, well inside the v5e 16 MiB budget.

Fully-masked KV blocks (beyond the causal frontier or outside the
window) skip their FLOPs via pl.when; a production grid would also skip
their DMAs (noted in EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, causal, window, softcap, block_q, block_k, kv_len, num_k_blocks,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    q_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_idx = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # block-level skip: entirely above the causal diagonal / outside window
    run = jnp.asarray(True)
    if causal:
        run = run & (k_start <= q_start + block_q - 1)
    if window is not None:
        run = run & (k_start + block_k - 1 >= q_start - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)                # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # (bq, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = k_idx < kv_len
        if causal:
            mask &= k_idx <= q_idx
        if window is not None:
            mask &= k_idx > q_idx - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                                # (bq, 128)
        m_cur = jnp.max(s, axis=1, keepdims=True)          # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)                 # broadcast
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "softcap", "scale",
        "block_q", "block_k", "kv_len", "interpret",
    ),
)
def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    kv_len: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D); Hq % Hkv == 0.

    Sq/Sk must be multiples of block_q/block_k (ops.py pads); `kv_len`
    is the true (pre-padding) KV length masked inside the kernel.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, Dv = v.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if kv_len is None:
        kv_len = Sk
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=bq, block_k=bk, kv_len=kv_len, num_k_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
