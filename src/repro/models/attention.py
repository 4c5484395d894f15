"""Attention for the model zoo: GQA/MQA, RoPE/M-RoPE, sliding windows,
logit softcapping, cross-attention, chunked (flash-style) XLA path for
long sequences, and KV-cache decode.

The Pallas kernel (`repro.kernels.flash_attention`) is the TPU target
for the S x S hot spot; `chunked_attention` is the identical-math XLA
path used for lowering on any backend (lax.scan over KV blocks, online
softmax — never materializes the full score matrix).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import P_, dense, mrope, rope


def _constrain_heads(x, dp):
    """Shard (B, H, S, dh) on batch x heads when the dims divide — keeps
    the S x S score tensors head-sharded instead of replicated."""
    if dp is None:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.shape:
        return x
    dp_size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_size *= mesh.shape.get(a, 1)
    spec = [None] * x.ndim
    if x.shape[0] % dp_size == 0:
        spec[0] = dp
    if x.shape[1] % mesh.shape["model"] == 0:
        spec[1] = "model"
    return jax.lax.with_sharding_constraint(x, P(*spec))

__all__ = [
    "attn_params", "attention", "decode_attention", "chunked_attention",
    "init_kv_cache", "init_paged_kv_cache", "paged_decode_attention",
]

_NEG_INF = -1e30


def attn_params(cfg: ModelConfig, cross: bool = False) -> dict:
    D, H, Hkv, dh = cfg.d_model, cfg.num_heads, cfg.kv_heads, cfg.head_width
    return {
        "wq": P_((D, H * dh), P("data", "model")),
        "wk": P_((D, Hkv * dh), P("data", "model")),
        "wv": P_((D, Hkv * dh), P("data", "model")),
        "wo": P_((H * dh, D), P("model", "data")),
    }


def _heads(x, n, dh):
    B, S, _ = x.shape
    return x.reshape(B, S, n, dh).transpose(0, 2, 1, 3)  # (B, H, S, dh)


def _unheads(x):
    B, H, S, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * dh)


def _apply_rope(cfg: ModelConfig, x, positions):
    if cfg.mrope_sections is not None:
        return mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return rope(x, positions, cfg.rope_theta)


def _scale(cfg: ModelConfig) -> float:
    if cfg.query_scale is not None:
        return cfg.query_scale
    return 1.0 / math.sqrt(cfg.head_width)


def _mask_bias(q_pos, k_pos, *, causal: bool, window: Optional[int]):
    """(..., Sq, Sk) additive bias from position tensors."""
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]), bool)
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    if causal:
        m &= kp <= qp
    if window is not None:
        m &= kp > qp - window
    return jnp.where(m, 0.0, _NEG_INF)


def full_attention(q, k, v, bias, *, softcap, scale):
    """Direct attention; q: (B,H,Sq,dh), k/v: (B,Hkv,Sk,dh)."""
    B, H, Sq, dh = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, Sq, dh)
    s = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg.astype(jnp.float32) * scale,
        k.astype(jnp.float32), preferred_element_type=jnp.float32,
    )
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    s = s + bias[:, None, None] if bias.ndim == 3 else s + bias
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return o.reshape(B, H, Sq, dh).astype(q.dtype)


def chunked_attention(
    q, k, v, q_pos, k_pos, *, causal, window, softcap, scale,
    chunk: int = 1024, unroll: bool = False,
):
    """Online-softmax attention scanned over KV chunks (XLA flash path).

    q: (B,H,Sq,dh); k/v: (B,Hkv,Sk,dh); q_pos: (B,Sq); k_pos: (B,Sk).
    """
    B, H, Sq, dh = q.shape
    _, Hkv, Sk, dv = v.shape
    g = H // Hkv
    nchunks = -(-Sk // chunk)
    pad = nchunks * chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    qf = q.astype(jnp.float32).reshape(B, Hkv, g, Sq, dh) * scale
    kc = k.reshape(B, Hkv, nchunks, chunk, dh).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, Hkv, nchunks, chunk, dv).transpose(2, 0, 1, 3, 4)
    pc = k_pos.reshape(B, nchunks, chunk).transpose(1, 0, 2)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, kpb = inp                       # (B,Hkv,c,dh), ..., (B,c)
        s = jnp.einsum(
            "bhgqd,bhkd->bhgqk", qf, kb.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        mask = kpb[:, :] >= 0                   # (B, c) padding
        qp = q_pos[:, None, None, :, None]      # (B,1,1,Sq,1)
        kp = kpb[:, None, None, None, :]        # (B,1,1,1,c)
        keep = mask[:, None, None, None, :]
        if causal:
            keep &= kp <= qp
        if window is not None:
            keep &= kp > qp - window
        s = jnp.where(keep, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vb.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, g, Sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, g, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, g, Sq, dv), jnp.float32)
    # remat the chunk step: otherwise backward saves every chunk's score
    # tensor and the memory win evaporates (§Perf M6)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, a0), (kc, vc, pc),
        unroll=True if unroll else 1,
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H, Sq, dv).astype(q.dtype)


def banded_local_attention(
    q, k, v, q_pos, k_pos, *, window, softcap, scale, block: int = 1024
):
    """Causal sliding-window attention restricted to the diagonal band.

    q blocks attend only to the ceil(window/block)+1 KV blocks that can
    fall inside their window: flops scale with S*(window+block) instead
    of S^2 (§Perf P2.1 — 6-11x on the 32k local-attention cells).  KV is
    front-padded so band indices are static gathers; padded positions
    are -1 and masked.
    """
    B, H, Sq, dh = q.shape
    _, Hkv, Sk, dv = v.shape
    g = H // Hkv
    c = min(block, Sq)
    pad_t = (-Sq) % c
    if pad_t:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_t)), constant_values=-1)
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad_t)), constant_values=-1)
    S = q.shape[2]
    nb = S // c
    band = -(-window // c) + 1        # blocks that can intersect the window
    qf = (q.astype(jnp.float32) * scale).reshape(B, Hkv, g, nb, c, dh)
    kb = k.reshape(B, Hkv, nb, c, dh)
    vb = v.reshape(B, Hkv, nb, c, dv)
    pb = k_pos.reshape(B, nb, c)
    # front-pad (band-1) dummy blocks; padded block row i covers true
    # blocks [i-band+1 .. i]
    kb = jnp.pad(kb, ((0, 0), (0, 0), (band - 1, 0), (0, 0), (0, 0)))
    vb = jnp.pad(vb, ((0, 0), (0, 0), (band - 1, 0), (0, 0), (0, 0)))
    pb = jnp.pad(pb, ((0, 0), (band - 1, 0), (0, 0)), constant_values=-1)
    idx = jnp.arange(nb)[:, None] + jnp.arange(band)[None, :]   # (nb, band)
    kband = kb[:, :, idx].reshape(B, Hkv, nb, band * c, dh)
    vband = vb[:, :, idx].reshape(B, Hkv, nb, band * c, dv)
    pband = pb[:, idx].reshape(B, nb, band * c)

    s = jnp.einsum(
        "bhgncd,bhnkd->bhgnck", qf, kband.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )                                          # (B, Hkv, g, nb, c, band*c)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    qp = q_pos.reshape(B, nb, c)[:, None, None, :, :, None]
    kp = pband[:, None, None, :, None, :]
    keep = (kp >= 0) & (kp <= qp) & (kp > qp - window)
    s = jnp.where(keep, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bhgnck,bhnkd->bhgncd", p, vband.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    o = o.reshape(B, H, S, dv)[:, :, :Sq]
    return o.astype(q.dtype)


def attention(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    *,
    kind: str = "attn",                 # attn | local
    causal: bool = True,
    memory: Optional[jax.Array] = None,  # cross-attention source (B,Sm,D)
    memory_positions: Optional[jax.Array] = None,
    chunk_threshold: int = 2047,
    dp=("data",),
) -> jax.Array:
    """Self- (or cross-) attention over a full sequence (train/prefill)."""
    H, Hkv, dh = cfg.num_heads, cfg.kv_heads, cfg.head_width
    window = cfg.window if kind == "local" else None
    src = x if memory is None else memory
    q = _constrain_heads(_heads(dense(x, params["wq"]), H, dh), dp)
    k = _constrain_heads(_heads(dense(src, params["wk"]), Hkv, dh), dp)
    v = _constrain_heads(_heads(dense(src, params["wv"]), Hkv, dh), dp)
    if memory is None:
        q = _apply_rope(cfg, q, positions)
        k = _apply_rope(cfg, k, positions)
        k_pos = positions if positions.ndim == 2 else positions[..., 0]
    else:
        # cross-attention: no rotary on encoder memory (whisper style)
        k_pos = (
            memory_positions
            if memory_positions is not None
            else jnp.broadcast_to(jnp.arange(src.shape[1])[None], src.shape[:2])
        )
    q_pos = positions if positions.ndim == 2 else positions[..., 0]
    scale = _scale(cfg)
    softcap = cfg.attn_logit_softcap
    Sk = src.shape[1]
    # sliding-window layers take the BANDED path (flops ~ S*(window+c),
    # §Perf P2.1); global attention above the threshold takes the
    # online-softmax chunked path (memory ~ S*c, §Perf M2)
    if window is not None and causal and memory is None and Sk > window:
        o = banded_local_attention(
            q, k, v, q_pos, k_pos,
            window=window, softcap=softcap, scale=scale,
            block=min(1024, window),
        )
    elif Sk > chunk_threshold:
        o = chunked_attention(
            q, k, v, q_pos, k_pos,
            causal=causal and memory is None, window=window,
            softcap=softcap, scale=scale,
            chunk=min(1024, Sk), unroll=cfg.scan_unroll,
        )
    else:
        bias = _mask_bias(
            q_pos, k_pos, causal=causal and memory is None, window=window
        )
        o = full_attention(q, k, v, bias, softcap=softcap, scale=scale)
    return dense(_unheads(o), params["wo"])


# ------------------------------ decode --------------------------------


def init_kv_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int) -> dict:
    """Cache for one attention layer. Local layers keep only a rotating
    window-sized buffer (bounded state — the long_500k enabler for
    hybrid archs)."""
    L = min(cfg.window, max_len) if (kind == "local" and cfg.window) else max_len
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    return {
        "k": jnp.zeros((batch, cfg.kv_heads, L, cfg.head_width), dt),
        "v": jnp.zeros((batch, cfg.kv_heads, L, cfg.head_width), dt),
        "pos": jnp.full((batch, L), -1, jnp.int32),
    }


def decode_attention(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,              # (B, 1, D)
    cache: dict,
    step: jax.Array,           # scalar int32: absolute position
    *,
    kind: str = "attn",
    memory_kv: Optional[tuple] = None,  # precomputed cross (k, v, k_pos)
) -> tuple[jax.Array, dict]:
    H, Hkv, dh = cfg.num_heads, cfg.kv_heads, cfg.head_width
    B = x.shape[0]
    q = _heads(dense(x, params["wq"]), H, dh)        # (B,H,1,dh)
    if memory_kv is not None:
        k, v, k_pos = memory_kv
        bias = jnp.zeros((B, 1, k.shape[2]), jnp.float32)
        o = full_attention(q, k, v, bias, softcap=cfg.attn_logit_softcap,
                           scale=_scale(cfg))
        return dense(_unheads(o), params["wo"]), cache

    pos_b = jnp.broadcast_to(step[None] if step.ndim == 0 else step, (B,))
    if cfg.mrope_sections is not None:
        qpos = jnp.broadcast_to(pos_b[:, None, None], (B, 1, 3))
    else:
        qpos = pos_b[:, None]
    q = _apply_rope(cfg, q, qpos)
    k_new = _heads(dense(x, params["wk"]), Hkv, dh)
    v_new = _heads(dense(x, params["wv"]), Hkv, dh)
    k_new = _apply_rope(cfg, k_new, qpos)

    L = cache["k"].shape[2]
    slot = (step % L).astype(jnp.int32)
    k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, 0, slot, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, 0, slot, 0))
    pos = jax.lax.dynamic_update_slice(
        cache["pos"], pos_b[:, None].astype(jnp.int32), (0, slot)
    )
    window = cfg.window if kind == "local" else None
    valid = pos >= 0
    keep = valid & (pos <= pos_b[:, None])
    if window is not None:
        keep &= pos > (pos_b[:, None] - window)
    bias = jnp.where(keep, 0.0, _NEG_INF)[:, None, :]   # (B,1,Sk)->broadcast
    o = full_attention(q, k, v, bias, softcap=cfg.attn_logit_softcap,
                       scale=_scale(cfg))
    out = dense(_unheads(o), params["wo"])
    return out, {"k": k, "v": v, "pos": pos}


# --------------------------- paged decode ------------------------------


def init_paged_kv_cache(
    cfg: ModelConfig, num_pages: int, page_size: int
) -> dict:
    """Page-pool KV cache for one attention layer.

    Pages are the unit of allocation (`serve.kv_pages.PageTable` owns the
    slot -> page mapping); one extra trash page at index `num_pages`
    swallows writes of inactive slots so the jitted step signature stays
    static regardless of which slots hold live requests.
    """
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    shape = (num_pages + 1, cfg.kv_heads, page_size, cfg.head_width)
    return {"k_pages": jnp.zeros(shape, dt), "v_pages": jnp.zeros(shape, dt)}


def paged_decode_attention(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,              # (B, 1, D)
    cache: dict,               # k_pages/v_pages (N+1, Hkv, ps, dh)
    page_map: jax.Array,       # (B, P) physical page per logical page, N=trash
    steps: jax.Array,          # (B,) int32 per-slot absolute position
    write_mask: jax.Array,     # (B,) bool — False routes the write to trash
    *,
    kind: str = "attn",
) -> tuple[jax.Array, dict]:
    """`decode_attention` reading/writing KV through a page table.

    The logical sequence of slot b lives at pages `page_map[b]` in order:
    position t maps to page t // ps, offset t % ps, so the gathered
    (B, Hkv, P*ps, dh) view reproduces the dense cache layout exactly and
    the attention math below is bitwise-identical to the dense path
    (padded/stale entries carry an exact -inf bias, contributing exact
    zeros to the softmax on both paths).  Per-slot `steps` replace the
    dense path's scalar clock — slots at different depths decode in one
    batched call (the continuous-batching enabler).
    """
    H, Hkv, dh = cfg.num_heads, cfg.kv_heads, cfg.head_width
    B = x.shape[0]
    num_pages = cache["k_pages"].shape[0] - 1
    ps = cache["k_pages"].shape[2]
    P = page_map.shape[1]

    q = _heads(dense(x, params["wq"]), H, dh)        # (B,H,1,dh)
    pos_b = steps.astype(jnp.int32)
    if cfg.mrope_sections is not None:
        qpos = jnp.broadcast_to(pos_b[:, None, None], (B, 1, 3))
    else:
        qpos = pos_b[:, None]
    q = _apply_rope(cfg, q, qpos)
    k_new = _heads(dense(x, params["wk"]), Hkv, dh)  # (B,Hkv,1,dh)
    v_new = _heads(dense(x, params["wv"]), Hkv, dh)
    k_new = _apply_rope(cfg, k_new, qpos)

    # scatter the new token's KV into its page (trash page when masked)
    logical = jnp.clip(pos_b // ps, 0, P - 1)
    phys = jnp.take_along_axis(page_map, logical[:, None], axis=1)[:, 0]
    phys = jnp.where(write_mask, phys, num_pages)
    off = pos_b % ps
    k_pages = cache["k_pages"].at[phys, :, off, :].set(
        k_new[:, :, 0, :], mode="drop"
    )
    v_pages = cache["v_pages"].at[phys, :, off, :].set(
        v_new[:, :, 0, :], mode="drop"
    )

    # gather the slot's pages back into a contiguous logical view
    k = k_pages[page_map]                            # (B,P,Hkv,ps,dh)
    v = v_pages[page_map]
    k = k.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, P * ps, dh)
    v = v.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, P * ps, dh)
    k_pos = jnp.broadcast_to(jnp.arange(P * ps, dtype=jnp.int32)[None], (B, P * ps))
    keep = k_pos <= pos_b[:, None]
    window = cfg.window if kind == "local" else None
    if window is not None:
        keep &= k_pos > (pos_b[:, None] - window)
    bias = jnp.where(keep, 0.0, _NEG_INF)[:, None, :]
    o = full_attention(q, k, v, bias, softcap=cfg.attn_logit_softcap,
                       scale=_scale(cfg))
    out = dense(_unheads(o), params["wo"])
    return out, {"k_pages": k_pages, "v_pages": v_pages}
