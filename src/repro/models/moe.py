"""Mixture-of-experts FFN (llama4-maverick top-1 x 128e, grok-1 top-2 x 8e).

Sort-based dispatch with a static per-expert capacity (MaxText-style):
token->expert assignments are sorted by expert id, each token gets its
rank within its expert group, tokens beyond capacity are dropped (their
residual passes through — standard capacity-drop semantics).  Expert
weights are laid out (E, din, dout) with experts sharded over "model"
when divisible (EP) and the hidden dim sharded otherwise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import P_

__all__ = ["moe_params", "moe_ffn"]


def _constrain_tokens(x, dp):
    """Shard a (T, ...) flattened-token tensor over dp on dim 0."""
    if dp is None:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    dp_size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_size *= mesh.shape.get(a, 1)
    if x.shape[0] % dp_size != 0:
        return x
    return jax.lax.with_sharding_constraint(
        x, P(dp, *([None] * (x.ndim - 1)))
    )


def _constrain_bsd(x, dp):
    """Shard a (B, S, D) tensor over dp on batch (post-combine)."""
    if dp is None:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    dp_size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_size *= mesh.shape.get(a, 1)
    if x.shape[0] % dp_size != 0:
        return x
    return jax.lax.with_sharding_constraint(x, P(dp, None, None))


def _constrain_ecd(x, dp):
    """Shard (E, C, F_or_D) expert buffers: experts over "model" when
    they divide it (EP), else capacity over dp + feature over "model" —
    without this GSPMD tends to replicate the expert einsums (observed
    21x flops and 20 GiB fp32 activations on grok-1)."""
    if dp is None:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.shape:
        return x
    E = x.shape[0]
    model = mesh.shape["model"]
    dp_size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_size *= mesh.shape.get(a, 1)
    spec = [None] * x.ndim
    if E % model == 0:
        spec[0] = "model"
        if x.shape[1] % dp_size == 0:
            spec[1] = dp
    else:
        if x.shape[1] % dp_size == 0:
            spec[1] = dp
        if x.shape[-1] % model == 0:
            spec[-1] = "model"
    return jax.lax.with_sharding_constraint(x, P(*spec))


def moe_params(cfg: ModelConfig, model_axis: int = 16) -> dict:
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff
    if E % model_axis == 0:
        espec_in = P("model", "data", None)    # expert-parallel
        espec_out = P("model", None, "data")
    else:
        espec_in = P(None, "data", "model")    # tensor-parallel inside expert
        espec_out = P(None, "model", "data")
    return {
        "router": P_((D, E), P("data", None), scale=0.1),
        "wi": P_((E, D, F), espec_in),
        "wg": P_((E, D, F), espec_in),
        "wo": P_((E, F, D), espec_out),
    }


def moe_ffn(params: dict, cfg: ModelConfig, x: jax.Array,
            dp=("data",), token_chunk: int = 131_072) -> jax.Array:
    """x: (B, S, D) -> (B, S, D).

    Tokens are processed in chunks under jax.checkpoint: the gather/
    scatter cotangents and dispatch buffers scale with the CHUNK, not
    the 1M-token global batch (§Perf M8).  Routing (and capacity) is
    per-chunk — standard local-capacity semantics.
    """
    B, S, D = x.shape
    T = B * S
    tc = min(token_chunk, T)
    if T % tc != 0:
        tc = T  # irregular sizes (smoke tests): single chunk
    n = T // tc
    xt_all = _constrain_tokens(x.reshape(T, D), dp)
    if n == 1:
        return _constrain_bsd(
            _moe_chunk(params, cfg, xt_all, dp).reshape(B, S, D), dp
        )
    xs = xt_all.reshape(n, tc, D)

    def chunk_fn(_, xc):
        return 0, _constrain_tokens(_moe_chunk(params, cfg, xc, dp), dp)

    _, out = jax.lax.scan(
        jax.checkpoint(chunk_fn), 0, xs,
        unroll=True if cfg.scan_unroll else 1,
    )
    # constrain the STACKED (n, tc, D) scan output: per-iteration
    # constraints inside the body do not bind the stack buffer
    if dp is not None:
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.empty:
            dp_size = 1
            for a in (dp if isinstance(dp, tuple) else (dp,)):
                dp_size *= mesh.shape.get(a, 1)
            if tc % dp_size == 0:
                out = jax.lax.with_sharding_constraint(out, P(None, dp, None))
    return _constrain_bsd(out.reshape(B, S, D), dp)


def _moe_chunk(params: dict, cfg: ModelConfig, xt: jax.Array, dp) -> jax.Array:
    """Route + dispatch + expert FFN + combine for (T, D) tokens."""
    T, D = xt.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    # fp32 router LOGITS without materializing an fp32 copy of xt
    logits = jnp.einsum(
        "td,de->te", xt, params["router"].astype(xt.dtype),
        preferred_element_type=jnp.float32,
    )
    gate_all = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(gate_all, K)            # (T, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # flatten assignments and rank tokens within each expert
    flat_e = experts.reshape(-1)                            # (T*K,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # rank within group = index - first index of this expert id
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank = jnp.arange(T * K) - first
    C = max(1, int(cfg.moe_capacity_factor * T * K / E))
    C = C + (-C) % 256                                      # shard-friendly
    keep = rank < C
    token_id = order // K                                   # source token
    slot_e = sorted_e
    slot_c = jnp.where(keep, rank, C)                       # overflow -> sink

    # dispatch: scatter only an (E, C+pad) int32 INDEX map, then GATHER
    # the big (E, C, D) buffer — scattering activations directly defeats
    # GSPMD sharding (§Perf M4)
    pad_slots = 256
    idx = jnp.full((E, C + pad_slots), T, jnp.int32)
    idx = idx.at[slot_e, slot_c].set(token_id.astype(jnp.int32), mode="drop")
    xt_pad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
    h = _constrain_ecd(jnp.take(xt_pad, idx[:, :C], axis=0), dp)  # (E, C, D)

    # expert einsums emit the model dtype (MXU accumulates fp32; a forced
    # fp32 preferred type materializes fp32 copies of every buffer)
    up = jnp.einsum("ecd,edf->ecf", h, params["wi"])
    gset = jnp.einsum("ecd,edf->ecf", h, params["wg"])
    act = _constrain_ecd(jax.nn.silu(gset) * up, dp)
    out_e = _constrain_ecd(jnp.einsum("ecf,efd->ecd", act, params["wo"]), dp)

    # combine: pure GATHER back via the inverse sort permutation — a
    # scatter-add into (T, D) defeats GSPMD sharding (§Perf M4)
    out_pad = jnp.concatenate(
        [out_e, jnp.zeros((E, 1, D), out_e.dtype)], axis=1
    )                                                       # (E, C+1, D)
    inv = jnp.argsort(order)                                # (T*K,)
    c_of = slot_c[inv].reshape(T, K)
    keep_tk = keep[inv].reshape(T, K)
    gathered = _constrain_tokens(out_pad[experts, c_of], dp)   # (T, K, D)
    w = (gates * keep_tk).astype(xt.dtype)
    combined = jnp.einsum("tkd,tk->td", gathered, w)
    return combined.astype(xt.dtype)
