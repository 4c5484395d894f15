"""RWKV-6 "Finch" block (rwkv6-3b): attention-free time mix with
data-dependent per-channel decay + squared-ReLU channel mix.

Time-mix (per head of width N):
    y_t = (S_{t-1} + (u * k_t) v_t^T)^T r_t,   S_t = diag(w_t) S_{t-1} + k_t v_t^T
with w_t = exp(-exp(w0 + tanh(x_w A) B)) — the defining Finch feature
(data-dependent decay, paper arXiv:2404.05892).  r/k/v/g use static
token-shift lerps; the decay path carries the low-rank data-dependent
delta.  The wkv recurrence lowers through `repro.kernels.rwkv6`: the
lax.scan oracle unless `use_pallas=True` asks for the Pallas kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import P_, dense

__all__ = [
    "rwkv_params", "rwkv_time_mix", "rwkv_channel_mix",
    "rwkv_time_mix_decode", "rwkv_channel_mix_decode", "init_rwkv_state",
]

_DECAY_LORA = 64


def rwkv_params(cfg: ModelConfig) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    H, N = cfg.rwkv_heads, cfg.rwkv_head_dim
    return {
        "time": {
            "mu_r": P_((D,), P("model"), init="normal", scale=0.2),
            "mu_k": P_((D,), P("model"), init="normal", scale=0.2),
            "mu_v": P_((D,), P("model"), init="normal", scale=0.2),
            "mu_g": P_((D,), P("model"), init="normal", scale=0.2),
            "mu_w": P_((D,), P("model"), init="normal", scale=0.2),
            "wr": P_((D, D), P("data", "model")),
            "wk": P_((D, D), P("data", "model")),
            "wv": P_((D, D), P("data", "model")),
            "wg": P_((D, D), P("data", "model")),
            "w0": P_((D,), P("model"), init="normal", scale=0.5),
            "wa": P_((D, _DECAY_LORA), P("data", None), scale=0.5),
            "wb": P_((_DECAY_LORA, D), P(None, "model"), scale=0.5),
            "u": P_((H, N), P("model", None), init="normal", scale=0.2),
            "ln_scale": P_((D,), P("model"), init="ones", dtype="float32"),
            "wo": P_((D, D), P("model", "data")),
        },
        "channel": {
            "mu_k": P_((D,), P("model"), init="normal", scale=0.2),
            "mu_r": P_((D,), P("model"), init="normal", scale=0.2),
            "wk": P_((D, F), P("data", "model")),
            "wv": P_((F, D), P("model", "data")),
            "wr": P_((D, D), P("data", "model")),
        },
    }


def _shift(x, prev=None):
    """Token shift: x_{t-1} (zeros / `prev` at t=0). x: (B,S,D)."""
    if prev is None:
        prev = jnp.zeros_like(x[:, :1])
    return jnp.concatenate([prev, x[:, :-1]], axis=1)


def _decay(p, xw):
    lora = jnp.einsum(
        "bsd,dk->bsk", jnp.tanh(jnp.einsum("bsd,dk->bsk", xw, p["wa"])), p["wb"]
    )
    return jnp.exp(-jnp.exp(p["w0"].astype(jnp.float32) + lora.astype(jnp.float32)))


def _group_norm(y, scale, H, N, eps=1e-5):
    """Per-head layernorm of the wkv output (B,S,H,N)."""
    yf = y.astype(jnp.float32)
    mu = yf.mean(-1, keepdims=True)
    var = yf.var(-1, keepdims=True)
    yn = (yf - mu) * jax.lax.rsqrt(var + eps)
    return (yn.reshape(*y.shape[:2], H * N) * scale).astype(y.dtype)


def rwkv_time_mix(
    p: dict, cfg: ModelConfig, x: jax.Array, *, use_pallas: bool = False
) -> jax.Array:
    from repro.kernels.rwkv6 import rwkv6_wkv

    B, S, D = x.shape
    H, N = cfg.rwkv_heads, cfg.rwkv_head_dim
    sx = _shift(x) - x
    xr = x + sx * p["mu_r"]
    xk = x + sx * p["mu_k"]
    xv = x + sx * p["mu_v"]
    xg = x + sx * p["mu_g"]
    xw = (x + sx * p["mu_w"]).astype(jnp.float32)
    r = dense(xr, p["wr"])
    k = dense(xk, p["wk"])
    v = dense(xv, p["wv"])
    g = jax.nn.silu(dense(xg, p["wg"]))
    w = _decay(p, xw)                                       # (B,S,D) in (0,1)

    def to_bh(a):  # (B,S,D) -> (B*H, S, N)
        return a.reshape(B, S, H, N).transpose(0, 2, 1, 3).reshape(B * H, S, N)

    u = jnp.broadcast_to(p["u"][None], (B, H, N)).reshape(B * H, N)
    # the decay stays fp32: bf16-rounding w compounds through the state
    y = rwkv6_wkv(
        to_bh(r), to_bh(k), to_bh(v), to_bh(w), u.astype(r.dtype),
        use_pallas=use_pallas, unroll=cfg.scan_unroll,
    )                                                        # (B*H, S, N)
    y = y.reshape(B, H, S, N).transpose(0, 2, 1, 3)          # (B,S,H,N)
    y = _group_norm(y, p["ln_scale"], H, N)
    return dense(y * g, p["wo"])


def rwkv_channel_mix(p: dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    sx = _shift(x) - x
    xk = x + sx * p["mu_k"]
    xr = x + sx * p["mu_r"]
    k = jnp.square(jax.nn.relu(dense(xk, p["wk"])))
    return jax.nn.sigmoid(dense(xr, p["wr"])) * dense(k, p["wv"])


# ------------------------------ decode --------------------------------


def init_rwkv_state(cfg: ModelConfig, batch: int) -> dict:
    H, N = cfg.rwkv_heads, cfg.rwkv_head_dim
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    return {
        "tm_prev": jnp.zeros((batch, 1, cfg.d_model), dt),
        "cm_prev": jnp.zeros((batch, 1, cfg.d_model), dt),
        "wkv": jnp.zeros((batch * H, N, N), jnp.float32),
    }


def rwkv_time_mix_decode(
    p: dict, cfg: ModelConfig, x: jax.Array, state: dict
) -> tuple[jax.Array, dict]:
    """x: (B, 1, D); O(1) state update."""
    B, _, D = x.shape
    H, N = cfg.rwkv_heads, cfg.rwkv_head_dim
    sx = state["tm_prev"] - x
    xr, xk, xv, xg = (x + sx * p[m] for m in ("mu_r", "mu_k", "mu_v", "mu_g"))
    xw = (x + sx * p["mu_w"]).astype(jnp.float32)
    r = dense(xr, p["wr"]).reshape(B * H, N)
    k = dense(xk, p["wk"]).reshape(B * H, N).astype(jnp.float32)
    v = dense(xv, p["wv"]).reshape(B * H, N).astype(jnp.float32)
    g = jax.nn.silu(dense(xg, p["wg"]))
    w = _decay(p, xw).reshape(B * H, N)
    u = jnp.broadcast_to(p["u"][None], (B, H, N)).reshape(B * H, N).astype(jnp.float32)
    s = state["wkv"]                                        # (BH, N, N)
    kv = k[:, :, None] * v[:, None, :]
    y = jnp.einsum("bnm,bn->bm", s + u[:, :, None] * kv, r.astype(jnp.float32))
    s_new = w[:, :, None] * s + kv
    y = y.reshape(B, 1, H, N).astype(x.dtype)
    y = _group_norm(y, p["ln_scale"], H, N)
    out = dense((y * g).astype(x.dtype), p["wo"])
    return out, {**state, "tm_prev": x, "wkv": s_new}


def rwkv_channel_mix_decode(
    p: dict, cfg: ModelConfig, x: jax.Array, state: dict
) -> tuple[jax.Array, dict]:
    sx = state["cm_prev"] - x
    xk = x + sx * p["mu_k"]
    xr = x + sx * p["mu_r"]
    k = jnp.square(jax.nn.relu(dense(xk, p["wk"])))
    out = jax.nn.sigmoid(dense(xr, p["wr"])) * dense(k, p["wv"])
    return out, {**state, "cm_prev": x}
