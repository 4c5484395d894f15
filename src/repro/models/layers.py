"""Parameter descriptors and basic layers (pure JAX, no framework dep).

A model is declared once as a tree of `P_` descriptors (shape +
PartitionSpec + init); the same tree materializes real params
(`init_tree`), abstract params for the dry-run (`abstract_tree`), and
the sharding tree (`spec_tree`).  Sharding uses two logical mesh axes:
"data" (FSDP/ZeRO shard axis) and "model" (tensor-parallel axis); the
multi-pod "pod" axis replicates params and enters only through input
batch sharding and gradient synchronization (DESIGN.md §6).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "P_", "init_tree", "abstract_tree", "spec_tree", "count_params",
    "rms_norm", "layer_norm", "rope", "mrope", "mlp",
    "dense", "constrain_act", "DTYPES",
]

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class P_:
    """Parameter descriptor: shape, partition spec, init kind."""

    shape: tuple[int, ...]
    spec: P = P()
    init: str = "fan_in"     # fan_in | zeros | ones | normal | embed
    scale: float = 1.0
    dtype: Optional[str] = None  # override model dtype (e.g. fp32 norms)

    def initialize(self, key, default_dtype):
        dt = DTYPES[self.dtype] if self.dtype else default_dtype
        if self.init == "zeros":
            return jnp.zeros(self.shape, dt)
        if self.init == "ones":
            return jnp.ones(self.shape, dt)
        if self.init == "embed":
            sd = 1.0
        elif self.init == "normal":
            sd = self.scale
        else:  # fan_in
            fan_in = self.shape[0] if len(self.shape) >= 2 else max(self.shape[-1], 1)
            if len(self.shape) == 3:  # (heads, in, out) style or (E, in, out)
                fan_in = self.shape[1]
            sd = self.scale / math.sqrt(fan_in)
        return (jax.random.normal(key, self.shape, jnp.float32) * sd).astype(dt)

    def abstract(self, default_dtype):
        dt = DTYPES[self.dtype] if self.dtype else default_dtype
        return jax.ShapeDtypeStruct(self.shape, dt)


def _is_leaf(x):
    return isinstance(x, P_)


def init_tree(tree, key, dtype):
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    vals = [l.initialize(k, dtype) for l, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def abstract_tree(tree, dtype):
    return jax.tree.map(lambda l: l.abstract(dtype), tree, is_leaf=_is_leaf)


def spec_tree(tree):
    return jax.tree.map(lambda l: l.spec, tree, is_leaf=_is_leaf)


def count_params(tree) -> int:
    return sum(
        int(math.prod(l.shape))
        for l in jax.tree.leaves(tree, is_leaf=_is_leaf)
    )


# ----------------------------- layers ---------------------------------


def constrain_act(x, dp, axis: int = -1):
    """Shard an activation's last dim over "model" (and dim 0 over dp)
    when a mesh is in context and the dims divide; no-op otherwise."""
    if dp is None:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.shape:
        return x
    spec = [None] * x.ndim
    dp_size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_size *= mesh.shape[a]
    if x.shape[0] % dp_size == 0:
        spec[0] = dp
    if x.shape[-1] % mesh.shape["model"] == 0:
        spec[-1] = "model"
    return jax.lax.with_sharding_constraint(x, P(*spec))


def rms_norm(x, scale, eps):
    """Stats in fp32; the (B,S,D) tensor itself stays in model dtype.
    The mean-square reduces through a dot with fp32 accumulation, so no
    fp32 copy of x ever materializes (§Perf M5/M9)."""
    sq = jnp.einsum(
        "...d,...d->...", x, x, preferred_element_type=jnp.float32
    )
    var = (sq / x.shape[-1])[..., None]
    factor = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * factor * (1.0 + scale).astype(x.dtype)


def layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True) - jnp.square(mu)
    factor = jax.lax.rsqrt(var + eps)
    out = (x - mu.astype(x.dtype)) * factor.astype(x.dtype)
    return out * scale.astype(x.dtype) + bias.astype(x.dtype)


def dense(x, w):
    """x: (..., in), w: (in, out) in the model dtype.  No forced fp32
    output: the MXU accumulates in fp32 regardless, and a forced
    preferred_element_type=f32 materializes an fp32 copy of every
    activation in the lowered module (§Perf M5)."""
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
    )


def _rope_angles(positions, dims, theta):
    """positions: (..., S) int; returns cos/sin (..., S, dims//2) fp32."""
    half = dims // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, positions, theta):
    """x: (B, H, S, D); positions: (B, S). Rotates pairs (even, odd)."""
    B, H, S, D = x.shape
    cos, sin = _rope_angles(positions, D, theta)     # (B, S, D/2)
    cos, sin = cos[:, None], sin[:, None]            # (B, 1, S, D/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mrope(x, positions, theta, sections):
    """Multimodal RoPE (qwen2-vl): positions (B, S, 3) = (t, h, w) ids;
    the D/2 rotary frequencies are split into 3 sections, each rotated
    by its own position stream."""
    B, H, S, D = x.shape
    half = D // 2
    assert sum(sections) == half, (sections, half)
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # pick the position stream per frequency index
    sec_id = jnp.repeat(
        jnp.arange(3), jnp.asarray(sections), total_repeat_length=half
    )                                                 # (half,)
    pos = positions.astype(jnp.float32)               # (B, S, 3)
    pos_per_freq = jnp.take_along_axis(
        pos[..., None, :], sec_id[None, None, :, None].astype(jnp.int32), axis=-1
    )[..., 0]                                         # (B, S, half)
    ang = pos_per_freq * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------- MLP -----------------------------------


def mlp_params(d_model: int, d_ff: int, kind: str) -> dict:
    if kind in ("swiglu", "geglu"):
        return {
            "wi": P_((d_model, d_ff), P("data", "model")),
            "wg": P_((d_model, d_ff), P("data", "model")),
            "wo": P_((d_ff, d_model), P("model", "data")),
        }
    return {  # plain gelu (whisper)
        "wi": P_((d_model, d_ff), P("data", "model")),
        "wo": P_((d_ff, d_model), P("model", "data")),
    }


def mlp(x, params, kind: str):
    if kind == "swiglu":
        return dense(jax.nn.silu(dense(x, params["wg"])) * dense(x, params["wi"]),
                     params["wo"])
    if kind == "geglu":
        return dense(
            jax.nn.gelu(dense(x, params["wg"]), approximate=True)
            * dense(x, params["wi"]),
            params["wo"],
        )
    return dense(jax.nn.gelu(dense(x, params["wi"]), approximate=True), params["wo"])
