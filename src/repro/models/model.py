"""Unified transformer covering the 10 assigned architectures.

The decoder stack is expressed as scan-over-layers per homogeneous
pattern group (cfg.scan_groups) with per-group stacked parameters —
compile-time stays flat in depth, remat wraps each pattern unit, and
the residual stream is sharding-constrained at unit boundaries to
P(dp, None, "model") so saved activations are fully sharded (DESIGN §6).

Entry points:
  Transformer(cfg)           — descriptor tree, init/abstract/specs
  forward(params, cfg, batch)      — logits (train / prefill)
  loss_fn(params, cfg, batch)      — mean next-token CE
  init_cache / decode_step         — single-token serving
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .attention import (
    attention, attn_params, decode_attention, init_kv_cache,
    init_paged_kv_cache, paged_decode_attention,
)
from .config import ModelConfig
from .layers import (
    P_, abstract_tree, count_params, dense, init_tree,
    layer_norm, mlp, mlp_params, rms_norm, spec_tree, DTYPES,
)
from .moe import moe_ffn, moe_params
from .rglru import (
    init_rglru_state, rglru_block, rglru_decode, rglru_params,
)
from .rwkv import (
    init_rwkv_state, rwkv_channel_mix, rwkv_channel_mix_decode, rwkv_params,
    rwkv_time_mix, rwkv_time_mix_decode,
)

__all__ = [
    "Transformer", "forward", "loss_fn", "init_cache", "decode_step",
    "init_paged_cache", "paged_decode_step",
]

DP_DEFAULT = ("data",)


# --------------------------- parameter tree ---------------------------


def _norm_params(cfg: ModelConfig, kind: str) -> dict:
    if kind == "rwkv":  # LayerNorm with bias
        return {
            "scale": P_((cfg.d_model,), P("model"), init="ones", dtype="float32"),
            "bias": P_((cfg.d_model,), P("model"), init="zeros", dtype="float32"),
        }
    return {"scale": P_((cfg.d_model,), P("model"), init="zeros", dtype="float32")}


def _apply_norm(p: dict, cfg: ModelConfig, x, kind: str):
    if "bias" in p:
        return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rms_norm(x, p["scale"], cfg.norm_eps)


def block_params(cfg: ModelConfig, kind: str, *, cross: bool = False,
                 model_axis: int = 16) -> dict:
    d: dict = {"ln1": _norm_params(cfg, kind), "ln2": _norm_params(cfg, kind)}
    if kind in ("attn", "local"):
        d["attn"] = attn_params(cfg)
        if cross:
            d["xattn"] = attn_params(cfg, cross=True)
            d["lnx"] = _norm_params(cfg, kind)
        if cfg.num_experts:
            d["moe"] = moe_params(cfg, model_axis)
        else:
            d["mlp"] = mlp_params(cfg.d_model, cfg.d_ff, cfg.mlp_kind)
        if cfg.post_norms:
            d["post1"] = _norm_params(cfg, kind)
            d["post2"] = _norm_params(cfg, kind)
    elif kind == "rglru":
        d["rglru"] = rglru_params(cfg)
        d["mlp"] = mlp_params(cfg.d_model, cfg.d_ff, cfg.mlp_kind)
    elif kind == "rwkv":
        d.update(rwkv_params(cfg))
    else:
        raise ValueError(kind)
    return d


def _stack_descr(tree, repeats: int):
    def f(l: P_) -> P_:
        return P_(
            (repeats,) + l.shape, P(None, *l.spec), l.init, l.scale, l.dtype
        )
    return jax.tree.map(f, tree, is_leaf=lambda x: isinstance(x, P_))


def model_params(cfg: ModelConfig, model_axis: int = 16) -> dict:
    V, D = cfg.vocab_size, cfg.d_model
    tree: dict = {
        "embed": P_((V, D), P("model", "data"), init="embed"),
        "final_norm": _norm_params(cfg, "attn"),
    }
    if not cfg.tie_embeddings:
        tree["unembed"] = P_((D, V), P("data", "model"))
    groups = []
    for unit, repeats in cfg.scan_groups():
        unit_tree = {
            f"b{i}": block_params(
                cfg, kind, cross=cfg.encoder_layers > 0, model_axis=model_axis
            )
            for i, kind in enumerate(unit)
        }
        groups.append(_stack_descr(unit_tree, repeats))
    tree["groups"] = groups
    if cfg.encoder_layers:
        enc_unit = {"b0": block_params(cfg, "attn", model_axis=model_axis)}
        tree["encoder"] = {
            "blocks": _stack_descr(enc_unit, cfg.encoder_layers),
            "final_norm": _norm_params(cfg, "attn"),
        }
    return tree


# ------------------------------ forward -------------------------------


def _constrain(x, dp):
    if dp is None:                       # decentralized per-replica mode
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:       # single-device smoke tests
        return x
    spec = (
        P(dp, None, "model")
        if "model" in mesh.shape and x.shape[-1] % mesh.shape["model"] == 0
        else P(dp)
    )
    return jax.lax.with_sharding_constraint(x, spec)


def _block_forward(p, cfg: ModelConfig, kind: str, x, positions, *,
                   memory=None, causal=True, dp=DP_DEFAULT):
    if kind in ("attn", "local"):
        h = attention(
            p["attn"], cfg, _apply_norm(p["ln1"], cfg, x, kind), positions,
            kind=kind, causal=causal, dp=dp,
        )
        if cfg.post_norms:
            h = _apply_norm(p["post1"], cfg, h, kind)
        x = x + h
        if memory is not None and "xattn" in p:
            x = x + attention(
                p["xattn"], cfg, _apply_norm(p["lnx"], cfg, x, kind), positions,
                memory=memory, dp=dp,
            )
        z = _apply_norm(p["ln2"], cfg, x, kind)
        h = (moe_ffn(p["moe"], cfg, z, dp=dp) if cfg.num_experts
             else mlp(z, p["mlp"], cfg.mlp_kind))
        if cfg.post_norms:
            h = _apply_norm(p["post2"], cfg, h, kind)
        return x + h
    if kind == "rglru":
        x = x + rglru_block(p["rglru"], cfg, _apply_norm(p["ln1"], cfg, x, kind),
                            dp=dp)
        return x + mlp(_apply_norm(p["ln2"], cfg, x, kind), p["mlp"], cfg.mlp_kind)
    if kind == "rwkv":
        x = x + rwkv_time_mix(p["time"], cfg, _apply_norm(p["ln1"], cfg, x, kind))
        return x + rwkv_channel_mix(p["channel"], cfg, _apply_norm(p["ln2"], cfg, x, kind))
    raise ValueError(kind)


def _run_groups(params, cfg: ModelConfig, x, positions, *, memory=None,
                causal=True, dp=DP_DEFAULT):
    for g_idx, (unit, repeats) in enumerate(cfg.scan_groups()):
        gp = params["groups"][g_idx]

        def unit_fn(h, layer_p, unit=unit):
            for i, kind in enumerate(unit):
                h = _block_forward(
                    layer_p[f"b{i}"], cfg, kind, h, positions,
                    memory=memory, causal=causal, dp=dp,
                )
            return _constrain(h, dp), None

        f = jax.checkpoint(unit_fn) if cfg.remat else unit_fn
        x, _ = jax.lax.scan(f, x, gp, unroll=True if cfg.scan_unroll else 1)
    return x


def _embed(params, cfg: ModelConfig, tokens):
    e = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embeddings:
        e = e * jnp.asarray(cfg.d_model**0.5, e.dtype)
    return e.astype(DTYPES[cfg.dtype])


def _unembed(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        logits = jax.lax.dot_general(
            x, params["embed"], (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # T5/PaLM tied-head scaling: this repo's embed init is unit-variance
        # (see layers.P_), so against RMS-1 activations the raw tied product
        # emits std-sqrt(D) logits (loss ~3x ln V at init, huge per-batch
        # variance, and any final_logit_softcap saturated from step 0);
        # 1/sqrt(D) restores unit-scale logits for every from-scratch run.
        # If a reference-checkpoint import path is ever added, this pairs
        # with the init and must become per-config alongside it.
        logits = logits * jnp.asarray(cfg.d_model**-0.5, jnp.float32)
    else:
        logits = dense(x, params["unembed"]).astype(jnp.float32)
    if cfg.final_logit_softcap is not None:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits


def _encode(params, cfg: ModelConfig, frames, dp):
    """Whisper-style encoder over precomputed frame embeddings (stub
    frontend) with sinusoidal positions and non-causal attention."""
    B, S, D = frames.shape
    pos = jnp.arange(S)
    half = D // 2
    freq = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (9.21 / max(half - 1, 1)))
    ang = pos[:, None].astype(jnp.float32) * freq[None]
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    x = (frames.astype(jnp.float32) + pe[None]).astype(DTYPES[cfg.dtype])
    positions = jnp.broadcast_to(pos[None], (B, S))
    enc = params["encoder"]

    def unit_fn(h, layer_p):
        h = _block_forward(layer_p["b0"], cfg, "attn", h, positions,
                           causal=False, dp=dp)
        return _constrain(h, dp), None

    f = jax.checkpoint(unit_fn) if cfg.remat else unit_fn
    x, _ = jax.lax.scan(f, x, enc["blocks"], unroll=True if cfg.scan_unroll else 1)
    return _apply_norm(enc["final_norm"], cfg, x, "attn")


def _hidden(params, cfg: ModelConfig, batch: dict, *, dp=DP_DEFAULT):
    """Backbone through the final norm (pre-unembed)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    if cfg.mrope_sections is not None:
        positions = batch["positions"]
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    memory = None
    if cfg.encoder_layers:
        memory = _encode(params, cfg, batch["frames"], dp)
    x = _constrain(_embed(params, cfg, tokens), dp)
    x = _run_groups(params, cfg, x, positions, memory=memory, dp=dp)
    return _apply_norm(params["final_norm"], cfg, x, "attn")


def forward(params, cfg: ModelConfig, batch: dict, *, dp=DP_DEFAULT):
    """batch: tokens (B,S) [+ positions (B,S,3) for M-RoPE,
    + frames (B,Se,D) for enc-dec]. Returns fp32 logits (B,S,V)."""
    return _unembed(params, cfg, _hidden(params, cfg, batch, dp=dp))


def loss_fn(params, cfg: ModelConfig, batch: dict, *, dp=DP_DEFAULT,
            loss_chunk: int = 512):
    """Mean next-token cross-entropy; labels < 0 are masked.

    The (tokens, vocab) fp32 logits never materialize for the whole
    sequence: unembed + CE run CHUNKED over the sequence under
    jax.checkpoint (recomputed in backward).  At vocab 256k this is the
    difference between ~70 GiB and <2 GiB of loss buffers per chip
    (EXPERIMENTS.md §Perf, iteration M1).
    """
    x = _hidden(params, cfg, batch, dp=dp)
    labels = batch["labels"]
    B, S, D = x.shape
    c = min(loss_chunk, S)
    pad = (-S) % c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    n = (S + pad) // c
    xs = x.reshape(B, n, c, D).swapaxes(0, 1)          # (n, B, c, D)
    ls = labels.reshape(B, n, c).swapaxes(0, 1)        # (n, B, c)

    def chunk_nll(carry, xl):
        xc, lc = xl
        logits = _unembed(params, cfg, xc)             # (B, c, V) fp32
        mask = (lc >= 0).astype(jnp.float32)
        safe = jnp.maximum(lc, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        s, m = carry
        return (s + ((logz - gold) * mask).sum(), m + mask.sum()), None

    (nll, cnt), _ = jax.lax.scan(
        jax.checkpoint(chunk_nll), (jnp.zeros(()), jnp.zeros(())), (xs, ls),
        unroll=True if cfg.scan_unroll else 1,
    )
    return nll / jnp.maximum(cnt, 1.0)


# ------------------------------ serving -------------------------------


def init_cache(params, cfg: ModelConfig, batch: int, max_len: int,
               frames: Optional[jax.Array] = None, dp=DP_DEFAULT) -> dict:
    """Per-layer decode state, stacked to mirror the scan groups."""
    memory = None
    if cfg.encoder_layers:
        memory = _encode(params, cfg, frames, dp)

    def layer_state(kind):
        if kind in ("attn", "local"):
            return init_kv_cache(cfg, kind, batch, max_len)
        if kind == "rglru":
            return init_rglru_state(cfg, batch)
        return init_rwkv_state(cfg, batch)

    groups = []
    for unit, repeats in cfg.scan_groups():
        unit_state = {
            f"b{i}": layer_state(kind) for i, kind in enumerate(unit)
        }
        groups.append(
            jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (repeats,) + a.shape),
                unit_state,
            )
        )
    return {
        "groups": groups,
        "step": jnp.zeros((), jnp.int32),
        "memory": memory,
    }


def _block_decode(p, cfg: ModelConfig, kind: str, x, state, step, memory):
    if kind in ("attn", "local"):
        h, new = decode_attention(
            p["attn"], cfg, _apply_norm(p["ln1"], cfg, x, kind), state, step,
            kind=kind,
        )
        if cfg.post_norms:
            h = _apply_norm(p["post1"], cfg, h, kind)
        x = x + h
        if memory is not None and "xattn" in p:
            zx = _apply_norm(p["lnx"], cfg, x, kind)
            hx = attention(p["xattn"], cfg, zx,
                           jnp.broadcast_to(step[None, None], (x.shape[0], 1)),
                           memory=memory)
            x = x + hx
        z = _apply_norm(p["ln2"], cfg, x, kind)
        h = moe_ffn(p["moe"], cfg, z) if cfg.num_experts else mlp(z, p["mlp"], cfg.mlp_kind)
        if cfg.post_norms:
            h = _apply_norm(p["post2"], cfg, h, kind)
        return x + h, new
    if kind == "rglru":
        h, new = rglru_decode(p["rglru"], cfg, _apply_norm(p["ln1"], cfg, x, kind), state)
        x = x + h
        return x + mlp(_apply_norm(p["ln2"], cfg, x, kind), p["mlp"], cfg.mlp_kind), new
    if kind == "rwkv":
        h, new_t = rwkv_time_mix_decode(
            p["time"], cfg, _apply_norm(p["ln1"], cfg, x, kind), state
        )
        x = x + h
        h, new_c = rwkv_channel_mix_decode(
            p["channel"], cfg, _apply_norm(p["ln2"], cfg, x, kind), new_t
        )
        return x + h, new_c
    raise ValueError(kind)


def decode_step(params, cfg: ModelConfig, cache: dict, tokens: jax.Array,
                *, dp=DP_DEFAULT):
    """One serving step: tokens (B,) -> logits (B, V), updated cache."""
    B = tokens.shape[0]
    step = cache["step"]
    x = _embed(params, cfg, tokens[:, None])
    memory = cache["memory"]
    new_groups = []
    for g_idx, (unit, repeats) in enumerate(cfg.scan_groups()):
        gp = params["groups"][g_idx]
        gs = cache["groups"][g_idx]

        def unit_fn(h, inp, unit=unit):
            layer_p, layer_s = inp
            new_s = {}
            for i, kind in enumerate(unit):
                h, ns = _block_decode(
                    layer_p[f"b{i}"], cfg, kind, h, layer_s[f"b{i}"], step, memory
                )
                new_s[f"b{i}"] = ns
            return h, new_s

        x, ns = jax.lax.scan(
            unit_fn, x, (gp, gs), unroll=True if cfg.scan_unroll else 1
        )
        new_groups.append(ns)
    x = _apply_norm(params["final_norm"], cfg, x, "attn")
    logits = _unembed(params, cfg, x)[:, 0]
    new_cache = {"groups": new_groups, "step": step + 1, "memory": memory}
    return logits, new_cache


# --------------------------- paged serving ----------------------------


def init_paged_cache(
    cfg: ModelConfig, num_slots: int, num_pages: int, page_size: int
) -> dict:
    """Decode state for the paged/continuous-batching path.

    Attention layers share one page pool per layer (plus a trash page —
    see `attention.init_paged_kv_cache`); recurrent layers (rglru/rwkv)
    keep ordinary per-slot state that the engine re-initializes on
    admission via the step's `write_mask`.  Encoder-decoder configs are
    not paged (their decode state is per-request memory, not a KV pool).
    """
    if cfg.encoder_layers:
        raise ValueError(
            "paged serving supports decoder-only configs; "
            f"{cfg.name} has encoder layers"
        )

    def layer_state(kind):
        if kind in ("attn", "local"):
            return init_paged_kv_cache(cfg, num_pages, page_size)
        if kind == "rglru":
            return init_rglru_state(cfg, num_slots)
        return init_rwkv_state(cfg, num_slots)

    groups = []
    for unit, repeats in cfg.scan_groups():
        unit_state = {f"b{i}": layer_state(kind) for i, kind in enumerate(unit)}
        groups.append(
            jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (repeats,) + a.shape),
                unit_state,
            )
        )
    return {"groups": groups}


def _block_decode_paged(p, cfg: ModelConfig, kind: str, x, state,
                        page_map, steps, write_mask):
    if kind in ("attn", "local"):
        h, new = paged_decode_attention(
            p["attn"], cfg, _apply_norm(p["ln1"], cfg, x, kind), state,
            page_map, steps, write_mask, kind=kind,
        )
        if cfg.post_norms:
            h = _apply_norm(p["post1"], cfg, h, kind)
        x = x + h
        z = _apply_norm(p["ln2"], cfg, x, kind)
        h = (moe_ffn(p["moe"], cfg, z) if cfg.num_experts
             else mlp(z, p["mlp"], cfg.mlp_kind))
        if cfg.post_norms:
            h = _apply_norm(p["post2"], cfg, h, kind)
        return x + h, new
    # recurrent layers: per-slot (B, ...) state — zero a slot's state at
    # the first token of a fresh admission (init state is all-zeros, so
    # slot reuse cannot leak the previous request's recurrence), run the
    # dense decode body, then hold back non-written slots' updates
    def bmask(m, a):
        if a.shape[0] != m.shape[0]:      # rwkv wkv state is (B*H, N, N)
            m = jnp.repeat(m, a.shape[0] // m.shape[0])
        return m.reshape((-1,) + (1,) * (a.ndim - 1))

    fresh = write_mask & (steps == 0)
    state = jax.tree.map(
        lambda o: jnp.where(bmask(fresh, o), jnp.zeros((), o.dtype), o),
        state,
    )
    h, new = _block_decode(p, cfg, kind, x, state, steps, None)
    return h, jax.tree.map(
        lambda n, o: jnp.where(bmask(write_mask, n), n, o), new, state
    )


def paged_decode_step(
    params,
    cfg: ModelConfig,
    cache: dict,               # from init_paged_cache
    tokens: jax.Array,         # (B,) current token per slot
    page_map: jax.Array,       # (B, P) int32 physical page ids (trash = N)
    steps: jax.Array,          # (B,) int32 per-slot absolute position
    write_mask: jax.Array,     # (B,) bool — gate KV writes / state updates
):
    """One continuous-batching step: every slot decodes its own position.

    Identical math to `decode_step` per live slot (bitwise on the lax
    path when P * page_size == the dense cache's max_len); masked slots
    write to the trash page and keep their recurrent state, so one
    compiled step serves any admit/retire pattern.
    """
    x = _embed(params, cfg, tokens[:, None])
    new_groups = []
    for g_idx, (unit, repeats) in enumerate(cfg.scan_groups()):
        gp = params["groups"][g_idx]
        gs = cache["groups"][g_idx]

        def unit_fn(h, inp, unit=unit):
            layer_p, layer_s = inp
            new_s = {}
            for i, kind in enumerate(unit):
                h, ns = _block_decode_paged(
                    layer_p[f"b{i}"], cfg, kind, h, layer_s[f"b{i}"],
                    page_map, steps, write_mask,
                )
                new_s[f"b{i}"] = ns
            return h, new_s

        x, ns = jax.lax.scan(
            unit_fn, x, (gp, gs), unroll=True if cfg.scan_unroll else 1
        )
        new_groups.append(ns)
    x = _apply_norm(params["final_norm"], cfg, x, "attn")
    logits = _unembed(params, cfg, x)[:, 0]
    return logits, {"groups": new_groups}


# ------------------------------ facade --------------------------------


@dataclasses.dataclass
class Transformer:
    cfg: ModelConfig
    model_axis: int = 16

    def __post_init__(self):
        self.cfg.validate()
        self.descr = model_params(self.cfg, self.model_axis)

    def init(self, key):
        return init_tree(self.descr, key, DTYPES[self.cfg.dtype])

    def abstract(self):
        return abstract_tree(self.descr, DTYPES[self.cfg.dtype])

    def specs(self):
        return spec_tree(self.descr)

    @property
    def num_params(self) -> int:
        return count_params(self.descr)
