"""Abstract inputs + shardings for every (arch x shape x mesh) cell.

`build_cell(cfg, shape_name, mesh)` returns everything the dry-run (and
the real launcher) needs: the step callable, abstract arguments
(ShapeDtypeStructs — no allocation), and NamedShardings, with
divisibility-sanitized specs (a mesh axis that does not divide a dim is
dropped to replication for that dim — e.g. whisper's 51865 vocab on a
16-way model axis).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs.registry import SHAPES
from repro.models import Transformer, decode_step, forward, init_cache
from repro.models.config import ModelConfig
from repro.models.layers import DTYPES
from repro.optim import adafactor, adamw, cosine_schedule, make_optimizer
from repro.train import init_train_state, make_train_step

__all__ = ["build_cell", "sanitize_spec", "state_shardings", "Cell"]


def sanitize_spec(spec: P, shape: tuple, mesh) -> P:
    new = []
    for i, ax in enumerate(spec):
        if ax is None or i >= len(shape):
            new.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        new.append(ax if shape[i] % size == 0 else None)
    return P(*new)


def _ns(mesh, spec: P, shape: tuple) -> NamedSharding:
    return NamedSharding(mesh, sanitize_spec(spec, shape, mesh))


def state_shardings(mesh, params_abs, param_specs, opt_abs) -> dict:
    """Shardings for {params, opt, step}: optimizer moments follow the
    param layout; adafactor's factored vectors drop the reduced dim."""
    p_sh = jax.tree.map(
        lambda s, a: _ns(mesh, s, a.shape), param_specs, params_abs
    )

    def opt_entry(name, sub_abs):
        if name in ("m",):  # momentum mirrors params
            return p_sh
        if name == "count":
            return NamedSharding(mesh, P())
        if name == "v":
            # adamw: mirrors params; adafactor: {vr, vc} per param
            def build(spec, abs_sub):
                if isinstance(abs_sub, dict) and "vr" in abs_sub:
                    return {
                        "vr": _ns(mesh, P(*spec[:-1]), abs_sub["vr"].shape),
                        "vc": _ns(
                            mesh, P(*(tuple(spec[:-2]) + (spec[-1],)))
                            if len(spec) >= 2 else P(),
                            abs_sub["vc"].shape,
                        ),
                    }
                if isinstance(abs_sub, dict) and "v" in abs_sub:
                    return {"v": _ns(mesh, spec, abs_sub["v"].shape)}
                # adamw leaf mirrors the param
                return _ns(mesh, spec, abs_sub.shape)

            return jax.tree.map(
                build, param_specs, sub_abs,
                is_leaf=lambda x: isinstance(x, P),
            )
        raise KeyError(name)

    opt_sh = {k: opt_entry(k, v) for k, v in opt_abs.items()}
    return {
        "params": p_sh,
        "opt": opt_sh,
        "step": NamedSharding(mesh, P()),
    }


def _batch_abs_and_sh(cfg: ModelConfig, B: int, S: int, mesh, dp,
                      with_labels: bool):
    abs_, sh = {}, {}

    def add(name, shape, dtype, spec):
        abs_[name] = jax.ShapeDtypeStruct(shape, dtype)
        sh[name] = _ns(mesh, spec, shape)

    add("tokens", (B, S), jnp.int32, P(dp, None))
    if with_labels:
        add("labels", (B, S), jnp.int32, P(dp, None))
    if cfg.mrope_sections is not None:
        add("positions", (B, S, 3), jnp.int32, P(dp, None, None))
    if cfg.encoder_layers:
        add(
            "frames", (B, cfg.encoder_seq, cfg.d_model),
            DTYPES[cfg.dtype], P(dp, None, None),
        )
    return abs_, sh


def _cache_shardings(cfg: ModelConfig, cache_abs, mesh, dp):
    """Name-based sharding rules for decode state."""
    dp_size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_size *= mesh.shape[a]

    def rule(path, leaf):
        name = ""
        for pp in reversed(path):
            if isinstance(pp, jax.tree_util.DictKey):
                name = str(pp.key)
                break
        shape = leaf.shape

        def spec4(base: P) -> P:
            # per-layer states under "groups" carry a leading stacked
            # (scan repeats) axis — prepend None for it
            if len(shape) == len(base) + 1:
                return P(None, *base)
            return base

        if name in ("k", "v"):          # (B, Hkv, L, dh) [+stack]
            hkv_axis = len(shape) - 3
            if shape[hkv_axis] % mesh.shape["model"] == 0:
                return _ns(mesh, spec4(P(dp, "model", None, None)), shape)
            # GQA heads below the TP degree: shard the cache SEQUENCE dim
            # instead (flash-decode style) — softmax stats psum over model
            return _ns(mesh, spec4(P(dp, None, "model", None)), shape)
        if name == "pos":               # (B, L) [+stack]
            return _ns(mesh, spec4(P(dp, None)), shape)
        if name == "wkv":               # (B*H, N, N) [+stack]
            return _ns(mesh, spec4(P(dp, None, None)), shape)
        if name in ("h",):              # (B, D) [+stack]
            return _ns(mesh, spec4(P(dp, "model")), shape)
        if name in ("conv", "tm_prev", "cm_prev"):   # (B, w, D) [+stack]
            return _ns(mesh, spec4(P(dp, None, "model")), shape)
        if name == "memory":            # (B, S, D) — not stacked
            return _ns(mesh, P(dp, None, None), shape)
        if name == "step":
            return NamedSharding(mesh, P())
        return _ns(mesh, P(*([None] * len(shape))), shape)

    return jax.tree_util.tree_map_with_path(rule, cache_abs)


@dataclasses.dataclass
class Cell:
    fn: object            # step callable
    args_abs: tuple       # abstract arguments
    in_shardings: tuple
    out_shardings: object
    donate: tuple
    mode: str
    meta: dict


def build_cell(cfg: ModelConfig, shape_name: str, mesh,
               model_axis: int = 16) -> Cell:
    cell = _build_cell(cfg, shape_name, mesh, model_axis)
    if AxisType.Explicit in mesh.axis_types:
        # the model's shardings are propagation hints
        # (`with_sharding_constraint`), which act as asserts on Explicit
        # axes: run the step with the mesh's axes switched to Auto, and
        # replicate the outputs the cell leaves unconstrained
        outs = jax.tree.map(
            lambda s: P() if s is None else s, cell.out_shardings,
            is_leaf=lambda s: s is None,
        )
        cell.fn = jax.sharding.auto_axes(cell.fn, out_sharding=outs)
    return cell


def _build_cell(cfg: ModelConfig, shape_name: str, mesh,
                model_axis: int) -> Cell:
    S, B, mode = SHAPES[shape_name]
    dp = tuple(n for n in mesh.axis_names if n != "model")
    model = Transformer(cfg, model_axis=model_axis)
    params_abs = model.abstract()
    specs = model.specs()
    p_sh = jax.tree.map(lambda s, a: _ns(mesh, s, a.shape), specs, params_abs)
    meta = {"num_params": model.num_params, "dp": dp, "mode": mode}

    if mode == "train":
        opt = make_optimizer(cfg.optimizer)
        lr = cosine_schedule(3e-4, 2000, 100_000)
        state_abs = jax.eval_shape(lambda p: init_train_state(p, opt), params_abs)
        st_sh = state_shardings(mesh, params_abs, specs, state_abs["opt"])
        batch_abs, batch_sh = _batch_abs_and_sh(cfg, B, S, mesh, dp, True)
        fn = make_train_step(cfg, opt, lr, dp=dp)
        return Cell(
            fn=fn,
            args_abs=(state_abs, batch_abs),
            in_shardings=(st_sh, batch_sh),
            out_shardings=(st_sh, None),
            donate=(0,),
            mode=mode,
            meta=meta,
        )

    if mode == "prefill":
        batch_abs, batch_sh = _batch_abs_and_sh(cfg, B, S, mesh, dp, False)
        fn = lambda p, b: forward(p, cfg, b, dp=dp)
        return Cell(
            fn=fn,
            args_abs=(params_abs, batch_abs),
            in_shardings=(p_sh, batch_sh),
            out_shardings=None,
            donate=(),
            mode=mode,
            meta=meta,
        )

    # decode: one new token against a seq_len-deep cache
    frames_abs = (
        jax.ShapeDtypeStruct((B, cfg.encoder_seq, cfg.d_model), DTYPES[cfg.dtype])
        if cfg.encoder_layers else None
    )
    cache_abs = jax.eval_shape(
        lambda p, f: init_cache(p, cfg, batch=B, max_len=S, frames=f, dp=dp),
        params_abs, frames_abs,
    )
    cache_sh = _cache_shardings(cfg, cache_abs, mesh, dp)
    tok_abs = jax.ShapeDtypeStruct((B,), jnp.int32)
    tok_sh = _ns(mesh, P(dp), (B,))
    fn = lambda p, c, t: decode_step(p, cfg, c, t, dp=dp)
    return Cell(
        fn=fn,
        args_abs=(params_abs, cache_abs, tok_abs),
        in_shardings=(p_sh, cache_sh, tok_sh),
        out_shardings=(None, cache_sh),
        donate=(1,),
        mode=mode,
        meta=meta,
    )
