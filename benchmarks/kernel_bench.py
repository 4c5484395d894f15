"""Kernel & engine microbenchmarks (CPU host; Pallas kernels target TPU
and are validated in interpret mode — these numbers time the XLA oracle
paths and the simulation engine, which ARE the CPU-resident layers)."""
from __future__ import annotations

import time

import numpy as np


def _time(fn, *args, reps=5):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(reps):
        r = fn(*args)
    try:
        r.block_until_ready()
    except AttributeError:
        pass
    return (time.time() - t0) / reps * 1e6  # us


def pair_apply_bench(
    sweep=((64, 16), (64, 64), (256, 16)), B: int = 32, V: int = 2,
    as_rows: bool = True,
):
    """Presampled-schedule value-pass sweep over (schedule length T,
    cell size C): `core.value_pass.pair_apply`, jitted (key suffix
    ``lax``, as in the trajectory's earlier entries).

    Returns CSV rows (`as_rows=True`) or a {key: us_per_call} dict for
    the BENCH_gossip.json trajectory.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.value_pass import pair_apply
    from .common import csv_line

    rng = np.random.default_rng(11)
    rows, flat = [], {}
    for T, C in sweep:
        x = jnp.asarray(rng.normal(size=(B, C, V)), jnp.float32)
        i = jnp.asarray(rng.integers(0, C, (T, B)), jnp.int32)
        j = jnp.asarray(rng.integers(0, C, (T, B)), jnp.int32)
        ui = jnp.asarray(rng.uniform(size=(T, B)) < 0.9)
        uj = jnp.asarray(rng.uniform(size=(T, B)) < 0.95)

        apply_f = jax.jit(pair_apply)
        us = _time(lambda: apply_f(x, i, j, ui, uj))
        key = f"T{T}_C{C}_lax"
        flat[key] = us
        rows.append(csv_line(
            f"kernel/pair_apply_{key}", us,
            f"B={B} V={V} ticks_per_us={T/max(us,1e-9):.2f}",
        ))
    return rows if as_rows else flat


def run() -> list[str]:
    import jax.numpy as jnp

    from repro.core import batched_graphs, gossip_until, random_geometric_graph
    from repro.kernels.cell_mixing import cell_mixing, mixing_matrix
    from .common import csv_line

    lines = []

    # batched async gossip engine throughput
    g = random_geometric_graph(1000, seed=3)
    from repro.core.partition import build_partition
    part = build_partition(1000)
    cell = part.cell_of(g.coords, part.k)
    from repro.core.rgg import induced_subgraph
    subs = [induced_subgraph(g, np.where(cell == c)[0])[0]
            for c in np.unique(cell)]
    neighbors, degrees, n_nodes, mask = batched_graphs(subs)
    x0 = np.where(mask, np.random.default_rng(0).normal(size=mask.shape), 0)

    t0 = time.time()
    res = gossip_until(x0.astype(np.float32), neighbors, degrees, n_nodes,
                       eps=-1.0, fixed_ticks=512, seed=0)
    dt = time.time() - t0
    ticks = int(res.ticks.sum())
    lines.append(csv_line(
        "engine/async_ticks", dt * 1e6,
        f"cells={len(subs)} ticks={ticks} ticks_per_sec={ticks/dt:.0f}",
    ))

    # presampled-schedule value pass
    lines.extend(pair_apply_bench())

    # synchronous cell mixing (jnp oracle = production XLA path)
    w = jnp.asarray(mixing_matrix(neighbors, degrees, n_nodes))
    xb = jnp.asarray(np.where(mask[..., None], np.random.default_rng(1)
                              .normal(size=(*mask.shape, 128)), 0), jnp.float32)
    us = _time(lambda: cell_mixing(w, xb, rounds=8, use_pallas=False))
    B, C = mask.shape
    flops = 2 * B * C * C * 128 * 8
    lines.append(csv_line(
        "kernel/cell_mixing_r8_d128", us,
        f"B={B} m={C} gflops_per_call={flops/1e9:.2f}",
    ))

    # flash attention oracle vs chunked XLA path
    from repro.kernels.flash_attention import attention_ref
    from repro.models.attention import chunked_attention
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(1, 4, 1024, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 1024, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 1024, 64)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(1024)[None], (1, 1024))
    import jax
    ref_f = jax.jit(lambda: attention_ref(q, k, v, causal=True))
    chk_f = jax.jit(lambda: chunked_attention(
        q, k, v, pos, pos, causal=True, window=None, softcap=None,
        scale=0.125, chunk=256))
    us_ref = _time(ref_f)
    us_chk = _time(chk_f)
    lines.append(csv_line("kernel/attention_ref_1k", us_ref, "full softmax"))
    lines.append(csv_line(
        "kernel/attention_chunked_1k", us_chk,
        f"online-softmax scan (flash XLA path) ratio={us_chk/us_ref:.2f}",
    ))

    # rwkv6 scan oracle
    from repro.kernels.rwkv6 import rwkv6_ref
    r_ = jnp.asarray(rng.normal(size=(8, 512, 64)), jnp.float32)
    w_ = jnp.asarray(rng.uniform(0.9, 0.999, size=(8, 512, 64)), jnp.float32)
    u_ = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
    wkv_f = jax.jit(lambda: rwkv6_ref(r_, r_, r_, w_, u_))
    us_wkv = _time(wkv_f)
    lines.append(csv_line("kernel/rwkv6_scan_512", us_wkv, "BH=8 N=64"))
    return lines


if __name__ == "__main__":
    for line in run():
        print(line)
