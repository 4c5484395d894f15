"""The per-tick gossip scan: the tests' oracle for the executor's chunk.

The library samples a whole chunk's schedule first and then walks the
pair list (`core.value_pass.pair_apply`).  The oracle does what the
algorithm says, one tick at a time: sample the tick (`sample_tick`),
read both endpoints, average them, write the partner row and then the
initiator row by scatters, and count the exchange by a scatter-add.
Both must give the same bits: values, usage, messages, ticks.

`per_tick_chunk` has the signature of `core.gossip._presampled_chunk`
and the same chunk and check rule, so a test swaps it in with
`monkeypatch` and the library's loop, levels and promotion run around
it.  `gossip_until_per_tick` is `gossip_until` with the swap made.
"""
from unittest import mock

import jax
import jax.numpy as jnp

from repro.core import gossip
from repro.core.schedule import sample_tick


def apply_tick(x, i, j, upd_i, upd_j):
    """One tick's pair average on (B, C, V) state by scatters: j's row,
    then i's, each where its update fires."""
    bidx = jnp.arange(x.shape[0])
    xi = x[bidx, i]
    xj = x[bidx, j]
    avg = 0.5 * (xi + xj)
    x = x.at[bidx, j].set(jnp.where(upd_j[:, None], avg, xj))
    return x.at[bidx, i].set(jnp.where(upd_i[:, None], avg, xi))


def sequential_pair_apply(x, i, j, upd_i, upd_j):
    """A (T, B) pair list applied tick by tick with `apply_tick`."""
    def tick(x, sched):
        return apply_tick(x, *sched), None

    x, _ = jax.lax.scan(tick, x, (i, j, upd_i, upd_j))
    return x


def per_tick_chunk(adj, key, loss_p, check_every, err, tol,
                   node_shard=None, failure_ctx=None, cost_model=None,
                   hop_cap=1, usage_count="edges"):
    """Chunk body that samples and applies each tick in turn."""
    if node_shard is not None or failure_ctx is not None \
            or cost_model is not None or usage_count != "edges":
        raise NotImplementedError(
            "the per-tick oracle runs neither node shards, failure "
            "scenarios, cost pricing nor slot counts")

    def tick(state, t):
        x, usage, msgs, done = state
        s = sample_tick(t, key, adj, loss_p, x.dtype)
        active = (~done) & s.valid
        upd_j = active & s.fwd_ok      # j updates iff the request arrived
        x = apply_tick(x, s.i, s.j, upd_j & s.rep_ok, upd_j)
        usage = usage.at[s.pos].add(active.astype(jnp.int32))
        msgs = msgs + jnp.where(active, s.cost, 0)
        return (x, usage, msgs, done), None

    def chunk(carry):
        x, usage, msgs, done, ticks, t0 = carry
        ts = t0 + jnp.arange(check_every)
        (x, usage, msgs, done), _ = jax.lax.scan(
            tick, (x, usage, msgs, done), ts)
        ticks = ticks + jnp.where(done, 0, check_every)
        done = done | (err(x) <= tol)
        return x, usage, msgs, done, ticks, t0 + check_every

    return chunk


def fresh_gossip_loop():
    """A new jit of `gossip_until`'s loop: the module's own would reuse
    a trace made before a test swapped part of what it calls."""
    def loop(*args, **kwargs):
        return gossip.gossip_core(*args, **kwargs)

    return jax.jit(loop, static_argnames=("max_ticks", "check_every",
                                          "loss_p"))


def gossip_until_per_tick(*args, **kwargs) -> gossip.GossipResult:
    """`gossip_until` (same arguments) on the per-tick oracle."""
    with mock.patch.object(gossip, "_presampled_chunk", per_tick_chunk), \
            mock.patch.object(gossip, "_gossip_loop", fresh_gossip_loop()):
        return gossip.gossip_until(*args, **kwargs)
