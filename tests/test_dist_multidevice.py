"""Multi-device distribution tests.

Each test spawns a SUBPROCESS that forces
XLA_FLAGS=--xla_force_host_platform_device_count=8 before importing jax
(the main pytest process must keep seeing 1 device for the smoke
tests).  These execute REAL sharded computations on an 8-device host
mesh — a miniature of the production (pod, data, model) topology.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str) -> str:
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        assert len(jax.devices()) == 8
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"  # a child never reaches for a chip
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, f"stderr:\n{proc.stderr[-3000:]}"
    return proc.stdout


def test_train_step_executes_on_multipod_mesh():
    out = _run("""
    import dataclasses
    from repro.configs import get_config, reduce_config
    from repro.launch.specs import build_cell
    from repro.configs.registry import SHAPES

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = dataclasses.replace(
        reduce_config(get_config("llama3.2-3b")),
        d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    )
    SHAPES["tiny_train"] = (32, 8, "train")
    cell = build_cell(cfg, "tiny_train", mesh, model_axis=2)
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings)
        # materialize real inputs per the abstract specs
        def materialize(a, sh):
            arr = (np.random.default_rng(0).normal(0, 0.02, a.shape)
                   if jnp.issubdtype(a.dtype, jnp.floating)
                   else np.zeros(a.shape, a.dtype))
            return jax.device_put(jnp.asarray(arr, a.dtype), sh)
        args = jax.tree.map(materialize, cell.args_abs, cell.in_shardings,
                            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        state, metrics = jitted(*args)
        loss = float(metrics["loss"])
        assert np.isfinite(loss), loss
        print("LOSS", loss)
    """)
    assert "LOSS" in out


def test_sync_strategies_execute_with_collectives():
    out = _run("""
    from repro.dist import SyncConfig, suggest_levels, sync_gradients
    from repro.launch.hlo_analysis import collective_bytes

    R = 8
    mesh = jax.make_mesh((R,), ("replica",))
    sh = NamedSharding(mesh, P("replica", None))
    g = {"w": jax.device_put(
        jnp.asarray(np.random.default_rng(0).normal(size=(R, 256)), jnp.float32),
        sh)}
    want = np.asarray(g["w"]).mean(0)
    for strat in ("allreduce", "hierarchical", "ring", "multiscale"):
        cfg = SyncConfig(strategy=strat, levels=suggest_levels(R),
                         rounds=(64,) if strat == "ring" else ())
        with jax.set_mesh(mesh):
            f = jax.jit(lambda x: sync_gradients(x, cfg, R),
                        in_shardings=(dict(w=sh),), out_shardings=dict(w=sh))
            out = f(g)
            hlo = f.lower(g).compile().as_text()
        stats = collective_bytes(hlo, pod_size=4)
        got = np.asarray(out["w"])
        err = np.abs(got - want[None]).max()
        exact = strat in ("allreduce", "hierarchical")
        assert stats.count > 0, (strat, "no collectives found")
        if exact:
            assert err < 1e-5, (strat, err)
        else:
            spread = np.abs(got - got.mean(0, keepdims=True)).max()
            before = np.abs(np.asarray(g["w"]) - want[None]).max()
            assert spread < 0.5 * before, (strat, spread, before)
        print("STRAT", strat, stats.count, round(float(err), 6))

    # compressed + rotated execute_sync on the same sharded mesh: residual
    # state and the step index thread through a real collective lowering
    from repro.dist import (CompressionConfig, build_sync_plan, execute_sync,
                            init_residual)
    plan = build_sync_plan(
        SyncConfig("multiscale", levels=suggest_levels(R),
                   compression=CompressionConfig("topk", topk_fraction=0.25),
                   rotation_period=3),
        R)
    with jax.set_mesh(mesh):
        f = jax.jit(lambda x, r, s: execute_sync(plan, x, r, s),
                    in_shardings=((dict(w=sh), dict(w=sh), None)),
                    out_shardings=(dict(w=sh), dict(w=sh)))
        mixed, res = f(g, init_residual(g), jnp.int32(0))
    assert np.isfinite(np.asarray(mixed["w"])).all()
    # EF decomposition survives sharding: residual is exactly the unsent mass
    assert np.abs(np.asarray(res["w"])).max() > 0
    print("COMPRESSED OK")
    """)
    assert out.count("STRAT") == 4
    assert "COMPRESSED OK" in out


def test_sharded_executor_matches_dense_and_overlaps():
    out = _run("""
    from repro.dist import (CompressionConfig, SyncConfig, async_execute_sync,
                            build_sync_plan, execute_sync,
                            execute_sync_sharded, init_inflight,
                            init_residual, suggest_levels)
    from repro.launch.hlo_analysis import collective_bytes

    R = 8
    mesh = jax.make_mesh((R,), ("replica",))
    sh = NamedSharding(mesh, P("replica", None))
    g = {"w": jax.device_put(
        jnp.asarray(np.random.default_rng(0).normal(size=(R, 96)), jnp.float32),
        sh)}
    cases = {
        "allreduce": SyncConfig("allreduce"),
        "hierarchical": SyncConfig("hierarchical"),
        "ring": SyncConfig("ring", rounds=(16,)),
        "multiscale": SyncConfig("multiscale"),
        "ms_exact": SyncConfig("multiscale", exact_fusion=True),
        "ms_rotated": SyncConfig("multiscale", rotation_period=3,
                                 rotation_seed=5),
        "ms_topk": SyncConfig("multiscale",
                              compression=CompressionConfig("topk", 0.25)),
    }
    for name, cfg in cases.items():
        plan = build_sync_plan(cfg, R)
        res = (init_residual(g)
               if plan.compression.scheme != "none" else None)
        for step in (0, 2):
            dense, dres = execute_sync(plan, g, res, step)
            f = jax.jit(lambda x, r, s, p=plan: execute_sync_sharded(
                p, x, r, s, mesh=mesh))
            sharded, sres = f(g, res, jnp.int32(step))
            hlo = f.lower(g, res, jnp.int32(step)).compile().as_text()
            stats = collective_bytes(hlo, pod_size=4)
            assert stats.count > 0, (name, "no collectives in shard_map path")
            np.testing.assert_allclose(
                np.asarray(dense["w"]), np.asarray(sharded["w"]),
                rtol=2e-6, atol=2e-6)
            if res is not None:
                np.testing.assert_allclose(
                    np.asarray(dres["w"]), np.asarray(sres["w"]),
                    rtol=2e-6, atol=2e-6)
        print("PARITY", name)

    # async pipeline stage under the mesh: the applied output is the mix
    # of the in-flight buffer (zeros at warmup), not of the fresh grads
    plan = build_sync_plan(
        SyncConfig("multiscale", exact_fusion=True, overlap="one_step"), R)
    f = jax.jit(lambda cur, prev, s, p=plan: async_execute_sync(
        p, cur, prev, None, s, mesh=mesh))
    applied, inflight, _ = f(g, init_inflight(g), jnp.int32(0))
    assert float(np.abs(np.asarray(applied["w"])).max()) == 0.0
    np.testing.assert_array_equal(np.asarray(inflight["w"]),
                                  np.asarray(g["w"]))
    applied, _, _ = f(g, inflight, jnp.int32(1))
    np.testing.assert_allclose(
        np.asarray(applied["w"]).mean(0), np.asarray(g["w"]).mean(0),
        rtol=1e-5, atol=1e-6)
    print("ASYNC OK")
    """)
    assert out.count("PARITY") == 7
    assert "ASYNC OK" in out


def test_sharded_executor_failure_parity_with_dense():
    """Fault injection on the 8-device replica mesh: the per-program
    masks recomputed inside shard_map must match the dense executor's
    global draw for the same (seed, step) — outputs agree across every
    aggregation mode, dropped rows are zero on both paths, and an inert
    SyncFailureModel stays bitwise-identical to a failure-free plan."""
    out = _run("""
    import dataclasses
    from repro.dist import (CompressionConfig, SyncConfig, SyncFailureModel,
                            build_sync_plan, execute_sync,
                            execute_sync_sharded, init_residual,
                            replica_fault_masks)

    R = 8
    mesh = jax.make_mesh((R,), ("replica",))
    sh = NamedSharding(mesh, P("replica", None))
    g = {"w": jax.device_put(
        jnp.asarray(np.random.default_rng(0).normal(size=(R, 96)), jnp.float32),
        sh)}
    fm = SyncFailureModel(churn_fraction=0.25, straggler_fraction=0.125,
                          byzantine_fraction=0.125, seed=11)
    cases = {
        "mean": SyncConfig("multiscale", failures=fm),
        "survivor": SyncConfig("multiscale", aggregation="survivor_weighted",
                               failures=fm),
        "trimmed": SyncConfig("allreduce", aggregation="trimmed_mean",
                              failures=fm),
        "median": SyncConfig("allreduce", aggregation="coordinate_median",
                             failures=fm),
        "topk_churn": SyncConfig("multiscale",
                                 compression=CompressionConfig("topk", 0.25),
                                 failures=fm),
        "rotated_churn": SyncConfig("multiscale", rotation_period=3,
                                    rotation_seed=5, failures=fm),
    }
    for name, cfg in cases.items():
        plan = build_sync_plan(cfg, R)
        res = (init_residual(g)
               if plan.compression.scheme != "none" else None)
        f = jax.jit(lambda x, r, s, p=plan: execute_sync_sharded(
            p, x, r, s, mesh=mesh))
        for step in (0, 3):
            dense, dres = execute_sync(plan, g, res, step)
            sharded, sres = f(g, res, jnp.int32(step))
            np.testing.assert_allclose(
                np.asarray(dense["w"]), np.asarray(sharded["w"]),
                rtol=2e-6, atol=2e-6)
            if res is not None:
                np.testing.assert_allclose(
                    np.asarray(dres["w"]), np.asarray(sres["w"]),
                    rtol=2e-6, atol=2e-6)
            dropped = np.asarray(replica_fault_masks(fm, R, step).dropped)
            assert dropped.sum() == 3
            assert np.all(np.asarray(sharded["w"])[dropped] == 0.0), name
        print("FAULT PARITY", name)

    # inert model: bitwise equality with the failure-free plan, sharded
    clean = build_sync_plan(SyncConfig("multiscale"), R)
    inert = build_sync_plan(
        SyncConfig("multiscale", failures=SyncFailureModel()), R)
    fc = jax.jit(lambda x, s, p=clean: execute_sync_sharded(
        p, x, None, s, mesh=mesh))
    fi = jax.jit(lambda x, s, p=inert: execute_sync_sharded(
        p, x, None, s, mesh=mesh))
    a, _ = fc(g, jnp.int32(1))
    b, _ = fi(g, jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(a["w"]), np.asarray(b["w"]))
    print("INERT BITWISE OK")
    """)
    assert out.count("FAULT PARITY") == 6
    assert "INERT BITWISE OK" in out


def test_elastic_checkpoint_restore_across_meshes():
    out = _run("""
    import tempfile
    from repro.train import restore_checkpoint, save_checkpoint

    mesh_a = jax.make_mesh((4, 2), ("data", "model"))
    mesh_b = jax.make_mesh((2, 4), ("data", "model"))
    sh_a = NamedSharding(mesh_a, P("data", "model"))
    sh_b = NamedSharding(mesh_b, P("data", "model"))
    state = {"w": jax.device_put(jnp.arange(64.0).reshape(8, 8), sh_a)}
    d = tempfile.mkdtemp()
    save_checkpoint(d, state, 3)
    like = {"w": jnp.zeros((8, 8))}
    restored, step = restore_checkpoint(d, like, shardings={"w": sh_b})
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(64.0).reshape(8, 8))
    assert restored["w"].sharding.mesh.shape["data"] == 2
    print("ELASTIC OK")
    """)
    assert "ELASTIC OK" in out


def test_trial_mesh_sharding_matches_unsharded():
    """execute_plan(mesh=) shards the vmapped Monte-Carlo trial axis
    over an 8-device host mesh; per-trial results must be bitwise
    independent of the sharding, including a T not divisible by the
    device count (padding trials are discarded)."""
    out = _run("""
    from jax.sharding import Mesh
    from repro.core import (
        ExecOptions, build_plan, execute_plan, random_geometric_graph,
    )

    g = random_geometric_graph(90, seed=7)
    x0 = np.random.default_rng(4).normal(0, 1, 90)
    plan = build_plan(g, seed=0)
    mesh = Mesh(np.array(jax.devices()), ("trials",))
    seeds = tuple(range(6))  # 6 trials on 8 devices: forces padding
    sharded = execute_plan(
        plan, x0, eps=1e-4, seeds=seeds, weighted=True,
        options=ExecOptions(mesh=mesh))
    dense = execute_plan(plan, x0, eps=1e-4, seeds=seeds, weighted=True)
    assert sharded.x_final.shape == (6, 90)
    np.testing.assert_array_equal(sharded.x_final, dense.x_final)
    np.testing.assert_array_equal(sharded.messages, dense.messages)
    np.testing.assert_array_equal(sharded.node_sends, dense.node_sends)
    np.testing.assert_array_equal(sharded.level_ticks, dense.level_ticks)
    print("TRIAL MESH OK")
    """)
    assert "TRIAL MESH OK" in out


def test_node_mesh_2d_matches_trial_mesh():
    """The (trials, nodes) 2-D mesh blocks every level's graph batch
    over the nodes axis (halo exchange only at promotion boundaries);
    results must be bitwise-equal to both the unsharded run and the
    1-axis trial mesh, in the eps-oracle AND fixed-iterations modes, and
    its per-slot usage counts attribute the flat per-edge counters'
    sends."""
    out = _run("""
    from jax.sharding import Mesh
    from repro.core import (
        ExecOptions, build_plan, execute_plan, random_geometric_graph,
    )

    g = random_geometric_graph(200, seed=11)
    x0 = np.random.default_rng(6).normal(0, 1, 200)
    plan = build_plan(g, seed=0)
    devs = np.array(jax.devices())
    mesh2d = Mesh(devs.reshape(2, 4), ("trials", "nodes"))
    mesh1d = Mesh(devs, ("trials",))
    for kw in (dict(eps=1e-4), dict(eps=1e-3, fixed_ticks_scale=1.0)):
        seeds = (0, 1, 2)  # 3 trials on a 2-way trial axis: forces padding
        node = execute_plan(
            plan, x0, seeds=seeds, weighted=True,
            options=ExecOptions(mesh=mesh2d), **kw)
        trial = execute_plan(
            plan, x0, seeds=seeds, weighted=True,
            options=ExecOptions(mesh=mesh1d), **kw)
        dense = execute_plan(plan, x0, seeds=seeds, weighted=True, **kw)
        for other in (trial, dense):
            np.testing.assert_array_equal(node.x_final, other.x_final)
            np.testing.assert_array_equal(node.messages, other.messages)
            np.testing.assert_array_equal(node.node_sends, other.node_sends)
            np.testing.assert_array_equal(
                node.level_ticks, other.level_ticks)
            np.testing.assert_array_equal(
                node.level_messages, other.level_messages)
        print("NODE MESH OK", kw["eps"])

    # the cell level counts each slot's endpoint sends over the shard's
    # own columns: node_sends against no mesh, and against the flat
    # per-edge counters there
    from repro.core import FailureModel
    kw = dict(eps=1e-3, fixed_ticks_scale=1.0, seeds=(5,),
              failures=FailureModel(loss_p=0.8))
    node = execute_plan(plan, x0, options=ExecOptions(mesh=mesh2d), **kw)
    dense = execute_plan(plan, x0, **kw)
    flat = execute_plan(plan, x0, options=ExecOptions(collect_usage=True),
                        **kw)
    for other in (dense, flat):
        np.testing.assert_array_equal(node.node_sends, other.node_sends)
        np.testing.assert_array_equal(node.messages, other.messages)
        np.testing.assert_array_equal(node.x_final, other.x_final)
    print("NODE MESH SLOTS OK")

    # guardrail: the node-sharded path cannot collect per-edge usage
    # (counters live sharded)
    try:
        execute_plan(plan, x0, seeds=(0,),
                     options=ExecOptions(mesh=mesh2d, collect_usage=True))
        raise AssertionError("collect_usage + node mesh must be rejected")
    except ValueError:
        pass
    print("NODE MESH GUARDS OK")
    """)
    assert out.count("NODE MESH OK") == 2
    assert "NODE MESH SLOTS OK" in out
    assert "NODE MESH GUARDS OK" in out
