"""Distributed-semantics tests (subprocess with forced host devices)."""
import pytest

from helpers import run_with_devices


@pytest.mark.parametrize("topology", ["graph", "ring"])
@pytest.mark.slow
def test_dtsvm_dist_matches_reference(topology):
    out = run_with_devices(f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import dtsvm, dtsvm_dist, graph
        from repro.data import synthetic
        V, T = 8, 2
        n = np.full((V, T), 8, int)
        data = synthetic.make_multitask_data(V=V, T=T, p=10, n_train=n,
                                             n_test=50, seed=1)
        A = graph.ring(V) if "{topology}" == "ring" else \\
            graph.make_graph("random", V, 0.7, seed=0)
        prob = dtsvm.make_problem(data["X"], data["y"], data["mask"], A)
        st_ref, _ = jax.jit(lambda p: dtsvm.run_dtsvm(p, 12, qp_iters=50))(prob)
        st_dist = dtsvm_dist.run_dtsvm_dist(prob, 12, topology="{topology}",
                                            qp_iters=50)
        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                  zip(jax.tree.leaves(st_ref), jax.tree.leaves(st_dist)))
        assert err < 1e-5, err
        print("MATCH", err)
    """)
    assert "MATCH" in out


@pytest.mark.slow
def test_dtsvm_dist_second_run_lowers_nothing():
    """The node-sharded build and scan are cached programs: a second
    ``run_dtsvm_dist`` of one shape, on new data, lowers nothing, and
    computes what the first program computes for it."""
    out = run_with_devices("""
        import numpy as np, jax
        from repro import obs
        from repro.core import dtsvm, dtsvm_dist, graph
        from repro.data import synthetic
        V, T = 4, 2
        A = graph.ring(V)

        def prob(seed):
            data = synthetic.make_multitask_data(
                V=V, T=T, p=6, n_train=np.full((V, T), 8, int), n_test=8,
                seed=seed)
            return dtsvm.make_problem(data["X"], data["y"], data["mask"], A)

        p0, p1 = prob(0), prob(1)
        dtsvm_dist.run_dtsvm_dist(p0, 3, topology="ring", qp_iters=10)
        low = obs.compile_counts()["programs"]
        assert low["jit(_node_build)"]["lower"]["count"] == 1
        assert low["jit(_node_scan)"]["lower"]["count"] == 1
        obs.clear_compiles()
        st = dtsvm_dist.run_dtsvm_dist(p1, 3, topology="ring", qp_iters=10)
        lowered = {prog: ph["lower"]["count"]
                   for prog, ph in obs.compile_counts()["programs"].items()
                   if "lower" in ph}
        assert lowered == {}, lowered
        dtsvm_dist._node_build.clear_cache()
        dtsvm_dist._node_scan.clear_cache()
        fresh = dtsvm_dist.run_dtsvm_dist(p1, 3, topology="ring",
                                          qp_iters=10)
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(fresh)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("CACHED")
    """, n_devices=4)
    assert "CACHED" in out


@pytest.mark.slow
def test_consensus_trainer_agrees_and_learns():
    """ADMM-consensus training on a ring: loss decreases AND replicas
    converge toward consensus (gap shrinks) — the deep-net lift of the
    paper's Prop.-1 dynamics.  The consensus step runs shard_map with
    axis_names={"data"}, so the model axis stays auto (partial-auto
    partitioning over data=4, model=2)."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.configs import get_reduced_config
        from repro.configs.base import InputShape
        from repro.core.consensus import ConsensusConfig
        from repro.launch import mesh as mesh_lib
        from repro.train import steps as steps_lib
        from repro.data.synthetic import token_batch

        cfg = get_reduced_config("qwen2-0.5b")
        mesh = mesh_lib.make_debug_mesh(data=4, model=2)
        shape = InputShape("t", 64, 8, "train")
        rng = jax.random.key(0)
        state = steps_lib.make_consensus_train_state(cfg, rng, mesh, shape,
                                                     lr=3e-3)
        # desynchronize the replicas so consensus has work to do
        state = state._replace(params=jax.tree.map(
            lambda x: x * (1.0 + 0.05 * jax.random.normal(
                jax.random.key(1), x.shape, jnp.float32)).astype(x.dtype),
            state.params))
        step = steps_lib.make_consensus_train_step(
            cfg, mesh, ConsensusConfig(eta=0.1, every=1), lr=3e-3)
        batch = token_batch(jax.random.key(2), cfg.vocab_size, 8, 64)
        with jax.set_mesh(mesh):
            losses, gaps = [], []
            for i in range(10):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
                gaps.append(float(m["consensus_gap"]))
        assert losses[-1] < losses[0], losses
        assert gaps[-1] < gaps[0], gaps
        print("OK", losses[0], losses[-1], gaps[0], gaps[-1])
    """, n_devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_consensus_every_k_skips_exchange():
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.configs import get_reduced_config
        from repro.configs.base import InputShape
        from repro.core.consensus import ConsensusConfig
        from repro.launch import mesh as mesh_lib
        from repro.train import steps as steps_lib
        from repro.data.synthetic import token_batch

        cfg = get_reduced_config("qwen2-0.5b")
        mesh = mesh_lib.make_debug_mesh(data=4, model=1)
        shape = InputShape("t", 32, 4, "train")
        rng = jax.random.key(0)
        state = steps_lib.make_consensus_train_state(cfg, rng, mesh, shape)
        step = steps_lib.make_consensus_train_step(
            cfg, mesh, ConsensusConfig(eta=0.1, every=4), lr=1e-3)
        batch = token_batch(jax.random.key(2), cfg.vocab_size, 4, 32)
        with jax.set_mesh(mesh):
            for i in range(3):
                state, m = step(state, batch)
        assert int(state.step) == 3
        print("OK")
    """, n_devices=4)
    assert "OK" in out


@pytest.mark.parametrize("topology", ["graph", "ring"])
@pytest.mark.slow
def test_sweep_shard_map_matches_vmap(topology):
    """The batched sweep's device-tiled path == the single-host vmapped
    path, bitwise — both for configs-only tiling (1-D 'sweep' mesh) and
    for configs ALONGSIDE nodes (2-D (sweep, nodes) mesh with collective
    neighbor sums, graph and ring topologies)."""
    out = run_with_devices(f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro import engine
        from repro.core import dtsvm, graph
        from repro.data import synthetic
        V, T = 4, 2
        n = np.full((V, T), 6, int)
        data = synthetic.make_multitask_data(V=V, T=T, p=6, n_train=n,
                                             n_test=20, seed=0)
        A = graph.ring(V) if "{topology}" == "ring" else \\
            graph.make_graph("random", V, 0.7, seed=0)
        prob = dtsvm.make_problem(data["X"], data["y"], data["mask"], A)
        cfgs = [dict(C=0.02), dict(eps2=3.0), dict(eta2=0.7), dict(C=0.1)]
        splan = engine.compile_sweep(prob, cfgs, qp_iters=20)
        st_ref, _ = splan.run(iters=5)
        st_1d = splan.run_sharded(5, mesh=engine.make_sweep_mesh(len(cfgs)))
        st_2d = splan.run_sharded(
            5, mesh=engine.make_sweep_mesh(len(cfgs), V),
            node_axis="nodes", topology="{topology}")
        for sharded in (st_1d, st_2d):
            for a, b in zip(jax.tree.leaves(st_ref),
                            jax.tree.leaves(sharded)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("MATCH")
    """)
    assert "MATCH" in out


@pytest.mark.slow
def test_allreduce_train_step_sharded():
    """Standard trainer under a debug mesh: one sharded step runs and the
    replicated loss is finite."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.configs import get_reduced_config
        from repro.configs.base import InputShape
        from repro.dist import sharding as shp
        from repro.launch import mesh as mesh_lib
        from repro.train import steps as steps_lib
        from repro.data.synthetic import token_batch

        cfg = get_reduced_config("gemma2-2b")
        mesh = mesh_lib.make_debug_mesh(data=2, model=2)
        shape = InputShape("t", 64, 4, "train")
        rng = jax.random.key(0)
        with jax.set_mesh(mesh):
            state = steps_lib.make_train_state(cfg, rng, shape)
            spec = shp.param_specs(
                jax.eval_shape(lambda: state), mesh, shp.ctx_for(cfg))
            state = jax.device_put(state, shp.named(mesh, spec))
            step = jax.jit(steps_lib.make_train_step(cfg),
                           donate_argnums=(0,))
            batch = token_batch(jax.random.key(1), cfg.vocab_size, 4, 64)
            state, m = step(state, batch)
            assert bool(jnp.isfinite(m["loss"]))
        print("OK", float(m["loss"]))
    """, n_devices=4)
    assert "OK" in out
