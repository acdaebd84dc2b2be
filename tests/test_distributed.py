"""Distributed (shard_map) execution tests.

These spawn subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count
so the main pytest process keeps exactly 1 device (dry-run isolation rule).
"""

import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_shard_map_equals_vmap_all_modes():
    out = _run("""
        import jax, numpy as np
        from repro.graph import rmat_graph, partition_graph
        from repro.core import EngineConfig, GraphDEngine, PageRank
        g = rmat_graph(scale=8, edge_factor=8, seed=3)
        pg, _ = partition_graph(g, n_shards=8, edge_block=64)
        mesh = jax.make_mesh((8,), ('machines',))
        for mode in ['recoded', 'basic', 'basic_sc']:
            (v_sm, _), _ = GraphDEngine(
                               pg,
                               PageRank(supersteps=5),
                               config=EngineConfig(mode=mode),
                               mesh=mesh,
                           ).run()
            (v_vm, _), _ = GraphDEngine(
                               pg,
                               PageRank(supersteps=5),
                               config=EngineConfig(mode=mode),
                               mesh=None,
                           ).run()
            err = np.abs(np.asarray(v_sm) - np.asarray(v_vm)).max()
            assert err < 1e-7, (mode, err)
        print('OK')
    """)
    assert "OK" in out


def test_shard_map_sparse_sssp():
    out = _run("""
        import jax, numpy as np, collections
        from repro.graph import rmat_graph, partition_graph
        from repro.core import EngineConfig, GraphDEngine, SSSP
        g = rmat_graph(scale=8, edge_factor=8, seed=3)
        pg, rmap = partition_graph(g, n_shards=8, edge_block=64)
        mesh = jax.make_mesh((8,), ('machines',))
        src = int(rmap.to_new(np.array([int(g.vertex_ids[0])]))[0])
        es = GraphDEngine(
                 pg,
                 SSSP(src),
                 config=EngineConfig(adapt_threshold=0.6, sparse_cap_frac=0.6),
                 mesh=mesh,
             )
        (vs, _), hs = es.run()
        ev = GraphDEngine(
                 pg,
                 SSSP(src),
                 config=EngineConfig(adapt_threshold=-1),
                 mesh=None,
             )
        (vv, _), _ = ev.run()
        assert np.array_equal(np.asarray(vs), np.asarray(vv))
        modes = collections.Counter(h.mode for h in hs)
        print('OK', dict(modes))
    """)
    assert "OK" in out


def test_shard_map_pallas_backend():
    out = _run("""
        import jax, numpy as np
        from repro.graph import rmat_graph, partition_graph
        from repro.core import EngineConfig, GraphDEngine, PageRank
        g = rmat_graph(scale=8, edge_factor=8, seed=3)
        pg, _ = partition_graph(g, n_shards=4, edge_block=64, vertex_pad=32)
        mesh = jax.make_mesh((4,), ('machines',))
        (vp, _), _ = GraphDEngine(
                         pg,
                         PageRank(supersteps=4),
                         config=EngineConfig(backend='pallas', kernel_windows=32),
                         mesh=mesh,
                     ).run()
        (vj, _), _ = GraphDEngine(
                         pg,
                         PageRank(supersteps=4),
                         config=EngineConfig(backend='jnp'),
                     ).run()
        err = np.abs(np.asarray(vp) - np.asarray(vj)).max()
        assert err < 1e-6, err
        print('OK')
    """, devices=4)
    assert "OK" in out


def test_logged_mode_shard_map_and_recovery():
    out = _run("""
        import jax, numpy as np, tempfile, os
        from repro.graph import rmat_graph, partition_graph
        from repro.core import EngineConfig, GraphDEngine, PageRank
        from repro.core.checkpoint import Checkpointer, MessageLog, recover_shard
        g = rmat_graph(scale=7, edge_factor=8, seed=3)
        pg, _ = partition_graph(g, n_shards=4, edge_block=64)
        mesh = jax.make_mesh((4,), ('machines',))
        prog = PageRank(supersteps=6)
        (v_ref, _), _ = GraphDEngine(pg, prog).run()
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(os.path.join(d, 'c'), every=2)
            ml = MessageLog(os.path.join(d, 'l'))
            eng = GraphDEngine(pg, prog, mesh=mesh, message_log=ml)
            ck.save(0, *eng.init())
            (v, _), _ = eng.run(checkpointer=ck)
            assert np.allclose(np.asarray(v), np.asarray(v_ref))
            vj, _ = recover_shard(pg, prog, failed=3, ckpt=ck, log=ml,
                                  target_step=6)
            assert np.abs(np.asarray(vj) - np.asarray(v_ref)[3]).max() < 1e-6
        print('OK')
    """, devices=4)
    assert "OK" in out


def test_sharded_train_step_matches_single_device():
    """FSDP+TP train step on a (2,4) mesh == single-device numerics."""
    out = _run("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.data.tokens import synthetic_batch
        from repro.models.transformer import init_params
        from repro.models import sharding as shd
        from repro.launch.mesh import batch_specs_tree, param_specs, to_shardings
        from repro.training.optimizer import AdamWConfig
        from repro.training.train import init_train_state, make_train_step

        cfg = get_config('minitron-4b').reduced()
        params = init_params(cfg, jax.random.key(0))
        opt = init_train_state(cfg, params)
        batch = synthetic_batch(cfg, 0, 32, 8)
        ref_step = jax.jit(make_train_step(cfg, AdamWConfig(total_steps=10)))
        p1, o1, m1 = ref_step(params, opt, batch)

        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(AxisType.Auto,) * 2)
        ps = param_specs(params, mesh)
        os_ = dict(mu=ps, nu=ps, step=P())
        bs = batch_specs_tree(batch, mesh)
        with mesh, shd.rules(batch='data', model='model', mesh=mesh):
            fn = jax.jit(
                make_train_step(cfg, AdamWConfig(total_steps=10)),
                in_shardings=to_shardings((ps, os_, bs), mesh),
            )
            p2, o2, m2 = fn(params, opt, batch)
        assert abs(float(m1['loss']) - float(m2['loss'])) < 1e-3
        d = max(float(jnp.abs(a.astype(jnp.float32) -
                              b.astype(jnp.float32)).max())
                for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        assert d < 1e-2, d
        print('OK', float(m1['loss']), float(m2['loss']))
    """)
    assert "OK" in out


def test_graphd_dryrun_small_mesh():
    """The GraphD dry-run path lowers+compiles on a small flat ring."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core.algorithms import PageRank
        from repro.core.engine import superstep_spmd
        from repro.graph.partition import abstract_partitioned_graph

        n = 8
        mesh = Mesh(np.asarray(jax.devices()[:n]), ('machines',))
        pg = abstract_partitioned_graph(n, 1_000_000, 16_000_000,
                                        edge_block=1024, vertex_pad=128)
        prog = PageRank(supersteps=3)

        def step(pg_, v, a, s):
            sq = lambda t: jax.tree.map(lambda x: x[0], t)
            nv, na, st = superstep_spmd(prog, sq(pg_), sq(v), sq(a), s,
                                        axis='machines', mode='recoded')
            return nv[None], na[None], st

        spec = P('machines')
        fn = jax.shard_map(step, mesh=mesh,
                       in_specs=(spec, spec, spec, P()),
                       out_specs=(spec, spec, P()))
        vals = jax.ShapeDtypeStruct((n, pg.P), jnp.float32)
        act = jax.ShapeDtypeStruct((n, pg.P), jnp.bool_)
        stp = jax.ShapeDtypeStruct((), jnp.int32)
        sh = NamedSharding(mesh, spec)
        compiled = jax.jit(
            fn, in_shardings=(jax.tree.map(lambda _: sh, pg), sh, sh,
                              NamedSharding(mesh, P())),
        ).lower(pg, vals, act, stp).compile()
        cost = compiled.cost_analysis()
        assert cost.get('flops', 0) > 0
        print('OK', cost.get('flops'))
    """)
    assert "OK" in out


def test_ring_vs_alltoall_collective_equivalence():
    """The ring reduce-scatter (recoded) and the all_to_all (logged) paths
    produce identical digests — the collective schedule is semantically
    transparent."""
    out = _run("""
        import jax, numpy as np, tempfile, os
        from repro.graph import rmat_graph, partition_graph
        from repro.core import GraphDEngine, HashMin
        from repro.core.checkpoint import MessageLog
        g = rmat_graph(scale=7, edge_factor=6, seed=5, directed=False)
        pg, _ = partition_graph(g, n_shards=8, edge_block=32)
        mesh = jax.make_mesh((8,), ('machines',))
        (v1, _), _ = GraphDEngine(pg, HashMin(), mesh=mesh).run()
        with tempfile.TemporaryDirectory() as d:
            ml = MessageLog(os.path.join(d, 'l'))
            (v2, _), _ = GraphDEngine(pg, HashMin(), mesh=mesh,
                                      message_log=ml).run()
        assert np.array_equal(np.asarray(v1), np.asarray(v2))
        print('OK')
    """)
    assert "OK" in out
