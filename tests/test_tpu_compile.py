"""Compiles the Pallas kernels and the Pallas superstep for a described
TPU v5e chip — no chip attached, nothing runs. This is the only file that
describes the chip: the topology is built inside a module fixture (never at
import), and every test asserts the compiled program holds a Mosaic kernel
(``tpu_custom_call``), i.e. the kernel compiled instead of being
interpreted."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import EngineConfig, GraphDEngine, PageRank
from repro.graph import partition_graph, rmat_graph
from repro.kernels import digest as _digest
from repro.kernels import edge_combine as _ec
from repro.kernels.edge_combine import COMBINERS

WIN = 512  # BLK = SRC_WIN = DST_WIN, the engine's default window
P = 8 * WIN
NB = 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip can be written to the persistent cache
    # but never read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("combiner", COMBINERS)
def test_edge_combine_compiles(one_chip, combiner):
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    fn = lambda *a: _ec.edge_combine_group(
        *a, SRC_WIN=WIN, DST_WIN=WIN, msg_kind="div_deg", combiner=combiner)
    args = (
        s((3, P), jnp.float32), s((NB, WIN), jnp.int32),
        s((NB, WIN), jnp.int32), s((NB, WIN), jnp.float32),
        s((NB,), jnp.int32), s((1,), jnp.int32),
        s((NB,), jnp.int32), s((NB,), jnp.int32),
    )
    _assert_kernel(jax.jit(fn).lower(*args).compile())


def test_digest_compiles(one_chip):
    s = lambda dt: _shape(one_chip, (P,), dt)
    fn = lambda *a: _digest.digest(*a, combiner="sum", WIN=WIN)
    args = (s(jnp.float32), s(jnp.int32), s(jnp.float32), s(jnp.int32))
    _assert_kernel(jax.jit(fn).lower(*args).compile())


def test_pallas_recoded_step_compiles(one_chip):
    """The engine's backend='pallas' superstep, vmapped over 8 shards."""
    g = rmat_graph(scale=10, edge_factor=8, seed=4)
    pg, _ = partition_graph(g, n_shards=8, edge_block=128, vertex_pad=128)
    eng = GraphDEngine(pg, PageRank(supersteps=2),
                       config=EngineConfig(mode="recoded", backend="pallas"))
    place = lambda t: jax.tree.map(
        lambda x: _shape(one_chip, x.shape, x.dtype), t)
    n = pg.n_shards
    lowered = eng.lower_step(
        _shape(one_chip, (n, pg.P), jnp.float32),
        _shape(one_chip, (n, pg.P), jnp.bool_),
        _shape(one_chip, (), jnp.int32),
        pg=place(pg), kl=place(eng.kl),
    )
    _assert_kernel(lowered.compile())
