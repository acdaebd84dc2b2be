"""Out-of-core streamed execution (tentpole): the on-disk edge-block store,
the prefetching reader, cross-mode result equivalence, the O(|V|/n) memory
guarantee, skip()-driven I/O avoidance, and manifest-aware recovery."""

import collections
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ChannelConfig, EngineConfig, GraphDEngine, HashMin, PageRank, SSSP,
    StreamConfig,
)
from repro.core.checkpoint import Checkpointer, RunFileMessageLog
from repro.graph import (
    chain_graph, partition_graph, partition_graph_streamed, rmat_graph,
    spill_partition,
)
from repro.streams import (
    EdgeStreamStore, StagedChunk, StreamReader, plan_stream_schedule,
)


@pytest.fixture
def spilled(tmp_path):
    g = rmat_graph(scale=7, edge_factor=8, seed=3)
    pg_full, _ = partition_graph(g, n_shards=4, edge_block=64)
    pg, rmap, store = partition_graph_streamed(
        g, 4, str(tmp_path / "spill"), edge_block=64
    )
    return g, pg_full, pg, rmap, store


# ---------------------------------------------------------------------------
# the store: on-disk layout == in-memory layout, open() roundtrip, skip()
# ---------------------------------------------------------------------------

class TestEdgeStreamStore:
    def test_spill_preserves_groups(self, spilled):
        _, pg_full, pg, _, store = spilled
        sp0 = np.asarray(pg_full.src_pos)
        dp0 = np.asarray(pg_full.dst_pos)
        w0 = np.asarray(pg_full.eweight)
        n, E_cap = pg_full.n_shards, pg_full.E_cap
        for i in range(n):
            for k in range(n):
                sp, dp, w = store.group_edges(i, k)
                assert np.array_equal(sp.reshape(-1), sp0[i, k])
                assert np.array_equal(dp.reshape(-1), dp0[i, k])
                assert np.array_equal(w.reshape(-1), w0[i, k])
        # and the vertex-only partition really dropped the O(|E|) arrays
        assert np.asarray(pg.src_pos).size == 0
        assert np.asarray(pg.blk_lo).size == 0

    def test_open_roundtrip(self, spilled, tmp_path):
        _, _, _, _, store = spilled
        reopened = EdgeStreamStore.open(store.dir)
        assert reopened.geom == store.geom
        assert reopened.signature() == store.signature()
        assert np.array_equal(reopened.blk_lo, store.blk_lo)
        assert np.array_equal(reopened.blk_hi, store.blk_hi)

    def test_block_manifest_matches_partition(self, spilled):
        _, pg_full, _, _, store = spilled
        assert np.array_equal(store.blk_lo, np.asarray(pg_full.blk_lo))
        assert np.array_equal(store.blk_hi, np.asarray(pg_full.blk_hi))

    def test_signature_covers_edge_content(self, tmp_path):
        """Equal topology + different weights must NOT look interchangeable
        to checkpoint recovery."""
        g1 = rmat_graph(scale=6, edge_factor=4, seed=2)
        g2 = rmat_graph(scale=6, edge_factor=4, seed=2, weights="uniform")
        assert np.array_equal(g1.src, g2.src)  # same topology
        _, _, s1 = partition_graph_streamed(g1, 2, str(tmp_path / "a"),
                                            edge_block=32)
        _, _, s2 = partition_graph_streamed(g2, 2, str(tmp_path / "b"),
                                            edge_block=32)
        assert s1.signature() != s2.signature()

    def test_skip_no_active_no_blocks(self, spilled):
        _, _, pg, _, store = spilled
        dead = np.zeros(pg.P, bool)
        prefix = np.concatenate([[0], np.cumsum(dead.astype(np.int64))])
        for i in range(4):
            for k in range(4):
                assert store.active_blocks(i, k, prefix).size == 0

    def test_skip_matches_block_ranges(self, spilled):
        _, _, pg, _, store = spilled
        rng = np.random.default_rng(0)
        active = rng.random(pg.P) < 0.2
        prefix = np.concatenate([[0], np.cumsum(active.astype(np.int64))])
        for i in range(4):
            for k in range(4):
                got = set(store.active_blocks(i, k, prefix).tolist())
                want = set()
                for b in range(store.geom.n_blocks):
                    lo, hi = store.blk_lo[i, k, b], store.blk_hi[i, k, b]
                    if hi >= 0 and active[lo:hi + 1].any():
                        want.add(b)
                assert got == want


class TestStreamReader:
    def test_chunks_cover_schedule_exactly(self, spilled):
        _, pg_full, pg, _, store = spilled
        active = np.ones((4, pg.P), bool)
        schedule, density, _ = plan_stream_schedule(store, active)
        assert density == 1.0
        reader = StreamReader(store, chunk_blocks=1, depth=2)
        seen = collections.Counter()
        edges = 0
        for chunk in reader.stream(schedule):
            seen[(chunk.src_shard, chunk.dst_shard)] += chunk.n_real_blocks
            edges += int((chunk.sp >= 0).sum())
        want = {
            (i, k): int(ids.size) for i, k, ids in schedule
        }
        assert dict(seen) == want
        assert edges == pg_full.n_edges
        assert reader.stats.blocks_read == sum(want.values())

    def test_partial_chunks_padded_neutral(self, spilled):
        _, _, pg, _, store = spilled
        active = np.ones((4, pg.P), bool)
        schedule, _, _ = plan_stream_schedule(store, active)
        # chunk_blocks larger than any group => every chunk is partial
        reader = StreamReader(store, chunk_blocks=16, depth=2)
        B = store.geom.edge_block
        for chunk in reader.stream(schedule):
            tail = chunk.sp[chunk.n_real_blocks * B:]
            assert (tail == -1).all()  # compute-neutral padding

    def test_staging_is_constant_sized(self, spilled):
        _, _, _, _, store = spilled
        r = StreamReader(store, chunk_blocks=4, depth=2)
        B = store.geom.edge_block
        assert r.staging_bytes() == 3 * (4 * B * 12)  # (depth+1) buffers


class _Token:
    """Stand-in for a fold's result: ready once ``done`` is set."""

    def __init__(self, ready: bool = False):
        self.done = threading.Event()
        if ready:
            self.done.set()

    def is_ready(self) -> bool:
        return self.done.is_set()

    def block_until_ready(self) -> None:
        assert self.done.wait(timeout=10.0)


def _full_schedule(store, P):
    schedule, _, _ = plan_stream_schedule(store, np.ones((4, P), bool))
    return schedule


class TestReleaseTokens:
    """A chunk given a release token keeps its staging buffers until the
    token is ready; a chunk without one is released when the consumer
    advances."""

    def test_unfinished_token_holds_the_buffer(self, spilled):
        _, _, pg, _, store = spilled
        reader = StreamReader(store, chunk_blocks=1, depth=1)  # 2 buffers
        it = reader.stream(_full_schedule(store, pg.P))
        c0 = next(it)
        snap = (c0.sp.copy(), c0.dp.copy(), c0.w.copy())
        t0 = _Token()
        c0.release_after(t0)
        next(it)  # advance: c0's buffer goes back to the pool under t0
        time.sleep(0.3)  # the staging thread wants that buffer for chunk 2
        assert np.array_equal(c0.sp, snap[0])
        assert np.array_equal(c0.dp, snap[1])
        assert np.array_equal(c0.w, snap[2])
        t0.done.set()
        c2 = next(it)
        assert np.shares_memory(c2.sp, c0.sp)  # refilled only now
        assert not np.array_equal(c2.dp, snap[1])
        for _ in it:
            pass
        assert reader.stats.recycle_waits == 1
        assert reader.stats.recycle_wait_seconds >= 0.25
        assert reader.totals.recycle_waits == 1

    def test_chunk_without_token_released_on_advance(self, spilled):
        _, _, pg, _, store = spilled
        schedule = _full_schedule(store, pg.P)
        reader = StreamReader(store, chunk_blocks=1, depth=1)
        chunks, copies = [], []
        for chunk in reader.stream(schedule):
            chunks.append(chunk)
            copies.append((chunk.sp.copy(), chunk.dp.copy()))
        # two buffers: chunk n+2 is staged into chunk n's buffer
        assert np.shares_memory(chunks[0].sp, chunks[2].sp)
        assert reader.stats.recycle_waits == 0
        # ... and every chunk held its own edges when it was yielded
        B = store.geom.edge_block
        want = []
        for i, k, ids in schedule:
            for o in range(len(ids)):
                sp, dp = np.empty((1, B), np.int32), np.empty((1, B), np.int32)
                store.read_blocks(i, k, ids[o:o + 1], sp, dp,
                                  np.empty((1, B), np.float32))
                want.append((sp.reshape(-1), dp.reshape(-1)))
        assert len(copies) == len(want)
        for (sp, dp), (sp_w, dp_w) in zip(copies, want):
            assert np.array_equal(sp, sp_w) and np.array_equal(dp, dp_w)

    def test_ready_tokens_cost_no_wait(self, spilled):
        _, _, pg, _, store = spilled
        reader = StreamReader(store, chunk_blocks=1, depth=1)
        n = 0
        for chunk in reader.stream(_full_schedule(store, pg.P)):
            chunk.release_after(_Token(ready=True))
            n += 1
        assert reader.stats.chunks == n
        assert reader.stats.recycle_waits == 0

    def test_abort_skips_the_held_chunks_token(self, spilled):
        """Aborted while the staging thread waits for a free buffer: the
        unblock hands it no token, whatever the held chunk carries."""
        _, _, pg, _, store = spilled
        reader = StreamReader(store, chunk_blocks=1, depth=1)
        it = reader.stream(_full_schedule(store, pg.P))
        next(it).release_after(_Token())  # never ready, never advanced past
        time.sleep(0.1)  # chunk 1 staged; the staging thread needs a buffer
        t0 = time.perf_counter()
        it.close()  # no RuntimeError from the 5 s join guard
        assert time.perf_counter() - t0 < 2.0
        assert not reader._worker.is_alive()
        # the reader runs a fresh pass afterwards
        assert sum(1 for _ in reader.stream(_full_schedule(store, pg.P))) > 0

    def test_abort_during_a_token_wait_refills_nothing(self, spilled):
        """Aborted while the staging thread waits on a token: it stops once
        the device work is done, and stages nothing more."""
        _, _, pg, _, store = spilled
        reader = StreamReader(store, chunk_blocks=1, depth=1)
        it = reader.stream(_full_schedule(store, pg.P))
        c0 = next(it)
        snap = c0.sp.copy()
        token = _Token()
        c0.release_after(token)
        next(it)
        time.sleep(0.1)  # the staging thread now waits on the token
        timer = threading.Timer(0.3, token.done.set)
        timer.start()
        try:
            it.close()
        finally:
            timer.join()
        assert not reader._worker.is_alive()
        assert reader.stats.chunks == 2
        assert np.array_equal(c0.sp, snap)  # not refilled after the stop


# ---------------------------------------------------------------------------
# cross-mode equivalence: streamed must agree with every in-memory mode
# ---------------------------------------------------------------------------

class TestCrossModeEquivalence:
    MODES = ["recoded", "basic", "basic_sc"]

    def _run_all(self, g, prog_factory, tmp_path, n=4, edge_block=64):
        pg, rmap = partition_graph(g, n_shards=n, edge_block=edge_block)
        pgs, _, store = partition_graph_streamed(
            g, n, str(tmp_path / "s"), edge_block=edge_block, recode=rmap
        )
        outs = {}
        for mode in self.MODES:
            eng = GraphDEngine(
                      pg,
                      prog_factory(rmap),
                      config=EngineConfig(mode=mode),
                  )
            (vals, _), _ = eng.run()
            outs[mode] = eng.gather_values(vals)
        eng = GraphDEngine(
                  pgs,
                  prog_factory(rmap),
                  config=EngineConfig(mode="streamed"),
                  stream_store=store,
              )
        (vals, _), _ = eng.run()
        outs["streamed"] = eng.gather_values(vals)
        return outs

    def test_pagerank(self, tmp_path):
        g = rmat_graph(scale=7, edge_factor=8, seed=3)
        outs = self._run_all(g, lambda _: PageRank(supersteps=6), tmp_path)
        ref = outs["recoded"]
        for mode, got in outs.items():
            # tolerance-aware: float accumulation order differs per mode
            err = max(abs(got[k] - ref[k]) for k in ref)
            assert err < 1e-6, (mode, err)

    def test_sssp(self, tmp_path):
        g = rmat_graph(scale=7, edge_factor=6, seed=5, weights="uniform")
        def mk(rmap):
            src = int(rmap.to_new(np.array([int(g.vertex_ids[0])]))[0])
            return SSSP(src)
        outs = self._run_all(g, mk, tmp_path)
        ref = outs["recoded"]
        for mode, got in outs.items():
            for k, v in ref.items():
                o = got[k]
                assert (np.isinf(v) and np.isinf(o)) or abs(o - v) < 1e-5, mode

    def test_hashmin(self, tmp_path):
        g = rmat_graph(scale=7, edge_factor=4, seed=11)
        outs = self._run_all(g, lambda _: HashMin(), tmp_path)
        ref = outs["recoded"]
        for mode, got in outs.items():
            assert got == ref, mode  # integer labels: bit-for-bit


# ---------------------------------------------------------------------------
# early recycle: one-block chunks and two staging buffers, so every buffer is
# refilled as soon as the reader may. A fold that still read a refilled
# buffer would change the values.
# ---------------------------------------------------------------------------

RECYCLE_PROGRAMS = {
    "pagerank": lambda g, rmap: PageRank(supersteps=5),
    "sssp": lambda g, rmap: SSSP(
        int(rmap.to_new(np.array([int(g.vertex_ids[0])]))[0])),
    "hashmin": lambda g, rmap: HashMin(),
}
# the combiner loops that hand the reader release tokens
RECYCLE_PATHS = ["unpipelined", "full-duplex", "logged"]


@pytest.fixture(scope="module")
def recycle_graph(tmp_path_factory):
    g = rmat_graph(scale=7, edge_factor=8, seed=3, weights="uniform")
    pg, rmap = partition_graph(g, n_shards=4, edge_block=16)
    d = tmp_path_factory.mktemp("recycle")
    pgs, _, store = partition_graph_streamed(
        g, 4, str(d / "spill"), edge_block=16, recode=rmap
    )
    return g, rmap, pg, pgs, store, d


def _late_reading(fold):
    """``fold`` that reads its staged edges only after about 2 ms of busy
    work, so the device is still on a chunk when the reader wants its
    buffer back. The edges get ``+ 0``: the same values."""

    @jax.jit
    def late(A, cnt, values, degree, active, sp, dp, w, step):
        x = jnp.arange(1 << 14, dtype=jnp.float32) * step.astype(jnp.float32)
        acc = jax.lax.fori_loop(
            0, 8, lambda r, a: a + jnp.sum(jnp.sin(x + r)), jnp.float32(0)
        )
        zero = (acc * 0).astype(jnp.int32)
        return fold(A, cnt, values, degree, active, sp + zero, dp + zero,
                    w + zero.astype(w.dtype), step)

    return late


@pytest.mark.parametrize("path", RECYCLE_PATHS)
@pytest.mark.parametrize("algo", sorted(RECYCLE_PROGRAMS))
def test_no_early_recycle(recycle_graph, algo, path, monkeypatch):
    g, rmap, pg, pgs, store, d = recycle_graph
    make = RECYCLE_PROGRAMS[algo]

    def streamed(tag):
        log = (RunFileMessageLog(str(d / f"log-{algo}-{tag}"))
               if path == "logged" else None)
        eng = GraphDEngine(
            pgs, make(g, rmap),
            config=EngineConfig(
                mode="streamed",
                stream=StreamConfig(chunk_blocks=1, depth=1),
                channel=ChannelConfig(pipeline=path == "full-duplex"),
            ),
            stream_store=store, message_log=log,
        )
        eng._stream_fold = _late_reading(eng._stream_fold)
        (vals, _), hist = eng.run()
        return np.asarray(vals), eng.gather_values(vals), hist

    # the reference protocol: wait for each fold, then release on advance
    with monkeypatch.context() as m:
        m.setattr(StagedChunk, "release_after",
                  lambda self, token: jax.block_until_ready(token))
        v_sync, _, _ = streamed("sync")
    released = []
    release_after = StagedChunk.release_after

    def spy(self, token):
        released.append(token)
        release_after(self, token)

    monkeypatch.setattr(StagedChunk, "release_after", spy)
    v, got, hist = streamed("async")
    # the same ops in the same order: bit-identical to the waiting loop
    assert np.array_equal(v, v_sync)
    eng = GraphDEngine(pg, make(g, rmap), config=EngineConfig(mode="recoded"))
    (vals, _), _ = eng.run()
    ref = eng.gather_values(vals)
    if algo == "pagerank":  # float sums, reassociated by the grouping
        assert max(abs(got[k] - ref[k]) for k in ref) < 1e-6
    else:
        assert got == ref
    chunks = sum(r.blocks_read + r.cache_hits for r in hist)
    if path == "unpipelined":
        assert len(released) == chunks  # every chunk's fold is a token
    else:  # single-chunk groups are copied into a batched fold instead
        assert 0 < len(released) <= chunks
    for r in hist:
        assert 0 <= r.recycle_waits <= r.blocks_read + r.cache_hits
        assert r.recycle_wait_seconds >= 0.0
    # the late reads made the reader wait for the device at least once
    assert sum(r.recycle_waits for r in hist) > 0


# ---------------------------------------------------------------------------
# the memory guarantee: resident bytes are O(|V|/n), independent of |E|
# ---------------------------------------------------------------------------

class TestMemoryGuarantee:
    def _engines(self, edge_factor, tmp_path, tag):
        # |E| >> |V|: scale 8 => |V| <= 256, edge_factor up to 48 edges/vertex
        g = rmat_graph(scale=8, edge_factor=edge_factor, seed=7)
        pg, _ = partition_graph(g, n_shards=4, edge_block=32)
        pgs, _, store = partition_graph_streamed(
            g, 4, str(tmp_path / f"sp{tag}"), edge_block=32
        )
        mem = GraphDEngine(
                  pg,
                  PageRank(supersteps=2),
                  config=EngineConfig(mode="recoded"),
              )
        out = GraphDEngine(
                  pgs,
                  PageRank(supersteps=2),
                  config=EngineConfig(mode="streamed", stream=StreamConfig(chunk_blocks=2)),
                  stream_store=store,
              )
        return g, mem, out

    @staticmethod
    def _ram(m):
        return m["resident"] + m["buffers"] + m["staging"]

    def test_resident_independent_of_E(self, tmp_path):
        g1, mem1, out1 = self._engines(4, tmp_path, "a")
        g2, mem2, out2 = self._engines(48, tmp_path, "b")
        assert g2.n_edges > 4 * g1.n_edges  # |E| really grew
        assert g2.n_vertices == g1.n_vertices
        s1, s2 = out1.memory_model(), out2.memory_model()
        # streamed RAM footprint: exactly equal despite >4x the edges
        assert self._ram(s1) == self._ram(s2)
        # ... while the on-disk tier grows with |E|
        assert s2["streamed"] > s1["streamed"]
        # ... and the in-memory engine's device edge bytes grow too
        m1, m2 = mem1.memory_model(), mem2.memory_model()
        assert m2["streamed"] > 4 * m1["streamed"]

    def test_resident_small_constant_of_V_over_n(self, tmp_path):
        g, mem, out = self._engines(48, tmp_path, "c")
        s = out.memory_model()
        pg = out.pg
        # per-shard vertex state: P slots, <= 32 B/slot across all arrays
        vertex_bytes = pg.P * 32
        # staging pool is a compiled-in constant: chunk_blocks * edge_block
        assert self._ram(s) <= 4 * vertex_bytes + out._stream_reader.staging_bytes()
        # and the in-memory engine holds edge-sized state the streamed one
        # does not: its device footprint exceeds the streamed RAM total
        m = mem.memory_model()
        assert self._ram(m) + m["streamed"] > self._ram(s)
        # the spilled partition itself holds no edge-sized arrays
        per_shard_resident = sum(
            np.asarray(a).nbytes
            for a in (pg.degree, pg.vmask, pg.old_ids, pg.gids)
        ) // pg.n_shards + np.asarray(pg.src_pos).nbytes
        assert per_shard_resident <= vertex_bytes


# ---------------------------------------------------------------------------
# skip() really avoids I/O + streamed fault tolerance
# ---------------------------------------------------------------------------

class TestStreamedExecution:
    def test_chain_sssp_reads_few_blocks(self, tmp_path):
        """On a chain with a 1-vertex frontier, skip() must keep per-step
        disk reads near-constant instead of scanning all blocks."""
        g = chain_graph(256)
        pgs, rmap, store = partition_graph_streamed(
            g, 4, str(tmp_path / "chain"), edge_block=8
        )
        src_new = int(rmap.to_new(np.array([0]))[0])
        eng = GraphDEngine(
                  pgs,
                  SSSP(src_new),
                  config=EngineConfig(mode="streamed", stream=StreamConfig(chunk_blocks=2)),
                  stream_store=store,
              )
        blocks_per_step = []
        (vals, _), hist = eng.run(
            max_supersteps=300,
            on_step=lambda rec, s: blocks_per_step.append(
                eng._stream_reader.stats.blocks_read
            ),
        )
        got = eng.gather_values(vals)
        assert all(got[k] == k for k in got)  # dist(0 -> k) = k on the chain
        total = store.nonempty_blocks()
        # the frontier touches O(1) blocks per superstep; a full scan would
        # read `total` every time
        assert max(blocks_per_step[1:]) <= max(4, total // 4)

    def test_streamed_quiescence(self, tmp_path):
        g = chain_graph(32)
        pgs, rmap, store = partition_graph_streamed(
            g, 2, str(tmp_path / "q"), edge_block=8
        )
        src_new = int(rmap.to_new(np.array([31]))[0])  # sink: no out-edges
        eng = GraphDEngine(
                  pgs,
                  SSSP(src_new),
                  config=EngineConfig(mode="streamed"),
                  stream_store=store,
              )
        (_, _), hist = eng.run()
        assert len(hist) == 1  # immediately quiescent

    def test_checkpoint_restart_matches(self, spilled, tmp_path):
        _, _, pg, _, store = spilled
        (v_ref, _), _ = GraphDEngine(
                            pg,
                            PageRank(supersteps=8),
                            config=EngineConfig(mode="streamed"),
                            stream_store=store,
                        ).run()
        ck = Checkpointer(str(tmp_path / "ck"), every=3)
        eng = GraphDEngine(
                  pg,
                  PageRank(supersteps=8),
                  config=EngineConfig(mode="streamed"),
                  stream_store=store,
              )
        eng.run(max_supersteps=5, checkpointer=ck)  # "crash" after step 5
        eng2 = GraphDEngine(
                   pg,
                   PageRank(supersteps=8),
                   config=EngineConfig(mode="streamed"),
                   stream_store=store,
               )
        (v2, _), hist = eng2.run(checkpointer=ck)  # resumes from step 3
        assert hist[0].step == 3
        assert np.allclose(np.asarray(v2), np.asarray(v_ref))

    def test_manifest_mismatch_refused(self, spilled, tmp_path):
        """A checkpoint written against one edge stream must not silently
        restore against another (manifest-aware recovery)."""
        g, _, pg, _, store = spilled
        ck = Checkpointer(str(tmp_path / "ck2"), every=2)
        GraphDEngine(
            pg,
            PageRank(supersteps=4),
            config=EngineConfig(mode="streamed"),
            stream_store=store,
        ).run(checkpointer=ck)
        g2 = rmat_graph(scale=7, edge_factor=4, seed=99)
        pg2, _, store2 = partition_graph_streamed(
            g2, 4, str(tmp_path / "other"), edge_block=64
        )
        with pytest.raises(ValueError, match="different edge streams"):
            ck.restore(expected_meta=store2.signature())

    def test_spilled_partition_rejected_by_in_memory_modes(self, spilled):
        """A vertex-only partition in mode='recoded' would silently compute
        a wrong fixpoint (no edges -> no messages); must raise instead."""
        _, _, pg, _, _ = spilled
        with pytest.raises(ValueError, match="vertex-only"):
            GraphDEngine(pg, PageRank(), config=EngineConfig(mode="recoded"))

    def test_density_semantics_match_in_memory(self, spilled):
        """rec.density means 'fraction of blocks active NEXT superstep' in
        every mode — histories must line up step for step."""
        g, pg_full, pg, rmap, store = spilled
        src_new = int(rmap.to_new(np.array([int(g.vertex_ids[0])]))[0])
        (_, _), h_mem = GraphDEngine(
                            pg_full,
                            SSSP(src_new),
                            config=EngineConfig(mode="recoded", adapt_threshold=-1),
                        ).run()
        eng = GraphDEngine(
                  pg,
                  SSSP(src_new),
                  config=EngineConfig(mode="streamed"),
                  stream_store=store,
              )
        (_, _), h_st = eng.run()
        assert len(h_mem) == len(h_st)
        for a, b in zip(h_mem, h_st):
            assert abs(a.density - b.density) < 1e-6

    def test_engine_validates_geometry(self, spilled, tmp_path):
        g, _, _, _, store = spilled
        pg_other, _ = partition_graph(g, n_shards=2, edge_block=64)
        with pytest.raises(ValueError, match="geometry"):
            GraphDEngine(
                pg_other,
                PageRank(),
                config=EngineConfig(mode="streamed"),
                stream_store=store,
            )

    def test_requires_store_and_rejects_plain_log(self, spilled, tmp_path):
        from repro.core.algorithms import DistinctInLabels
        from repro.core.checkpoint import MessageLog

        _, _, pg, _, store = spilled
        with pytest.raises(ValueError, match="stream_store"):
            GraphDEngine(pg, PageRank(), config=EngineConfig(mode="streamed"))
        # combiner-less programs are first-class in streamed mode now (the
        # OMS disk tier, tests/test_msgstore.py); what IS rejected is a
        # dense MessageLog, which would materialize O(n²·P) buffers
        GraphDEngine(
            pg,
            DistinctInLabels(),
            config=EngineConfig(mode="streamed"),
            stream_store=store,
        )
        with pytest.raises(ValueError, match="RunFileMessageLog"):
            GraphDEngine(
                pg,
                PageRank(),
                config=EngineConfig(mode="streamed"),
                stream_store=store,
                message_log=MessageLog(str(tmp_path / "ml")),
            )

    def test_spill_partition_matches_streamed_ctor(self, tmp_path):
        """spill_partition on an existing pg == partition_graph_streamed."""
        g = rmat_graph(scale=6, edge_factor=6, seed=2)
        pg_full, _ = partition_graph(g, n_shards=3, edge_block=32)
        pg_v, store = spill_partition(pg_full, str(tmp_path / "sp"))
        eng = GraphDEngine(
                  pg_v,
                  PageRank(supersteps=4),
                  config=EngineConfig(mode="streamed"),
                  stream_store=store,
              )
        (v, _), _ = eng.run()
        (v_ref, _), _ = GraphDEngine(pg_full, PageRank(supersteps=4)).run()
        assert np.abs(np.asarray(v) - np.asarray(v_ref)).max() < 1e-6


# ---------------------------------------------------------------------------
# manifest-driven row ownership (multi-process stepping stone) + compressed
# edge streams (ISSUE 3 tentpole)
# ---------------------------------------------------------------------------

class TestRowOwnership:
    def test_owner_view_serves_only_its_row(self, spilled):
        _, _, _, _, store = spilled
        view = store.owner_view(2)
        a = store.group_edges(2, 1)
        b = view.group_edges(2, 1)
        assert all(np.array_equal(x, y) for x, y in zip(a, b))
        with pytest.raises(PermissionError, match="owns only"):
            view.group_edges(0, 1)
        B = store.geom.edge_block
        bufs = (np.empty((1, B), np.int32), np.empty((1, B), np.int32),
                np.empty((1, B), np.float32))
        with pytest.raises(PermissionError, match="owns only"):
            view.read_blocks(1, 0, np.array([0]), *bufs)

    def test_open_with_owner_uses_manifest(self, spilled):
        """A machine opens its row straight from the published manifest —
        no full-store instance required (the multi-process access path)."""
        import json

        _, _, _, _, store = spilled
        with open(os.path.join(store.dir, "manifest.json")) as f:
            m = json.load(f)
        assert m["row_ownership"]["axis"] == "src_shard"
        rb = m["row_ownership"]["row_bytes"]
        assert all(len(v) == store.geom.n_shards + 1 for v in rb.values())
        view = EdgeStreamStore.open(store.dir, owner=1)
        assert view.owner == 1
        a = store.group_edges(1, 3)
        b = view.group_edges(1, 3)
        assert all(np.array_equal(x, y) for x, y in zip(a, b))

    def test_pipelined_engine_reads_through_owner_views(self, spilled):
        _, _, pg, _, store = spilled
        eng = GraphDEngine(
                  pg,
                  PageRank(supersteps=2),
                  config=EngineConfig(mode="streamed", channel=ChannelConfig(pipeline=True)),
                  stream_store=store,
              )
        eng.run()
        views = eng._stream_reader._views
        assert views is not None and views  # per-source views were used
        assert all(v.owner == i for i, v in views.items())

    def test_reader_owner_views_cover_schedule(self, spilled):
        _, pg_full, pg, _, store = spilled
        active = np.ones((4, pg.P), bool)
        schedule, _, _ = plan_stream_schedule(store, active)
        reader = StreamReader(store, chunk_blocks=2, owner_views=True)
        edges = sum(int((c.sp >= 0).sum()) for c in reader.stream(schedule))
        assert edges == pg_full.n_edges


class TestCompressedEdgeStore:
    def test_compressed_spill_same_content_smaller_disk(self, tmp_path):
        g = rmat_graph(scale=7, edge_factor=8, seed=3)
        pg, rmap = partition_graph(g, n_shards=4, edge_block=64)
        _, _, plain = partition_graph_streamed(
            g, 4, str(tmp_path / "p"), edge_block=64, recode=rmap
        )
        _, _, comp = partition_graph_streamed(
            g, 4, str(tmp_path / "c"), edge_block=64, recode=rmap,
            compress=True,
        )
        assert comp.disk_bytes() < plain.disk_bytes()
        # identical logical content => identical recovery signature
        assert comp.signature() == plain.signature()
        for i in range(4):
            for k in range(4):
                a, b = plain.group_edges(i, k), comp.group_edges(i, k)
                assert all(np.array_equal(x.reshape(-1), y.reshape(-1))
                           for x, y in zip(a, b))

    def test_compressed_open_roundtrip_and_owner_view(self, tmp_path):
        g = rmat_graph(scale=6, edge_factor=6, seed=2)
        _, _, store = partition_graph_streamed(
            g, 3, str(tmp_path / "c"), edge_block=32, compress=True
        )
        re = EdgeStreamStore.open(store.dir)
        assert re.compress
        view = EdgeStreamStore.open(store.dir, owner=2)
        a = store.group_edges(2, 0)
        b = view.group_edges(2, 0)
        assert all(np.array_equal(x, y) for x, y in zip(a, b))
        with pytest.raises(PermissionError):
            view.group_edges(1, 0)

    def test_streamed_over_compressed_store_bitmatches(self, tmp_path):
        g = rmat_graph(scale=7, edge_factor=6, seed=5)
        pg_full, rmap = partition_graph(g, n_shards=4, edge_block=64)
        pgs, _, store = partition_graph_streamed(
            g, 4, str(tmp_path / "c"), edge_block=64, recode=rmap,
            compress=True,
        )
        (v_ref, _), _ = GraphDEngine(
                            pg_full,
                            HashMin(),
                            config=EngineConfig(mode="basic"),
                        ).run()
        (v, _), _ = GraphDEngine(
                        pgs,
                        HashMin(),
                        config=EngineConfig(mode="streamed"),
                        stream_store=store,
                    ).run()
        assert np.array_equal(np.asarray(v), np.asarray(v_ref))


class TestPipelinedMemoryModel:
    def test_channel_budget_constant_and_ram_flat(self, tmp_path):
        """The in-flight channel budget is a compiled-in constant: the
        pipelined RAM total must not move as |E| grows (Theorem 1 still
        holds with the §4 overlap enabled)."""
        rams = []
        for tag, ef in (("a", 4), ("b", 48)):
            g = rmat_graph(scale=8, edge_factor=ef, seed=7)
            pgs, _, store = partition_graph_streamed(
                g, 4, str(tmp_path / f"sp{tag}"), edge_block=32
            )
            eng = GraphDEngine(
                      pgs,
                      PageRank(supersteps=2),
                      config=EngineConfig(mode="streamed", stream=StreamConfig(chunk_blocks=2), channel=ChannelConfig(pipeline=True)),
                      stream_store=store,
                  )
            m = eng.memory_model()
            assert m["channel"] == eng.channel_inflight * pgs.P * (4 + 4 + 4)
            rams.append(m["resident"] + m["buffers"] + m["staging"]
                        + m["channel"])
        assert rams[0] == rams[1]


class TestPayloadCompressedEdgeStore:
    """compress_payload= on the weight channel (PR 5): per-block payload
    blobs, same logical content, smaller disk, owner views intact."""

    def test_payload_spill_same_content_smaller_disk(self, tmp_path):
        g = rmat_graph(scale=7, edge_factor=8, seed=3, weights="uniform")
        pg, rmap = partition_graph(g, n_shards=4, edge_block=64)
        _, _, comp = partition_graph_streamed(
            g, 4, str(tmp_path / "c"), edge_block=64, recode=rmap,
            compress=True,
        )
        _, _, full = partition_graph_streamed(
            g, 4, str(tmp_path / "cp"), edge_block=64, recode=rmap,
            compress=True, compress_payload=True,
        )
        assert full.disk_bytes() < comp.disk_bytes()
        # identical logical content => identical recovery signature
        assert full.signature() == comp.signature()
        for i in range(4):
            for k in range(4):
                a, b = comp.group_edges(i, k), full.group_edges(i, k)
                assert all(np.array_equal(x.reshape(-1), y.reshape(-1))
                           for x, y in zip(a, b))

    def test_payload_open_roundtrip_and_owner_view(self, tmp_path):
        g = rmat_graph(scale=6, edge_factor=6, seed=2, weights="uniform")
        _, _, store = partition_graph_streamed(
            g, 3, str(tmp_path / "cp"), edge_block=32, compress=True,
            compress_payload=True,
        )
        re = EdgeStreamStore.open(store.dir)
        assert re.compress and re.compress_payload
        view = EdgeStreamStore.open(store.dir, owner=2)
        a = store.group_edges(2, 0)
        b = view.group_edges(2, 0)
        assert all(np.array_equal(x, y) for x, y in zip(a, b))
        with pytest.raises(PermissionError):
            view.group_edges(1, 0)

    def test_streamed_over_payload_store_bitmatches(self, tmp_path):
        g = rmat_graph(scale=7, edge_factor=6, seed=5, weights="uniform")
        pg_full, rmap = partition_graph(g, n_shards=4, edge_block=64)
        pgs, _, store = partition_graph_streamed(
            g, 4, str(tmp_path / "cp"), edge_block=64, recode=rmap,
            compress=True, compress_payload=True,
        )
        (v_ref, _), _ = GraphDEngine(
                            pg_full,
                            SSSP(0),
                            config=EngineConfig(mode="basic"),
                        ).run()
        (v, _), _ = GraphDEngine(
                        pgs,
                        SSSP(0),
                        config=EngineConfig(mode="streamed"),
                        stream_store=store,
                    ).run()
        assert np.array_equal(np.asarray(v), np.asarray(v_ref))
