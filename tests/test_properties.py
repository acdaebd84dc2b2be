"""Hypothesis property tests (recoding bijections, mode agreement, combiner
algebra, kernel-vs-oracle sweeps).

This module is the repo's only consumer of `hypothesis`; conftest.py skips it
cleanly when the package is absent so the tier-1 command stays green on a
bare interpreter. Fixed-seed versions of the load-bearing checks live in the
regular test modules and always run.
"""

import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed (see requirements-dev.txt)"
)

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import EngineConfig, GraphDEngine, HashMin
from repro.core.api import IMAX, IMIN, MAX, MIN, OR, SUM
from repro.graph import Graph, partition_graph, recode_ids
from repro.graph.recode import recode_distributed


def edge_strategy(max_v=200, max_e=400):
    return st.lists(
        st.tuples(st.integers(0, max_v - 1), st.integers(0, max_v - 1)),
        min_size=1, max_size=max_e,
    )


# ---------------------------------------------------------------------------
# recoding (graph substrate)
# ---------------------------------------------------------------------------

@given(edge_strategy(), st.integers(1, 9))
@settings(max_examples=30, deadline=None)
def test_recode_bijection(edges, n):
    ids = np.unique(np.array([v for e in edges for v in e], dtype=np.int64))
    rmap = recode_ids(ids, n)
    new = rmap.to_new(ids)
    assert len(set(new.tolist())) == len(ids)
    assert np.array_equal(rmap.to_old(new), ids)
    for g in new:
        assert 0 <= g < n * rmap.max_positions


@given(edge_strategy(), st.integers(1, 6))
@settings(max_examples=20, deadline=None)
def test_distributed_recoding_matches_fast_path(edges, n):
    """Paper §5: the 3-superstep recoding job produces the same streams."""
    src = np.array([e[0] for e in edges], dtype=np.int64)
    dst = np.array([e[1] for e in edges], dtype=np.int64)
    ids = np.unique(np.concatenate([src, dst]))
    s1, d1, rmap = recode_distributed(src, dst, ids, n)
    assert np.array_equal(s1, rmap.to_new(src))
    assert np.array_equal(d1, rmap.to_new(dst))


@given(st.integers(2, 12))
@settings(max_examples=10, deadline=None)
def test_balance_random_ids(n):
    rng = np.random.default_rng(n)
    ids = np.unique(rng.integers(0, 2**48, size=5000))
    rmap = recode_ids(ids, n)
    assert rmap.max_positions < 2 * len(ids) / n


# ---------------------------------------------------------------------------
# combiner algebra (paper §2.1/§5: commutative, associative, identity e0)
# ---------------------------------------------------------------------------

_COMBINERS = {"sum": SUM, "min": MIN, "max": MAX, "or": OR,
              "imin": IMIN, "imax": IMAX}


def _domain(name, draw_ints):
    # OR operates on the boolean semiring; int combiners on int32.
    if name == "or":
        return np.array(draw_ints, dtype=np.int32) % 2
    return np.array(draw_ints, dtype=np.int32)


@pytest.mark.parametrize("name", list(_COMBINERS))
@given(st.lists(st.integers(-1000, 1000), min_size=3, max_size=3))
@settings(max_examples=25, deadline=None)
def test_combiner_associative_commutative_identity(name, vals):
    import jax.numpy as jnp

    comb = _COMBINERS[name]
    a, b, c = (jnp.asarray(v) for v in _domain(name, vals))
    as_bool = name == "or"
    norm = (lambda x: np.asarray(x).astype(bool)) if as_bool else np.asarray
    # commutative / associative
    assert norm(comb.combine(a, b)) == norm(comb.combine(b, a))
    assert norm(comb.combine(comb.combine(a, b), c)) == norm(
        comb.combine(a, comb.combine(b, c))
    )
    # e0 is a true identity
    dtype = jnp.int32 if name in ("or", "imin", "imax") else jnp.float32
    e0 = jnp.asarray(comb.e0, dtype)
    av = a.astype(dtype)
    assert norm(comb.combine(av, e0)) == norm(av)
    assert norm(comb.combine(e0, av)) == norm(av)


@pytest.mark.parametrize("name", ["sum", "min", "max", "or"])
@given(
    st.lists(st.tuples(st.integers(0, 15), st.integers(0, 50)),
             min_size=1, max_size=64),
)
@settings(max_examples=25, deadline=None)
def test_combiner_scatter_reduce_agree(name, pairs):
    """The scatter path (A_s in-memory combine) and the reduce path (stacked
    buffer fold) must realize the same abstract combine."""
    import jax.numpy as jnp

    comb = _COMBINERS[name]
    P = 16
    idx = np.array([p[0] for p in pairs], dtype=np.int32)
    msgs = _domain(name, [p[1] for p in pairs]).astype(np.float32)
    scattered = comb.scatter(
        comb.identity((P,), jnp.float32), jnp.asarray(idx), jnp.asarray(msgs)
    )
    # reduce path: one stacked one-slot buffer per message
    stack = np.full((len(pairs), P), float(comb.e0), dtype=np.float32)
    stack[np.arange(len(pairs)), idx] = msgs
    reduced = comb.reduce(jnp.asarray(stack), 0)
    sa, ra = np.asarray(scattered), np.asarray(reduced)
    if name == "or":
        np.testing.assert_array_equal(sa.astype(bool), ra.astype(bool))
    else:
        np.testing.assert_allclose(sa, ra, rtol=1e-6)


# ---------------------------------------------------------------------------
# engine: all exchange modes agree on random graphs
# ---------------------------------------------------------------------------

@given(
    st.lists(st.tuples(st.integers(0, 60), st.integers(0, 60)),
             min_size=1, max_size=150),
    st.integers(1, 5),
)
@settings(max_examples=15, deadline=None)
def test_property_modes_agree_on_random_graphs(edges, n):
    """Property: all exchange modes compute identical HashMin fixpoints."""
    src = np.array([e[0] for e in edges], dtype=np.int64)
    dst = np.array([e[1] for e in edges], dtype=np.int64)
    keep = src != dst
    if not keep.any():
        return
    g = Graph(src=src[keep], dst=dst[keep], weight=None, directed=False)
    pg, _ = partition_graph(g, n_shards=n, edge_block=8)
    outs = []
    for mode in ["recoded", "basic", "basic_sc"]:
        eng = GraphDEngine(pg, HashMin(), config=EngineConfig(mode=mode))
        (vals, _), _ = eng.run()
        outs.append(eng.gather_values(vals))
    assert outs[0] == outs[1] == outs[2]


# ---------------------------------------------------------------------------
# kernels: Pallas vs oracle on random graphs × random frontiers
# ---------------------------------------------------------------------------

@given(st.integers(0, 2**31 - 1), st.floats(0.0, 1.0))
@settings(max_examples=10, deadline=None)
def test_property_kernel_matches_ref(seed, density):
    import jax.numpy as jnp

    from repro.graph import rmat_graph
    from repro.graph.kblocks import build_kernel_layout
    from repro.kernels import ops
    from repro.kernels.ref import edge_combine_ref

    g = rmat_graph(scale=6, edge_factor=4, seed=seed % 1000)
    pg, _ = partition_graph(g, n_shards=2, edge_block=64, vertex_pad=16)
    kl = build_kernel_layout(pg, BLK=16, SRC_WIN=16, DST_WIN=16)
    rng = np.random.default_rng(seed % 97)
    P = pg.P
    state3 = jnp.stack([
        jnp.asarray(rng.random(P, dtype=np.float32)),
        jnp.asarray(np.asarray(pg.degree)[0].astype(np.float32)),
        jnp.asarray((rng.random(P) < density).astype(np.float32)),
    ], axis=0)
    i, k = 0, 1
    args = (
        state3, kl.sp[i, k], kl.dp[i, k], kl.w[i, k],
        jnp.arange(kl.NB, dtype=jnp.int32), jnp.int32(kl.NB),
        kl.blk_swin[i, k], kl.blk_dwin[i, k],
    )
    kw = dict(SRC_WIN=16, DST_WIN=16, msg_kind="div_deg", combiner="sum")
    A_k, _ = ops.edge_combine(*args, **kw)
    A_r, _ = edge_combine_ref(*args, **kw)
    np.testing.assert_allclose(np.asarray(A_k), np.asarray(A_r),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# varint-delta codec: arbitrary integer streams round-trip (streams/codec.py)
# ---------------------------------------------------------------------------

@given(
    st.lists(st.integers(-(2**50), 2**50), min_size=0, max_size=300),
    st.booleans(),
)
@settings(max_examples=60, deadline=None)
def test_property_varint_delta_roundtrip(vals, presort):
    """encode∘decode == id for sorted (the real use: dst_pos columns) AND
    unsorted input (zigzag covers sign flips, e.g. the -1 padding tail)."""
    from repro.streams.codec import decode_varint_delta, encode_varint_delta

    v = np.array(sorted(vals) if presort else vals, dtype=np.int64)
    out = decode_varint_delta(encode_varint_delta(v))
    assert np.array_equal(out, v)


@given(
    st.lists(st.integers(0, 2**31 - 1), min_size=1, max_size=300),
    st.integers(1, 64),
)
@settings(max_examples=40, deadline=None)
def test_property_streaming_decoder_matches_bulk(vals, take):
    """Chunked streaming decode == bulk decode for every take size (the
    merge cursors rely on this to keep O(read_chunk) residency)."""
    from repro.streams.codec import (
        VarintDeltaDecoder, decode_varint_delta, encode_varint_delta,
    )

    v = np.array(sorted(vals), dtype=np.int64)
    blob = encode_varint_delta(v)
    dec = VarintDeltaDecoder(blob, len(v))
    parts = []
    while dec.remaining:
        parts.append(dec.take(take))
    assert np.array_equal(np.concatenate(parts), decode_varint_delta(blob))


# ---------------------------------------------------------------------------
# channel ordering: arbitrary interleavings of per-shard appends must merge
# into destination-sorted runs (streams/channel.py + msgstore external merge)
# ---------------------------------------------------------------------------

@given(
    st.lists(  # per packet: (source shard, destination shard, run length)
        st.tuples(st.integers(0, 2), st.integers(0, 2), st.integers(0, 40)),
        min_size=0, max_size=25,
    ),
    st.integers(0, 2**31 - 1),
    st.booleans(),
)
@settings(max_examples=25, deadline=None)
def test_property_channel_interleavings_merge_sorted(packets, seed, compress):
    """Whatever interleaving of per-shard sends (and whatever payload), each
    inbox's k-way merge must yield one globally destination-sorted stream
    holding exactly the multiset of transmitted messages."""
    import tempfile

    from repro.streams import MessageRunStore, ShardChannels

    P = 32
    rng = np.random.default_rng(seed % (2**32))
    with tempfile.TemporaryDirectory(prefix="graphd-chan-prop-") as d:
        store = MessageRunStore(d, 3, P, np.float32, compress=compress)
        chan = ShardChannels(store, inflight=2)
        want = {k: [] for k in range(3)}
        for src, k, ln in packets:
            dp = np.sort(rng.integers(0, P, ln)).astype(np.int32)
            msg = rng.random(ln).astype(np.float32)
            chan.send(k, dp, msg, tag=src)
            want[k].append((dp, msg))
        chan.close()
        for k in range(3):
            merged = list(store.iter_merged(k, read_chunk=7))
            got_dp = (np.concatenate([m[0] for m in merged])
                      if merged else np.empty(0, np.int64))
            got_msg = (np.concatenate([m[1] for m in merged])
                       if merged else np.empty(0, np.float32))
            all_dp = (np.concatenate([dp for dp, _ in want[k]])
                      if want[k] else np.empty(0, np.int32))
            all_msg = (np.concatenate([m for _, m in want[k]])
                       if want[k] else np.empty(0, np.float32))
            assert np.all(np.diff(got_dp) >= 0)
            # multiset equality of (dst, payload) pairs
            ow = np.lexsort((all_msg, all_dp))
            og = np.lexsort((got_msg, got_dp))
            assert np.array_equal(all_dp[ow], got_dp[og])
            assert np.array_equal(all_msg[ow], got_msg[og])
