"""Hash partitioning + per-destination edge groups (the OMS layout) + blocks.

Layout produced per shard (= per "machine" in the paper):

* the in-memory state array ``A``: ``values/active/degree/vmask/old_ids``,
  padded to ``P = ceil(|V|/n)`` (rounded to ``vertex_pad``) entries,
* the edge stream ``S^E`` organized into ``n`` per-destination groups (the
  outgoing-message-stream layout of §3.3.1): group ``(i, k)`` holds shard i's
  edges whose destination lives on shard k, sorted by source position and
  padded to a common capacity ``E_cap`` (a multiple of ``edge_block``),
* per-block source ranges ``blk_lo/blk_hi`` — the skip() metadata of §3.2:
  because groups are sorted by source position, a block can be skipped iff no
  vertex in ``[blk_lo, blk_hi]`` is active (checked with a prefix sum over the
  active bitmap at runtime).

Padded edge slots carry ``src_pos = -1`` and scatter the combiner identity to
position 0, so they are compute-neutral in every mode.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import numpy as np
import jax.numpy as jnp

from repro.core import spans
from repro.graph.csr import Graph
from repro.graph.recode import RecodeMap, recode_ids


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def block_ranges(sp_blocks: np.ndarray, P: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-block [min, max] source range over the trailing axis — the skip()
    metadata of §3.2, shared by the device layout and the on-disk stream
    layout (streams/store.py). Sentinels (P, -1) mark empty blocks."""
    valid = sp_blocks >= 0
    lo = np.where(valid, sp_blocks, P).min(axis=-1).astype(np.int32)
    hi = np.where(valid, sp_blocks, -1).max(axis=-1).astype(np.int32)
    return lo, hi


@jax.tree_util.register_dataclass
@dataclass
class PartitionedGraph:
    """Device-resident partitioned graph. Leading axis of every array = shard."""

    # static metadata
    n_shards: int = dataclasses.field(metadata=dict(static=True))
    n_vertices: int = dataclasses.field(metadata=dict(static=True))
    n_edges: int = dataclasses.field(metadata=dict(static=True))
    P: int = dataclasses.field(metadata=dict(static=True))  # padded verts/shard
    E_cap: int = dataclasses.field(metadata=dict(static=True))  # padded edges/group
    edge_block: int = dataclasses.field(metadata=dict(static=True))
    n_blocks: int = dataclasses.field(metadata=dict(static=True))

    # vertex state array A (paper Eq. 1 minus a(v), which the engine owns)
    degree: jax.Array  # (n, P) int32 — global out-degree d(v)
    vmask: jax.Array  # (n, P) bool — position holds a real vertex
    old_ids: jax.Array  # (n, P) int64 — original ids (for dumping results)
    gids: jax.Array  # (n, P) int64 — recoded global id (stable across elastic
    # repartitioning; equals n*pos + i at initial build, -1 for holes)

    # per-destination edge groups: [i, k, e]
    src_pos: jax.Array  # (n, n, E_cap) int32, -1 for padding
    dst_pos: jax.Array  # (n, n, E_cap) int32
    eweight: jax.Array  # (n, n, E_cap) float32

    # skip() block metadata
    blk_lo: jax.Array  # (n, n, n_blocks) int32 — min src_pos (P for empty)
    blk_hi: jax.Array  # (n, n, n_blocks) int32 — max src_pos (-1 for empty)

    @property
    def shape_summary(self) -> str:
        return (
            f"PartitionedGraph(n={self.n_shards}, |V|={self.n_vertices}, "
            f"|E|={self.n_edges}, P={self.P}, E_cap={self.E_cap}, "
            f"blocks={self.n_blocks}x{self.edge_block})"
        )


def build_partition(
    n: int,
    src_g: np.ndarray,  # (E,) edge sources, *global recoded* ids
    dst_g: np.ndarray,  # (E,) edge destinations, global recoded ids
    weight: np.ndarray,  # (E,)
    gids_real: np.ndarray,  # (V,) all real vertex global ids
    old_ids_real: np.ndarray,  # (V,) their original ids
    edge_block: int = 512,
    vertex_pad: int = 8,
) -> PartitionedGraph:
    """Assemble the device layout from global-recoded-id edge/vertex arrays.

    Global ids obey shard = g mod n, pos = g // n for ANY n — this is what
    makes elastic repartitioning (core/elastic.py) a pure index transform.
    """
    with spans.span("partition.build"):
        geom, host = _host_layout(n, src_g, dst_g, weight, gids_real,
                                  old_ids_real, edge_block, vertex_pad)
    with spans.span("partition.upload"):
        return PartitionedGraph(
            n_shards=n,
            n_vertices=int(gids_real.shape[0]),
            n_edges=int(src_g.shape[0]),
            edge_block=edge_block,
            **geom,
            **{k: jnp.asarray(v) for k, v in host.items()},
        )


def _host_layout(n, src_g, dst_g, weight, gids_real, old_ids_real,
                 edge_block, vertex_pad):
    """:func:`build_partition`'s arrays on the host: the geometry
    (``P``, ``E_cap``, ``n_blocks``) and the arrays by field name."""
    P = max(_round_up(int(gids_real.max()) // n + 1 if gids_real.size else 1,
                      vertex_pad), vertex_pad)
    src_shard, src_p = src_g % n, src_g // n
    dst_shard, dst_p = dst_g % n, dst_g // n

    # out-degree per global id (for PageRank's a(v)/d(v))
    deg_global = np.bincount(src_g, minlength=n * P).astype(np.int32)

    # group edges by (src_shard, dst_shard), sort each group by src position
    group_key = src_shard * n + dst_shard
    order = np.lexsort((src_p, group_key))
    gk, sp, dp, w = group_key[order], src_p[order], dst_p[order], weight[order]
    counts = np.bincount(gk, minlength=n * n)
    E_cap = max(_round_up(int(counts.max()) if counts.size else 0, edge_block),
                edge_block)
    n_blocks = E_cap // edge_block

    src_pos = np.full((n, n, E_cap), -1, dtype=np.int32)
    dst_pos = np.zeros((n, n, E_cap), dtype=np.int32)
    eweight = np.zeros((n, n, E_cap), dtype=np.float32)
    offs = np.zeros(n * n + 1, dtype=np.int64)
    np.cumsum(counts, out=offs[1:])
    for i in range(n):
        for k in range(n):
            a, b = offs[i * n + k], offs[i * n + k + 1]
            c = b - a
            src_pos[i, k, :c] = sp[a:b]
            dst_pos[i, k, :c] = dp[a:b]
            eweight[i, k, :c] = w[a:b]

    # block metadata: min/max src pos per block (P / -1 sentinels when empty)
    blk_lo, blk_hi = block_ranges(
        src_pos.reshape(n, n, n_blocks, edge_block), P
    )

    # state array A
    degree = np.zeros((n, P), dtype=np.int32)
    vmask = np.zeros((n, P), dtype=bool)
    old_ids = np.full((n, P), -1, dtype=np.int64)
    gid_arr = np.full((n, P), -1, dtype=np.int64)
    degree[gids_real % n, gids_real // n] = deg_global[gids_real]
    vmask[gids_real % n, gids_real // n] = True
    old_ids[gids_real % n, gids_real // n] = old_ids_real
    gid_arr[gids_real % n, gids_real // n] = gids_real

    return dict(P=P, E_cap=E_cap, n_blocks=n_blocks), dict(
        degree=degree, vmask=vmask, old_ids=old_ids, gids=gid_arr,
        src_pos=src_pos, dst_pos=dst_pos, eweight=eweight,
        blk_lo=blk_lo, blk_hi=blk_hi,
    )


def partition_graph(
    g: Graph,
    n_shards: int,
    edge_block: int = 512,
    vertex_pad: int = 8,
    recode: RecodeMap | None = None,
) -> tuple[PartitionedGraph, RecodeMap]:
    """Preprocess (host-side, the paper's loading + ID-recoding pass)."""
    with spans.span("partition.recode"):
        rmap = (recode if recode is not None
                else recode_ids(g.vertex_ids, n_shards))
        src, dst = rmap.to_new(g.src), rmap.to_new(g.dst)
    pg = build_partition(
        n_shards,
        src,
        dst,
        g.weight,
        rmap.new_for_old_sorted,
        rmap.old_sorted,
        edge_block=edge_block,
        vertex_pad=vertex_pad,
    )
    return pg, rmap


def drop_edges(pg: PartitionedGraph) -> PartitionedGraph:
    """Vertex-only view of a partition: the O(|V|/n) state array A survives,
    the O(|E|) edge groups are replaced by zero-length placeholders.

    Used after spilling the edge streams to disk (``spill_partition``): the
    static geometry (``E_cap``/``edge_block``/``n_blocks``) still describes
    the on-disk layout, but nothing edge-sized is resident. Such a partition
    only runs under ``mode="streamed"``.
    """
    n = pg.n_shards
    return dataclasses.replace(
        pg,
        src_pos=jnp.full((n, n, 0), -1, jnp.int32),
        dst_pos=jnp.zeros((n, n, 0), jnp.int32),
        eweight=jnp.zeros((n, n, 0), jnp.float32),
        blk_lo=jnp.zeros((n, n, 0), jnp.int32),
        blk_hi=jnp.zeros((n, n, 0), jnp.int32),
    )


def spill_partition(pg: PartitionedGraph, directory: str,
                    compress: bool = False, compress_payload: bool = False):
    """Write the edge groups of ``pg`` to an on-disk ``EdgeStreamStore`` and
    return ``(vertex_only_pg, store)`` — the paper's partition-time spill:
    edges are written once, sequentially, in the per-destination group
    layout, and streamed back every superstep. ``compress=True`` varint-delta
    encodes the position channels; ``compress_payload=True`` payload-encodes
    the weight channel (both streams/codec.py, both lossless)."""
    from repro.streams.store import EdgeStreamStore  # deferred: streams -> partition

    with spans.span("partition.spill"):
        store = EdgeStreamStore.from_partition(
            pg, directory, compress=compress,
            compress_payload=compress_payload,
        )
        return drop_edges(pg), store


def partition_graph_streamed(
    g: Graph,
    n_shards: int,
    spill_dir: str,
    edge_block: int = 512,
    vertex_pad: int = 8,
    recode: RecodeMap | None = None,
    compress: bool = False,
    compress_payload: bool = False,
):
    """``partition_graph`` for the out-of-core path: partitions, spills the
    edge streams to ``spill_dir``, and returns ``(pg, rmap, store)`` where
    ``pg`` holds only the O(|V|/n) vertex arrays."""
    pg_full, rmap = partition_graph(
        g, n_shards, edge_block=edge_block, vertex_pad=vertex_pad,
        recode=recode,
    )
    pg, store = spill_partition(pg_full, spill_dir, compress=compress,
                                compress_payload=compress_payload)
    return pg, rmap, store


def partition_for_plan(g: Graph, plan, spill_dir: str,
                       recode: RecodeMap | None = None):
    """Materialize the physical layout an ``core.plan.ExecutionPlan`` chose:
    hash-partition with the plan's geometry knobs, and — when the plan picked
    the out-of-core mode — spill the edge groups to ``spill_dir`` (compressed
    iff the plan says so). Returns ``(pg, rmap, store)`` with ``store`` None
    for the in-memory modes; the one partitioning entry point
    ``core.job.GraphDJob`` builds every mode through."""
    if plan.mode == "streamed":
        return partition_graph_streamed(
            g, plan.n_shards, spill_dir, edge_block=plan.edge_block,
            vertex_pad=plan.vertex_pad, recode=recode,
            compress=plan.compress,
            compress_payload=bool(plan.compress_payload),
        )
    pg, rmap = partition_graph(
        g, plan.n_shards, edge_block=plan.edge_block,
        vertex_pad=plan.vertex_pad, recode=recode,
    )
    return pg, rmap, None


def abstract_partitioned_graph(
    n_shards: int,
    n_vertices: int,
    n_edges: int,
    edge_block: int = 4096,
    vertex_pad: int = 128,
    skew: float = 1.5,
) -> PartitionedGraph:
    """ShapeDtypeStruct-only PartitionedGraph for dry-runs (no allocation).

    ``skew`` models the per-group padding overhead (max/mean group size).
    """
    n = n_shards
    P = max(_round_up((n_vertices + n - 1) // n, vertex_pad), vertex_pad)
    mean_group = n_edges / (n * n)
    E_cap = max(_round_up(int(mean_group * skew), edge_block), edge_block)
    n_blocks = E_cap // edge_block
    s = jax.ShapeDtypeStruct
    return PartitionedGraph(
        n_shards=n, n_vertices=n_vertices, n_edges=n_edges, P=P,
        E_cap=E_cap, edge_block=edge_block, n_blocks=n_blocks,
        degree=s((n, P), jnp.int32),
        vmask=s((n, P), jnp.bool_),
        old_ids=s((n, P), jnp.int64),
        gids=s((n, P), jnp.int64),
        src_pos=s((n, n, E_cap), jnp.int32),
        dst_pos=s((n, n, E_cap), jnp.int32),
        eweight=s((n, n, E_cap), jnp.float32),
        blk_lo=s((n, n, n_blocks), jnp.int32),
        blk_hi=s((n, n, n_blocks), jnp.int32),
    )
