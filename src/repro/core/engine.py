"""The DSS superstep engine (paper §3–§5), one SPMD body, two drivers.

Execution modes (benchmarked against each other, mirroring Tables 2–8):

* ``recoded``  — paper §5 (IO-Recoded): sender-side in-memory scatter-combine
  into ``A_s`` (one destination at a time), ring exchange, receiver-side
  in-memory digest into ``A_r``. No sorting anywhere. The ring is a classic
  reduce-scatter with a static shift-by-one ``ppermute``: at round r shard i
  contributes its messages for destination ``(i + n-1-r) mod n`` into the
  travelling accumulator — compute for round r+1 overlaps the collective
  permute of round r, which is exactly the paper's U_c ∥ U_s overlap (C3).

* ``basic``    — paper §3.3 (IO-Basic): raw ``(dst, payload)`` messages are
  exchanged uncombined (``all_to_all``), the receiver sorts by destination and
  segment-combines — the IMS merge-sort. Network bytes ∝ |E| (vs ∝ |V| for
  recoded), the measured gap reproduces the IO-Basic vs IO-Recoded rows.

* ``basic_sc`` — IO-Basic *with* combiner: the sender sort-combines each
  OMS (the external merge-sort of §3.3.1) before the ring exchange; transfer
  volume matches ``recoded`` but pays the sort.

* ``streamed`` — the paper's actual out-of-core deployment (§3, Theorem 1):
  per-shard resident state is ONLY the O(|V|/n) vertex arrays (values,
  active bitmap, degree, masks) plus constant-size combine buffers; the edge
  groups live on local disk in a ``streams.EdgeStreamStore`` and arrive
  group-by-group through a double-buffered ``streams.StreamReader`` whose
  background thread stages the next block chunk while the device digests the
  current one (U_c ∥ U_s at the host/device boundary). The §3.2 ``skip()``
  test runs against the store's block manifest BEFORE any I/O, so inactive
  blocks are never read off disk. Resident bytes are independent of |E| —
  see ``GraphDEngine.memory_model()`` and benchmarks/bench_memory.py.
  Typically paired with ``graph.partition_graph_streamed`` (spill at
  partition time, vertex-only PartitionedGraph). Host-driven: no mesh /
  Pallas backend; pick it when the graph does not fit device memory.
  With ``pipeline=True`` the §4 pipeline comes on, full duplex: a
  background sender (``streams/channel.py``) serializes each combined
  outgoing group (positions varint-delta compressed with ``compress=True``,
  payloads through the lossless/bf16 payload codec with
  ``compress_payload=``) and appends it to the destination's inbox run
  files, while a background receiver digests the runs already landed — both
  directions hidden under the fold of the next group, a bounded in-flight
  budget, and per-source owner views of the edge store (each emulated
  machine maps only its own rows). ``full_duplex=False`` falls back to the
  sender-only pipeline.

Sparse adaptation (C2, ``skip()``): per destination group the engine skips
edge blocks whose source range contains no active vertex, using the
``blk_lo/blk_hi`` metadata and a prefix sum over the active bitmap. The
sparse variant gathers only ``sparse_cap`` blocks (a compiled-in bound); the
host driver auto-dispatches dense vs sparse from the measured frontier
density, and the worst case equals one full dense scan — guarantee (3) of
§3.2.

The SPMD body runs identically under ``jax.vmap(axis_name=...)`` (n shards
emulated on one device — used by tests/benchmarks) and ``shard_map`` over a
device mesh (the production path; the dry-run lowers it on 256/512 chips).
"""

from __future__ import annotations

import dataclasses
import os
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import spans
from repro.core.api import Combiner, ShardContext, VertexProgram
from repro.core.config import MODES, ConfigError, EngineConfig
from repro.graph.partition import PartitionedGraph


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _shard_ctx(pg: PartitionedGraph, axis: str) -> ShardContext:
    return ShardContext(
        shard=lax.axis_index(axis),
        n_shards=pg.n_shards,
        n_vertices=pg.n_vertices,
        P=pg.P,
        degree=pg.degree,
        vmask=pg.vmask,
        old_ids=pg.old_ids,
        gids=pg.gids,
    )


def _active_prefix(active: jax.Array) -> jax.Array:
    """(P+1,) inclusive-prefix of the active bitmap; block [lo,hi] has an
    active source iff prefix[hi+1] - prefix[lo] > 0 (skip() test, §3.2)."""
    return jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(active.astype(jnp.int32))]
    )


def _block_active(pg: PartitionedGraph, prefix: jax.Array, lo, hi) -> jax.Array:
    nonempty = hi >= 0
    cnt = prefix[jnp.clip(hi + 1, 0, pg.P)] - prefix[jnp.clip(lo, 0, pg.P)]
    return nonempty & (cnt > 0)


# --------------------------------------------------------------------------
# local combine (the U_c hot loop): gen messages for one destination group and
# combine them into A_s. Dense, sparse (skip) and sort (merge-sort) variants.
# --------------------------------------------------------------------------

def _source_rows(values, degree, active):
    """``rows(i)``: source shard i's (values, degree, active) rows, sliced
    at the first call for i. A row slice is two op-by-op dispatches, which
    a streamed pass would otherwise pay for every chunk of the source."""
    cache: dict[int, tuple] = {}

    def rows(i):
        if i not in cache:
            cache[i] = (values[i], degree[i], active[i])
        return cache[i]

    return rows


def _gen_messages(program, values, degree, sp, dp, w, active, step):
    """Gather source state, evaluate program.message, mask invalid/inactive."""
    spc = jnp.clip(sp, 0)
    aval = values[spc]
    adeg = degree[spc]
    aact = (sp >= 0) & active[spc]
    msg = program.message(aval, adeg, w, step).astype(program.msg_dtype)
    e0 = jnp.asarray(
        program.combiner.e0 if program.combiner is not None else 0,
        dtype=program.msg_dtype,
    )
    return jnp.where(aact, msg, e0), dp, aact


def _combine_scatter(program, P_dest, msg, dp, aact):
    """IO-Recoded: direct in-memory scatter-combine (A_s, paper §5)."""
    comb = program.combiner
    A_s = comb.identity((P_dest,), program.msg_dtype)
    A_s = comb.scatter(A_s, dp, msg)
    cnt = jnp.zeros((P_dest,), jnp.int32).at[dp].add(aact.astype(jnp.int32))
    return A_s, cnt


def _combine_sort(program, P_dest, msg, dp, aact):
    """IO-Basic w/ combiner: sort by destination then combine (merge-sort)."""
    comb = program.combiner
    key = jnp.where(aact, dp, P_dest)  # invalid entries sort to the tail
    skey, smsg, sact = lax.sort((key, msg, aact.astype(jnp.int32)), num_keys=1)
    A_s = comb.identity((P_dest,), program.msg_dtype)
    A_s = comb.scatter(A_s, jnp.where(skey < P_dest, skey, 0),
                       jnp.where(skey < P_dest, smsg,
                                 jnp.asarray(comb.e0, program.msg_dtype)))
    cnt = jnp.zeros((P_dest,), jnp.int32).at[skey].add(sact, mode="drop")
    return A_s, cnt


def _contrib_dense(program, pg, values, active, step, dest, combine):
    sp = lax.dynamic_index_in_dim(pg.src_pos, dest, 0, keepdims=False)
    dp = lax.dynamic_index_in_dim(pg.dst_pos, dest, 0, keepdims=False)
    w = lax.dynamic_index_in_dim(pg.eweight, dest, 0, keepdims=False)
    msg, dp, aact = _gen_messages(program, values, pg.degree, sp, dp, w, active, step)
    return combine(program, pg.P, msg, dp, aact)


def _contrib_pallas(program, pg, kl, values, active, prefix, step, dest):
    """Kernel-backed contribution: the fused Pallas edge_combine with the
    always-on skip-compacted block list (degenerates to the dense scan when
    the frontier is dense — the paper's adaptivity with zero dispatch)."""
    from repro.kernels import ops as kops

    pick = lambda a: lax.dynamic_index_in_dim(a, dest, 0, keepdims=False)
    sp, dp, w = pick(kl.sp), pick(kl.dp), pick(kl.w)
    swin, dwin = pick(kl.blk_swin), pick(kl.blk_dwin)
    lo, hi = pick(kl.blk_lo), pick(kl.blk_hi)
    keep = kops.skip_keep_mask(lo, hi, dwin, prefix)
    ids, nk = kops.compact_blocks(keep)
    # Sanitize ±inf (e.g. unreached SSSP distances) before the one-hot MXU
    # gather: 0 * inf = NaN would poison whole window rows. Active vertices
    # are always finite and inactive gathers are masked to e0 afterwards, so
    # a large-finite sentinel is exact.
    vals_f = jnp.nan_to_num(
        values.astype(jnp.float32), nan=0.0, posinf=1e30, neginf=-1e30
    )
    state3 = jnp.stack(
        [
            vals_f,
            pg.degree.astype(jnp.float32),
            active.astype(jnp.float32),
        ],
        axis=0,
    )
    A_s, cnt = kops.edge_combine(
        state3, sp, dp, w, ids, nk, swin, dwin,
        SRC_WIN=kl.SRC_WIN, DST_WIN=kl.DST_WIN,
        msg_kind=program.msg_kind, combiner=program.combiner.name,
    )
    return A_s, cnt.astype(jnp.int32)


def _contrib_sparse(program, pg, values, active, prefix, step, dest, cap, combine):
    """skip(): gather only active edge blocks (≤ cap of them) for this group."""
    B, nb = pg.edge_block, pg.n_blocks
    lo = lax.dynamic_index_in_dim(pg.blk_lo, dest, 0, keepdims=False)
    hi = lax.dynamic_index_in_dim(pg.blk_hi, dest, 0, keepdims=False)
    act_blk = _block_active(pg, prefix, lo, hi)
    (idx,) = jnp.nonzero(act_blk, size=cap, fill_value=nb)
    take = lambda a, fill: jnp.take(
        lax.dynamic_index_in_dim(a, dest, 0, keepdims=False).reshape(nb, B),
        idx, axis=0, mode="fill", fill_value=fill,
    ).reshape(cap * B)
    sp = take(pg.src_pos, -1)
    dp = take(pg.dst_pos, 0)
    w = take(pg.eweight, 0.0)
    msg, dp, aact = _gen_messages(program, values, pg.degree, sp, dp, w, active, step)
    return combine(program, pg.P, msg, dp, aact)


# --------------------------------------------------------------------------
# exchanges
# --------------------------------------------------------------------------

def _ring_exchange(program, pg, values, active, step, axis, contrib,
                   digest=None):
    """Ring reduce-scatter of per-destination combined buffers (§4.2/§5).

    Static shift-by-one permutation; n rounds; the accumulator arriving at
    shard i in round r is destined for ``(i + n-1-r) mod n``, so shard i folds
    in its own A_s for that destination and forwards. Round r+1's local
    combine is independent of round r's permute -> XLA overlaps them (C3).

    ``digest(acc_A, acc_cnt, A_s, cnt)`` merges a contribution into the
    travelling accumulator (default: jnp combine; the Pallas backend fuses it
    in kernels/digest.py).
    """
    n = pg.n_shards
    i = lax.axis_index(axis)
    comb: Combiner = program.combiner
    if digest is None:
        digest = lambda A, c, A2, c2: (comb.combine(A, A2), c + c2)
    perm = [(j, (j + 1) % n) for j in range(n)]

    acc = contrib((i + n - 1) % n)
    if n == 1:
        return acc

    def _round(r, acc):
        acc = jax.tree.map(lambda x: lax.ppermute(x, axis, perm), acc)
        dest = (i + (n - 1 - r)) % n
        A_s, cnt = contrib(dest)
        return digest(acc[0], acc[1], A_s, cnt)

    return lax.fori_loop(1, n, _round, acc)


def _basic_exchange(program, pg, values, active, step, axis):
    """IO-Basic: raw (dst, payload) pairs all-to-all, receiver-side merge-sort
    into the IMS, then one combining pass (§3.3.2)."""
    comb: Combiner = program.combiner
    Pn = pg.P
    msg, dp, aact = _gen_messages(
        program, values, pg.degree, pg.src_pos, pg.dst_pos, pg.eweight, active, step
    )  # (n, E_cap) each
    dp_send = jnp.where(aact, dp, Pn).astype(jnp.int32)
    recv_dp = lax.all_to_all(dp_send, axis, split_axis=0, concat_axis=0)
    recv_msg = lax.all_to_all(msg, axis, split_axis=0, concat_axis=0)
    flat_dp = recv_dp.reshape(-1)
    flat_msg = recv_msg.reshape(-1)
    # IMS construction: sort received messages by destination id
    sdp, smsg = lax.sort((flat_dp, flat_msg), num_keys=1)
    valid = sdp < Pn
    cnt = jnp.zeros((Pn,), jnp.int32).at[sdp].add(valid.astype(jnp.int32), mode="drop")
    if comb is None:  # non-combiner program: apply_list consumes the runs
        return None, cnt, sdp, smsg
    A_r = comb.identity((Pn,), program.msg_dtype)
    A_r = comb.scatter(A_r, jnp.where(valid, sdp, 0),
                       jnp.where(valid, smsg, jnp.asarray(comb.e0, program.msg_dtype)))
    return A_r, cnt, sdp, smsg


# --------------------------------------------------------------------------
# the SPMD superstep
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass
class StepStats:
    n_active: jax.Array  # global active vertices after apply
    n_msgs: jax.Array  # global messages digested this superstep
    agg: jax.Array  # program aggregator (psum)
    density: jax.Array  # fraction of edge blocks active for NEXT superstep
    max_group_blocks: jax.Array  # max active blocks in any (shard,dest) group
    # (hard bound for the sparse path: sparse is safe iff this ≤ sparse_cap)


def _compact_exchange(program, pg, values, active, step, axis):
    """§Perf (beyond paper): one-hop all_to_all of *compact* combined buffers
    — bf16 message values + 1-byte has-msg flags (vs f32+int32 on the ring:
    8 B -> 3 B per slot, one rounding per message instead of per hop).
    Receiver digests in f32."""
    comb = program.combiner
    dests = jnp.arange(pg.n_shards, dtype=jnp.int32)
    A_s_all, cnt_all = jax.vmap(
        lambda d: _contrib_dense(program, pg, values, active, step, d,
                                 _combine_scatter)
    )(dests)
    wire_A = A_s_all.astype(jnp.bfloat16)
    wire_h = (cnt_all > 0).astype(jnp.int8)
    recv_A = lax.all_to_all(wire_A, axis, split_axis=0, concat_axis=0)
    recv_h = lax.all_to_all(wire_h, axis, split_axis=0, concat_axis=0)
    A_r = comb.reduce(recv_A.astype(program.msg_dtype), 0)
    cnt = jnp.sum(recv_h.astype(jnp.int32), 0)
    return A_r, cnt


def superstep_spmd(
    program: VertexProgram,
    pg: PartitionedGraph,
    values: jax.Array,
    active: jax.Array,
    step: jax.Array,
    *,
    axis: str,
    mode: str = "recoded",
    sparse_cap: int | None = None,
    kl=None,  # graph.kblocks.KernelLayout per-shard view => Pallas backend
):
    """One full superstep: scatter -> exchange -> digest -> apply -> vote."""
    ctx = _shard_ctx(pg, axis)

    if mode == "recoded_compact":
        A_r, cnt = _compact_exchange(program, pg, values, active, step, axis)
    elif mode == "basic" and program.combiner is None:
        # general Pregel path: destination-sorted message LISTS (§3.3.2)
        _, cnt, sdp, smsg = _basic_exchange(
            program, pg, values, active, step, axis
        )
        has_msg = (cnt > 0) & pg.vmask
        new_values, new_active = program.apply_list(
            values, pg.degree, sdp, smsg, has_msg, active, step, ctx
        )
        return _finish_superstep(
            program, pg, values, new_values, new_active, cnt, has_msg, axis
        )
    elif mode == "basic":
        A_r, cnt, _, _ = _basic_exchange(program, pg, values, active, step, axis)
    elif kl is not None:
        from repro.kernels import ops as kops

        prefix = _active_prefix(active)
        contrib = lambda dest: _contrib_pallas(
            program, pg, kl, values, active, prefix, step, dest
        )
        digest = lambda A, c, A2, c2: kops.digest(
            A, c, A2, c2, combiner=program.combiner.name,
            WIN=kl.DST_WIN,
        )
        A_r, cnt = _ring_exchange(
            program, pg, values, active, step, axis, contrib, digest=digest
        )
        A_r = A_r.astype(program.msg_dtype)
    else:
        combine = _combine_sort if mode == "basic_sc" else _combine_scatter
        if sparse_cap is not None:
            prefix = _active_prefix(active)
            contrib = lambda dest: _contrib_sparse(
                program, pg, values, active, prefix, step, dest, sparse_cap, combine
            )
        else:
            contrib = lambda dest: _contrib_dense(
                program, pg, values, active, step, dest, combine
            )
        A_r, cnt = _ring_exchange(program, pg, values, active, step, axis, contrib)

    has_msg = (cnt > 0) & pg.vmask
    new_values, new_active = program.apply(
        values, pg.degree, A_r, has_msg, active, step, ctx
    )
    return _finish_superstep(
        program, pg, values, new_values, new_active, cnt, has_msg, axis
    )


def _finish_superstep(program, pg, values, new_values, new_active, cnt,
                      has_msg, axis):
    """Shared superstep tail: halt voting, aggregator, frontier stats."""
    new_active = new_active & pg.vmask
    n_active = lax.psum(jnp.sum(new_active.astype(jnp.int32)), axis)
    n_msgs = lax.psum(jnp.sum(cnt), axis)
    agg = program.aggregate(values, new_values, has_msg)
    agg = (
        lax.psum(jnp.sum(agg.astype(jnp.float32)), axis)
        if agg is not None
        else jnp.float32(0)
    )
    # frontier density for the next superstep (drives dense/sparse dispatch)
    prefix2 = _active_prefix(new_active)
    act_blk = _block_active(pg, prefix2, pg.blk_lo, pg.blk_hi)  # (n, n_blocks)
    nonempty = pg.blk_hi >= 0
    num = lax.psum(jnp.sum(act_blk.astype(jnp.int32)), axis)
    den = lax.psum(jnp.sum(nonempty.astype(jnp.int32)), axis)
    density = num.astype(jnp.float32) / jnp.maximum(den, 1).astype(jnp.float32)
    max_grp = lax.pmax(jnp.max(jnp.sum(act_blk.astype(jnp.int32), axis=-1)), axis)

    return new_values, new_active, StepStats(n_active, n_msgs, agg, density, max_grp)


def superstep_logged_spmd(
    program: VertexProgram,
    pg: PartitionedGraph,
    values: jax.Array,
    active: jax.Array,
    step: jax.Array,
    *,
    axis: str,
):
    """Recoded superstep that also *materializes* every per-destination
    outgoing buffer A_s (so the driver can persist them — "keep all OMSs on
    local disk until a new checkpoint is written", §3.4). Exchange is an
    all_to_all of the combined buffers instead of the ring."""
    ctx = _shard_ctx(pg, axis)
    comb = program.combiner
    dests = jnp.arange(pg.n_shards, dtype=jnp.int32)
    A_s_all, cnt_all = jax.vmap(
        lambda d: _contrib_dense(program, pg, values, active, step, d,
                                 _combine_scatter)
    )(dests)  # (n_dest, P) each
    recv_A = lax.all_to_all(A_s_all, axis, split_axis=0, concat_axis=0)
    recv_c = lax.all_to_all(cnt_all, axis, split_axis=0, concat_axis=0)
    A_r = comb.reduce(recv_A, 0)
    cnt = jnp.sum(recv_c, 0)

    has_msg = (cnt > 0) & pg.vmask
    new_values, new_active = program.apply(
        values, pg.degree, A_r, has_msg, active, step, ctx
    )
    new_active = new_active & pg.vmask
    n_active = lax.psum(jnp.sum(new_active.astype(jnp.int32)), axis)
    n_msgs = lax.psum(jnp.sum(cnt), axis)
    agg = program.aggregate(values, new_values, has_msg)
    agg = (
        lax.psum(jnp.sum(agg.astype(jnp.float32)), axis)
        if agg is not None
        else jnp.float32(0)
    )
    prefix2 = _active_prefix(new_active)
    act_blk = _block_active(pg, prefix2, pg.blk_lo, pg.blk_hi)
    nonempty = pg.blk_hi >= 0
    num = lax.psum(jnp.sum(act_blk.astype(jnp.int32)), axis)
    den = lax.psum(jnp.sum(nonempty.astype(jnp.int32)), axis)
    density = num.astype(jnp.float32) / jnp.maximum(den, 1).astype(jnp.float32)
    max_grp = lax.pmax(jnp.max(jnp.sum(act_blk.astype(jnp.int32), axis=-1)), axis)
    stats = StepStats(n_active, n_msgs, agg, density, max_grp)
    return new_values, new_active, stats, A_s_all, cnt_all


def init_spmd(program: VertexProgram, pg: PartitionedGraph, *, axis: str):
    ctx = _shard_ctx(pg, axis)
    values, active = program.init(ctx)
    return values.astype(program.value_dtype), active & pg.vmask


# --------------------------------------------------------------------------
# streamed-mode kernels, shared by the in-process engine and worker processes
# --------------------------------------------------------------------------

class StreamKernels:
    """The jitted per-shard streamed-mode kernels, built from the program
    plus the partition SCALARS only (n_shards, n_vertices, P) — every
    per-shard array (values, degree, vmask, ...) is a call argument, never
    closed over. Both :class:`GraphDEngine` and the one-process-per-shard
    worker (``repro.launch.procs``) build their kernels here, so the two
    execution paths run literally the same compiled math and cannot drift.

    Combiner programs get ``fold``/``fold_batch``/``apply``/``digest``;
    combiner-less programs get ``msgs``/``apply_list``/``finish``. ``init``
    is always present (the per-row replica of :func:`init_spmd`).
    """

    def __init__(self, program: VertexProgram, n_shards: int,
                 n_vertices: int, P: int):
        self.program = program
        self.n_shards = int(n_shards)
        self.n_vertices = int(n_vertices)
        self.P = int(P)
        self.combined = program.combiner is not None
        self.init = jax.jit(self._make_init())
        if self.combined:
            comb = program.combiner
            self.fold = jax.jit(self._make_fold())
            self.fold_batch = jax.jit(self._make_fold_batch())
            self.apply = jax.jit(self._make_apply())
            # receiver digest of one densified inbox group (pipelined
            # path): identical per-position sequence to the unpipelined
            # grouped fold, so pipelining cannot change results
            self.digest = jax.jit(
                lambda A, c, A2, c2: (comb.combine(A, A2), c + c2)
            )
        else:
            self.msgs = jax.jit(self._make_msgs())
            self.apply_list = jax.jit(self._make_apply_list())
            self.finish = jax.jit(self._make_finish())

    def _ctx(self, shard, degree, vmask, old_ids, gids) -> ShardContext:
        return ShardContext(
            shard=shard, n_shards=self.n_shards, n_vertices=self.n_vertices,
            P=self.P, degree=degree, vmask=vmask, old_ids=old_ids, gids=gids,
        )

    def _make_init(self):
        """Jitted per-shard init: one row of :func:`init_spmd` (the worker
        process holds only its own row, so ``shard`` is an argument instead
        of ``lax.axis_index``)."""
        program = self.program

        def init_row(shard, degree, vmask, old_ids, gids):
            ctx = self._ctx(shard, degree, vmask, old_ids, gids)
            values, active = program.init(ctx)
            return values.astype(program.value_dtype), active & vmask

        return init_row

    def _make_fold(self):
        """Jitted chunk combine: fold one staged edge chunk into the
        destination accumulator (the in-memory A_s combine of §5, applied to
        an O(1)-sized staged slice instead of the whole resident group)."""
        program = self.program
        comb = program.combiner

        def fold(A, cnt, values, degree, active, sp, dp, w, step):
            msg, dp2, aact = _gen_messages(
                program, values, degree, sp, dp, w, active, step
            )
            A = comb.scatter(A, dp2, msg)
            cnt = cnt.at[dp2].add(aact.astype(jnp.int32))
            return A, cnt

        return fold

    def _make_fold_batch(self):
        """Jitted multi-group fold: ``group_batch`` SMALL groups (each one
        staged chunk) scatter-combined in one vmapped dispatch — per lane
        the exact op sequence of :meth:`_make_fold` on a fresh identity
        accumulator, so batching is pure dispatch amortization and results
        stay bit-identical (the lanes never mix)."""
        program, P_dest = self.program, self.P
        comb = program.combiner

        def fold_batch(values, degree, active, src, sp, dp, w, step):
            # values/degree/active: the full (n, P) stacks; src: (G,) source
            # shard per lane; sp/dp/w: (G, chunk_slots). Padding lanes carry
            # sp = -1 everywhere and fold to the identity.
            def one(src_g, sp_g, dp_g, w_g):
                msg, dp2, aact = _gen_messages(
                    program, values[src_g], degree[src_g], sp_g, dp_g, w_g,
                    active[src_g], step,
                )
                A = comb.scatter(
                    comb.identity((P_dest,), program.msg_dtype), dp2, msg
                )
                cnt = jnp.zeros((P_dest,), jnp.int32).at[dp2].add(
                    aact.astype(jnp.int32)
                )
                return A, cnt

            return jax.vmap(one)(src, sp, dp, w)

        return fold_batch

    def _make_apply(self):
        """Jitted per-shard digest + apply + vote (shard index is traced, so
        one compilation serves all shards)."""
        program = self.program

        def apply_shard(values, degree, vmask, old_ids, gids, A_r, cnt,
                        active, step, shard):
            ctx = self._ctx(shard, degree, vmask, old_ids, gids)
            has_msg = (cnt > 0) & vmask
            new_values, new_active = program.apply(
                values, degree, A_r, has_msg, active, step, ctx
            )
            new_active = new_active & vmask
            agg = program.aggregate(values, new_values, has_msg)
            agg = (
                jnp.sum(agg.astype(jnp.float32))
                if agg is not None
                else jnp.float32(0)
            )
            return (
                new_values.astype(program.value_dtype),
                new_active,
                jnp.sum(new_active.astype(jnp.int32)),
                jnp.sum(cnt),
                agg,
            )

        return apply_shard

    def _make_msgs(self):
        """Jitted raw-message generation for one staged edge chunk (the
        combiner-less scatter half): returns ``(payload, dst_pos, valid)``
        for the host to sort by destination and spill into an OMS run."""
        program = self.program

        def gen(values, degree, active, sp, dp, w, step):
            msg, dp2, aact = _gen_messages(
                program, values, degree, sp, dp, w, active, step
            )
            return msg, dp2, aact

        return gen

    def _make_apply_list(self):
        """Jitted apply over ONE destination-aligned slice of the merged
        message stream. ``cnt`` is the full per-position message count, so
        ``has_msg`` matches mode="basic" exactly; only the destinations whose
        runs live in this slice are kept by the caller."""
        program = self.program

        def apply_slice(values, degree, vmask, old_ids, gids, sdp, smsg,
                        cnt, active, step, shard):
            ctx = self._ctx(shard, degree, vmask, old_ids, gids)
            has_msg = (cnt > 0) & vmask
            new_values, new_active = program.apply_list(
                values, degree, sdp, smsg, has_msg, active, step, ctx
            )
            return new_values.astype(program.value_dtype), new_active & vmask

        return apply_slice

    def _make_finish(self):
        """Jitted per-shard superstep tail for the combiner-less path
        (active count, message count, aggregator)."""
        program = self.program

        def fin(values, new_values, new_active, cnt, vmask):
            has_msg = (cnt > 0) & vmask
            agg = program.aggregate(values, new_values, has_msg)
            agg = (
                jnp.sum(agg.astype(jnp.float32))
                if agg is not None
                else jnp.float32(0)
            )
            return (
                jnp.sum(new_active.astype(jnp.int32)),
                jnp.sum(cnt),
                agg,
            )

        return fin


# --------------------------------------------------------------------------
# drivers
# --------------------------------------------------------------------------

@dataclass
class SuperstepRecord:
    step: int
    n_active: int
    n_msgs: int
    agg: float
    density: float
    mode: str
    seconds: float
    # step a checkpoint auto-restore resumed from (first record only)
    restored_from: int | None = None
    # residency observability (streamed mode; defaults elsewhere): edge
    # blocks actually read off disk this superstep, blocks served from the
    # hot cache, cache evictions, and blocks the §3.2 skip() test kept off
    # the schedule entirely (selective scheduling)
    blocks_read: int = 0
    cache_hits: int = 0
    cache_evictions: int = 0
    blocks_skipped: int = 0
    # streamed mode: staging-buffer refills that waited for the device to
    # finish reading the buffer (StreamStats.recycle_waits), and the
    # staging thread's seconds spent so
    recycle_waits: int = 0
    recycle_wait_seconds: float = 0.0
    # streamed mode: this superstep's seconds by span name (core/spans.py).
    # The compute thread's phases are disjoint parts of ``seconds``;
    # "stream.stage" is the staging thread's reads, beside them
    phase_seconds: dict[str, float] = field(default_factory=dict)


class GraphDEngine:
    """Host driver: jits the SPMD body under vmap (emulation) or shard_map
    (device mesh), adapts dense/sparse per superstep, runs the job loop."""

    AXIS = "machines"

    MODES = MODES  # single source of truth: repro.core.config.MODES

    def __init__(
        self,
        pg: PartitionedGraph,
        program: VertexProgram,
        config: EngineConfig | None = None,
        *,
        mesh: Mesh | None = None,
        message_log=None,  # core.checkpoint.MessageLog for fast recovery
        stream_store=None,  # streams.EdgeStreamStore, required for "streamed"
        **flat,  # rejected: the PR-4 flat-kwarg shim's window is over
    ):
        if flat:
            raise ConfigError(
                "GraphDEngine no longer accepts flat keyword arguments "
                f"({', '.join(sorted(flat))}); build an EngineConfig — e.g. "
                "config=EngineConfig(mode='streamed', "
                "channel=ChannelConfig(pipeline=True))"
            )
        if config is None:
            config = EngineConfig()
        if not isinstance(config, EngineConfig):
            raise ConfigError(
                "config must be an EngineConfig (the positional mode string "
                f"was removed with the flat-kwarg shim), got "
                f"{type(config).__name__}"
            )
        cfg = config.finalize()
        self.config = cfg
        mode = cfg.mode
        backend = cfg.backend
        pipeline = cfg.channel.pipeline
        compress = cfg.channel.compress
        # Config-value and cross-config validation happened in finalize();
        # what follows needs the program, the partition, or a live object —
        # facts no config can know.
        if mode != "streamed" and pg.E_cap > 0 and pg.src_pos.shape[-1] == 0:
            raise ValueError(
                "this partition is vertex-only (its edge groups were spilled "
                "to disk by drop_edges/partition_graph_streamed); it can only "
                "run with mode='streamed' and the matching stream_store"
            )
        if mode in ("recoded", "recoded_compact", "basic_sc") and (
            program.combiner is None
        ):
            raise ValueError(f"mode={mode} requires a message combiner (paper §5)")
        if mode == "recoded_compact" and program.msg_dtype not in (
            jnp.float32, jnp.bfloat16
        ):
            # bf16 wire rounds integers > 256 — min-label algorithms would
            # silently merge distinct labels. Float-message programs only.
            raise ValueError("recoded_compact needs float messages")
        if (cfg.channel.payload_scheme == "bf16"
                and program.msg_dtype != jnp.float32):
            # the same guard as recoded_compact, applied to the wire codec
            raise ValueError(
                "compress_payload='bf16' rounds float32 messages on the "
                "wire; integer/min-label programs need the lossless scheme"
            )
        if cfg.channel.payload_scheme == "bf16" and message_log is not None:
            # logged OMSs are recovery state: recover_shard_streamed
            # regenerates the failed shard's own groups EXACTLY and digests
            # them against the logged runs — rounding the log would make
            # recovered state diverge from the live run, breaking the
            # bit-match invariant every fault drill asserts
            raise ValueError(
                "compress_payload='bf16' is a lossy wire codec and cannot "
                "back a message log (recovery must replay bit-identically);"
                " use the lossless scheme with message logging"
            )
        if cfg.channel.payload_scheme == "auto" and message_log is not None:
            # a run-file log fixes its wire format once at configure();
            # the auto-pick resolves it only after the first superstep's
            # sample, and a recovery replay could not re-derive the same
            # mid-run switch point
            raise ValueError(
                "compress_payload='auto' resolves the codec from a "
                "first-superstep sample; a message log needs a fixed wire "
                "format — pass 'lossless' (or False) explicitly"
            )
        if backend == "pallas" and getattr(program, "msg_kind", None) is None:
            raise ValueError(
                "backend='pallas' needs mode='recoded' and a program.msg_kind"
            )
        if mode == "streamed":
            if stream_store is None:
                raise ValueError(
                    "mode='streamed' needs stream_store= (an "
                    "streams.EdgeStreamStore; see graph.partition_graph_streamed)"
                )
            if mesh is not None:
                raise ValueError(
                    "mode='streamed' is host-driven: backend='jnp', mesh=None"
                )
            if message_log is not None and not hasattr(message_log, "save_group"):
                raise ValueError(
                    "mode='streamed' logs messages incrementally to run files;"
                    " pass a core.checkpoint.RunFileMessageLog"
                )
            geom = stream_store.geom
            if (geom.n_shards, geom.P, geom.edge_block) != (
                pg.n_shards, pg.P, pg.edge_block
            ):
                raise ValueError(
                    "stream store geometry does not match the partition: "
                    f"store (n={geom.n_shards}, P={geom.P}, B={geom.edge_block})"
                    f" vs pg (n={pg.n_shards}, P={pg.P}, B={pg.edge_block})"
                )
        if message_log is not None and hasattr(message_log, "configure"):
            # run-file logs densify sparse runs back with the combiner
            # identity; they must learn it (and the geometry) from the
            # program, whatever the mode
            message_log.configure(
                n_shards=pg.n_shards, P=pg.P,
                msg_dtype=np.dtype(program.msg_dtype),
                e0=program.combiner.e0 if program.combiner is not None else 0,
                combined=program.combiner is not None,
                compress=compress,
                compress_payload=cfg.channel.payload_scheme,
            )
        self.pg = pg
        self.program = program
        self.mode = mode
        self.mesh = mesh
        self.backend = backend
        self.adapt_threshold = cfg.adapt_threshold
        self.sparse_cap = max(1, int(pg.n_blocks * cfg.sparse_cap_frac))
        self.message_log = message_log
        self.stream_store = stream_store
        self.pipeline = bool(pipeline)
        self.compress = bool(compress)
        scheme = cfg.channel.payload_scheme  # None | scheme | "auto"
        # "auto": spill the first superstep raw while a PayloadAutoPicker
        # trial-encodes a sample of its runs; the end-of-superstep decision
        # (see _run_streamed) fixes compress_payload/_payload_channels for
        # every later per-step store and records itself in
        # channel_stats.payload_choice
        self._payload_auto = scheme == "auto"
        self._payload_picker = None
        self._payload_channels: tuple | None = None
        self.compress_payload = None if self._payload_auto else scheme
        self.full_duplex = bool(cfg.channel.full_duplex)
        axis = self.AXIS

        if mode == "streamed":
            from repro.streams.channel import ChannelStats
            from repro.streams.reader import StreamReader
            from repro.streams.residency import BlockResidency

            # every streamed superstep path reads through the residency
            # tier: cache_bytes=0 degenerates to pure streaming (counted
            # pass-through), a positive budget pins hot blocks. ONE
            # residency serves all n emulated shards, so its capacity is
            # the per-shard budget times n — launch="processes" workers
            # each build their own with just the per-shard share instead
            self._residency = BlockResidency(
                stream_store,
                int(cfg.stream.cache_bytes) * pg.n_shards,
            )
            self._stream_reader = StreamReader(
                stream_store, chunk_blocks=cfg.stream.chunk_blocks,
                depth=cfg.stream.depth, owner_views=self.pipeline,
                residency=self._residency,
            )
            self.channel_inflight = int(cfg.channel.inflight)
            self._channel_fault = cfg.channel.fault
            self._recv_fault = cfg.channel.recv_fault
            self.group_batch = int(cfg.stream.group_batch)
            # cumulative over the current run(); bench_memory reads it for
            # the pipeline_overlap section (both directions)
            self.channel_stats = ChannelStats()
            # zombie channel threads recorded by crash-path aborts; surfaced
            # at the next run() instead of masking the original exception
            self.thread_leaks: list[Exception] = []
            self._inbox_dir = os.path.join(stream_store.dir, "inbox")
            self.msg_spill_dir = cfg.spill.spill_dir or os.path.join(
                stream_store.dir, "oms"
            )
            self.msg_slice_cap = int(cfg.spill.slice_cap)
            # effective slice capacity; bumped (in powers of two) if a vertex
            # in-degree ever exceeds it — Pregel's compute() needs a vertex's
            # whole message list in one slice
            self._msg_slice_cap_eff = int(cfg.spill.slice_cap)
            self.msg_read_chunk = int(cfg.spill.read_chunk)
            self.msg_merge_fanin = int(cfg.spill.merge_fanin)
            # one kernel bundle serves this engine and (via launch/procs)
            # any per-shard worker process — same compiled math by
            # construction
            kern = StreamKernels(program, pg.n_shards, pg.n_vertices, pg.P)
            self._kernels = kern
            if program.combiner is not None:
                self._stream_fold = kern.fold
                self._stream_fold_batch = kern.fold_batch
                self._stream_apply = kern.apply
                self._stream_digest = kern.digest
            else:
                self._stream_msgs = kern.msgs
                self._stream_apply_list = kern.apply_list
                self._stream_finish = kern.finish
            self._step_dense = self._step_sparse = self._step_logged = None
            self._init = jax.jit(self._wrap(
                lambda pg_: init_spmd(program, pg_, axis=axis), n_in=1,
                n_stats=0,
            ))
            return

        self.kl = None
        if backend == "pallas":
            from repro.graph.kblocks import build_kernel_layout

            win = cfg.kernel_windows
            while pg.P % win:
                win //= 2  # largest power-of-2 window dividing P
            self.kl = build_kernel_layout(
                pg, BLK=min(512, max(win, 8)), SRC_WIN=win, DST_WIN=win
            )

        def _dense(pg_, v, a, s):
            return superstep_spmd(program, pg_, v, a, s, axis=axis, mode=mode)

        def _sparse(pg_, v, a, s):
            return superstep_spmd(
                program, pg_, v, a, s, axis=axis, mode=mode,
                sparse_cap=self.sparse_cap,
            )

        def _pallas(pg_, kl_, v, a, s):
            return superstep_spmd(program, pg_, v, a, s, axis=axis,
                                  mode=mode, kl=kl_)

        def _logged(pg_, v, a, s):
            return superstep_logged_spmd(program, pg_, v, a, s, axis=axis)

        def _init(pg_):
            return init_spmd(program, pg_, axis=axis)

        if backend == "pallas":
            step_fn = self._step_pallas = jax.jit(self._wrap_kl(_pallas))
            self._step_dense = lambda pg_, v, a, s: step_fn(pg_, self.kl, v, a, s)
            self._step_sparse = self._step_dense  # skip is always-on in-kernel
        else:
            self._step_dense = jax.jit(self._wrap(_dense, n_in=4, n_stats=1))
            self._step_sparse = (
                jax.jit(self._wrap(_sparse, n_in=4, n_stats=1))
                if mode in ("recoded", "basic_sc")
                else self._step_dense
            )
        self._step_logged = (
            jax.jit(self._wrap_logged(_logged)) if message_log is not None else None
        )
        self._init = jax.jit(self._wrap(_init, n_in=1, n_stats=0))

    def lower_step(self, values, active, step=0, *, pg=None, kl=None):
        """The dense superstep lowered for these arguments, not run.
        ``pg``/``kl`` default to the engine's own; any argument may be a
        ``jax.ShapeDtypeStruct`` placed on a described device, which
        compiles for that device. ``.compile().as_text()`` shows what runs
        — a compiled Pallas kernel appears as a ``tpu_custom_call``."""
        if self._step_dense is None:
            raise ValueError("mode='streamed' has no dense superstep")
        pg = self.pg if pg is None else pg
        if isinstance(step, int):
            step = jnp.int32(step)
        if self.backend == "pallas":
            kl = self.kl if kl is None else kl
            return self._step_pallas.lower(pg, kl, values, active, step)
        return self._step_dense.lower(pg, values, active, step)

    # -- vmap / shard_map wrapping ------------------------------------------
    def _wrap(self, fn, n_in: int, n_stats: int):
        """Run the SPMD body over the machines axis: vmap (emulated shards on
        one device) or shard_map (one shard per device on a mesh)."""
        axis = self.AXIS
        is_step = n_in == 4  # (pg, values, active, step) -> (v, a, stats)
        if self.mesh is None:
            if is_step:
                def wrapped(pg_, v, a, s):
                    nv, na, st = jax.vmap(
                        fn, axis_name=axis, in_axes=(0, 0, 0, None)
                    )(pg_, v, a, s)
                    # psum'd stats are identical across shards; take shard 0
                    return nv, na, jax.tree.map(lambda x: x[0], st)
                return wrapped
            return lambda pg_: jax.vmap(fn, axis_name=axis)(pg_)
        # shard_map keeps a size-1 local leading axis; squeeze it around fn so
        # the SPMD body sees the same per-shard shapes as under vmap.
        sq = lambda t: jax.tree.map(lambda x: x[0], t)
        spec = P(axis)
        if is_step:
            def body(pg_, v, a, s):
                nv, na, st = fn(sq(pg_), sq(v), sq(a), s)
                return nv[None], na[None], st
            return jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(spec, spec, spec, P()), out_specs=(spec, spec, P()),
            )

        def body(pg_):
            v, a = fn(sq(pg_))
            return v[None], a[None]
        return jax.shard_map(body, mesh=self.mesh, in_specs=(spec,),
                             out_specs=(spec, spec))

    def _wrap_kl(self, fn):
        """Like _wrap(is_step) but with the kernel layout as a second arg."""
        axis = self.AXIS
        if self.mesh is None:
            def wrapped(pg_, kl_, v, a, s):
                nv, na, st = jax.vmap(
                    fn, axis_name=axis, in_axes=(0, 0, 0, 0, None)
                )(pg_, kl_, v, a, s)
                return nv, na, jax.tree.map(lambda x: x[0], st)
            return wrapped
        sq = lambda t: jax.tree.map(lambda x: x[0], t)
        spec = P(axis)

        def body(pg_, kl_, v, a, s):
            nv, na, st = fn(sq(pg_), sq(kl_), sq(v), sq(a), s)
            return nv[None], na[None], st

        # check_vma=False: pallas_call outputs carry no varying-mesh-axes
        # metadata, which the vma checker would otherwise reject.
        return jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(spec, spec, spec, spec, P()),
            out_specs=(spec, spec, P()),
            check_vma=False,
        )

    def _wrap_logged(self, fn):
        axis = self.AXIS
        if self.mesh is None:
            def wrapped(pg_, v, a, s):
                nv, na, st, As, cn = jax.vmap(
                    fn, axis_name=axis, in_axes=(0, 0, 0, None)
                )(pg_, v, a, s)
                return nv, na, jax.tree.map(lambda x: x[0], st), As, cn
            return wrapped
        sq = lambda t: jax.tree.map(lambda x: x[0], t)
        spec = P(axis)

        def body(pg_, v, a, s):
            nv, na, st, As, cn = fn(sq(pg_), sq(v), sq(a), s)
            return nv[None], na[None], st, As[None], cn[None]

        return jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(spec, spec, spec, P()),
            out_specs=(spec, spec, P(), spec, spec),
        )

    # -- streamed mode (out-of-core, paper §3 / Theorem 1) --------------------
    def _fold_groups(self, values, active, step, schedule, sink):
        """Fold staged edge chunks into per-(src, dst) group accumulators
        (§5's A_s, one group at a time) and hand each COMPLETED group to
        ``sink(src, dst, A_g, cnt_g)`` in schedule order. Shared by the
        logged unpipelined superstep (sink: combine locally + save_group)
        and the pipelined superstep (sink: channel transmit) — the group
        keying, identity re-init and buffer-recycle contract live in
        exactly one place, so the two paths' bit-identical-grouping
        guarantee cannot drift. A chunk's fold is dispatched and left
        running: its result is the chunk's release token, so the reader
        refills the staging buffer once the device has read it, and the
        only wait is the sink's, once a group.

        Small groups (a single staged chunk) are folded ``group_batch`` at
        a time through one padded vmapped dispatch — per lane the same ops
        on a fresh identity accumulator, so sinks still see each group's
        exact unbatched result; only the Python/dispatch overhead is
        amortized (graphs with many small destinations pay one dispatch
        per G groups instead of one per group)."""
        program, pg, comb = self.program, self.pg, self.program.combiner
        G = max(1, self.group_batch)
        CB = self._stream_reader.chunk_blocks
        # chunks per (src, dst) group, known from the schedule up front
        n_chunks = {(i, k): -(-len(ids) // CB) for i, k, ids in schedule}
        slots = CB * pg.edge_block
        pad = (np.full((slots,), -1, np.int32), np.zeros((slots,), np.int32),
               np.zeros((slots,), np.float32))
        pending: list = []  # copied single-chunk groups awaiting one dispatch
        state = {"cur": None, "A": None, "cnt": None}
        rows = _source_rows(values, pg.degree, active)

        def emit(i, k, A, cnt, lane=None):
            # after the fold's span has closed: phases do not nest
            with spans.span("channel.send"):
                if lane is not None:  # the group's lane of a batched fold
                    A, cnt = A[lane], cnt[lane]
                sink(i, k, A, cnt)

        def close_cur():
            if state["cur"] is not None:
                emit(*state["cur"], state["A"], state["cnt"])
                state["cur"] = None

        def flush_batch():
            if not pending:
                return
            if len(pending) == 1:
                i, k, sp, dp, w = pending[0]
                with spans.span("stream.fold"):
                    A_g, cnt_g = self._stream_fold(
                        comb.identity((pg.P,), program.msg_dtype),
                        jnp.zeros((pg.P,), jnp.int32), *rows(i),
                        jnp.asarray(sp), jnp.asarray(dp), jnp.asarray(w),
                        step,
                    )
                emit(i, k, A_g, cnt_g)
            else:
                with spans.span("stream.fold"):
                    lanes = pending + [(0, -1) + pad] * (G - len(pending))
                    src = jnp.asarray(np.array([p[0] for p in lanes],
                                               np.int32))
                    sp = jnp.asarray(np.stack([p[2] for p in lanes]))
                    dp = jnp.asarray(np.stack([p[3] for p in lanes]))
                    w = jnp.asarray(np.stack([p[4] for p in lanes]))
                    A_b, cnt_b = self._stream_fold_batch(
                        values, pg.degree, active, src, sp, dp, w, step
                    )
                for g, (i, k, *_rest) in enumerate(pending):
                    emit(i, k, A_b, cnt_b, lane=g)
            pending.clear()

        for chunk in self._stream_reader.stream(schedule):
            i, k = chunk.src_shard, chunk.dst_shard
            if state["cur"] is not None and state["cur"] != (i, k):
                close_cur()  # the previous multi-chunk group just completed
            if G > 1 and n_chunks[(i, k)] == 1:
                # copy out of the reader's recycled staging buffers; the
                # batch holds at most G chunks (modeled in the staging tier)
                with spans.span("stream.fold"):
                    pending.append((i, k, np.array(chunk.sp),
                                    np.array(chunk.dp), np.array(chunk.w)))
                if len(pending) == G:
                    flush_batch()
                continue
            if state["cur"] != (i, k):
                flush_batch()  # batched groups precede this one in order
                state["cur"] = (i, k)
                state["A"] = comb.identity((pg.P,), program.msg_dtype)
                state["cnt"] = jnp.zeros((pg.P,), jnp.int32)
            with spans.span("stream.fold"):
                state["A"], state["cnt"] = self._stream_fold(
                    state["A"], state["cnt"], *rows(i),
                    chunk.sp, chunk.dp, chunk.w, step,
                )
                # the fold may still read (on CPU jax: alias) the staged
                # arrays after dispatch returns: the reader refills them
                # once its result is ready
                chunk.release_after(state["cnt"])
        close_cur()
        flush_batch()

    def _superstep_streamed_comb(self, values, active, s, plan):
        """One streamed superstep with a combiner: fold staged edge chunks
        straight into the O(|V|/n) destination accumulators (§5 applied to
        O(1)-sized staged slices). With a message log, fold per (src,dst)
        group instead so each combined OMS A_s(i→k) persists to the run
        files as its group completes (§3.4)."""
        program, pg, comb = self.program, self.pg, self.program.combiner
        n = pg.n_shards
        reader = self._stream_reader
        log = self.message_log
        step = jnp.int32(s)
        A_r = [comb.identity((pg.P,), program.msg_dtype) for _ in range(n)]
        cnt = [jnp.zeros((pg.P,), jnp.int32) for _ in range(n)]
        schedule = [entry for per_dest in plan for entry in per_dest]
        # U_c ∥ U_s: the reader thread stages chunk t+1 while fold digests
        # chunk t
        if log is None:
            rows = _source_rows(values, pg.degree, active)
            for chunk in reader.stream(schedule):
                i, k = chunk.src_shard, chunk.dst_shard
                with spans.span("stream.fold"):
                    A_r[k], cnt[k] = self._stream_fold(
                        A_r[k], cnt[k], *rows(i),
                        chunk.sp, chunk.dp, chunk.w, step,
                    )
                    # refill the staged arrays once the fold has read them
                    chunk.release_after(cnt[k])
        else:
            # create the step's run store up front: even an all-skipped
            # superstep must publish an (empty) index or recovery of that
            # step would find no directory at all
            log.open_step(s)

            def _digest_and_log(gi, gk, A_g, cnt_g):
                A_r[gk] = comb.combine(A_r[gk], A_g)
                cnt[gk] = cnt[gk] + cnt_g
                log.save_group(s, gi, gk, np.asarray(A_g), np.asarray(cnt_g))

            self._fold_groups(values, active, step, schedule, _digest_and_log)
            log.close_step(s)  # release write handles; runs stay readable
        new_v, new_a = [], []
        n_active = n_msgs = 0
        agg = 0.0
        for k in range(n):
            with spans.span("stream.apply"):
                nv, na, nact, nm, ag = self._stream_apply(
                    values[k], pg.degree[k], pg.vmask[k], pg.old_ids[k],
                    pg.gids[k], A_r[k], cnt[k], active[k], step,
                    jnp.int32(k),
                )
                new_v.append(nv)
                new_a.append(na)
                n_active += int(nact)
                n_msgs += int(nm)
                agg += float(ag)
        st = reader.stats
        io_note = f"{st.blocks_read}blk/{st.bytes_read >> 10}KiB"
        return (jnp.stack(new_v), jnp.stack(new_a), n_active, n_msgs, agg,
                io_note)

    def _open_inbox(self, s: int, with_counts: bool):
        """The superstep's inbox store: the message log's per-step run store
        when a log is attached (transmitted groups ARE the persisted OMSs of
        §3.4 — recoverable and GC'd with the log), else a scratch store under
        the stream store, deleted once applied."""
        from repro.streams.msgstore import MessageRunStore

        if self.message_log is not None:
            return self.message_log.open_step(s)
        store = MessageRunStore(
            os.path.join(self._inbox_dir, f"step-{s:06d}"),
            self.pg.n_shards, self.pg.P, np.dtype(self.program.msg_dtype),
            with_counts=with_counts, compress=self.compress,
            compress_payload=self.compress_payload or False,
            payload_channels=self._payload_channels,
        )
        self._attach_payload_sampler(store)
        return store

    def _attach_payload_sampler(self, store) -> None:
        """Under ``compress_payload="auto"`` (and until the decision), let
        the picker see every value column this step's store spills."""
        if self._payload_auto:
            if self._payload_picker is None:
                from repro.streams.codec import PayloadAutoPicker

                self._payload_picker = PayloadAutoPicker()
            store.payload_sampler = self._payload_picker

    def _decide_payload_codec(self) -> None:
        """End-of-superstep half of the auto-pick: once the sample exists,
        fix the per-channel wire format for every later per-step store and
        record the verdict (measured ratios included) in the run's
        channel stats."""
        picker = self._payload_picker
        if not self._payload_auto or picker is None or not picker.sampled:
            return
        picked = picker.choose()
        self.compress_payload = "lossless" if picked else None
        self._payload_channels = picked or None
        self.channel_stats.payload_choice = picker.summary()
        self._payload_auto = False  # decided: stop sampling
        self._payload_picker = None

    def _close_inbox(self, s: int, inbox, ok: bool) -> None:
        """Publish/delete the inbox at superstep end. On failure (``ok``
        False, e.g. a sender crash) the step store is left WITHOUT an index:
        a rerun's ``open_step`` truncates it and the engine's startup sweep
        removes scratch leftovers — a torn inbox is never consumed."""
        if self.message_log is not None:
            if ok:
                self.message_log.close_step(s)
        elif ok:
            inbox.delete()

    def _abort_channels(self, channel, receiver) -> None:
        """Crash-path teardown of both pipeline directions. A zombie thread
        detected by abort() is RECORDED here, not raised — the superstep's
        own exception is already propagating and must stay visible; the
        recorded leak is surfaced by the next run() instead."""
        from repro.streams.channel import ChannelError

        for part in (channel, receiver):
            if part is None:
                continue
            try:
                part.abort()
            except ChannelError as e:
                self.thread_leaks.append(e)

    def _accum_channel(self, channel) -> None:
        st, tot = channel.stats, self.channel_stats
        tot.packets += st.packets
        tot.messages += st.messages
        tot.payload_bytes += st.payload_bytes
        tot.wire_bytes += st.wire_bytes
        tot.send_seconds += st.send_seconds
        tot.stall_seconds += st.stall_seconds
        tot.recv_runs += st.recv_runs
        tot.recv_seconds += st.recv_seconds
        tot.recv_stall_seconds += st.recv_stall_seconds

    def _superstep_streamed_comb_pipelined(self, values, active, s, plan):
        """One pipelined streamed superstep with a combiner — the paper's §4
        compute ∥ communicate overlap, full duplex: while the fold is still
        digesting edge chunks of the NEXT group, each finished combined
        group A_s(i→k) is serialized (sparse, optionally compressed) and
        appended to destination k's inbox run files by the background
        sender — AND the background receiver densifies and digests every
        run the sender has landed, in transmit order, so U_r hides under
        U_c exactly like U_s does. ``receiver.collect(k)`` after the
        per-destination flush barrier is the only receiver-side sync point.
        With ``full_duplex=False`` (PR-3's half-duplex pipeline, kept for
        A/B benchmarking) the receiver digests inline after the barrier.
        Either way the digest order is the transmit order — bit-identical
        to the unpipelined grouped fold.

        ``plan`` is destination-grouped; resident state stays O(|V|/n):
        one group accumulator, one receiver accumulator, one densified run,
        and at most ``channel_inflight`` sparse packets in flight.
        """
        from repro.streams.channel import ChannelReceiver, ShardChannels

        program, pg, comb = self.program, self.pg, self.program.combiner
        n = pg.n_shards
        reader = self._stream_reader
        step = jnp.int32(s)
        inbox = self._open_inbox(s, with_counts=True)
        receiver = None
        if self.full_duplex:
            identity = lambda: (comb.identity((pg.P,), program.msg_dtype),
                                jnp.zeros((pg.P,), jnp.int32))

            def _recv_digest(A, cnt, A_d, c_d):
                A, cnt = self._stream_digest(
                    A, cnt, jnp.asarray(A_d), jnp.asarray(c_d)
                )
                # block so recv_seconds measures real digest work (and the
                # accumulator is materialized before the next run's fold)
                jax.block_until_ready(cnt)
                return A, cnt

            receiver = ChannelReceiver(inbox, _recv_digest, identity,
                                       comb.e0, fault=self._recv_fault)
        channel = ShardChannels(inbox, inflight=self.channel_inflight,
                                fault=self._channel_fault, receiver=receiver)
        new_v, new_a = [], []
        n_active = n_msgs = 0
        agg = 0.0
        blocks = kib = 0
        ok = False
        try:
            for k in range(n):

                def _transmit(gi, gk, A_g, cnt_g):
                    # the sender sparsifies on its own thread (the shared
                    # append_combined wire format, streams/msgstore.py)
                    channel.send_combined(gk, np.asarray(A_g),
                                          np.asarray(cnt_g), tag=gi)

                self._fold_groups(values, active, step, plan[k], _transmit)
                blocks += reader.stats.blocks_read
                kib += reader.stats.bytes_read >> 10
                # barrier: every group for dest k has landed in its inbox
                # (and, full duplex, been announced to the receiver)
                channel.flush()
                if receiver is not None:
                    # receiver-side barrier: most digests already ran under
                    # the fold; this only waits out the tail
                    A_r, cnt = receiver.collect(k)
                else:
                    # half-duplex: digest inline, in transmit order
                    with spans.span("channel.digest"):
                        A_r = comb.identity((pg.P,), program.msg_dtype)
                        cnt = jnp.zeros((pg.P,), jnp.int32)
                        for seg in inbox.runs(k):
                            A_d, c_d = inbox.read_combined(k, seg, comb.e0)
                            A_r, cnt = self._stream_digest(
                                A_r, cnt, jnp.asarray(A_d), jnp.asarray(c_d)
                            )
                with spans.span("stream.apply"):
                    nv, na, nact, nm, ag = self._stream_apply(
                        values[k], pg.degree[k], pg.vmask[k],
                        pg.old_ids[k], pg.gids[k], A_r, cnt, active[k],
                        step, jnp.int32(k),
                    )
                    new_v.append(nv)
                    new_a.append(na)
                    n_active += int(nact)
                    n_msgs += int(nm)
                    agg += float(ag)
            channel.close()  # surface a late sender error before publishing
            if receiver is not None:
                receiver.close()
            ok = True
        finally:
            if not ok:
                self._abort_channels(channel, receiver)
            self._accum_channel(channel)
            self._close_inbox(s, inbox, ok)
        st = channel.stats
        io_note = (f"{blocks}blk/{kib}KiB "
                   f"tx={st.packets}pk/{st.wire_bytes >> 10}KiB "
                   f"ov={st.sender_overlap_seconds() * 1e3:.1f}"
                   f"/{st.receiver_overlap_seconds() * 1e3:.1f}ms")
        return (jnp.stack(new_v), jnp.stack(new_a), n_active, n_msgs, agg,
                io_note)

    def _apply_list_merged(self, mstore, dest, values_k, active_k, step,
                           channel=None):
        """Merge destination ``dest``'s spilled runs and fold destination-
        aligned apply_list slices into that shard's new (values, active)
        rows; returns them with the full per-position message count. Shared
        by the superstep loop and single-shard recovery so the two can never
        drift in slice semantics.

        With a live ``channel`` (the full-duplex pipelined path) the merge
        runs on an accounted receiver thread (``streams.channel
        .receive_iter``): its merge/decode time lands in the channel's
        ``recv_seconds`` — receiver digest hidden under apply compute is
        the OMS path's U_r overlap — and the receiver-side FaultPoint can
        kill it mid-merge. Either producer yields the same slices in the
        same order, so results cannot depend on which one ran."""
        from repro.streams.channel import receive_iter
        from repro.streams.reader import prefetch_iter

        program, pg = self.program, self.pg
        counts = mstore.dest_counts(dest)
        max_run = int(counts.max()) if counts.size else 0
        while self._msg_slice_cap_eff < max_run:
            self._msg_slice_cap_eff *= 2
        cap = self._msg_slice_cap_eff
        cnt_k = jnp.asarray(
            np.minimum(counts, np.iinfo(np.int32).max).astype(np.int32)
        )
        shard = jnp.int32(dest)
        acc_v = acc_a = None
        slices = mstore.merged_slices(dest, cap, self.msg_read_chunk)
        if channel is not None and self.full_duplex:
            it = receive_iter(slices, stats=channel.stats,
                              fault=self._recv_fault,
                              depth=self._stream_reader.depth)
        else:
            it = prefetch_iter(slices, depth=self._stream_reader.depth)
        # slices are prefetched so merge-read I/O hides behind apply compute
        for sdp, smsg, covered in it:
            nv, na = self._stream_apply_list(
                values_k, pg.degree[dest], pg.vmask[dest], pg.old_ids[dest],
                pg.gids[dest], jnp.asarray(sdp), jnp.asarray(smsg),
                cnt_k, active_k, step, shard,
            )
            if acc_v is None:
                # any one call is already exact for every vertex without
                # messages; per-slice overwrites fix the covered rest
                acc_v, acc_a = nv, na
            else:
                cov = jnp.asarray(covered)
                acc_v = jnp.where(cov, nv, acc_v)
                acc_a = jnp.where(cov, na, acc_a)
        if acc_v is None:  # no messages at all: one padding-only call
            acc_v, acc_a = self._stream_apply_list(
                values_k, pg.degree[dest], pg.vmask[dest], pg.old_ids[dest],
                pg.gids[dest],
                jnp.asarray(np.full((cap,), pg.P, np.int32)),
                jnp.asarray(np.zeros((cap,), np.dtype(program.msg_dtype))),
                cnt_k, active_k, step, shard,
            )
        return acc_v, acc_a, cnt_k

    def _superstep_streamed_nocomb(self, values, active, s, plan):
        """One combiner-less streamed superstep (§3.3): stream edges in,
        spill destination-sorted raw-message runs to local disk, external-
        merge them back, and apply destination-aligned slices — O(|E|)
        messages flow through, never resident.

        ``plan`` is destination-grouped: destination k's spill, merge, apply
        and run cleanup all finish before destination k+1's edges are read,
        so peak spill disk is one destination's traffic, not the superstep's.

        With ``pipeline=True`` the spill sort + run append (and the §3.3.1
        compaction passes) run on the channel's background sender in strict
        send order — the run table evolves exactly as inline, so results are
        byte-identical — while the compute thread goes on generating the
        next chunk's messages (§4's U_c ∥ U_s); with ``full_duplex`` the
        external merge feeding apply slices runs on the accounted receiver
        thread too (U_r), so merge-read I/O hides under apply compute.
        """
        from repro.streams.channel import ShardChannels
        from repro.streams.msgstore import MessageRunStore

        program, pg = self.program, self.pg
        n = pg.n_shards
        reader = self._stream_reader
        log = self.message_log
        step = jnp.int32(s)
        if log is not None:
            # the run files persist under the log: the OMSs ARE the log (§3.4)
            mstore = log.open_step(s)
        else:
            mstore = MessageRunStore(
                os.path.join(self.msg_spill_dir, f"step-{s:06d}"), n, pg.P,
                np.dtype(program.msg_dtype), compress=self.compress,
                compress_payload=self.compress_payload or False,
                payload_channels=self._payload_channels,
            )
            self._attach_payload_sampler(mstore)
        channel = (
            ShardChannels(mstore, inflight=self.channel_inflight,
                          fault=self._channel_fault)
            if self.pipeline else None
        )
        # one compaction entry point for both paths (the channel enqueues the
        # same op in FIFO order, so the run table evolves identically)
        compact = (channel.compact if channel is not None
                   else mstore.compact_tag)
        new_v, new_a = [], []
        n_active = n_msgs = 0
        agg = 0.0
        blocks = kib = 0
        rows = _source_rows(values, pg.degree, active)
        ok = False
        try:
            for k in range(n):
                # -- spill: raw messages out, one sorted run per edge chunk
                cur_src = None
                for chunk in reader.stream(plan[k]):
                    i = chunk.src_shard
                    if cur_src is not None and i != cur_src:
                        # keep the merge fan-in bounded: collapse the finished
                        # source's runs down to one (multi-pass §3.3.1)
                        with spans.span("channel.send"):
                            compact(k, cur_src, self.msg_merge_fanin,
                                    self.msg_read_chunk)
                    cur_src = i
                    with spans.span("stream.fold"):
                        msg, dp, valid = self._stream_msgs(
                            *rows(i), chunk.sp, chunk.dp, chunk.w, step,
                        )
                        # np.asarray both blocks on the async result and
                        # copies out of the reader's recycled staging buffers
                        msg = np.asarray(msg)
                        dp = np.asarray(dp)
                        valid = np.asarray(valid)
                    with spans.span("channel.send"):
                        if channel is not None:
                            # sort + append move to the sender thread; the
                            # next chunk's message generation overlaps them
                            channel.send_raw(k, dp, msg, valid, tag=i)
                        else:
                            mstore.append_raw(k, dp, msg, valid, tag=i)
                if cur_src is not None:
                    with spans.span("channel.send"):
                        compact(k, cur_src, self.msg_merge_fanin,
                                self.msg_read_chunk)
                blocks += reader.stats.blocks_read
                kib += reader.stats.bytes_read >> 10
                if channel is not None:
                    channel.flush()  # dest k's runs all landed; safe to merge

                # -- merge + apply (shared with recovery); with a channel
                # the merge runs on the accounted receiver thread (U_r)
                with spans.span("stream.apply"):
                    acc_v, acc_a, cnt_k = self._apply_list_merged(
                        mstore, k, values[k], active[k], step,
                        channel=channel
                    )
                    nact, nm, ag = self._stream_finish(
                        values[k], acc_v, acc_a, cnt_k, pg.vmask[k]
                    )
                    new_v.append(acc_v)
                    new_a.append(acc_a)
                    n_active += int(nact)
                    n_msgs += int(nm)
                    agg += float(ag)
                if log is None:
                    mstore.clear_dest(k)  # applied => this OMS is dead (§3.3)
            if channel is not None:
                channel.close()
            ok = True
        finally:
            if channel is not None:
                if not ok:
                    self._abort_channels(channel, None)
                self._accum_channel(channel)
            if log is not None:
                if ok:
                    log.close_step(s)  # publish the run index, drop handles
            elif ok:
                mstore.delete()
        io_note = f"{blocks}blk/{kib}KiB"
        if channel is not None:
            st = channel.stats
            io_note += (f" tx={st.packets}pk/{st.wire_bytes >> 10}KiB "
                        f"ov={st.sender_overlap_seconds() * 1e3:.1f}"
                        f"/{st.receiver_overlap_seconds() * 1e3:.1f}ms")
        return (jnp.stack(new_v), jnp.stack(new_a), n_active, n_msgs, agg,
                io_note)

    def _run_streamed(self, max_supersteps, state, start_step, verbose,
                      checkpointer, on_step):
        """Out-of-core superstep loop: edges arrive from disk group-by-group
        via the prefetching reader; resident per shard = vertex arrays +
        constant-size buffers. Mirrors ``run``'s contract exactly."""
        from repro.streams.schedule import plan_stream_schedule

        program, pg, comb = self.program, self.pg, self.program.combiner
        store = self.stream_store
        import shutil

        from repro.streams.channel import ChannelError, ChannelStats

        if self.thread_leaks:
            # a previous failed superstep left a channel thread alive; it
            # may still hold this store's inbox run files open — rerunning
            # over them would race the zombie's appends
            raise ChannelError(
                f"{len(self.thread_leaks)} channel thread(s) leaked by an "
                "earlier failed superstep; build a fresh engine/store "
                "instead of rerunning over their open inbox files"
            ) from self.thread_leaks[0]

        # scratch inboxes / OMS spills live under the store; a crashed
        # superstep leaves its step dir behind — sweep at run start (like
        # Checkpointer sweeps .tmp-step-*) so crashes cannot leak disk.
        # Done here, not at construction: a recovery engine (which never
        # runs) must not clobber another engine's in-flight scratch state.
        for d in (self._inbox_dir, self.msg_spill_dir):
            if os.path.isdir(d):
                for name in os.listdir(d):
                    if name.startswith(("step-", "recover-")):
                        shutil.rmtree(os.path.join(d, name),
                                      ignore_errors=True)
        self.channel_stats = ChannelStats()  # fresh overlap accounting
        values, active = state if state is not None else self.init()
        history: list[SuperstepRecord] = []
        target = min(
            program.num_supersteps
            if program.num_supersteps is not None
            else max_supersteps,
            max_supersteps,
        )
        restored_from = None
        if (
            checkpointer is not None
            and state is None
            and checkpointer.latest() is not None
        ):
            values, active, start_step = checkpointer.restore(
                expected_meta=store.signature()
            )
            restored_from = start_step
        # skip() against the block manifest BEFORE any disk I/O; the plan for
        # step s is made from step s's frontier, then re-made after apply so
        # rec.density matches StepStats semantics (frontier of the NEXT step)
        plan, _, _ = plan_stream_schedule(
            store, np.asarray(active), by_dest=True
        )
        residency = self._residency
        totals = self._stream_reader.totals
        nonempty_total = store.nonempty_blocks()
        if comb is None:
            superstep = self._superstep_streamed_nocomb
        elif self.pipeline:
            superstep = self._superstep_streamed_comb_pipelined
        else:
            superstep = self._superstep_streamed_comb
        for s in range(start_step, target):
            phases: dict[str, float] = {}
            t0 = time.perf_counter()
            with spans.collect(phases):
                with spans.span("stream.schedule"):
                    # selective scheduling: everything skip() left off this
                    # step's plan is disk I/O that never happens — tally it
                    # before the step so the record's counters describe
                    # THIS superstep
                    scheduled = sum(
                        len(ids) for per_dest in plan for _, _, ids in per_dest
                    )
                    residency.note_skipped(nonempty_total - scheduled)
                    hits0, miss0, evict0, _ = residency.counters()
                    waits0 = totals.recycle_waits
                    wait_s0 = totals.recycle_wait_seconds
                values, active, n_active, n_msgs, agg, io_note = superstep(
                    values, active, s, plan
                )
                with spans.span("stream.schedule"):
                    hits1, miss1, evict1, _ = residency.counters()
                    self._decide_payload_codec()  # no-op unless "auto"
                    plan, density, max_grp = plan_stream_schedule(
                        store, np.asarray(active), by_dest=True
                    )
            dt = time.perf_counter() - t0
            rec = SuperstepRecord(
                step=s, n_active=n_active, n_msgs=n_msgs, agg=agg,
                density=density, mode="streamed", seconds=dt,
                restored_from=restored_from if s == start_step else None,
                blocks_read=miss1 - miss0, cache_hits=hits1 - hits0,
                cache_evictions=evict1 - evict0,
                blocks_skipped=nonempty_total - scheduled,
                recycle_waits=totals.recycle_waits - waits0,
                recycle_wait_seconds=totals.recycle_wait_seconds - wait_s0,
                phase_seconds=phases,
            )
            history.append(rec)
            if verbose:
                print(
                    f"  superstep {s:4d}: active={n_active:>9d} "
                    f"msgs={n_msgs:>10d} agg={agg:.6g} "
                    f"density={density:.4f} [streamed {io_note}] "
                    f"{dt*1e3:.1f} ms"
                )
            if on_step is not None:
                on_step(rec, (values, active))
            if checkpointer is not None:
                saved = checkpointer.maybe_save(
                    s + 1, values, active, meta=store.signature()
                )
                if saved and self.message_log is not None:
                    # paper §3.4: OMS logs live until a newer checkpoint is
                    # durable
                    self.message_log.gc_before(s + 1)
            if program.num_supersteps is None and n_active == 0:
                break
        return (values, active), history

    # -- job API --------------------------------------------------------------
    def init(self):
        return self._init(self.pg)

    def run(
        self,
        max_supersteps: int = 10_000,
        state=None,
        start_step: int = 0,
        verbose: bool = False,
        checkpointer=None,
        on_step=None,
    ):
        """Host superstep loop with dense/sparse auto-dispatch (§3.2)."""
        if self.mode == "streamed":
            return self._run_streamed(
                max_supersteps, state, start_step, verbose, checkpointer,
                on_step,
            )
        values, active = state if state is not None else self.init()
        history: list[SuperstepRecord] = []
        target = min(
            self.program.num_supersteps
            if self.program.num_supersteps is not None
            else max_supersteps,
            max_supersteps,
        )
        density = 1.0  # step 0: unknown, assume dense
        max_grp = self.pg.n_blocks  # hard per-group bound; start pessimistic
        restored_from = None
        # auto-restore only when the caller did NOT hand us state: an
        # explicit (state, start_step) — e.g. after elastic repartitioning —
        # must win over whatever the checkpoint directory holds
        if (
            checkpointer is not None
            and state is None
            and checkpointer.latest() is not None
        ):
            values, active, start_step = checkpointer.restore()
            restored_from = start_step
        for s in range(start_step, target):
            use_sparse = (
                self.mode in ("recoded", "basic_sc")
                and max_grp <= self.sparse_cap  # no group overflows (correctness)
                and density < self.adapt_threshold  # sparse is worth it (perf)
            )
            t0 = time.perf_counter()
            if self.message_log is not None:
                values, active, stats, A_s_all, cnt_all = self._step_logged(
                    self.pg, values, active, jnp.int32(s)
                )
                self.message_log.save(s, A_s_all, cnt_all)
            else:
                fn = self._step_sparse if use_sparse else self._step_dense
                values, active, stats = fn(self.pg, values, active, jnp.int32(s))
            n_active = int(stats.n_active)
            density = float(stats.density)
            max_grp = int(stats.max_group_blocks)
            dt = time.perf_counter() - t0
            rec = SuperstepRecord(
                step=s, n_active=n_active, n_msgs=int(stats.n_msgs),
                agg=float(stats.agg), density=density,
                mode="sparse" if use_sparse else "dense", seconds=dt,
                restored_from=restored_from if s == start_step else None,
            )
            history.append(rec)
            if verbose:
                print(
                    f"  superstep {s:4d}: active={rec.n_active:>9d} "
                    f"msgs={rec.n_msgs:>10d} agg={rec.agg:.6g} "
                    f"density={rec.density:.4f} [{rec.mode}] {dt*1e3:.1f} ms"
                )
            if on_step is not None:
                on_step(rec, (values, active))
            if checkpointer is not None:
                saved = checkpointer.maybe_save(s + 1, values, active)
                if saved and self.message_log is not None:
                    # paper §3.4: OMS logs live until a newer checkpoint is
                    # durable — GC everything older as soon as one lands
                    self.message_log.gc_before(s + 1)
            if self.program.num_supersteps is None and n_active == 0:
                break
        return (values, active), history

    # -- result extraction ----------------------------------------------------
    def gather_values(self, values) -> dict[int, Any]:
        """{old_id: value} for all real vertices (the paper's HDFS dump)."""
        vals = np.asarray(values)
        old = np.asarray(self.pg.old_ids)
        mask = np.asarray(self.pg.vmask)
        return dict(zip(old[mask].tolist(), vals[mask].tolist()))

    def memory_model(self) -> dict[str, int]:
        """Bytes per shard held resident vs streamed (Lemma 1 / Theorem 1
        accounting).

        ``resident`` + ``buffers`` + ``staging`` (+ ``msg_staging`` +
        ``channel``) is what a machine must keep in RAM. For the in-memory
        modes the edge groups are device-resident (``streamed`` counts their
        HBM bytes); for ``mode="streamed"`` the edge groups are on disk
        (``streamed`` counts disk bytes) and the only edge-sized thing in
        RAM is the constant staging pool — so the RAM total is O(|V|/n),
        independent of |E|.

        Delegates to ``core.plan.estimate_memory`` — the SAME algebra the
        resource planner runs predictively — parameterized with the
        *realized* geometry and knobs (including the auto-bumped effective
        apply-slice cap and the actual on-disk stream bytes), so planned and
        realized models cannot drift.
        """
        from repro.core.plan import estimate_memory

        pg = self.pg
        streamed = self.mode == "streamed"
        return estimate_memory(
            mode=self.mode,
            n_shards=pg.n_shards,
            P=pg.P,
            E_cap=pg.E_cap,
            edge_block=pg.edge_block,
            value_itemsize=np.dtype(self.program.value_dtype).itemsize,
            msg_itemsize=np.dtype(self.program.msg_dtype).itemsize,
            combined=self.program.combiner is not None,
            pipeline=self.pipeline,
            compress=self.compress,
            compress_payload=(self.compress_payload or False) if streamed
            else self.config.channel.compress_payload,
            full_duplex=self.full_duplex if streamed
            else self.config.channel.full_duplex,
            chunk_blocks=(self._stream_reader.chunk_blocks if streamed
                          else self.config.stream.chunk_blocks),
            depth=(self._stream_reader.depth if streamed
                   else self.config.stream.depth),
            group_batch=(self.group_batch if streamed
                         else self.config.stream.group_batch),
            slice_cap=(self._msg_slice_cap_eff if streamed
                       else self.config.spill.slice_cap),
            read_chunk=self.config.spill.read_chunk,
            merge_fanin=self.config.spill.merge_fanin,
            inflight=self.config.channel.inflight,
            cache_bytes=self.config.stream.cache_bytes,
            disk_bytes_per_shard=(
                self.stream_store.disk_bytes() // pg.n_shards
                if streamed else None
            ),
        )
