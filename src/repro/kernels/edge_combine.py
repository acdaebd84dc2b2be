"""Fused edge-stream combine kernel (the paper's U_c hot loop, §3.2 + §5).

One grid step processes one edge block of a (shard, dest) group laid out by
``graph.kblocks``:

  HBM -> VMEM   sp/dp/w edge block           (the streaming buffer B, §3.2;
                                              double-buffered by the Pallas
                                              pipeline = overlap C3)
  HBM -> VMEM   values/degree/active window  (the in-memory state array A —
                                              only an aligned SRC_WIN slice,
                                              selected by scalar-prefetched
                                              block metadata)
  MXU           one-hot gather of source state      (Mosaic has no vector
  MXU/VPU       one-hot combine into the A_s window  gather/scatter; one-hot
                                                      matmul is the TPU idiom)
  VMEM          window accumulator persists across the window's block run
                (output revisiting); first block of a window initializes it.

skip() (§3.2): the grid walks a scalar-prefetched *compacted* block list
(active blocks + each window's initializer block). Tail grid steps repeat the
last kept block with contributions masked to the combiner identity — they cost
no extra HBM traffic because Pallas skips the copy when the block index does
not change. Worst case = the dense scan, the paper's guarantee (3).

Supported message kinds (trace-time specialization of compute(.)'s send):
  div_deg: value / max(degree, 1)      (PageRank)
  add_w:   value + weight              (SSSP)
  add_1:   value + 1                   (BFS)
  copy:    value                       (Hash-Min / label propagation)
  deg:     degree                      (neighbourhood degree sums)
Combiners: sum (MXU matmul), min / max (VPU masked reduce).

Layout notes (TPU tiling): every operand carries a leading grid axis so the
last two dims of each block equal the array's — the state table as
(n_src_windows, 3, SRC_WIN), the edge channels as (NB, 1, BLK), the outputs
as (n_dst_windows, 1, DST_WIN). Window positions ride the lanes in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MSG_KINDS = ("div_deg", "add_w", "add_1", "copy", "deg")
COMBINERS = ("sum", "min", "max")

_E0 = {"sum": 0.0, "min": jnp.inf, "max": -jnp.inf}


def _msg(kind: str, vals, degs, w):
    if kind == "div_deg":
        return vals / jnp.maximum(degs, 1.0)
    if kind == "add_w":
        return vals + w
    if kind == "add_1":
        return vals + 1.0
    if kind == "copy":
        return vals
    if kind == "deg":
        return degs
    raise ValueError(kind)


def _combine2(comb: str, a, b):
    if comb == "sum":
        return a + b
    if comb == "min":
        return jnp.minimum(a, b)
    return jnp.maximum(a, b)


def _kernel(
    # scalar prefetch (SMEM)
    ids_ref,    # (NB,) i32 compacted block ids (ascending; tail repeats last)
    nkeep_ref,  # (1,) i32 number of kept blocks
    swin_ref,   # (NB,) i32 source-window index per block
    dwin_ref,   # (NB,) i32 dest-window index per block
    # blocked inputs (VMEM)
    state_ref,  # (3, SRC_WIN) f32 [values ; degree ; active] window
    sp_ref,     # (1, BLK) i32
    dp_ref,     # (1, BLK) i32
    w_ref,      # (1, BLK) f32
    # outputs (VMEM)
    out_ref,    # (1, DST_WIN) f32 A_s window accumulator
    cnt_ref,    # (1, DST_WIN) f32 message counts
    *,
    BLK: int,
    SRC_WIN: int,
    DST_WIN: int,
    msg_kind: str,
    combiner: str,
):
    # Edges ride the lanes as (1, BLK) rows and window positions ride the
    # sublanes, so every one-hot is a sublane broadcast of an edge row
    # against a sublane iota (no 1-D vectors). Only min/max, which reduce
    # over the lanes, transpose their (DST_WIN, 1) result.
    j = pl.program_id(0)
    blk = ids_ref[j]
    prev = ids_ref[jnp.maximum(j - 1, 0)]
    is_first = (j == 0) | (dwin_ref[blk] != dwin_ref[prev])
    live = j < nkeep_ref[0]

    sp = sp_ref[...]
    dp = dp_ref[...]
    w = w_ref[...]
    src_base = swin_ref[blk] * SRC_WIN
    dst_base = dwin_ref[blk] * DST_WIN

    # --- one-hot gather of source state (MXU; Mosaic has no vector gather) ---
    sl = jnp.clip(sp - src_base, 0, SRC_WIN - 1)
    valid = (sp >= 0) & live
    oh_s = (lax.broadcasted_iota(jnp.int32, (SRC_WIN, BLK), 0) == sl) & valid
    # (3, SRC_WIN) x (SRC_WIN, BLK) -> (3, BLK); HIGHEST keeps the f32
    # values exact (a one-hot row selects, it must not round to bf16)
    g = lax.dot_general(
        state_ref[...], oh_s.astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    vals, degs, acts = g[0:1, :], g[1:2, :], g[2:3, :]
    aact = valid & (acts > 0.0)

    # --- compute(.)'s send, masked to the combiner identity ------------------
    e0 = jnp.float32(_E0[combiner])
    msg = jnp.where(aact, _msg(msg_kind, vals, degs, w), e0)

    # --- one-hot combine into the A_s window (§5 in-memory combining) --------
    dl = jnp.clip(dp - dst_base, 0, DST_WIN - 1)
    oh_d = (lax.broadcasted_iota(jnp.int32, (DST_WIN, BLK), 0) == dl) & aact
    oh_df = oh_d.astype(jnp.float32)
    # (1, BLK) x (DST_WIN, BLK) -> (1, DST_WIN), contracting the edge lanes
    row_dot = lambda x: lax.dot_general(
        x, oh_df, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    if combiner == "sum":
        part = row_dot(msg)
    else:
        red = jnp.min if combiner == "min" else jnp.max
        col = red(jnp.where(oh_d, msg, e0), axis=1, keepdims=True)
        # (DST_WIN, 1) -> (1, DST_WIN): lane-broadcast then a 2-D transpose
        part = jnp.transpose(jnp.broadcast_to(col, (DST_WIN, 128)))[0:1, :]
    cpart = row_dot(aact.astype(jnp.float32))

    # --- window-run accumulation (first block initializes) -------------------
    @pl.when(is_first)
    def _init():
        out_ref[...] = part
        cnt_ref[...] = cpart

    @pl.when(jnp.logical_not(is_first))
    def _acc():
        out_ref[...] = _combine2(combiner, out_ref[...], part)
        cnt_ref[...] = cnt_ref[...] + cpart


def edge_combine_group(
    state3: jax.Array,  # (3, P) f32 [values ; degree ; active]
    sp: jax.Array,  # (NB, BLK) i32
    dp: jax.Array,  # (NB, BLK) i32
    w: jax.Array,  # (NB, BLK) f32
    blk_ids: jax.Array,  # (NB,) i32 compacted (dense: iota)
    n_keep: jax.Array,  # () or (1,) i32
    blk_swin: jax.Array,  # (NB,) i32
    blk_dwin: jax.Array,  # (NB,) i32
    *,
    SRC_WIN: int,
    DST_WIN: int,
    msg_kind: str,
    combiner: str,
    interpret: bool = False,
):
    """A_s, cnt for one (shard, dest) group. Returns ((P,) f32, (P,) f32)."""
    P = state3.shape[1]
    NB, BLK = sp.shape
    assert msg_kind in MSG_KINDS and combiner in COMBINERS
    n_swin, n_dwin = P // SRC_WIN, P // DST_WIN

    # Every array gets a leading grid axis so each block's last two dims
    # equal the array's (Mosaic's rule for blocks off the (8, 128) tiling).
    state_w = state3.reshape(3, n_swin, SRC_WIN).transpose(1, 0, 2)
    row = lambda x: x.reshape(NB, 1, BLK)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(NB,),
        in_specs=[
            pl.BlockSpec(
                (None, 3, SRC_WIN),
                lambda j, ids, nk, sw, dw: (sw[ids[j]], 0, 0),
            ),
            pl.BlockSpec((None, 1, BLK),
                         lambda j, ids, nk, sw, dw: (ids[j], 0, 0)),
            pl.BlockSpec((None, 1, BLK),
                         lambda j, ids, nk, sw, dw: (ids[j], 0, 0)),
            pl.BlockSpec((None, 1, BLK),
                         lambda j, ids, nk, sw, dw: (ids[j], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, 1, DST_WIN),
                         lambda j, ids, nk, sw, dw: (dw[ids[j]], 0, 0)),
            pl.BlockSpec((None, 1, DST_WIN),
                         lambda j, ids, nk, sw, dw: (dw[ids[j]], 0, 0)),
        ],
    )
    kernel = functools.partial(
        _kernel, BLK=BLK, SRC_WIN=SRC_WIN, DST_WIN=DST_WIN,
        msg_kind=msg_kind, combiner=combiner,
    )
    out_shape = [
        jax.ShapeDtypeStruct((n_dwin, 1, DST_WIN), jnp.float32),
        jax.ShapeDtypeStruct((n_dwin, 1, DST_WIN), jnp.float32),
    ]
    A_s, cnt = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(
        blk_ids.astype(jnp.int32),
        jnp.atleast_1d(n_keep).astype(jnp.int32),
        blk_swin.astype(jnp.int32),
        blk_dwin.astype(jnp.int32),
        state_w,
        row(sp),
        row(dp),
        row(w),
    )
    return A_s.reshape(P), cnt.reshape(P)
