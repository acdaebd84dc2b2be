"""Public jit'd wrappers for the Pallas kernels.

Which form of a kernel runs is decided where the program is lowered, by
the platform it is lowered for (``lax.platform_dependent``): for the CPU the
kernel executes with ``interpret=True`` — the body runs in XLA exactly as
written, which is how the tests validate it against ``ref.py`` — and for
any other platform it compiles through Mosaic. So a program compiled for
a TPU always holds the compiled kernel, even when the process that
compiles it runs JAX on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import digest as _digest
from repro.kernels import edge_combine as _ec


def _by_platform(kernel, *args, **kw):
    """``kernel(*args, **kw)``, interpreted on the CPU, compiled elsewhere."""
    return lax.platform_dependent(
        *args,
        cpu=functools.partial(kernel, interpret=True, **kw),
        default=functools.partial(kernel, interpret=False, **kw),
    )


def window_first_mask(blk_dwin: jax.Array) -> jax.Array:
    """True for each window's first block — those must survive skip
    compaction so every output window gets initialized."""
    NB = blk_dwin.shape[0]
    prev = jnp.concatenate([blk_dwin[:1] - 1, blk_dwin[:-1]])
    return blk_dwin != prev


def compact_blocks(keep: jax.Array):
    """Compacted ascending block-id list from a keep mask (skip(), §3.2).

    Tail entries repeat the last kept block so tail grid steps revisit it
    (no HBM refetch) and contribute the combiner identity."""
    NB = keep.shape[0]
    n_keep = jnp.sum(keep.astype(jnp.int32))
    (ids,) = jnp.nonzero(keep, size=NB, fill_value=0)
    last = ids[jnp.maximum(n_keep - 1, 0)]
    ids = jnp.where(jnp.arange(NB) < n_keep, ids, last)
    return ids.astype(jnp.int32), n_keep


def skip_keep_mask(blk_lo, blk_hi, blk_dwin, active_prefix):
    """keep = window-initializer OR has-an-active-source (the skip() test:
    prefix[hi+1] - prefix[lo] > 0 over the active bitmap)."""
    P = active_prefix.shape[0] - 1
    nonempty = blk_hi >= 0
    cnt = active_prefix[jnp.clip(blk_hi + 1, 0, P)] - active_prefix[
        jnp.clip(blk_lo, 0, P)
    ]
    return window_first_mask(blk_dwin) | (nonempty & (cnt > 0))


def edge_combine(
    state3, sp, dp, w, blk_ids, n_keep, blk_swin, blk_dwin,
    *, SRC_WIN, DST_WIN, msg_kind, combiner,
):
    return _by_platform(
        _ec.edge_combine_group,
        state3, sp, dp, w, blk_ids, n_keep, blk_swin, blk_dwin,
        SRC_WIN=SRC_WIN, DST_WIN=DST_WIN, msg_kind=msg_kind,
        combiner=combiner,
    )


def digest(A_r, cnt, recv, rcnt, *, combiner, WIN: int = 512):
    return _by_platform(
        _digest.digest, A_r, cnt, recv, rcnt, combiner=combiner, WIN=WIN,
    )
