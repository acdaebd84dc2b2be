"""Streaming A_r digest kernel (paper §5, "In-Memory Message Digesting").

Combines a received message buffer into the resident A_r accumulator in one
pass, fused with the has-message count update — the receiver-side dual of
edge_combine. Trivial compute, but it IS the U_r inner loop; as a Pallas
kernel it streams both buffers HBM->VMEM in (1, WIN) tiles with the pipeline
double-buffering the next tile during the combine (C3 overlap on the
receive path). The buffers are viewed as (n, 1, WIN) so each block's last
two dims equal the array's, which Mosaic accepts for any WIN."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(ar_ref, cnt_ref, recv_ref, rcnt_ref, out_ref, ocnt_ref, *, combiner):
    a = ar_ref[...]
    r = recv_ref[...]
    if combiner == "sum":
        out_ref[...] = a + r
    elif combiner == "min":
        out_ref[...] = jnp.minimum(a, r)
    else:
        out_ref[...] = jnp.maximum(a, r)
    ocnt_ref[...] = cnt_ref[...] + rcnt_ref[...]


def digest(A_r, cnt, recv, rcnt, *, combiner: str, WIN: int = 512,
           interpret: bool = False):
    """(A_r', cnt') = (combine(A_r, recv), cnt + rcnt); all shapes (P,)."""
    P = A_r.shape[0]
    WIN = min(WIN, P)
    assert P % WIN == 0
    n = P // WIN
    spec = pl.BlockSpec((None, 1, WIN), lambda j: (j, 0, 0))
    kern = functools.partial(_kernel, combiner=combiner)
    r2 = lambda x: x.reshape(n, 1, WIN)
    out, ocnt = pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[spec, spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1, WIN), A_r.dtype),
            jax.ShapeDtypeStruct((n, 1, WIN), cnt.dtype),
        ],
        interpret=interpret,
    )(r2(A_r), r2(cnt), r2(recv), r2(rcnt))
    return out.reshape(P), ocnt.reshape(P)
