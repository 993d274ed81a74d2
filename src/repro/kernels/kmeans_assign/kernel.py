"""K-means E-step (assignment) — Pallas TPU kernel.

The paper's K-means workload spends its FLOPs in the E-step: pairwise
squared distances point x centroid + argmin.  Tiling: grid over point
blocks (bn = 256 rows); the full centroid tile [K, D] stays resident in
VMEM across the grid (K <= a few hundred for the paper's K=3..64 range).
Distances use the matmul expansion ||x||^2 - 2 x.c + ||c||^2 so the inner
product runs on the MXU.  The outputs are ``[N, 1]`` columns: a 1-D
``(bn,)`` block takes a layout Mosaic cannot match to XLA's for large N,
and a vmapped call would put a non-tileable ``(.., bn)`` pair in the
block's last two dims.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _assign_kernel(x_ref, c_ref, out_ref, dist_ref):
    x = x_ref[...].astype(jnp.float32)               # [bn, D]
    c = c_ref[...].astype(jnp.float32)               # [K, D]
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [bn, K]
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)      # [bn, 1]
    c2 = jnp.sum(c * c, axis=-1)[None, :]            # [1, K]
    d2 = x2 - 2.0 * xc + c2                          # [bn, K]
    out_ref[...] = jnp.argmin(d2, axis=-1, keepdims=True).astype(jnp.int32)
    dist_ref[...] = jnp.min(d2, axis=-1, keepdims=True)


def assign_fwd(x: jax.Array, centers: jax.Array, block_n: int = 256,
               interpret: bool = False):
    """x: [N, D]; centers: [K, D] -> (assignments [N, 1] i32,
    min_d2 [N, 1] f32).

    N is padded to a block multiple by the ops wrapper.
    """
    n, d = x.shape
    k = centers.shape[0]
    bn = min(block_n, n)
    assert n % bn == 0, (n, bn)
    return pl.pallas_call(
        functools.partial(_assign_kernel),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),   # centroids resident
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, centers)
