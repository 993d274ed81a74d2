"""Mamba-2 SSD (state-space duality) — Pallas TPU kernel.

Chunked dual form: the grid is (batch, heads, S/chunk) with the chunk axis
innermost/sequential, carrying the running SSM state [P, N] in fp32 VMEM
scratch across chunks (the inter-chunk recurrence).  Per chunk the kernel
does the intra-chunk dense work on the MXU:

    G     = C_blk @ B_blk^T                    [L, L]   (MXU)
    Ydiag = (G * decay) @ X_blk                [L, P]   (MXU)
    Yoff  = (exp(a_cs) * (C_blk @ state^T))    [L, P]   (MXU)
    state = exp(a_last) * state + X^T @ (B_blk * decay_states)   (MXU)

with L = chunk length, P = head_dim, N = d_state.  The kernel works on
head-major copies (``x`` [B, H, S, P]; the within-chunk cumulative
decay ``a_cs`` as rows [B, H, 1, S], summed by XLA as the reference sums
it, since Mosaic has no cumsum), so that every block's last two dims tile
on the TPU; ``ssd_fwd`` makes them and transposes ``y`` back.  Every
product is taken at ``HIGHEST`` precision whatever the caller's default,
so the kernel computes float32 as its reference does.  VMEM per step: X [L,P] + B/C [L,N] + state [P,N] +
[L,L] temporaries — ~1 MB at (L=256, P=64, N=128).

B/C are shared across heads (ngroups=1): their index_map ignores the head
grid coordinate, so the same VMEM block is reused across the head axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = lax.Precision.HIGHEST


def _mm(a, b, contract):
    return lax.dot_general(a, b, (contract, ((), ())), precision=_HI,
                           preferred_element_type=jnp.float32)


def _ssd_kernel(x_ref, acs_ref, b_ref, c_ref, y_ref,
                state_out_ref, state_ref, *, chunk: int, n_chunks: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)              # [L, P]
    a_row = acs_ref[0, 0].astype(jnp.float32)        # [1, L]
    b = b_ref[0].astype(jnp.float32)                 # [L, N]
    c = c_ref[0].astype(jnp.float32)                 # [L, N]

    il = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jl = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # a_cs as a column: the row's diagonal, picked out exactly
    a_col = jnp.sum(jnp.where(il == jl, a_row, 0.0), axis=1, keepdims=True)
    # intra-chunk decay matrix: exp(a_cs[i] - a_cs[j]) for i >= j
    decay = jnp.where(jl <= il, jnp.exp(a_col - a_row), 0.0)  # [L, L]

    g = _mm(c, b, ((1,), (1,)))                      # [L, L]
    y_diag = _mm(g * decay, x, ((1,), (0,)))         # [L, P]

    # off-diagonal: contribution of the carried state
    state = state_ref[...]                           # [P, N]
    c_state = _mm(c, state, ((1,), (1,)))            # [L, P]
    y_off = jnp.exp(a_col) * c_state                 # [L, P]

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: state' = exp(a_last) * state + X^T (B * decay_states)
    a_last = a_col[chunk - 1:chunk, :]               # [1, 1]
    bw = b * jnp.exp(a_last - a_col)                 # [L, N]
    upd = _mm(x, bw, ((0,), (0,)))                   # [P, N]
    state_ref[...] = jnp.exp(a_last) * state + upd

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        state_out_ref[0, 0] = state_ref[...]


def ssd_fwd(x: jax.Array, da: jax.Array, b_mat: jax.Array, c_mat: jax.Array,
            chunk: int, interpret: bool = False):
    """x: [B,S,H,P] (pre-scaled by dt); da: [B,S,H]; b/c: [B,S,N].

    Returns (y [B,S,H,P], final_state [B,H,P,N] fp32).
    """
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk
    xh = x.transpose(0, 2, 1, 3)                     # [B, H, S, P]
    # the within-chunk cumulative decay, as the reference takes it
    a_cs = jnp.cumsum(da.astype(jnp.float32).reshape(
        bsz, n_chunks, chunk, h).transpose(0, 3, 1, 2), axis=-1)
    a_cs = a_cs.reshape(bsz, h, 1, s)                # [B, H, 1, S]

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    y, final_state = pl.pallas_call(
        kernel,
        grid=(bsz, h, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p),
                         lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk),
                         lambda b_, h_, ic: (b_, h_, 0, ic)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, ic: (b_, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, ic: (b_, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p),
                         lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, ic: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xh, a_cs, b_mat, c_mat)
    return y.transpose(0, 2, 1, 3), final_state
