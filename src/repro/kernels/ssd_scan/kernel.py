"""SSD intra-chunk Pallas kernel.

Computes, for each (batch, chunk, head) grid cell, the intra-chunk quadratic
term and the chunk state contribution:

  y_intra[i] = sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j
  S_chunk    = sum_j exp(cum_last - cum_j) dt_j B_j x_j^T

Both are (Q x Q) / (N x P) matmuls on VMEM-resident tiles -- the MXU-heavy
portion of Mamba2.  The cross-chunk recurrence (tiny, sequential) stays in
XLA (``lax.scan`` over chunk states); this split mirrors the SSD paper's
decomposition and keeps the kernel free of cross-grid dependencies.

Grid: (B, nc, H).  Blocks: x (Q, P), B/C (Q, N), dt/da (Q, 1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["ssd_intra_chunk_pallas"]


def _kernel(x_ref, b_ref, c_ref, dt_ref, da_ref, y_ref, s_ref, dec_ref, *,
            Q: int):
    x = x_ref[0, 0, :, 0, :].astype(jnp.float32)      # (Q, P)
    b = b_ref[0, 0, :, 0, :].astype(jnp.float32)      # (Q, N)
    c = c_ref[0, 0, :, 0, :].astype(jnp.float32)      # (Q, N)
    dt = dt_ref[0, 0, :, 0].astype(jnp.float32)       # (Q,)
    da = da_ref[0, 0, :, 0].astype(jnp.float32)       # (Q,)

    cum = jnp.cumsum(da)                               # (Q,)
    diff = cum[:, None] - cum[None, :]
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    decay = jnp.exp(jnp.where(cols <= rows, diff, -1e9))

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    w = cb * decay * dt[None, :]
    y_ref[0, 0, :, 0, :] = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)

    last = cum[Q - 1]
    wj = jnp.exp(last - cum) * dt                      # (Q,)
    s_ref[0, 0, 0, :, :] = jax.lax.dot_general(
        b * wj[:, None], x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(s_ref.dtype)   # (N, P)
    dec_ref[0, 0, 0] = jnp.exp(last).astype(dec_ref.dtype)


@jax.jit
def ssd_intra_chunk_pallas(xs, Bm, Cm, dt, da):
    """xs: (B, nc, Q, H, P); Bm/Cm: (B, nc, Q, H, N); dt/da: (B, nc, Q, H).

    Returns (y_intra (B,nc,Q,H,P), S_chunk (B,nc,H,N,P), decay (B,nc,H)).
    """
    B, nc, Q, H, P = xs.shape
    N = Bm.shape[-1]
    grid = (B, nc, H)
    y, S, dec = pl.pallas_call(
        functools.partial(_kernel, Q=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, 1, P), lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, Q, 1, N), lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, Q, 1, N), lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, c, h: (b, c, 0, h)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, c, h: (b, c, 0, h)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, 1, P), lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, 1, N, P), lambda b, c, h: (b, c, h, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, c, h: (b, c, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nc, Q, H, P), jnp.float32),
            jax.ShapeDtypeStruct((B, nc, H, N, P), jnp.float32),
            jax.ShapeDtypeStruct((B, nc, H), jnp.float32),
        ],
    )(xs, Bm, Cm, dt, da)
    return y, S, dec
