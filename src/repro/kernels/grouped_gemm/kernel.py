"""Grouped GEMM Pallas kernel: per-expert-slot batched matmul.

The MoE expert FFN executes one (C x K) @ (K x N) per physical expert slot.
On TPU we tile (M, N, K) so each block's working set sits in VMEM and the
MXU sees 128-aligned contractions:

  grid = (G, M/bm, N/bn, K/bk)   -- K innermost for accumulation
  x block  (1, bm, bk), w block (1, bk, bn), out block (1, bm, bn)

The fp32 accumulator lives in a VMEM scratch buffer across the K steps
(standard Pallas matmul pattern); the final K step casts to the output
dtype.  Capacity-padded rows are zero on input, so no masking is needed
inside the kernel (zeros contribute zeros).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul_kernel", "grouped_matmul_pallas",
           "grouped_swiglu_kernel", "grouped_swiglu_pallas",
           "grouped_matmul_q8_kernel", "grouped_matmul_q8_pallas",
           "grouped_swiglu_q8_kernel", "grouped_swiglu_q8_pallas"]


def grouped_matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0], w_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _store():
        o_ref[0, ...] = acc_ref[...].astype(o_ref.dtype)


def grouped_swiglu_kernel(x_ref, w1_ref, w3_ref, o_ref, acc_h, acc_g, *,
                          k_steps: int):
    """Fused grouped SwiGLU: ``silu(x@w1) * (x@w3)`` in one invocation.

    The unfused path runs two grouped GEMMs that each stream the same x block
    out of HBM and round-trip their (G, M, N) intermediates before the
    elementwise gate.  Here one x block feeds both MXU contractions, the two
    fp32 accumulators live in VMEM across the K steps, and the silu gate is
    applied on the final K step -- the h/g intermediates never touch HBM.
    """
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_h[...] = jnp.zeros_like(acc_h)
        acc_g[...] = jnp.zeros_like(acc_g)

    x_blk = x_ref[0]
    acc_h[...] += jax.lax.dot_general(
        x_blk, w1_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_g[...] += jax.lax.dot_general(
        x_blk, w3_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _store():
        h = acc_h[...]
        act = h * jax.lax.logistic(h) * acc_g[...]
        o_ref[0, ...] = act.astype(o_ref.dtype)


def grouped_matmul_q8_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *,
                             k_steps: int):
    """w8a8 tile: int8 x int8 -> int32 MXU accumulation, dequant at the end.

    The per-row activation scales (bm, 1) and per-column weight scales
    (1, bn) dequantize the int32 accumulator as a rank-1 outer product on
    the final K step -- scales never enter the contraction, so the integer
    arithmetic is exact and the only rounding is the one the encoder
    already paid.
    """
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0], w_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _store():
        o_ref[0, ...] = (acc_ref[...].astype(jnp.float32)
                         * xs_ref[0] * ws_ref[0])


def grouped_swiglu_q8_kernel(x_ref, w1_ref, w3_ref, xs_ref, w1s_ref, w3s_ref,
                             o_ref, acc_h, acc_g, *, k_steps: int):
    """Fused w8a8 SwiGLU: two int32 accumulators, fp32 gate on the last step.

    Same structure as :func:`grouped_swiglu_kernel` -- one int8 x block feeds
    both MXU contractions -- but accumulation is integer-exact and the h/g
    dequant happens in VMEM right before the silu gate, so the quantized
    path keeps the no-HBM-round-trip property of the fp kernel.
    """
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_h[...] = jnp.zeros_like(acc_h)
        acc_g[...] = jnp.zeros_like(acc_g)

    x_blk = x_ref[0]
    acc_h[...] += jax.lax.dot_general(
        x_blk, w1_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    acc_g[...] += jax.lax.dot_general(
        x_blk, w3_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _store():
        rs = xs_ref[0]
        h = acc_h[...].astype(jnp.float32) * rs * w1s_ref[0]
        g = acc_g[...].astype(jnp.float32) * rs * w3s_ref[0]
        o_ref[0, ...] = h * jax.lax.logistic(h) * g


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk"))
def grouped_swiglu_pallas(x: jax.Array, w1: jax.Array, w3: jax.Array, *,
                          bm: int = 128, bn: int = 128,
                          bk: int = 128) -> jax.Array:
    """x: (G, M, K), w1/w3: (G, K, N) -> silu(x@w1) * (x@w3): (G, M, N)."""
    G, M, K = x.shape
    _, _, N = w1.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(f"dims ({M},{N},{K}) not divisible by blocks "
                         f"({bm},{bn},{bk})")
    k_steps = K // bk
    grid = (G, M // bm, N // bn, k_steps)
    return pl.pallas_call(
        functools.partial(grouped_swiglu_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, k: (g, k, j)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, k: (g, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, bn), jnp.float32)],
    )(x, w1, w3)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk"))
def grouped_matmul_pallas(x: jax.Array, w: jax.Array, *, bm: int = 128,
                          bn: int = 128, bk: int = 128) -> jax.Array:
    """x: (G, M, K) @ w: (G, K, N) -> (G, M, N)."""
    G, M, K = x.shape
    _, _, N = w.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(f"dims ({M},{N},{K}) not divisible by blocks "
                         f"({bm},{bn},{bk})")
    k_steps = K // bk
    grid = (G, M // bm, N // bn, k_steps)
    return pl.pallas_call(
        functools.partial(grouped_matmul_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, k: (g, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )(x, w)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk"))
def grouped_matmul_q8_pallas(q: jax.Array, row_scale: jax.Array,
                             wq: jax.Array, col_scale: jax.Array, *,
                             bm: int = 128, bn: int = 128,
                             bk: int = 128) -> jax.Array:
    """q: (G, M, K) int8, row_scale: (G, M); wq: (G, K, N) int8,
    col_scale: (G, N) -> dequantized (G, M, N) fp32."""
    G, M, K = q.shape
    _, _, N = wq.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(f"dims ({M},{N},{K}) not divisible by blocks "
                         f"({bm},{bn},{bk})")
    k_steps = K // bk
    grid = (G, M // bm, N // bn, k_steps)
    return pl.pallas_call(
        functools.partial(grouped_matmul_q8_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, k: (g, k, j)),
            pl.BlockSpec((1, bm, 1), lambda g, i, j, k: (g, i, 0)),
            pl.BlockSpec((1, 1, bn), lambda g, i, j, k: (g, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
    )(q, wq, row_scale[:, :, None], col_scale[:, None, :])


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk"))
def grouped_swiglu_q8_pallas(q: jax.Array, row_scale: jax.Array,
                             w1q: jax.Array, w1s: jax.Array,
                             w3q: jax.Array, w3s: jax.Array, *,
                             bm: int = 128, bn: int = 128,
                             bk: int = 128) -> jax.Array:
    """w8a8 fused ``silu(x@w1) * (x@w3)``; scales as in the matmul variant."""
    G, M, K = q.shape
    _, _, N = w1q.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(f"dims ({M},{N},{K}) not divisible by blocks "
                         f"({bm},{bn},{bk})")
    k_steps = K // bk
    grid = (G, M // bm, N // bn, k_steps)
    return pl.pallas_call(
        functools.partial(grouped_swiglu_q8_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, k: (g, k, j)),
            pl.BlockSpec((1, bk, bn), lambda g, i, j, k: (g, k, j)),
            pl.BlockSpec((1, bm, 1), lambda g, i, j, k: (g, i, 0)),
            pl.BlockSpec((1, 1, bn), lambda g, i, j, k: (g, 0, j)),
            pl.BlockSpec((1, 1, bn), lambda g, i, j, k: (g, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, i, j, k: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((G, M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32),
                        pltpu.VMEM((bm, bn), jnp.int32)],
    )(q, w1q, w3q, row_scale[:, :, None], w1s[:, None, :], w3s[:, None, :])
