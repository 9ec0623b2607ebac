"""Grouped GEMM Pallas kernels: per-expert-slot SwiGLU FFN.

The MoE expert FFN runs one SwiGLU per physical expert slot over that
slot's capacity-padded (C, D) token buffer, of which only the first
``count`` rows hold routed pairs.

The fp kernel (:func:`grouped_ffn_pallas`) runs only those rows.  Its grid
is ``(work, F/bf)``: one work item per filled (slot, m-tile) pair, taken
from scalar-prefetched tables, with the F tiles innermost.  Each F step
feeds one x block to both up projections, gates in VMEM and accumulates
the down projection into a (bm, D) fp32 VMEM accumulator, so a slot's
weights are read once per filled m-tile and the (bm, F) activations never
touch HBM.  The grid is sized by a static bound on the work; steps past the
real work repeat the last block indices, so they fetch nothing, and skip
the math.
Output tiles no work item visits are left unwritten.

The w8a8 kernels tile every slot densely, ``grid = (G, M/bm, N/bn,
K/bk)`` with K innermost, and accumulate in int32; capacity-padded rows are
zero codes on input, so no masking is needed inside them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_ffn_kernel", "grouped_ffn_pallas",
           "grouped_matmul_q8_kernel", "grouped_matmul_q8_pallas",
           "grouped_swiglu_q8_kernel", "grouped_swiglu_q8_pallas"]


def grouped_ffn_kernel(slot_ref, tile_ref, nwork_ref, base_ref, x_ref,
                       w1_ref, w3_ref, w2_ref, o_ref, acc_ref, *,
                       f_steps: int):
    """One F tile of one work item: ``acc += swiglu(x @ w1, x @ w3) @ w2``.

    The scalar-prefetch tables (``slot_ref``, ``tile_ref``, ``nwork_ref``,
    ``base_ref``) only steer the index maps here; a step past the real
    work does nothing.
    """
    f = pl.program_id(1)

    @pl.when(pl.program_id(0) < nwork_ref[0])
    def _work():
        @pl.when(f == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[0]
        h = jax.lax.dot_general(x, w1_ref[0], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        g = jax.lax.dot_general(x, w3_ref[0], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        act = (h * jax.lax.logistic(h) * g).astype(x.dtype)
        acc_ref[...] += jax.lax.dot_general(
            act, w2_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(f == f_steps - 1)
        def _store():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)


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

    One int8 x block feeds both MXU contractions, accumulation is
    integer-exact, and the h/g dequant happens in VMEM right before the silu
    gate, so the h/g intermediates never round-trip HBM.
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


@functools.partial(jax.jit, static_argnames=("bm", "bf"))
def grouped_ffn_pallas(slot: jax.Array, tile: jax.Array, nwork: jax.Array,
                       base: jax.Array, x: jax.Array, w1: jax.Array,
                       w3: jax.Array, w2: jax.Array, *, bm: int,
                       bf: int) -> jax.Array:
    """SwiGLU FFN over the work items' row tiles.

    ``slot``/``tile``: (W,) int32, the slot and m-tile of each work item,
    every entry from ``nwork[0]`` on repeating the last real one; ``nwork``:
    (1,) int32; ``base``: (1,) int32, the weight row of slot 0.  x: (G, M,
    D); w1/w3: (G', D, F); w2: (G', F, D) with ``G' >= base + G``, ``M % bm
    == 0`` and ``F % bf == 0``.  Returns (G, M, D) in x's dtype, written on
    the work items' tiles only.
    """
    G, M, D = x.shape
    F = w1.shape[2]
    if M % bm or F % bf:
        raise ValueError(f"dims (M={M}, F={F}) not divisible by blocks "
                         f"({bm}, {bf})")
    f_steps = F // bf

    def f_of(w, f, nwork):
        # Past the real work: stay on the last F tile, as the last real
        # step left it, so the weight blocks are not fetched again.
        return jnp.where(w < nwork[0], f, f_steps - 1)

    def x_map(w, f, slot, tile, nwork, base):
        return slot[w], tile[w], 0

    def up_map(w, f, slot, tile, nwork, base):
        return base[0] + slot[w], 0, f_of(w, f, nwork)

    def down_map(w, f, slot, tile, nwork, base):
        return base[0] + slot[w], f_of(w, f, nwork), 0

    isz = x.dtype.itemsize
    # Double-buffered x, out, w1, w3, w2 blocks plus the fp32 accumulator.
    vmem = 2 * isz * (2 * bm * D + 3 * D * bf) + 4 * bm * D
    return pl.pallas_call(
        functools.partial(grouped_ffn_kernel, f_steps=f_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(slot.shape[0], f_steps),
            in_specs=[
                pl.BlockSpec((1, bm, D), x_map),
                pl.BlockSpec((1, D, bf), up_map),
                pl.BlockSpec((1, D, bf), up_map),
                pl.BlockSpec((1, bf, D), down_map),
            ],
            out_specs=pl.BlockSpec((1, bm, D), x_map),
            scratch_shapes=[pltpu.VMEM((bm, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((G, M, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, vmem + (8 << 20))),
    )(slot, tile, nwork, base, x, w1, w3, w2)


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
